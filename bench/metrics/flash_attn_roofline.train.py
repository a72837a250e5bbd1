"""The flash-attention kernels' share of their roofline in training: the
least time the chip could take for the causal attention, forward and
backward, of the traced steps' sequences (the larger of operations over the
bf16 peak and bytes over HBM bandwidth, ``counts/transformer
.causal_attention``) over the device time of the Pallas kernels
(``tpu_custom_call``: the flash forward, dq and dk/dv kernels are the only
ones the step runs) in the trace, averaged over the chips.  A forward pass that remat
runs again in the backward pass counts in the time, not in the work.

Layer: kernels (``kernels/flash_attention.py``, forward and backward).
Moves ``train_tokens_per_s``.
"""
import os

from lib import common, trace

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"
PROGRAM = r""

counts = common.load_module(os.path.join(common.BENCH, "counts",
                                         "transformer.py"))


def read(reading):
    ctx = reading.ctx
    t0, t1 = ctx.trace_window
    steps = sum(1 for r in ctx.spans.records
                if r[0] == "step" and t0 <= r[1] and r[2] <= t1)
    chips = len(ctx.devices)
    dev_s = trace.seconds_in(reading.summary, "kernels_s", PROGRAM)
    if not steps or dev_s <= 0:
        return None
    config, seq = ctx.files["config"], ctx.files["mix"]["seq"]
    rows = ctx.files["cell"]["training"]["batch"] / chips
    p = reading.peaks
    least = 0.0
    for backward in (False, True):
        ops, nbytes = counts.causal_attention(config, seq, backward)
        least += max(ops / p["bf16_flops_per_s"],
                     nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least * rows * steps / dev_s
