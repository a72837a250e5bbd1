"""The flash-attention forward kernel's share of its roofline in prefill:
the least time the chip could take for the causal attention of the traced
prompts (the larger of operations over the bf16 peak and bytes over HBM
bandwidth, ``counts/transformer.causal_attention``) over the device time
of the Pallas kernels (``tpu_custom_call``) of the prefill programs in the
trace: the flash forward is the only one they run.

Layer: kernels (``kernels/flash_attention.py``, forward).  Moves
``ttft_p95_ms``.
"""
import os

from lib import common, trace

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "ttft_p95_ms"
PROGRAM = r"prefill_step"

counts = common.load_module(os.path.join(common.BENCH, "counts",
                                         "transformer.py"))
_serve = common.load_module(os.path.join(os.path.dirname(__file__),
                                         "_serve.py"))


def read(reading):
    calls = _serve.in_trace(reading, "prefill")
    dev_s = trace.seconds_in(reading.summary, "kernels_s", PROGRAM)
    if not calls or dev_s <= 0:
        return None
    config, peaks = reading.ctx.files["config"], reading.peaks
    least = 0.0
    for r in calls:
        ops, nbytes = counts.causal_attention(config, r[3]["len"])
        least += max(ops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / dev_s
