"""Prefill's share of the chip's peak: the operations the traced prefills
need (``counts/transformer.prefill_ops``, by prompt length) over the device
time of the prefill programs times the bf16 peak.

Layer: model step, prefill (``models/transformer.forward`` with a cache,
under ``ServingEngine.prefill``).  Moves ``ttft_p95_ms``: a request's
first token waits for its prefill.  The prefill program holds the flash
kernel, so this whole program's share bounds what the kernel's roofline
share (``flash_attn_roofline.serve``) can claim.
"""
import os

from lib import common, trace

LAYER = "model step"
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
    dev_s = trace.seconds_in(reading.summary, "programs_s", PROGRAM)
    if not calls or dev_s <= 0:
        return None
    config = reading.ctx.files["config"]
    ops = sum(counts.prefill_ops(config, r[3]["len"]) for r in calls)
    return 100.0 * ops / (dev_s * reading.peaks["bf16_flops_per_s"])
