"""Decode's share of HBM bandwidth: the bytes the traced decode steps must
read (every weight once, and the cache positions each active lane attends,
``counts/transformer.decode_bytes``) over the device time of the decode
program times HBM bandwidth.

Layer: model step, decode (``models/transformer.decode_step`` under
``ServingEngine.decode``).  Moves ``itl_p95_ms``.
"""
import os

from lib import common, trace

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
PROGRAM = r"dstep"

counts = common.load_module(os.path.join(common.BENCH, "counts",
                                         "transformer.py"))
_serve = common.load_module(os.path.join(os.path.dirname(__file__),
                                         "_serve.py"))


def read(reading):
    calls = _serve.in_trace(reading, "decode")
    dev_s = trace.seconds_in(reading.summary, "programs_s", PROGRAM)
    if not calls or dev_s <= 0:
        return None
    config = reading.ctx.files["config"]
    nbytes = sum(counts.decode_bytes(config, r[3]["active"]) for r in calls)
    return 100.0 * nbytes / (dev_s * reading.peaks["hbm_bytes_per_s"])
