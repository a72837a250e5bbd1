"""Share of the traced stretch of the serving window in which the device
was idle while the host was inside the engine's step: the idle gaps of the
device's busy union in the stretch, intersected with the program's own
``serve.step`` spans put on the trace's clock (``lib/scopes.py``), over the
stretch.  The part of ``idle_share.serve`` that the engine's own host work
leaves the device waiting; the rest falls between steps.

Layer: serving engine (``serve/engine.py``: ``ServingEngine.step``).
Moves ``itl_p95_ms``.
"""
from lib import scopes, trace

LAYER = "serving engine"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(reading):
    pt = scopes.program_trace(reading)
    if pt is None:
        return None
    steps = trace.union([[s, s + d] for n, s, d in pt["spans"]
                         if n == "serve.step"])
    lo, hi = pt["window_ns"]
    if not steps or hi <= lo:
        return None
    gaps = pt["gaps"]
    idle = trace.length(gaps) - trace.length(trace.subtract(gaps, steps))
    return 100.0 * idle / (hi - lo)
