"""Share of the engine's step time spent admitting requests: the host
time inside the program's ``serve.admit`` spans (prefill, slot update and
the fetch of the first token) over its ``serve.step`` spans, both made in
the traced stretch.  Every lane's next token waits for the admissions of
its step.

Layer: serving engine (``serve/engine.py``: ``ServingEngine._insert``).
Moves ``itl_p95_ms``.
"""
from lib import scopes

LAYER = "serving engine"
UNIT = "%"
SOURCE = "program_span"
MOVES = "itl_p95_ms"


def _seconds(records, name):
    return sum(r[2] - r[1] for r in records
               if r[0] == name and r[2] is not None)


def read(reading):
    records = scopes.program_records(reading)
    if not records:
        return None
    step = _seconds(records, "serve.step")
    if step <= 0:
        return None
    return 100.0 * _seconds(records, "serve.admit") / step
