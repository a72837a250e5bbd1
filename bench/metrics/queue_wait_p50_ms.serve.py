"""The median wait of a request from when it was due to its admission into
a decode lane (the start of its prefill), over every request of the
traced run's window that was admitted, from the harness's own timestamps.

Layer: serving engine (``serve/engine.py``: the queue of pending requests
and the lanes they wait for).  Moves ``ttft_p95_ms``.
"""
LAYER = "serving engine"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "ttft_p95_ms"


def read(reading):
    waits = sorted(reading.out.get("queue_wait_s") or [])
    if not waits:
        return None
    n = len(waits)
    mid = (waits[(n - 1) // 2] + waits[n // 2]) / 2.0
    return 1e3 * mid
