"""Share of the traced stretch of the training window in which no operation
ran on the device: 1 - busy / window, busy being the union of the
operations' intervals in the trace.

Layer: device.  Moves ``train_tokens_per_s``.
"""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(reading):
    s = reading.summary
    if not s["window_s"] or s["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
