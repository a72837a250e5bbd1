"""What the serving metrics share: the host records of the traced run."""


def in_trace(reading, name):
    """Host records of ``name`` whose call started in the traced stretch."""
    t0, t1 = reading.ctx.trace_window
    return [r for r in reading.ctx.spans.records
            if r[0] == name and t0 <= r[1] < t1]
