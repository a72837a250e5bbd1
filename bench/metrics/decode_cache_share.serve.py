"""Share of the decode program's device time that moves the KV cache
rather than computing: operations under the ``attn/kv_cache`` scope (the
masked write of each lane's new key and value) and the layer loop's own
operations (under ``layers`` and outside the ``attn`` and ``mlp`` blocks:
the scan slicing each layer's cache out of the stack and writing it back),
over all operations of the decode program in the trace.

Layer: model step, decode (``models/transformer.decode_step``,
``models/attention.decode_attention``).  Moves ``itl_p95_ms``.
"""
from lib import scopes, trace

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
PROGRAM = r"dstep"
BLOCKS = {"attn", "mlp"}


def moves_cache(segments):
    return "kv_cache" in segments or (
        "layers" in segments and not BLOCKS.intersection(segments))


def read(reading):
    sc = scopes.scoped(reading)
    if sc is None:
        return None
    summary = sc["summary"]
    layers, _ = scopes.scope_seconds(summary, PROGRAM,
                                     lambda segs: "layers" in segs)
    dev_s = trace.seconds_in(summary, "programs_s", PROGRAM)
    if layers <= 0 or dev_s <= 0:
        return None
    part, _ = scopes.scope_seconds(summary, PROGRAM, moves_cache)
    return 100.0 * part / dev_s
