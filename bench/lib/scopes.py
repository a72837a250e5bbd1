"""The program's own scopes and spans in a profiler trace, on one clock.

``lib/trace.py`` reduces a trace by operation and program, and names each
idle gap by the benchmark's own spans.  This adds what the program itself
leaves there:

- each operation's scope path: the ``jax.named_scope``s it was traced under
  (``layers/attn/kv_cache``), from the ``tf_op`` stat (the HLO op_name)
  that the TPU's trace keeps with each operation.  ``ProfileData`` does not
  show that stat, so ``extract`` reads the ``.xplane.pb`` itself, with the
  few fields of XLA's ``xplane.proto`` it needs, and returns
  ``trace.extract``'s lists with the path as a sixth field of each
  operation;
- the program's spans (``repro.runtime.spans``), which it keeps in memory
  on the host's ``perf_counter`` clock while the profiler records.  The
  first and last clock marks (``spans.CLOCK``) of the traced stretch are in
  both, and give the offset between the two clocks at each end; shifted by
  it, each idle gap of the device is named by the innermost program span
  open in it.

Over a program without these (no ``repro.runtime.spans``, no named scopes),
``program_trace`` returns None and the readers built on it report nothing.
"""
from __future__ import annotations

import glob
import os
import re
import sys
import weakref

from lib import common, trace

# where bench/run.py has the profiler write the traced stretch
TRACE_DIR = os.path.join(common.BENCH, ".runs", "trace")

# segments of an op_name that are JAX's own structure, not a scope; the
# forward pass that remat runs again in the backward one is kept, as "remat"
PLUMBING = {"while", "body", "cond", "closed_call", "checkpoint", "remat2",
            "pjit", "scan"}
RENAMED = {"rematted_computation": "remat"}
TRANSFORM = re.compile(r"^(?:transpose|jvp|vmap|pmap)\((.*)\)$")

_SCHEMA = None
_READ = weakref.WeakKeyDictionary()


def _schema():
    """XSpace and its parts, with only the fields read here (others are
    skipped as unknown).  A map on the wire is a repeated (key, value)
    message, so the two metadata maps are declared that way."""
    global _SCHEMA
    if _SCHEMA is None:
        from google.protobuf import descriptor_pb2, descriptor_pool
        from google.protobuf import message_factory
        F = descriptor_pb2.FieldDescriptorProto
        f = descriptor_pb2.FileDescriptorProto(
            name="bench_xplane_subset.proto", package="bench_xplane",
            syntax="proto3")

        def msg(name, *fields):
            m = f.message_type.add(name=name)
            for fname, num, ftype, label, tname in fields:
                fd = m.field.add(name=fname, number=num, type=ftype,
                                 label=label)
                if tname:
                    fd.type_name = ".bench_xplane." + tname
        one, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
        i64, u64, dbl = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
        s, b, m = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
        msg("XStat", ("metadata_id", 1, i64, one, None),
            ("double_value", 2, dbl, one, None),
            ("uint64_value", 3, u64, one, None),
            ("int64_value", 4, i64, one, None),
            ("str_value", 5, s, one, None), ("bytes_value", 6, b, one, None),
            ("ref_value", 7, u64, one, None))
        msg("XEvent", ("metadata_id", 1, i64, one, None),
            ("offset_ps", 2, i64, one, None),
            ("duration_ps", 3, i64, one, None))
        msg("XLine", ("name", 2, s, one, None),
            ("timestamp_ns", 3, i64, one, None),
            ("events", 4, m, rep, "XEvent"))
        msg("XEventMetadata", ("id", 1, i64, one, None),
            ("name", 2, s, one, None), ("stats", 5, m, rep, "XStat"))
        msg("XStatMetadata", ("id", 1, i64, one, None),
            ("name", 2, s, one, None))
        msg("EventMetadataEntry", ("key", 1, i64, one, None),
            ("value", 2, m, one, "XEventMetadata"))
        msg("StatMetadataEntry", ("key", 1, i64, one, None),
            ("value", 2, m, one, "XStatMetadata"))
        msg("XPlane", ("name", 2, s, one, None),
            ("lines", 3, m, rep, "XLine"),
            ("event_metadata", 4, m, rep, "EventMetadataEntry"),
            ("stat_metadata", 5, m, rep, "StatMetadataEntry"))
        msg("XSpace", ("planes", 1, m, rep, "XPlane"))
        pool = descriptor_pool.DescriptorPool()
        pool.Add(f)
        _SCHEMA = message_factory.GetMessageClass(
            pool.FindMessageTypeByName("bench_xplane.XSpace"))
    return _SCHEMA


def read_xspace(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    space = _schema()()
    with open(paths[-1], "rb") as fh:
        space.ParseFromString(fh.read())
    return space


def scope_path(op_name):
    """``jit(train_step)/transpose(jvp(layers))/while/body/mlp/dot_general``
    -> ``layers/mlp``: the scopes, without the primitive (the last
    segment), the ``jit(...)`` calls, JAX's loop structure and the
    transforms around a scope (forward and backward alike); the forward
    pass that remat runs again shows as ``remat`` (``layers/remat/mlp``)."""
    segs = op_name.rstrip(":").split("/")[:-1]
    out = []
    for seg in segs:
        m = TRANSFORM.match(seg)
        while m:
            seg = m.group(1)
            m = TRANSFORM.match(seg)
        if seg and not seg.startswith("jit(") and seg not in PLUMBING:
            out.append(RENAMED.get(seg, seg))
    return "/".join(out)


def _stat_str(stat, stat_names):
    if stat.ref_value:
        return stat_names.get(stat.ref_value, "")
    return stat.str_value


def extract(trace_dir, span_names=None):
    """``trace.extract``'s lists from the ``.xplane.pb`` under
    ``trace_dir`` (times in whole ns, as ``ProfileData`` gives them), each
    operation with its scope path as a sixth field:
    {"devices": {plane: [[op, start_ns, dur_ns, program, kind, scope]]},
    "host": [[span, start_ns, dur_ns], ...]}."""
    space = read_xspace(trace_dir)
    devices, host = {}, []
    for plane in space.planes:
        meta = {e.key: e.value for e in plane.event_metadata}
        if plane.name.startswith("/device:TPU:"):
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            tf_op = next((k for k, v in stat_names.items() if v == "tf_op"),
                         None)
            scopes = {k: scope_path(next(
                (_stat_str(s, stat_names) for s in md.stats
                 if s.metadata_id == tf_op), "")) for k, md in meta.items()}
            lines = {line.name: line for line in plane.lines}

            def events(name):
                line = lines.get(name)
                if line is None:
                    return []
                return [(meta[e.metadata_id].name,
                         line.timestamp_ns + e.offset_ps // 1000,
                         e.duration_ps // 1000, e.metadata_id)
                        for e in line.events]
            mods = sorted((s, s + d, trace._program(n))
                          for n, s, d, _ in events(trace.MODULES_LINE))
            ops, j = [], 0
            for text, s, d, mid in sorted(events(trace.OPS_LINE),
                                          key=lambda e: e[1]):
                while j < len(mods) and mods[j][1] < s:
                    j += 1
                prog = mods[j][2] if j < len(mods) and mods[j][0] <= s else ""
                name = text.split(" = ", 1)[0].lstrip("%")
                ops.append([name, s, d, prog, trace._kind(text, name),
                            scopes[mid]])
            for text, s, d, mid in events(trace.ASYNC_LINE):
                name = text.split(" = ", 1)[0].lstrip("%")
                if trace.COLLECTIVE.search(name):
                    ops.append([name + "/async", s, d, "", "collective",
                                scopes[mid]])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = meta[e.metadata_id].name
                    if span_names is None or name in span_names:
                        host.append([name, line.timestamp_ns
                                     + e.offset_ps // 1000,
                                     e.duration_ps // 1000])
    return {"devices": devices, "host": host}


def summarize(tr, window_s, top=10):
    """``trace.summarize`` of the first five fields of each operation, and
    ``scopes_s``: device seconds (averaged over devices) of the operations
    that carry a scope path, by ``program/path`` (``path`` may be empty).
    Operations of five fields have no path and add nothing to it."""
    five = {plane: [e[:5] for e in events]
            for plane, events in tr["devices"].items()}
    out = trace.summarize({"devices": five, "host": tr["host"]}, window_s,
                          top)
    n_dev = max(len(tr["devices"]), 1)
    scopes = {}
    for events in tr["devices"].values():
        for e in trace.leaves(events):
            if len(e) > 5:
                key = f"{e[3]}/{e[5]}"
                scopes[key] = scopes.get(key, 0.0) + e[2] * 1e-9 / n_dev
    out["scopes_s"] = scopes
    return out


def scope_seconds(summary, program, test):
    """Device seconds of ``program``'s operations whose scope path, split
    into its segments, passes ``test``; and of all its operations that
    carry a path."""
    rx = re.compile(program)
    part = whole = 0.0
    for key, sec in summary["scopes_s"].items():
        prog, _, path = key.partition("/")
        if rx.search(prog):
            whole += sec
            if test(path.split("/") if path else []):
                part += sec
    return part, whole


def clock(records, host, mark, k=5):
    """The first and last ``mark`` records' ``perf_counter`` times and, at
    each, the nanoseconds to add to put a record on the trace's clock, from
    the trace's events of that name (in the same order; None if their counts
    differ or there are none).  Each end takes the least offset of its ``k``
    nearest marks: a delay between the record's stamp and the event's (the
    thread held between the two) can only add to it."""
    recs = [r[1] for r in records if r[0] == mark]
    evs = sorted(h[1] for h in host if h[0] == mark)
    if not recs or len(recs) != len(evs):
        return None
    offs = [e - r * 1e9 for r, e in zip(recs, evs)]
    k = max(1, min(k, len(offs) // 2))       # the two ends share no mark
    return ((recs[0], min(offs[:k])), (recs[-1], min(offs[-k:])))


def to_trace_ns(t_s, clk):
    """A ``perf_counter`` time on the trace's clock: the offset drawn
    straight between its readings at the first and last marks."""
    (a, oa), (b, ob) = clk
    off = oa if b <= a else oa + (ob - oa) * (t_s - a) / (b - a)
    return t_s * 1e9 + off


def shifted(records, clk):
    """Finished records as [name, start_ns, dur_ns] on the trace's clock."""
    out = []
    for r in records:
        if r[2] is not None:
            s = to_trace_ns(r[1], clk)
            out.append([r[0], s, to_trace_ns(r[2], clk) - s])
    return out


def idle_gaps(tr, lo, hi):
    """Idle intervals, in ns, of the first device's busy union in [lo, hi]."""
    events = tr["devices"][sorted(tr["devices"])[0]] if tr["devices"] else []
    return trace.gaps(trace.union([[e[1], e[1] + e[2]] for e in events]),
                      lo, hi)


def gap_parts(lo, hi, spans):
    """Nanoseconds of [lo, hi] under each innermost span ("none" outside
    all): the interval cut where a span opens or closes inside it."""
    inside = [sp for sp in spans if sp[1] < hi and sp[1] + sp[2] > lo]
    cuts = sorted({lo, hi} | {t for _, s, d in inside for t in (s, s + d)
                              if lo < t < hi})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        name = trace.innermost_span(inside, (a + b) / 2) or "none"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def named_gaps(gaps, spans):
    """Each gap as (the innermost span that holds most of it, or "none",
    seconds), longest first."""
    out = []
    for lo, hi in gaps:
        parts = gap_parts(lo, hi, spans)
        out.append((max(parts, key=parts.get), (hi - lo) * 1e-9))
    return sorted(out, key=lambda g: -g[1])


def idle_by_span(gaps, spans):
    """Seconds of the gaps under each innermost span ("none" outside all)."""
    out = {}
    for lo, hi in gaps:
        for name, ns in gap_parts(lo, hi, spans).items():
            out[name] = out.get(name, 0.0) + ns * 1e-9
    return out


def _spans_module():
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return spans


def program_records(reading):
    """The program's records made in the traced stretch (None where the
    program keeps no spans)."""
    spans = _spans_module()
    if spans is None:
        return None
    t0, t1 = reading.ctx.trace_window
    return [r for r in spans.PROGRAM.records if t0 <= r[1] < t1]


def _once(reading, key, make):
    """``make()``, computed once per run (per ``reading.ctx``)."""
    done = _READ.setdefault(reading.ctx, {})
    if key not in done:
        done[key] = make()
    return done[key]


def _extracted(reading):
    """The traced stretch, with the program's spans among the host's."""
    def make():
        spans = _spans_module()
        names = ({r[0] for r in spans.PROGRAM.records} if spans is not None
                 else set())
        return extract(TRACE_DIR, names)
    return _once(reading, "trace", make)


def scoped(reading):
    """The traced stretch with each operation's scope path, and its
    summary; None where no operation carries a path (a program without
    named scopes)."""
    def make():
        tr = _extracted(reading)
        t0, t1 = reading.ctx.trace_window
        summary = summarize(tr, t1 - t0)
        if not any(k.partition("/")[2] for k in summary["scopes_s"]):
            return None
        top = sorted(summary["scopes_s"].items(), key=lambda kv: -kv[1])
        print("program trace: device time by program/scope (s): "
              + ", ".join(f"{k} {v!r}" for k, v in top[:10]),
              file=sys.stderr)
        return {"trace": tr, "summary": summary}
    return _once(reading, "scoped", make)


def program_trace(reading):
    """What the readers of the program's spans share, read once per run:
    its records and counters, the clock (``clock``) from the marks at each
    end of the traced stretch, the traced stretch and its idle gaps on the
    trace's clock, and the program's spans shifted onto it.  None where the
    program keeps no spans or the trace holds no clock mark of them."""
    def make():
        spans = _spans_module()
        if spans is None or not spans.PROGRAM.records:
            return None
        records = spans.PROGRAM.records
        tr = _extracted(reading)
        clk = clock(records, tr["host"], spans.CLOCK)
        if clk is None:
            return None
        t0, t1 = reading.ctx.trace_window
        lo, hi = to_trace_ns(t0, clk), to_trace_ns(t1, clk)
        out = {"records": records, "trace": tr, "clock": clk,
               "window_ns": (lo, hi), "spans": shifted(records, clk),
               "gaps": idle_gaps(tr, lo, hi),
               "counters": dict(spans.PROGRAM.counters)}
        _report(out)
        return out
    return _once(reading, "program", make)


def decode_alignment_ns(pt, name="serve.decode"):
    """The largest distance, in ns, between a shifted record of ``name``
    and the trace's event of that name at the same place in order."""
    evs = sorted(h[1] for h in pt["trace"]["host"] if h[0] == name)
    recs = [s for n, s, _ in pt["spans"] if n == name]
    if not evs or len(evs) != len(recs):
        return None
    return max(abs(a - b) for a, b in zip(recs, evs))


def _report(pt, top=10):
    (_, o0), (_, o1) = pt["clock"]
    gaps = named_gaps(pt["gaps"], pt["spans"])
    align = decode_alignment_ns(pt)
    print(f"program trace: clock offset at the first mark {o0!r} ns, at the "
          f"last {o1!r} ns (differ by {(o1 - o0) / 1e3!r} us); serve.decode "
          f"records vs events at most "
          f"{None if align is None else align / 1e3!r} us apart; counters "
          f"{pt['counters']}", file=sys.stderr)
    print("program trace: longest idle gaps, each by the program span that "
          "holds most of it (s): "
          + ", ".join(f"{n} {s!r}" for n, s in gaps[:top]), file=sys.stderr)
    by_span = sorted(idle_by_span(pt["gaps"], pt["spans"]).items(),
                     key=lambda kv: -kv[1])
    print("program trace: idle time by innermost program span (s): "
          + ", ".join(f"{n} {s!r}" for n, s in by_span), file=sys.stderr)
