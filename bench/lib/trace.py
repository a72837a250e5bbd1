"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so that the second can be checked on a small
recorded trace:

- ``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
  keeps, as plain lists, each TPU's operations (name, start, duration, the
  program they belong to) and the host's spans (the benchmark's own
  ``TraceAnnotation`` names), all on the trace's one clock in nanoseconds;
- ``summarize`` reduces those lists: busy time as the union of operation
  intervals, device time by program and by kernel, collective time that no
  compute on the same device hides, and the idle gaps named by the host
  span open in them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all")
KERNEL = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Reading:
    """What a metric reader gets."""
    ctx: object
    out: dict
    summary: dict
    peaks: dict


def _kind(text, name):
    if KERNEL in text:
        return "kernel"
    if COLLECTIVE.search(name):
        return "collective"
    return "op"


def _program(name):
    """``jit_dstep(1234...)`` -> ``jit_dstep``."""
    return name.split("(", 1)[0]


def extract(trace_dir, span_names=None):
    """{"devices": {plane: [[op, start_ns, dur_ns, program, kind], ...]},
    "host": [[span, start_ns, dur_ns], ...]}.

    An operation's name is its HLO instruction's (the text before " = "),
    its program the XLA module running on that device when it starts, its
    kind "kernel" for a Pallas kernel (a ``tpu_custom_call``),
    "collective", or "op".  Collectives in flight (the "Async XLA Ops"
    line) are kept too, as "<name>/async".
    """
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           _program(e.name))
                          for e in lines.get(MODULES_LINE, []))
            ops, j = [], 0
            for e in sorted(lines.get(OPS_LINE, []), key=lambda e: e.start_ns):
                while j < len(mods) and mods[j][1] < e.start_ns:
                    j += 1
                prog = mods[j][2] if j < len(mods) and \
                    mods[j][0] <= e.start_ns else ""
                name = e.name.split(" = ", 1)[0].lstrip("%")
                ops.append([name, e.start_ns, e.duration_ns, prog,
                            _kind(e.name, name)])
            for e in lines.get(ASYNC_LINE, []):        # transfers in flight
                name = e.name.split(" = ", 1)[0].lstrip("%")
                if COLLECTIVE.search(name):
                    ops.append([name + "/async", e.start_ns, e.duration_ns,
                                "", "collective"])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if span_names is None or e.name in span_names:
                        host.append([e.name, e.start_ns, e.duration_ns])
    return {"devices": devices, "host": host}


def union(intervals):
    """Merged, sorted [start, end] pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the merged intervals ``a`` that no interval of ``b`` covers."""
    out = []
    b = union(b)
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def gaps(busy, lo, hi):
    """Idle intervals of [lo, hi] between merged busy intervals."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append([cur, min(s, hi)])
        cur = max(cur, e)
    if cur < hi:
        out.append([cur, hi])
    return [g for g in out if g[1] > g[0]]


def innermost_span(host, t):
    """Name of the shortest host span open at ``t`` (None if none)."""
    best = None
    for name, s, d in host:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else None


CONTROL = re.compile(r"^(while|conditional|call)(\.|$)")


def leaves(events):
    """The events whose time is counted once: all but the control-flow
    operations (a loop around its body, a call around its callee)."""
    return [e for e in events if not CONTROL.match(e[0])]


def summarize(trace, window_s, top=10):
    """Busy and idle time per device (averaged over devices); device time
    by program, by operation and of the Pallas kernels by program; time in
    collectives, and the part of it that no compute hides; and the
    breakdown of the run's result line.  Operations that contain others (a
    loop around its body) count in busy time only."""
    per_dev = []
    programs, ops, kernels = {}, {}, {}
    for plane, events in sorted(trace["devices"].items()):
        busy = union([[e[1], e[1] + e[2]] for e in events])
        own = leaves(events)
        coll = union([[e[1], e[1] + e[2]] for e in own
                      if e[4] == "collective"])
        compute = [[e[1], e[1] + e[2]] for e in own if e[4] != "collective"]
        per_dev.append({"busy": busy, "collective": length(coll),
                        "exposed": length(subtract(coll, compute))})
        for name, s, d, prog, kind in own:
            programs[prog] = programs.get(prog, 0) + d
            key = f"{prog}/{name}" if prog else name
            ops[key] = ops.get(key, 0) + d
            if kind == "kernel":
                kernels[prog] = kernels.get(prog, 0) + d
    n_dev = max(len(per_dev), 1)
    busy_s = sum(length(p["busy"]) for p in per_dev) / n_dev * 1e-9
    idle = []
    if per_dev and per_dev[0]["busy"]:
        b = per_dev[0]["busy"]
        for g in gaps(b, b[0][0], b[-1][1]):
            idle.append((innermost_span(trace["host"], (g[0] + g[1]) / 2)
                         or "none", (g[1] - g[0]) * 1e-9))
    idle.sort(key=lambda x: -x[1])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "programs_s": {k: v * 1e-9 / n_dev for k, v in programs.items()},
        "ops_s": {k: v * 1e-9 / n_dev for k, v in ops.items()},
        "kernels_s": {k: v * 1e-9 / n_dev for k, v in kernels.items()},
        "collective_s": sum(p["collective"] for p in per_dev) / n_dev * 1e-9,
        "exposed_collective_s": sum(p["exposed"] for p in per_dev)
        / n_dev * 1e-9,
        "breakdown": {
            "device_ops": [[k, v * 1e-9 / n_dev] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v] for k, v in idle[:top]],
        },
    }


def seconds_in(summary, key, pattern):
    """Device seconds, averaged over devices, of the programs
    (``key="programs_s"``) or operations (``"ops_s"``, named
    ``program/op``) whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in summary[key].items() if rx.search(k))
