#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its own file
(``bench/workloads/<name>.json``) names a driver (``bench/drivers/``), its
configuration file and traffic mix name the sizes and the traffic.  Set-up
(weights from the seed, warming every program the cell's traffic uses) is
``setup_s``, timed from the start of this process; then the window runs for
``--seconds``.  With ``--trace 0`` the result holds the cell's end-to-end
metrics; with ``--trace 1`` a profiler trace of a steady stretch of the
window is reduced to the cell's per-layer metrics
(``bench/metrics/<metric>.py``) and a breakdown.

After the window the run checks what the timed path produced against the
plain reference (``bench/reference/``) and prints each number compared
beside its limit, as its last lines on standard error and under
``checks``, the last key of the result line.  The last line of standard
output is the result, one JSON object.  Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from lib import common  # noqa: E402

TRACE_DIR = os.path.join(BENCH, ".runs", "trace")


class Heartbeat:
    """A thread that wakes every 10 ms through the window and keeps how late
    it woke.  The main thread gives up the interpreter while it waits on the
    device, so a long step with a punctual heartbeat waited on the device;
    a late heartbeat means the whole process was held on the host."""

    PERIOD = 0.01

    def __init__(self):
        self.late = []
        self._stop = threading.Event()
        self._thread = None

    def _beat(self):
        due = time.perf_counter() + self.PERIOD
        while not self._stop.wait(max(0.0, due - time.perf_counter())):
            now = time.perf_counter()
            self.late.append(now - due)
            due = max(due + self.PERIOD, now)

    def start(self):
        self._thread = threading.Thread(target=self._beat, daemon=True)
        self._thread.start()

    def stop(self):
        if self._thread is not None:
            self._stop.set()
            self._thread.join()
            self._thread = None

    def summary(self):
        return {"longest_late_s": max(self.late, default=0.0),
                "late_over_100ms": sum(1 for x in self.late if x > 0.1)}


class Context:
    """What a driver gets: the cell's files, the run's arguments, spans,
    the window's clock, the tracer, and the device's memory peak."""

    def __init__(self, files, seed, seconds, trace, devices, clog):
        self.files, self.seed, self.seconds = files, seed, seconds
        self.trace = trace
        self.devices = devices
        self.clog = clog
        self.spans = common.Spans(trace)
        self.generator = common.generator
        self.window = [None, None]
        self.compiles_at_start = None
        self.compiles_in_window = None
        self.memory_peak_bytes = None
        self.trace_window = [None, None]
        self.trace_cost = [None, None]     # seconds to start, to stop
        # a steady stretch: the window's last 5 s (at most its last third),
        # so that stopping the profiler, which holds the host for seconds,
        # falls after the window
        self.trace_for = min(5.0, seconds / 3.0)
        self.trace_from = seconds - self.trace_for
        self.gc_pauses = []                # seconds of each collection
        self._gc_t = None
        self.heartbeat = Heartbeat()

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self.gc_pauses.append(time.perf_counter() - self._gc_t)

    def window_starts(self):
        """Set-up ends: what it made is moved out of the collector's way
        (``gc.freeze``), and the window's collections are timed."""
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self._on_gc)
        self.compiles_at_start = self.clog.programs
        self.heartbeat.start()
        self.window[0] = time.perf_counter()

    def window_ends(self):
        self.window[1] = time.perf_counter()
        self.heartbeat.stop()
        self.compiles_in_window = self.clog.programs - self.compiles_at_start
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        gc.unfreeze()

    def gc_in_window(self):
        """Collections in the window: count, seconds in all, longest."""
        return {"collections": len(self.gc_pauses),
                "seconds": sum(self.gc_pauses),
                "longest_s": max(self.gc_pauses, default=0.0)}

    def tracer(self, elapsed):
        """Called by the driver's loop, between steps that have ended on
        the device, with the seconds since the window started (None when it
        ends): starts and stops the profiler."""
        if not self.trace:
            return
        import jax
        started, stopped = self.trace_window
        if started is None and elapsed is not None \
                and elapsed >= self.trace_from:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            t = time.perf_counter()
            jax.profiler.start_trace(TRACE_DIR)
            self.trace_window[0] = time.perf_counter()
            self.trace_cost[0] = self.trace_window[0] - t
        elif started is not None and stopped is None and (
                elapsed is None or elapsed >= self.trace_from + self.trace_for):
            self.trace_window[1] = time.perf_counter()
            jax.profiler.stop_trace()
            self.trace_cost[1] = time.perf_counter() - self.trace_window[1]

    def memory_peak(self):
        self.memory_peak_bytes = common.memory_peak_bytes(self.devices)


def per_layer(ctx, out, kind):
    """Reduce the trace and the host records to the cell's per-layer
    metrics; returns (metrics, device facts, breakdown)."""
    from lib import trace as tr
    names = {r[0] for r in ctx.spans.records}
    summary = tr.summarize(tr.extract(TRACE_DIR, names),
                           ctx.trace_window[1] - ctx.trace_window[0])
    reading = tr.Reading(ctx=ctx, out=out, summary=summary,
                         peaks=common.peaks(kind))
    metrics = {}
    for m in ctx.files["per_layer"]:
        value = common.metric_reader(m["name"]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"busy_s": summary["busy_s"], "window_s": summary["window_s"]}
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return metrics, device, summary["breakdown"]


def run_cell(files, seed, seconds, trace, devices, clog):
    """One run of a cell on ``devices``: the result line's object and the
    lines for standard error, the numbers compared last."""
    kind = devices[0].device_kind
    ctx = Context(files, seed, seconds, trace, devices, clog)
    out = common.driver(files["cell"]["driver"]).run(ctx)
    setup_s = ctx.window[0] - T_START

    checks = out["checks"]
    correct = all(lim is None or (val is not None and val <= lim)
                  for val, lim in checks.values())
    if trace:
        metrics, dev_extra, breakdown = per_layer(ctx, out, kind)
    else:
        metrics = {m["name"]: {"value": (setup_s if m["name"] == "setup_s"
                                         else out["metrics"].get(m["name"])),
                               "unit": m["unit"]}
                   for m in files["end_to_end"]}
        metrics = {k: v for k, v in metrics.items() if v["value"] is not None}
        dev_extra, breakdown = {}, None
    result = {
        "correct": correct,
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices),
                   "memory_peak_bytes": ctx.memory_peak_bytes, **dev_extra},
        "setup_s": setup_s,
        "compiles_in_window": ctx.compiles_in_window,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    lines = list(out.get("notes", []))
    if trace:
        lines.append(f"profiler: {ctx.trace_cost[0]!r} s to start, "
                     f"{ctx.trace_cost[1]!r} s to stop; traced stretch "
                     f"{ctx.trace_window[1] - ctx.trace_window[0]!r} s")
    g, h = ctx.gc_in_window(), ctx.heartbeat.summary()
    lines.append(f"gc in the window: {g['collections']} collections, "
                 f"{g['seconds']!r} s, longest {g['longest_s']!r} s; host "
                 f"heartbeat late at most {h['longest_late_s']!r} s, "
                 f"{h['late_over_100ms']} times over 100 ms")
    lines.append(f"setup_s {setup_s!r}; backend compiles {clog.programs} "
                 f"({clog.seconds:.2f} s), persistent-cache hits "
                 f"{clog.cache_hits}, compiles in the window "
                 f"{ctx.compiles_in_window}")
    lines += [f"check {k} {v!r} limit {lim!r}"
              for k, (v, lim) in checks.items()]
    return result, lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    files = common.cell_files(args.workload)

    common.enable_compile_cache()
    clog = common.CompileLog().install()
    devices = common.check_devices(files["entry"]["chips"])
    common.peaks(devices[0].device_kind)       # an unknown chip is an error
    result, lines = run_cell(files, args.seed, args.seconds,
                             bool(args.trace), devices, clog)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))


if __name__ == "__main__":
    try:
        main()
    except common.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        sys.exit(2)
