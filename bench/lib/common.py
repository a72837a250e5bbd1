"""What every cell's run shares: files found by name, the device check, the
compile log, spans, and the result line.

Nothing here names a cell, a configuration or a traffic mix: those are
files under ``bench/``, found through ``BENCHMARK.json``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path):
    """Import a file of ``bench/`` by path (its name may hold dots)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark():
    return load_json(ROOT, "BENCHMARK.json")


def cell_files(workload):
    """Everything one cell is made of, resolved by the names in
    ``BENCHMARK.json``: the cell's own file, its configuration, its traffic
    mix and the end-to-end and per-layer metrics it reports."""
    bench = benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    entry = by_name[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cell = load_json(BENCH, "workloads", workload + ".json")
    config = load_json(ROOT, configs[entry["config"]]["file"])
    mix = load_json(BENCH, "traffic", entry["traffic"] + ".json")
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    return {"name": workload, "entry": entry, "cell": cell, "config": config,
            "mix": mix, "end_to_end": end_to_end, "per_layer": per_layer}


def config_module(config, key):
    """The file a configuration names under ``key``: ``reference`` (its
    plain reference), ``weights`` (the maker of its weights from the seed)
    or ``program`` (the map of its sizes onto the program's config)."""
    return load_module(os.path.join(ROOT, config[key]))


def driver(name):
    return load_module(os.path.join(BENCH, "drivers", name + ".py"))


def generator(kind):
    return load_module(os.path.join(BENCH, "traffic", kind + ".py"))


def metric_reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))


def peaks(device_kind):
    table = load_json(BENCH, "peaks.json")["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]


def check_devices(chips):
    """The devices a run may use: ``chips`` TPUs, or NoChip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is on {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chips found, the cell asks for {chips}")
    return devs[:chips]


def enable_compile_cache():
    """JAX's persistent compile cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program, however
    quick to compile, so that only a cell's first run compiles."""
    import jax
    from repro.launch import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's events."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0

    def on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self


class Spans:
    """Host spans around each call into a layer.

    Off (``trace`` False) a span costs one attribute test.  On, each span is
    a ``jax.profiler.TraceAnnotation`` in the profiler's trace, and its
    host-clock start and end are kept in memory for the metric readers.
    """

    def __init__(self, trace):
        self.trace = trace
        self.records = []        # (name, start_s, end_s, info)

    @contextlib.contextmanager
    def __call__(self, name, **info):
        if not self.trace:
            yield
            return
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.records.append((name, t0, time.perf_counter(), info))

    def wrap(self, fn, name, info=None):
        """``fn`` with a span around each call; ``info(*args)`` is kept."""
        if not self.trace:
            return fn

        def wrapped(*args, **kw):
            with self(name, **(info(*args) if info else {})):
                return fn(*args, **kw)
        return wrapped


def seed_key(seed):
    """A PRNG key from any whole number, also one above 32 bits."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def memory_peak_bytes(devices):
    """The peak of the fullest device, where the backend reports it."""
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None
