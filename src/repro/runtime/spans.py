"""The program's own spans, counters and compile log.

A span is a named stretch of host time at a layer boundary: the serving
engine's step and its phases (``serve/engine.py``).  Off, a span costs one
attribute test.  On, each span is a ``jax.profiler.TraceAnnotation`` in the
profiler's trace and a ``Record`` in memory: its name, start and end on the
host's ``time.perf_counter`` clock, what the caller said of it (``info``),
the index in ``records`` of the span that encloses it (``parent``) and the
request it serves (``rid``, inherited from the enclosing span when not
given).  Counters (``count``) are kept at the same boundaries.  Records stay
in memory; whoever reads them does so at the end of the run.

``PROGRAM`` is the tracer the program's loops use unless given another.
JAX's profiler is one per process, and ``PROGRAM`` follows it: a loop calls
``tick`` once per step, which turns the spans on while the profiler records
and off once it stops, and leaves a ``CLOCK`` mark each time it finds it
recording.  The first and last marks of a traced stretch are in memory and
in the profiler's trace, so they tie the records to the trace's clock.
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import NamedTuple

import jax

CLOCK = "spans.clock"

_OFF = contextlib.nullcontext()


class Record(NamedTuple):
    name: str
    start_s: float
    end_s: float | None          # None while the span is open
    info: dict
    parent: int | None           # index in ``records`` of the enclosing span
    rid: int | None


class _Span:
    __slots__ = ("spans", "name", "rid", "info", "index", "annotation")

    def __init__(self, spans, name, rid, info):
        self.spans, self.name, self.rid, self.info = spans, name, rid, info

    def __enter__(self):
        sp = self.spans
        parent = sp._open[-1] if sp._open else None
        rid = self.rid
        if rid is None and parent is not None:
            rid = sp.records[parent].rid
        self.annotation = (jax.profiler.TraceAnnotation(self.name)
                           if rid is None else
                           jax.profiler.TraceAnnotation(self.name, rid=rid))
        self.index = len(sp.records)
        sp._open.append(self.index)
        t = time.perf_counter()
        self.annotation.__enter__()        # the profiler's event starts here
        sp.records.append(Record(self.name, t, None, self.info, parent, rid))
        return self

    def __exit__(self, *exc):
        self.annotation.__exit__(*exc)
        sp = self.spans
        sp._open.pop()
        sp.records[self.index] = sp.records[self.index]._replace(
            end_s=time.perf_counter())
        return False


class Spans:
    """Host spans and counters of one thread's loop.

    ``trace`` True records, False does not; ``follow_profiler`` makes
    ``tick`` set ``trace`` to whether JAX's profiler is recording.
    """

    def __init__(self, trace=False, follow_profiler=False):
        self.trace = trace
        self.follow_profiler = follow_profiler
        self.records: list[Record] = []
        self.counters: dict[str, int] = collections.defaultdict(int)
        self._open: list[int] = []

    def __call__(self, name, rid=None, **info):
        """A context manager: the span ``name`` around its body."""
        if not self.trace:
            return _OFF
        return _Span(self, name, rid, info)

    def mark(self, name, rid=None, **info):
        """A span of no length, at this instant."""
        if self.trace:
            with _Span(self, name, rid, info):
                pass

    def count(self, name, n=1):
        if self.trace:
            self.counters[name] += n

    def tick(self):
        """Once per step of a loop: with ``follow_profiler``, spans on while
        JAX's profiler records, and a ``CLOCK`` mark whenever it does."""
        if self.follow_profiler:
            self.trace = jax.profiler.TraceAnnotation.is_enabled()
            if self.trace:
                self.mark(CLOCK)

    def wrap(self, fn, name, info=None):
        """``fn`` with a span around each call; ``info(*args)`` is kept."""
        if not self.trace:
            return fn

        def wrapped(*args, **kw):
            with self(name, **(info(*args) if info else {})):
                return fn(*args, **kw)
        return wrapped


PROGRAM = Spans(follow_profiler=True)


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's events: in
    all, and compiles by the name of the function compiled, where JAX's
    event carries it."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.by_program: dict[str, int] = collections.defaultdict(int)

    def on_duration(self, event, duration, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1
            if fun_name is not None:
                self.by_program[fun_name] += 1

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def install(self):
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self
