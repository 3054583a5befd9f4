"""Spans and counters of the serving path: one in-memory table, on the
profiler's clock.

``span(name, **meta)`` is a context manager.  It enters a
``jax.profiler.TraceAnnotation(name, **meta)``, so every span lands on
the device trace's clock whenever a profile is being taken, and adds to
the table the span's count, its total seconds and its self seconds (the
total less the time its child spans cover).  The nesting stack is kept
per thread: the out-of-core prefetch gathers on a thread of its own.
After the block, ``.seconds`` of the object it returned holds the span's
duration, for the few callers that keep a timer of their own.

``count(name, n)`` adds to a plain integer counter in the same table.
``snapshot()`` returns ``{span: {"n", "total_s", "self_s"}}`` with each
counter beside the spans under its own name (an ``int``); readers take
deltas of two snapshots.  ``QueryScheduler.stats()["spans"]`` is one.

Python's cyclic collector is recorded as the span ``py.gc`` (metadata
``generation``) through ``gc.callbacks``, on the thread it pauses, so a
collection inside a span is that span's child and leaves its self time.

The recorder is on by default.  ``REPRO_SPANS=0`` (read once, at import)
makes ``span`` return one shared object that does nothing and times
nothing (its ``.seconds`` reads 0); counters are plain integer adds and
keep counting, because ``repro.kernels.LAUNCHES`` reads them.  Spans sit
at tick, dispatch group, round and launch granularity, never inside a
loop over lanes, entries or postings.
"""

from __future__ import annotations

import gc
import os
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["Recorder", "RECORDER", "ENABLED", "span", "count", "counter",
           "snapshot"]


class _Null:
    """The span of a recorder that is off: shared, does nothing."""

    __slots__ = ()
    seconds = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


class _Span:
    """One open span: its annotation, its start and its children's time."""

    __slots__ = ("rec", "name", "meta", "ann", "t0", "child", "seconds")

    def __init__(self, rec: "Recorder", name: str, meta: dict):
        self.rec = rec
        self.name = name
        self.meta = meta
        self.child = 0.0
        self.seconds = 0.0

    def __enter__(self):
        rec = self.rec
        self.ann = ann = TraceAnnotation(self.name, **self.meta)
        ann.__enter__()
        rec._stack().append(self)
        self.t0 = rec.clock()
        return self

    def __exit__(self, *exc):
        rec = self.rec
        dur = rec.clock() - self.t0
        stack = rec._stack()
        stack.pop()
        self.seconds = dur
        if stack:
            stack[-1].child += dur
        rec._add(self.name, dur, dur - self.child)
        self.ann.__exit__(None, None, None)
        return False


class Recorder:
    """A table of spans and counters.  ``clock`` gives seconds
    (``time.perf_counter`` unless a test fakes it); ``enabled=False`` makes
    every span the shared no-op."""

    def __init__(self, clock=time.perf_counter, enabled: bool = True):
        self.clock = clock
        self.enabled = enabled
        self._spans: dict[str, list] = {}     # name -> [n, total_s, self_s]
        self._counters: dict[str, int] = {}
        # reentrant: a collection may start, and record ``py.gc``, inside
        # an update on the same thread
        self._lock = threading.RLock()
        self._local = threading.local()
        self._gc_open: dict[int, _Span] = {}

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = stack = []
            return stack

    def _add(self, name: str, total: float, self_s: float) -> None:
        with self._lock:
            e = self._spans.get(name)
            if e is None:
                self._spans[name] = [1, total, self_s]
            else:
                e[0] += 1
                e[1] += total
                e[2] += self_s

    def span(self, name: str, **meta):
        """Context manager timing one span (see the module docstring)."""
        if not self.enabled:
            return _NULL
        return _Span(self, name, meta)

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + int(n)

    def counter(self, name: str) -> int:
        """One counter's value (0 when it never counted)."""
        return self._counters.get(name, 0)

    def counters(self, prefix: str = "") -> dict[str, int]:
        with self._lock:
            return {k: v for k, v in self._counters.items()
                    if k.startswith(prefix)}

    def snapshot(self) -> dict:
        """Every span as ``{"n", "total_s", "self_s"}`` and every counter
        as an int, by name."""
        with self._lock:
            out: dict = {k: {"n": e[0], "total_s": e[1], "self_s": e[2]}
                         for k, e in self._spans.items()}
            out.update(self._counters)
        return out

    # -- Python's cyclic collector ------------------------------------------

    def _on_gc(self, phase: str, info: dict) -> None:
        tid = threading.get_ident()
        if phase == "start":
            s = _Span(self, "py.gc", {"generation": info["generation"]})
            self._gc_open[tid] = s
            s.__enter__()
        else:
            s = self._gc_open.pop(tid, None)
            if s is not None:
                s.__exit__(None, None, None)

    def watch_gc(self) -> None:
        """Record every collection as ``py.gc`` (no-op when off)."""
        if self.enabled and self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def unwatch_gc(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)


#: whether spans are recorded in this process (env ``REPRO_SPANS``)
ENABLED = os.environ.get("REPRO_SPANS", "").strip().lower() not in (
    "0", "off", "false", "no")

#: the process's recorder: the serving path records into it
RECORDER = Recorder(enabled=ENABLED)
RECORDER.watch_gc()

span = RECORDER.span
count = RECORDER.count
counter = RECORDER.counter
snapshot = RECORDER.snapshot
