"""Span-based tracing for the generate→fit hot path.

A :class:`Tracer` hands out context-manager spans::

    with tracer.span("struct", shard=k):
        arrays = source.generate(rec)

Every span is measured on the monotonic clock (``time.perf_counter``)
and does two things on exit:

* **aggregates** — busy seconds and call counts per span name accumulate
  under one lock (the numbers ``ExecutorStats`` / ``job.timings`` are
  derived from, replacing the ad-hoc per-stage floats that used to live
  in ``datastream/source.py`` and ``datastream/executor.py``);
* **emits** — if any sink is attached (``repro.obs.sinks``), a flat event
  dict with start/duration/thread/nesting lands in each sink, which is
  what the JSONL event log and the Perfetto export render from.

Nesting is thread-aware: each thread keeps its own span stack in
thread-local storage, so the executor's struct spans (caller thread),
host feature spans (``shard-feat`` pool threads) and writer flush spans
(``shard-flush`` thread) nest independently and carry their own ``tid``
— exactly the three lanes a Chrome-trace Gantt shows overlapping.

Compiles: inside ``with tracer.watch_jax():`` JAX's own compile-pipeline
events become closed spans — ``compile.trace`` (jaxpr tracing),
``compile.lower`` (jaxpr → MLIR) and ``compile.backend`` (XLA compile or
a persistent-cache load) — each with ``fun`` = JAX's name for the
function, under the innermost span open on the compiling thread.  JAX
reports a phase when it ends, so nested phases (the per-primitive traces
inside a kernel's trace, the re-traces a lowering makes) arrive before
the phase that holds them; a phase absorbs the ones it holds, so a
thread's compile spans never overlap and their totals add up to the
union of its compile time.

Device traces: while ``repro.obs.jaxprof`` has a profile running, every
span also opens a ``jax.profiler.TraceAnnotation`` of its name on its
own thread, so the device trace carries the spans on its own clock.

Overhead: a sink-less tracer costs two ``perf_counter`` calls plus one
locked dict update per span — the same price as the legacy ad-hoc
timers it replaces.  The module-level :data:`NULL_TRACER` is cheaper
still: ``span()`` returns a shared no-op context manager and touches no
clock, no lock and no allocation, so instrumented code paths that run
without a tracer stay effectively free (< a microsecond per span; see
``tests/test_obs.py::test_disabled_mode_overhead_bound``).
"""
from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from typing import Any, Dict, List, Optional

from repro.obs import jaxprof

__all__ = ["Span", "Tracer", "NullTracer", "NULL_TRACER"]

#: JAX's compile-pipeline events and the span each is booked as
COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}


class Span:
    """One closed span: measured interval + identity.  ``ts``/``dur`` are
    seconds on the tracer's monotonic clock, relative to the tracer's
    epoch (its construction instant) so events from different threads
    share one timeline."""

    __slots__ = ("name", "ts", "dur", "tid", "span_id", "parent_id",
                 "attrs")

    def __init__(self, name: str, ts: float, dur: float, tid: str,
                 span_id: int, parent_id: Optional[int],
                 attrs: Optional[Dict[str, Any]]):
        self.name = name
        self.ts = ts
        self.dur = dur
        self.tid = tid
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = attrs

    def to_event(self) -> Dict[str, Any]:
        ev = {"ev": "span", "name": self.name, "ts": self.ts,
              "dur": self.dur, "tid": self.tid, "id": self.span_id}
        if self.parent_id is not None:
            ev["parent"] = self.parent_id
        if self.attrs:
            ev["args"] = self.attrs
        return ev


class _SpanCtx:
    """The live (open) span handle ``Tracer.span`` returns.  After exit,
    ``dur`` holds the measured seconds — callers that also need the
    number (e.g. ``FeatureSpec`` mirroring its legacy accumulators) read
    it instead of timing the region twice."""

    __slots__ = ("_tracer", "name", "attrs", "_t0", "dur", "span_id",
                 "parent_id", "_note")

    def __init__(self, tracer: "Tracer", name: str,
                 attrs: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.dur = 0.0
        self.span_id = 0
        self.parent_id = None
        self._note = None

    def __enter__(self) -> "_SpanCtx":
        tr = self._tracer
        stack = tr._stack()
        self.parent_id = stack[-1].span_id if stack else None
        self.span_id = next(tr._ids)
        stack.append(self)
        if jaxprof.profiling():
            import jax
            self._note = jax.profiler.TraceAnnotation(self.name)
            self._note.__enter__()
        self._t0 = tr._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        tr = self._tracer
        t1 = tr._clock()
        self.dur = t1 - self._t0
        if self._note is not None:
            self._note.__exit__(None, None, None)
            self._note = None
        stack = tr._stack()
        if stack and stack[-1] is self:
            stack.pop()
        tr._book(self.name, self._t0 - tr._epoch, self.dur, None,
                 self.span_id, self.parent_id, self.attrs)
        return None


class _NullCtx:
    """Shared no-op context manager — the whole disabled-mode cost."""

    __slots__ = ()
    dur = 0.0

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return None


_NULL_CTX = _NullCtx()


class Tracer:
    """Thread-safe span tracer with per-name aggregation and optional
    sink emission.

    ``sinks``: objects with ``emit(event: dict)`` (and optionally
    ``close()``) — see ``repro.obs.sinks``.  With no sinks the tracer
    only aggregates (cheap); attach a sink to get the event log.
    """

    def __init__(self, sinks: Optional[List] = None,
                 clock=time.perf_counter):
        self._clock = clock
        self._epoch = clock()
        self._unix_epoch = time.time()
        self._sinks: List = []
        self._lock = threading.Lock()
        self._totals: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._tls = threading.local()
        self._ids = itertools.count(1)
        #: ``watch_jax`` depth, and per compiling thread (by ident) its
        #: name and the compile phases not yet final (a later phase may
        #: hold them)
        self._watching = 0
        self._compiles: Dict[int, tuple] = {}
        for s in sinks or ():
            self.add_sink(s)

    # -- sinks -------------------------------------------------------------
    def add_sink(self, sink) -> None:
        sink.emit({"ev": "meta", "unix_t0": self._unix_epoch,
                   "pid": os.getpid(),
                   "clock_offset": self._clock() - self._epoch})
        with self._lock:
            self._sinks.append(sink)

    @property
    def emitting(self) -> bool:
        return bool(self._sinks)

    def close(self) -> None:
        """Flush and close every sink (idempotent)."""
        with self._lock:
            sinks, self._sinks = self._sinks, []
        for s in sinks:
            close = getattr(s, "close", None)
            if close is not None:
                close()

    # -- spans -------------------------------------------------------------
    def span(self, name: str, **attrs) -> _SpanCtx:
        return _SpanCtx(self, name, attrs or None)

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _book(self, name: str, ts: float, dur: float, tid: Optional[str],
              span_id: int, parent_id: Optional[int],
              attrs: Optional[Dict[str, Any]]) -> None:
        """Book one closed span: the aggregates, then every sink.  The
        one entry point for spans, live (``_SpanCtx``) or reported after
        the fact (JAX's compile events).  ``tid=None``: this thread."""
        with self._lock:
            self._totals[name] = self._totals.get(name, 0.0) + dur
            self._counts[name] = self._counts.get(name, 0) + 1
            sinks = tuple(self._sinks)
        if sinks:
            ev = Span(name, ts, dur,
                      tid or threading.current_thread().name, span_id,
                      parent_id, attrs).to_event()
            for s in sinks:
                s.emit(ev)

    # -- JAX compiles ------------------------------------------------------
    @contextlib.contextmanager
    def watch_jax(self):
        """Book JAX's compile-pipeline events (``COMPILE_SPANS``) as
        spans while open.  One ``jax.monitoring`` listener per tracer,
        registered by the outermost ``watch_jax`` and removed by it."""
        import jax.monitoring

        with self._lock:
            self._watching += 1
            first = self._watching == 1
            # a watched run has its compile spans, at zero if none ran
            for name in COMPILE_SPANS.values():
                self._totals.setdefault(name, 0.0)
                self._counts.setdefault(name, 0)
        if first:
            jax.monitoring.register_event_time_span_listener(
                self._on_compile)
        try:
            yield self
        finally:
            with self._lock:
                self._watching -= 1
                last = self._watching == 0
            if last:
                jax.monitoring.unregister_event_time_span_listener(
                    self._on_compile)
                with self._lock:
                    held, self._compiles = self._compiles, {}
                for tid, pending in held.values():
                    self._book_compiles(tid, pending)

    def _on_compile(self, event: str, start: float, end: float,
                    fun_name: Any = None, **_kw) -> None:
        """JAX's listener: called on the compiling thread when a phase
        ends, with the phase's ``time.time()`` start and end.  Phases
        this one holds (they started at or after it) are dropped: their
        time is this one's.  A backend compile ends a pipeline, so what
        the thread holds then is final and is booked.  Kept lean: a
        kernel's trace alone reports a few hundred nested phases."""
        name = COMPILE_SPANS.get(event)
        if name is None:
            return
        stack = self._stack()
        parent = stack[-1].span_id if stack else None
        ident = threading.get_ident()
        with self._lock:
            held = self._compiles.get(ident)
            if held is None:
                held = self._compiles[ident] = (
                    threading.current_thread().name, [])
            pending = held[1]
            while pending and pending[-1][0] >= start:
                pending.pop()
            pending.append((start, end, name, fun_name, parent))
            if name != "compile.backend":
                return
            del self._compiles[ident]
        self._book_compiles(*held)

    def _book_compiles(self, tid: str, pending: list) -> None:
        # JAX's wall clock → this tracer's clock
        shift = self._clock() - self._epoch - time.time()
        for start, end, name, fun, parent in pending:
            self._book(name, start + shift, end - start, tid,
                       next(self._ids), parent,
                       {"fun": fun} if fun is not None else None)

    def event(self, name: str, **attrs) -> None:
        """Emit a zero-duration instant event (sinks only — it does not
        touch the per-name busy aggregates)."""
        with self._lock:
            sinks = tuple(self._sinks)
        if not sinks:
            return
        ev = {"ev": "instant", "name": name,
              "ts": self._clock() - self._epoch,
              "tid": threading.current_thread().name}
        if attrs:
            ev["args"] = attrs
        for s in sinks:
            s.emit(ev)

    # -- aggregates --------------------------------------------------------
    def total(self, name: str) -> float:
        """Accumulated busy seconds of every closed span called ``name``."""
        with self._lock:
            return self._totals.get(name, 0.0)

    def count(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def totals(self) -> Dict[str, float]:
        """Snapshot of all per-name busy totals — diff two snapshots to
        scope aggregation to one run (several runs may share a tracer)."""
        with self._lock:
            return dict(self._totals)


class NullTracer:
    """Disabled tracing: every ``span()`` returns one shared no-op
    context manager; aggregates read as zero.  Near-zero overhead —
    instrument unconditionally, pass ``NULL_TRACER`` to turn it off."""

    emitting = False

    def span(self, name: str, **attrs) -> _NullCtx:
        return _NULL_CTX

    def event(self, name: str, **attrs) -> None:
        return None

    def watch_jax(self):
        return contextlib.nullcontext(self)

    def add_sink(self, sink) -> None:
        raise ValueError("NullTracer cannot emit — use a Tracer")

    def close(self) -> None:
        return None

    def total(self, name: str) -> float:
        return 0.0

    def count(self, name: str) -> int:
        return 0

    def totals(self) -> Dict[str, float]:
        return {}


#: the shared disabled tracer — instrumented code defaults to this
NULL_TRACER = NullTracer()
