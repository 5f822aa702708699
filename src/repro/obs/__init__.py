"""``repro.obs`` — tracing, metrics and profiling for the pipeline.

The stack generates (and now fits) synthetic graphs at sizes where
one-off print timing stops working; this package is the unified
observability layer every hot path reports through:

* ``trace``   — span tracer (``tracer.span("struct", shard=k)``) with
  thread-aware nesting, monotonic clocks, per-name busy aggregation and
  near-zero cost when disabled (``NULL_TRACER``).  The executor/
  pipeline stage timings (``gen_struct_s``/``gen_feat_s``/
  ``gen_align_s``/``gen_write_s``/``gen_overlap``) are *derived from*
  these spans — the ad-hoc lock-guarded floats they replaced are gone.
  ``tracer.watch_jax()`` (open for a whole ``ShardExecutor.run``) books
  JAX's compile events as ``compile.trace``/``compile.lower``/
  ``compile.backend`` spans (``fun`` = JAX's function name) under the
  span open where they ran.  The struct stage adds ``struct.fetch``
  spans (each device-to-host copy, with ``chunk``/``shard`` and
  ``bytes``) and the ``struct.chunks``/``struct.bytes_fetched``
  counters.
* ``metrics`` — counter/gauge/histogram registry (rows written, bytes
  flushed, queue depth, backpressure stalls, shard commit latency with
  p50/p95/p99) plus the unified ``BENCH_*.json`` envelope
  (``bench_envelope``: schema version, git SHA, host/device info).
* ``sinks``   — in-memory (tests) and crash-tolerant JSONL event logs
  (written next to the dataset manifest by ``--trace``).
* ``export``  — Chrome-trace/Perfetto conversion of an event log, so a
  pipelined run renders as a Gantt of struct/feature/write overlap.
* ``jaxprof`` — optional ``jax.profiler`` trace of a run
  (``--jax-profile``); while it records, every tracer span is also a
  ``TraceAnnotation`` of its name, so the spans sit on the device
  trace's clock.

``scripts/report_run.py`` turns an event log into a per-stage
breakdown, overlap factor and queue-stall attribution.
"""
from repro.obs.export import export_chrome_trace, to_chrome_trace
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               SCHEMA_VERSION, bench_envelope, run_env,
                               write_bench)
from repro.obs.sinks import JsonlSink, MemorySink, iter_events, load_events
from repro.obs.trace import NULL_TRACER, NullTracer, Span, Tracer

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "Span",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "MemorySink", "JsonlSink", "load_events", "iter_events",
    "to_chrome_trace", "export_chrome_trace",
    "bench_envelope", "write_bench", "run_env", "SCHEMA_VERSION",
]
