"""Observability layer tests (repro.obs + scripts/report_run.py):
thread-aware span nesting, disabled-mode cost bound, JSONL crash-safety
(torn tail survives a resume append), metric semantics, the Chrome-trace
export, the unified BENCH envelope, and reconciliation of the
span-derived executor/job timings with the report_run breakdown on a
golden-seed run."""
import importlib.util
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (SCHEMA_VERSION, JsonlSink, MemorySink,
                       MetricsRegistry, Tracer, bench_envelope,
                       load_events, to_chrome_trace)
from repro.obs.trace import NULL_TRACER


def _load_script(name):
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- tracer core -------------------------------------------------------------

def test_span_nesting_single_thread():
    sink = MemorySink()
    tr = Tracer([sink])
    with tr.span("outer", shard=3) as outer:
        with tr.span("inner") as inner:
            time.sleep(0.001)
    assert inner.parent_id == outer.span_id
    assert outer.parent_id is None
    assert inner.dur > 0 and outer.dur >= inner.dur
    assert tr.total("outer") == pytest.approx(outer.dur)
    assert tr.count("inner") == 1
    evs = {e["name"]: e for e in sink.spans()}
    assert evs["inner"]["parent"] == evs["outer"]["id"]
    assert evs["outer"]["args"] == {"shard": 3}
    # inner closed first, so it is emitted first — and both carry the
    # shared-timeline ts (inner starts inside outer's interval)
    assert evs["inner"]["ts"] >= evs["outer"]["ts"]


def test_span_nesting_is_per_thread():
    """Each thread keeps its own stack: a worker's top-level span must
    NOT parent under whatever span the main thread has open, and every
    event carries the emitting thread's name."""
    sink = MemorySink()
    tr = Tracer([sink])

    def work(k):
        with tr.span("outer", w=k):
            with tr.span("inner", w=k):
                time.sleep(0.002)

    with tr.span("run"):
        threads = [threading.Thread(target=work, args=(k,),
                                    name=f"obs-worker-{k}")
                   for k in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    by_id = {e["id"]: e for e in sink.spans()}
    outers = sink.spans("outer")
    inners = sink.spans("inner")
    assert len(outers) == len(inners) == 3
    assert {e["tid"] for e in outers} == {f"obs-worker-{k}"
                                          for k in range(3)}
    for inner in inners:
        parent = by_id[inner["parent"]]
        assert parent["name"] == "outer"
        assert parent["tid"] == inner["tid"]       # nesting never crosses
    for outer in outers:
        assert "parent" not in outer               # not under main's "run"
    assert tr.count("outer") == 3
    assert tr.total("inner") <= tr.total("outer")


def test_tracer_totals_snapshot_diff():
    tr = Tracer()
    with tr.span("a"):
        pass
    before = tr.totals()
    with tr.span("a"):
        time.sleep(0.001)
    delta = tr.total("a") - before["a"]
    assert delta >= 0.001
    assert tr.count("a") == 2


def test_disabled_mode_overhead_bound():
    """NULL_TRACER spans must stay effectively free: the instrumented
    hot paths run with it by default.  Bound the per-span cost loosely
    (shared CI boxes jitter) — the real <2% end-to-end budget is checked
    by benchmarks/executor_overlap.py."""
    n = 50_000
    t0 = time.perf_counter()
    for i in range(n):
        with NULL_TRACER.span("x", shard=i):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 20e-6, f"null span cost {per_span * 1e6:.2f}us"
    assert NULL_TRACER.total("x") == 0.0 and NULL_TRACER.count("x") == 0
    assert NULL_TRACER.span("x").dur == 0.0
    with pytest.raises(ValueError, match="cannot emit"):
        NULL_TRACER.add_sink(MemorySink())


# -- sinks: JSONL crash-safety ----------------------------------------------

def test_jsonl_torn_tail_survives_resume_append(tmp_path):
    """Kill-mid-write leaves a torn trailing line; the resumed job
    appends to the same log.  The merged file must still parse, losing
    at most the one record that shares the torn line."""
    path = str(tmp_path / "trace.jsonl")
    tr = Tracer([JsonlSink(path, flush_every=1)])
    for k in range(4):
        with tr.span("leg1", k=k):
            pass
    tr.close()
    with open(path, "ab") as f:               # crash mid-append
        f.write(b'{"ev":"span","name":"torn","ts":1.0,"dur"')
    tr2 = Tracer([JsonlSink(path, flush_every=1)])   # resume leg appends
    for k in range(3):
        with tr2.span("leg2", k=k):
            pass
    tr2.close()
    evs = load_events(path)
    names = [e["name"] for e in evs if e.get("ev") == "span"]
    assert names.count("leg1") == 4
    assert "torn" not in names
    # the resume sink's meta record merged into the torn line and is
    # dropped with it; every span after parses
    assert names.count("leg2") == 3
    assert sum(e.get("ev") == "meta" for e in evs) == 1


def test_jsonl_tolerates_corrupt_and_blank_lines(tmp_path):
    path = str(tmp_path / "log.jsonl")
    good = {"ev": "span", "name": "ok", "ts": 0.0, "dur": 1.0,
            "tid": "t", "id": 1}
    with open(path, "wb") as f:
        f.write(json.dumps(good).encode() + b"\n")
        f.write(b"\n")                        # blank
        f.write(b"not json at all\n")         # corrupt
        f.write(b"[1, 2, 3]\n")               # valid JSON, not an event dict
        f.write(json.dumps(good).encode() + b"\n")
    evs = load_events(path)
    assert len(evs) == 2 and all(e["name"] == "ok" for e in evs)


def test_jsonl_close_idempotent_and_emit_after_close(tmp_path):
    path = str(tmp_path / "log.jsonl")
    sink = JsonlSink(path, flush_every=1000)  # force buffering
    sink.emit({"ev": "span", "name": "a"})
    sink.close()
    sink.close()
    sink.emit({"ev": "span", "name": "late"})    # dropped, no raise
    names = [e["name"] for e in load_events(path)]
    assert names == ["a"]                     # close flushed the buffer


# -- metrics -----------------------------------------------------------------

def test_metrics_registry_semantics():
    reg = MetricsRegistry()
    c = reg.counter("rows", "rows")
    c.inc(5)
    c.inc(2.5)
    assert reg.counter("rows").value == 7.5   # get-or-create returns same
    g = reg.gauge("depth")
    g.set(3)
    g.set(1)
    assert g.value == 1 and g.max == 3
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("rows")
    snap = {m["name"]: m for m in reg.snapshot()}
    assert snap["rows"]["kind"] == "counter"
    assert snap["depth"]["max"] == 3


def test_histogram_percentiles_bounded_memory():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "s", )
    h._cap = 128                              # shrink reservoir for test
    for v in range(10_000):
        h.observe(float(v))
    assert h.count == 10_000
    assert len(h._samples) == 128             # bounded despite 10k obs
    snap = h.snapshot()
    assert snap["min"] == 0.0 and snap["max"] == 9999.0
    assert snap["mean"] == pytest.approx(4999.5)
    # uniform reservoir: quantiles land near truth even at 128 samples
    assert abs(snap["p50"] - 5000) < 2000
    assert snap["p95"] > snap["p50"] >= snap["min"]


def test_bench_envelope_schema():
    env = bench_envelope("unit", {"x": 1}, extra={"note": "t"})
    assert env["schema_version"] == SCHEMA_VERSION
    assert env["suite"] == "unit" and env["metrics"] == {"x": 1}
    assert env["note"] == "t"
    for key in ("git_sha", "host", "python", "cpu_count", "jax", "platform",
                "device_kind", "device_count"):
        assert key in env["env"]
    assert env["env"]["platform"] == jax.devices()[0].platform
    assert env["env"]["device_count"] == len(jax.devices())
    json.dumps(env)                           # serializable as-is


def test_jax_profile_start_failure_raises(monkeypatch, tmp_path):
    """A run that asked for a device trace fails when the profiler cannot
    start, instead of finishing without one."""
    from repro.obs import jaxprof

    def broken(log_dir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", broken)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with jaxprof.trace(str(tmp_path)):
            pass
    assert not jaxprof.profiling()


# -- JAX compiles as spans, spans as profiler annotations -------------------

_BACKEND = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"


class _JaxEvents:
    """The test's own count of JAX's compile events (duration listener,
    so independent of the tracer's time-span listener)."""

    def __init__(self):
        self.events = []

    def __call__(self, event, dur, **kw):
        if event.startswith("/jax/core/compile/"):
            self.events.append((event, dur, kw.get("fun_name")))

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)

    def of(self, event):
        return [e for e in self.events if e[0] == event]


def _time_span_listeners():
    from jax._src import monitoring
    return len(monitoring.get_event_time_span_listeners())


def test_watch_jax_books_compiles_under_the_open_span():
    sink = MemorySink()
    tr = Tracer([sink])

    def fresh_program(x):
        return jnp.sin(x) * 3 + 1

    with _JaxEvents() as seen, tr.watch_jax():
        with tr.span("struct.dispatch", chunk=0) as sp:
            jax.block_until_ready(jax.jit(fresh_program)(jnp.ones(17)))
    backend = sink.spans("compile.backend")
    lower = sink.spans("compile.lower")
    assert len(backend) == len(seen.of(_BACKEND)) >= 1
    mine = [e for e in backend + lower
            if "fresh_program" in e["args"]["fun"]]
    assert {e["name"] for e in mine} == {"compile.lower", "compile.backend"}
    assert all(e["parent"] == sp.span_id for e in mine)
    # closed spans on the caller's thread, inside the span's interval
    outer = sink.spans("struct.dispatch")[0]
    for e in mine:
        assert e["tid"] == threading.current_thread().name
        assert outer["ts"] - 1e-3 <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert tr.count("compile.backend") == len(backend)
    assert tr.total("compile.backend") == pytest.approx(
        sum(e["dur"] for e in backend))


def test_watch_jax_trace_total_is_the_union_of_nested_traces():
    """An eager Pallas call traces hundreds of primitives inside its own
    ``wrapped`` trace (and again while lowering); the booked trace time
    is the outer trace's, not the sum, and the sink gets one span."""
    from repro.core.sampler import get_backend

    be = get_backend("pallas_bits")
    th = np.tile(np.array([[0.57, 0.19, 0.19, 0.05]], np.float32), (8, 1))
    # compile the bit draws first: only the kernel's program is left
    jax.block_until_ready(be.sample_parts(jax.random.PRNGKey(0), th, 8, 8,
                                          1024)[0].lo)
    sink = MemorySink()
    tr = Tracer([sink])
    with _JaxEvents() as seen, tr.watch_jax():
        with tr.span("struct.dispatch"):
            out = be.sample_parts(jax.random.PRNGKey(1), th, 8, 8, 1024)
    jax.block_until_ready(out[0].lo)
    traces = seen.of(_TRACE)
    outermost = [d for _, d, fun in traces if fun == "wrapped"]
    assert len(outermost) == 1 and len(traces) > 100
    total = tr.total("compile.trace")
    assert 0 < total <= outermost[0] + 1e-9
    assert total < sum(d for _, d, _ in traces)
    spans = sink.spans("compile.trace")
    assert [e["args"]["fun"] for e in spans] == ["wrapped"]
    assert [e["args"]["fun"] for e in sink.spans("compile.backend")] \
        == [fun for _, _, fun in seen.of(_BACKEND)]
    # the phases do not overlap, so together they fit in the dispatch
    phases = sum(tr.total(n) for n in ("compile.trace", "compile.lower",
                                       "compile.backend"))
    assert phases <= tr.total("struct.dispatch")


def test_watch_jax_stops_booking_on_exit_and_null_tracer_registers_nothing():
    tr = Tracer()
    n0 = _time_span_listeners()
    with tr.watch_jax():
        with tr.watch_jax():                # re-entered: one listener
            assert _time_span_listeners() == n0 + 1
        assert _time_span_listeners() == n0 + 1
    assert _time_span_listeners() == n0
    booked = tr.count("compile.backend")

    def after_exit(x):
        return x * 5 - 2

    with _JaxEvents() as seen:
        jax.block_until_ready(jax.jit(after_exit)(jnp.ones(9)))
    assert seen.of(_BACKEND)
    assert tr.count("compile.backend") == booked
    assert tr.total("compile.trace") == 0.0

    with NULL_TRACER.watch_jax():
        assert _time_span_listeners() == n0
    assert NULL_TRACER.totals() == {}


class _Annotations:
    """Stand-in for ``jax.profiler.TraceAnnotation``: records each
    range's name, thread and whether it closed."""

    def __init__(self):
        self.opened = []
        self.closed = []

    def __call__(self, name):
        rec = self

        class Ann:
            def __enter__(self):
                rec.opened.append((name, threading.current_thread().name))
                return self

            def __exit__(self, *exc):
                rec.closed.append(name)

        return Ann()


def test_spans_open_trace_annotations_only_while_profiling(monkeypatch,
                                                           tmp_path):
    from repro.obs import jaxprof

    anns = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", anns)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    tr = Tracer()
    with tr.span("struct"):
        pass
    assert anns.opened == []

    def worker():
        with tr.span("write", shard=1):
            pass

    with jaxprof.trace(str(tmp_path)):
        with tr.span("struct"):
            with tr.span("struct.fetch", chunk=0):
                pass
        t = threading.Thread(target=worker, name="shard-flush")
        t.start()
        t.join()
        with NULL_TRACER.span("feat"):
            pass
    me = threading.current_thread().name
    assert anns.opened == [("struct", me), ("struct.fetch", me),
                           ("write", "shard-flush")]
    assert sorted(anns.closed) == ["struct", "struct.fetch", "write"]
    with tr.span("struct"):
        pass
    assert len(anns.opened) == 3


def test_report_run_puts_compiles_in_a_compile_row_outside_busy_stages():
    sink = MemorySink()
    tr = Tracer([sink])

    def reported_program(x):
        return jnp.cos(x) - 4

    with tr.watch_jax(), tr.span("run"):
        with tr.span("struct", shard=0):
            jax.block_until_ready(jax.jit(reported_program)(jnp.ones(11)))
    report_run = _load_script("report_run")
    rep = report_run.summarize(sink.events)
    phases = {n: tr.total(f"compile.{n}")
              for n in ("trace", "lower", "backend")}
    assert phases["backend"] > 0
    assert rep["compile"]["total_s"] == pytest.approx(sum(phases.values()))
    for n, v in phases.items():
        assert rep["compile"][n] == pytest.approx(v)
    assert not any(k.startswith("compile") for k in report_run.BUSY_STAGES)
    assert rep["busy_s"] == pytest.approx(tr.total("struct"))
    row = [ln for ln in report_run.format_report(rep).splitlines()
           if ln.startswith("compile ")]
    assert len(row) == 1 and "backend" in row[0]


# -- chrome trace export -----------------------------------------------------

def test_chrome_trace_export_structure(tmp_path):
    sink = MemorySink()
    tr = Tracer([sink])
    with tr.span("struct", shard=0):
        with tr.span("struct.dispatch"):
            pass
    tr.event("checkpoint", shard=0)
    trace = to_chrome_trace(sink.events, process_name="unit")
    evs = trace["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    xs = [e for e in evs if e["ph"] == "X"]
    inst = [e for e in evs if e["ph"] == "i"]
    assert any(e["name"] == "process_name" for e in meta)
    assert {e["name"] for e in xs} == {"struct", "struct.dispatch"}
    assert len(inst) == 1 and inst[0]["name"] == "checkpoint"
    for e in xs:                              # µs units, category = prefix
        assert e["dur"] >= 0 and e["cat"] == "struct"
    # thread metadata names the emitting thread
    tnames = [e["args"]["name"] for e in meta
              if e["name"] == "thread_name"]
    assert threading.current_thread().name in tnames


# -- reconciliation: spans vs executor stats vs report_run -------------------

def _summarize(events):
    return _load_script("report_run").summarize(events)


def test_executor_spans_reconcile_with_stats(tmp_path):
    """The stage seconds ExecutorStats reports and the ones report_run
    re-derives from the emitted event log are the same measurements —
    they must agree to well under the 5% acceptance bound."""
    from repro.datastream import Manifest, ShardExecutor, ShardRecord, \
        ShardSource, ShardWriter

    class SlowSource(ShardSource):
        name = "slow"

        def generate(self, rec):
            time.sleep(0.01)
            ids = np.full(rec.n_edges, rec.shard_id, np.int32)
            return {"src": ids, "dst": ids.copy()}

    n_shards, n_edges = 6, 64
    recs = [ShardRecord(i, f"shard-{i:05d}", [], n_edges)
            for i in range(n_shards)]
    manifest = Manifest(fit={}, seed=0, k_pref=0, shard_edges=n_edges,
                        num_workers=1, dtype="int32",
                        total_edges=n_shards * n_edges, n_src=1 << 20,
                        n_dst=1 << 20, bipartite=False, theta=[],
                        theta_digest="", shards=recs)
    sink = MemorySink()
    tracer = Tracer([sink])
    metrics = MetricsRegistry()
    writer = ShardWriter(str(tmp_path / "out"), manifest)
    ex = ShardExecutor(SlowSource(), writer, pipeline_depth=2,
                       host_workers=2, tracer=tracer, metrics=metrics)
    stats = ex.run(manifest.shards)

    rep = _summarize(sink.events)
    assert rep["stage_s"]["struct"] == pytest.approx(stats.struct_s,
                                                     rel=0.05, abs=1e-4)
    assert rep["stage_s"]["write"] == pytest.approx(stats.write_s,
                                                    rel=0.05, abs=1e-4)
    assert rep["wall_s"] == pytest.approx(stats.wall_s, rel=0.05)
    assert rep["overlap"] == pytest.approx(stats.overlap, rel=0.05)
    # stall attribution matches the stats aggregate
    assert rep["stall"]["total_s"] == pytest.approx(stats.stall_s,
                                                    rel=0.05, abs=1e-4)
    # the journal sub-span nests under its write span
    by_id = {e["id"]: e for e in sink.spans()}
    journals = sink.spans("write.journal")
    assert len(journals) == n_shards
    assert all(by_id[j["parent"]]["name"] == "write" for j in journals)
    # metrics side: adopted writer counted every committed row
    assert metrics.counter("writer.rows_written").value \
        == n_shards * n_edges
    assert metrics.counter("writer.shards_committed").value == n_shards


@pytest.mark.slow
def test_golden_seed_job_reconciles_with_report(tmp_path):
    """Acceptance: a real (golden-seed) pipelined DatasetJob run traced
    to an event log reconciles — report_run's span-derived stage times
    match job.timings within 5%."""
    from repro.core.structure import KroneckerFit
    from repro.datastream import DatasetJob, ShardedGraphDataset

    fit = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=13, m=13,
                       E=1 << 16)
    path = str(tmp_path / "trace.jsonl")
    tracer = Tracer([JsonlSink(path, flush_every=1)])
    metrics = MetricsRegistry()
    job = DatasetJob(fit, str(tmp_path / "ds"), shard_edges=1 << 14,
                     seed=0, backend="xla", pipeline_depth=2,
                     host_workers=2, tracer=tracer, metrics=metrics)
    job.run()
    tracer.close()

    assert ShardedGraphDataset(str(tmp_path / "ds")).total_edges == fit.E
    rep = _summarize(load_events(path))
    t = job.timings
    assert rep["stage_s"]["struct"] == pytest.approx(t["gen_struct_s"],
                                                     rel=0.05, abs=0.01)
    assert rep["stage_s"]["write"] == pytest.approx(t["write_s"],
                                                    rel=0.05, abs=0.01)
    assert rep["wall_s"] == pytest.approx(t["wall_s"], rel=0.05)
    assert rep["stall"]["total_s"] == pytest.approx(t["stall_s"],
                                                    rel=0.05, abs=0.01)
    assert metrics.counter("writer.rows_written").value == fit.E
    # the report formats without error and names every busy stage
    text = _load_script("report_run").format_report(rep)
    assert "struct" in text and "overlap" in text
