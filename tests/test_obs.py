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
