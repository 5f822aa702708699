"""One run of a dataset-generation cell: set-up, the measured window, the
traced slice and the check against the plain reference.

The window drives the program's own path, the one ``DatasetJob.run``
takes: ``ShardExecutor.run`` over the job's ``source``, into a
``ShardWriter`` on the job's planned manifest.  Only the order of the
records is the benchmark's (see ``order.py``): they are fed in the
traffic's ``shard_order`` from a sequence that stops handing them out at
the deadline, and the window ends when the executor has committed the
last shard it took, so the drain is inside it.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import importlib
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from bench import order as order_mod
from bench import trace_reduce

#: the monitoring event JAX records for each backend compile, and for
#: each program loaded from the persistent compilation cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the host annotation that puts the tracer's clock on the trace's clock
ANCHOR = "bench.anchor"


def job_seed(seed: int) -> int:
    """The program's seed for a benchmark seed.  JAX keys hold 32 bits
    without x64, so wider seeds are hashed, not truncated: two seeds
    that differ above bit 31 still draw different streams."""
    digest = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


@dataclasses.dataclass
class Context:
    """What the per-layer metric readers read."""
    window_s: float                     # the window, less trace start/stop
    spans: Dict[str, float]             # busy seconds per span, in window
    counters: Dict[str, float]          # counter growth in the window
    compiles_in_window: int
    trace: Optional[trace_reduce.Summary]
    peaks: Dict[str, object]


class _FirstClock:
    """``time.perf_counter`` that remembers its first reading: the epoch
    a ``Tracer`` built on it measures span times from."""

    def __init__(self):
        self.first: Optional[float] = None

    def __call__(self) -> float:
        t = time.perf_counter()
        if self.first is None:
            self.first = t
        return t


def log(msg: str, t_start: float) -> None:
    print(f"[{time.perf_counter() - t_start:8.2f} s] {msg}",
          file=sys.stderr, flush=True)


def _counters(registry) -> Dict[str, float]:
    return {m["name"]: float(m["value"]) for m in registry.snapshot()
            if m.get("kind") == "counter"}


def _warm_chunk_sizes(job, records) -> int:
    """Compile each chunk size that ``records`` hold through the call the
    chunk source makes per chunk, without fetching the result.  The
    program also builds programs per chunk size around its kernel call;
    a size first met inside the window compiles them there.  Returns the
    sizes warmed."""
    import jax

    from repro.core import rmat

    sched = job.scheduler
    first = {}
    for rec in records:
        for i in rec.chunk_indices:
            ck = sched.chunk(i)
            first.setdefault(ck.n_edges, ck)
    for ck in first.values():
        jax.block_until_ready(rmat.sample_chunk(
            sched.key_for(ck), job.fit, ck, sched.k_pref, sched.thetas,
            dtype=job.dtype, backend=job.backend))
    return len(first)


def _executor(job, writer, tracer, registry):
    from repro.datastream import ShardExecutor
    return ShardExecutor(
        job.source, writer, features=job.features, seed=job.seed,
        bipartite=job.fit.bipartite, feature_batch=job._feature_batch(),
        pipeline_depth=job.pipeline_depth, host_workers=job.host_workers,
        tracer=tracer, metrics=registry)


def _check(config: dict, out_dir: str, window_ids: List[int]
           ) -> Dict[str, float]:
    """The numbers compared, from the shards the window committed, read
    back from disk by the configuration's plain reference."""
    ref = importlib.import_module(f"bench.reference.{config['reference']}")
    kron = ref.Kronecker(**{k: config["kronecker"][k]
                            for k in ("a", "b", "c", "d", "n", "m", "E")})
    pl = ref.plan(kron, config["job"]["shard_edges"])
    done = ref.committed_records(out_dir)
    arrays, problems = {}, []
    for sid in window_ids:
        if sid in done:
            cols, why = ref.read_shard(out_dir, done[sid])
            problems += why
            if not why:
                arrays[sid] = cols
    numbers = {"shards_committed": len(arrays) + len(problems),
               "shards_missing": len([s for s in window_ids
                                      if s not in done]),
               "shards_unreadable": len(problems)}
    numbers.update(ref.compare(pl, arrays))
    numbers["problems"] = problems[:5]
    return numbers


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Each compared number beside its limit; correct when every number
    is at or under its limit and at least one shard was committed."""
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in limits.items()}
    ok = numbers.get("shards_committed", 0) > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def run(config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, t_start: float,
        readers: Dict[str, Tuple[str, Callable[[Context],
                                               Optional[float]]]],
        device: dict, peaks: dict, work_root: Optional[str] = None,
        expect_backend: Optional[str] = None) -> dict:
    """One run of the cell; returns the result line.  ``readers`` maps
    each per-layer metric to its unit and reader; ``device`` is the
    platform, kind and count JAX reports."""
    import jax

    from repro.core.structure import KroneckerFit
    from repro.datastream import DatasetJob, ShardWriter
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.sinks import MemorySink
    from repro.obs.trace import Tracer

    compiles: List[float] = []

    def on_event(name, _dur, **_kw):
        if name == COMPILE_EVENT:
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    work = tempfile.mkdtemp(prefix="bench-", dir=work_root)
    try:
        kc, jc = config["kronecker"], config["job"]
        fit = KroneckerFit(**kc)
        clock = _FirstClock()
        sink = MemorySink() if trace else None
        tracer = Tracer(sinks=[sink] if sink else None, clock=clock)
        registry = MetricsRegistry()
        out_dir = os.path.join(work, "dataset")
        job = DatasetJob(fit, out_dir, shard_edges=jc["shard_edges"],
                         seed=job_seed(seed), mode=jc["mode"],
                         backend=jc["backend"], id_dtype=jc["id_dtype"],
                         pipeline_depth=jc["pipeline_depth"],
                         host_workers=jc["host_workers"],
                         tracer=tracer, metrics=registry)
        want = expect_backend or jc["expect_backend"]
        if job.backend != want:
            raise RuntimeError(f"backend {job.backend!r} resolved, the "
                               f"configuration runs {want!r}")
        manifest = job.plan()
        writer = ShardWriter(out_dir, manifest)
        order = order_mod.shard_order(traffic["shard_order"],
                                      len(manifest.shards))
        # the order's first shard warms up, the window takes the rest;
        # the chunk sizes of the window's first ``warm_positions`` shards
        # (those a window reaches) are compiled before it
        warm_id, window_recs = order[0], [manifest.shards[i]
                                          for i in order[1:]]
        log(f"plan: {len(manifest.shards)} shards, k_pref {job.k_pref}",
            t_start)
        warmed = _warm_chunk_sizes(
            job, window_recs[:int(traffic["warm_positions"])])
        log(f"warm-up: {warmed} chunk sizes compiled", t_start)
        _executor(job, writer, tracer, registry).run(
            [manifest.shards[warm_id]])
        log(f"warm-up: shard {warm_id} committed", t_start)

        trace_dir = os.path.join(work, "trace")
        t_from = int(traffic["trace_from"])
        t_to = t_from + int(traffic["trace_shards"])
        slice_ = {"on": False, "perf": [None, None], "cost": 0.0}

        def start_trace():
            t = time.perf_counter()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            slice_["perf"][0] = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(ANCHOR):
                pass
            slice_["on"] = True
            slice_["cost"] += time.perf_counter() - t

        def stop_trace():
            t = time.perf_counter()
            slice_["perf"][1] = time.perf_counter_ns()
            jax.profiler.stop_trace()
            slice_["on"] = False
            slice_["cost"] += time.perf_counter() - t

        def on_next(pos):
            if not trace:
                return
            if pos == t_from:
                start_trace()
            elif pos == t_to and slice_["on"]:
                stop_trace()

        spans0, counters0 = tracer.totals(), _counters(registry)
        executor = _executor(job, writer, tracer, registry)
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        seq = order_mod.UntilDeadline(window_recs, t0 + seconds, on_next)
        try:
            stats = executor.run(seq)
        finally:
            if slice_["on"]:
                stop_trace()
        t1 = time.perf_counter()
        writer.checkpoint()
        log(f"window: {stats.n_shards} shards in {t1 - t0:.2f} s", t_start)

        taken = window_recs[:seq.handed_out]
        edges = sum(r.n_edges for r in taken[:stats.n_shards])
        spans1, counters1 = tracer.totals(), _counters(registry)
        ctx = Context(
            window_s=(t1 - t0) - slice_["cost"],
            spans={k: v - spans0.get(k, 0.0) for k, v in spans1.items()},
            counters={k: v - counters0.get(k, 0.0)
                      for k, v in counters1.items()},
            compiles_in_window=sum(t0 <= t <= t1 for t in compiles),
            trace=None, peaks=peaks)
        stats_mem = jax.devices()[0].memory_stats() or {}
        memory_peak = int(stats_mem.get("peak_bytes_in_use", 0))

        main_tid = threading.current_thread().name
        host_spans = ([(e["name"], e["ts"] + clock.first, e["dur"])
                       for e in sink.spans() if e["tid"] == main_tid]
                      if sink else [])
        del executor, job, writer, manifest
        gc.collect()

        if trace and slice_["perf"][1] is not None:
            ctx.trace = _reduce(trace_dir, slice_["perf"], host_spans)

        numbers = _check(config, out_dir, [r.shard_id for r in taken])
        log("check done", t_start)
        ok, checks = verdict(numbers, config["limits"])
        result = {
            "correct": ok,
            "attempted": len(taken),
            "failed": len(taken) - stats.n_shards
            + numbers["shards_unreadable"] + numbers["shards_off_plan"],
            "metrics": {},
            "device": dict(device, memory_peak_bytes=memory_peak),
        }
        if trace:
            for name, (unit, read) in readers.items():
                value = read(ctx)
                if value is not None:
                    result["metrics"][name] = {"value": value, "unit": unit}
            if ctx.trace is not None:
                result["device"]["busy_s"] = ctx.trace.busy_ns / 1e9
                result["device"]["window_s"] = ctx.trace.slice_ns / 1e9
                result["breakdown"] = {
                    "device_ops": [list(x) for x in ctx.trace.device_ops],
                    "idle_gaps": [list(x) for x in ctx.trace.idle_gaps]}
        else:
            result["metrics"] = {
                "edges_per_s": {"value": edges / (t1 - t0),
                                "unit": "edges/s"},
                "setup_s": {"value": setup_s, "unit": "s"}}
        result["window"] = {"seconds": t1 - t0, "edges": edges,
                            "shards": stats.n_shards,
                            "problems": numbers["problems"]}
        result["checks"] = checks
        return result
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        shutil.rmtree(work, ignore_errors=True)


def _reduce(trace_dir: str, perf_ns, host_spans) -> trace_reduce.Summary:
    """The traced slice, on the trace's clock: the anchor annotation
    gives the offset between ``perf_counter`` and the trace."""
    import jax

    profile = jax.profiler.ProfileData.from_file(
        trace_reduce.find_xplane(trace_dir))
    anchors = trace_reduce.host_events(profile, ANCHOR)
    if not anchors:
        raise RuntimeError("the trace holds no anchor annotation")
    offset = anchors[0][1] - perf_ns[0]
    lo, hi = perf_ns[0] + offset, perf_ns[1] + offset
    spans = [(n, s * 1e9 + offset, d * 1e9) for n, s, d in host_spans]
    return trace_reduce.summarize(trace_reduce.device_events(profile), lo,
                                  hi, spans)
