#!/usr/bin/env python
"""Materialize a synthetic graph dataset to disk (repro.datastream).

    PYTHONPATH=src python scripts/generate_dataset.py \
        --fit demo --edges 1e7 --shard-edges 1e6 --out /tmp/ds

Interrupt it (Ctrl-C / SIGKILL) and re-run with ``--resume``: finished
shards are skipped and the remainder is regenerated deterministically.
``--fit`` takes the built-in ``demo`` θ or a path to a JSON file with
KroneckerFit fields ({"a":..,"b":..,"c":..,"d":..,"n":..,"m":..,"E":..}).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time


def parse_count(s: str) -> int:
    """'1e7', '10_000', '1<<20' style edge counts (repro.utils is the
    canonical implementation; imported lazily so ``--help`` works
    without PYTHONPATH)."""
    from repro.utils import parse_count as _parse_count
    return _parse_count(s)


def build_fit(args):
    from repro.core.structure import KroneckerFit
    E = parse_count(args.edges) if args.edges else None
    if args.fit == "demo":
        if E is None:
            raise SystemExit("--fit demo needs --edges")
        # avg degree 8 demo graph: 2^n nodes per partite
        n = max(4, math.ceil(math.log2(max(E // 8, 16))))
        return KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=n, m=n, E=E,
                            noise=args.noise)
    with open(args.fit) as f:
        d = json.load(f)
    if isinstance(d.get("fit"), dict):
        # fit_dataset.py output: KroneckerFit under "fit" + provenance
        d = d["fit"]
    fit = KroneckerFit(**d)
    if E is not None:
        fit = dataclasses.replace(fit, E=E)
    if args.noise:
        fit = dataclasses.replace(fit, noise=args.noise)
    return fit


def worker_path(path: str, worker_id: int) -> str:
    """Namespace a per-run artifact path for one worker process:
    ``trace.jsonl`` -> ``trace.w0.jsonl``."""
    root, ext = os.path.splitext(path)
    return f"{root}.w{int(worker_id)}{ext}"


def worker_flags(args, worker_id, num_workers: int) -> list:
    """Rebuild the CLI flags for one spawned worker stripe (or, with
    ``worker_id=None``, for the planning child) from the coordinator's
    parsed args.  Everything byte-relevant (fit, seed, shard size, mode,
    backend, dtype) passes through unchanged; the stripe is selected by
    ``--num-workers/--worker-id``; per-worker artifacts (trace, metrics)
    keep the parent's flag and are namespaced by the worker itself."""
    flags = ["--fit", args.fit, "--out", args.out,
             "--shard-edges", args.shard_edges,
             "--seed", str(args.seed), "--mode", args.mode,
             "--num-workers", str(num_workers),
             "--pipeline-depth", str(args.pipeline_depth),
             "--host-workers", str(args.host_workers)]
    if worker_id is not None:
        flags += ["--worker-id", str(worker_id)]
    if args.edges:
        flags += ["--edges", args.edges]
    if args.k_pref is not None:
        flags += ["--k-pref", str(args.k_pref)]
    if args.noise:
        flags += ["--noise", str(args.noise)]
    if args.backend:
        flags += ["--backend", args.backend]
    if args.id_dtype:
        flags += ["--id-dtype", args.id_dtype]
    if args.max_shards is not None:
        flags += ["--max-shards", str(args.max_shards)]
    if args.fused:
        flags += ["--fused"]
    if args.serial:
        flags += ["--serial"]
    if args.trace is not None:
        flags += (["--trace"] if args.trace == "auto"
                  else ["--trace", args.trace])
    if args.metrics_out:
        flags += ["--metrics-out", args.metrics_out]
    return flags


def plan_only(args, job) -> int:
    """``--plan-only``: write the plan (or, with ``--resume``, validate
    the existing one) and exit.  A chip belongs to one process at a
    time, so on a TPU host ``--num-workers`` above the host's chip count
    is refused before any worker starts."""
    import jax

    from repro.datastream import Manifest

    chips = jax.local_device_count()
    if args.num_workers and jax.default_backend() == "tpu" \
            and args.num_workers > chips:
        raise SystemExit(
            f"error: --num-workers {args.num_workers} exceeds the {chips} "
            "TPU chip(s) of this host — each worker process needs a chip "
            "of its own; run at most one worker per chip")
    try:
        if args.resume and Manifest.exists(args.out):
            job._load_validated()  # refuse resumes that change streams
        else:
            job.plan(overwrite=args.resume)
    except FileExistsError:
        raise SystemExit(
            f"error: {args.out} already holds a dataset — pass "
            "--resume to continue it, or choose a different --out")
    except ValueError as e:
        raise SystemExit(f"error: {e}")
    return 0


def run_cluster(args) -> int:
    """Coordinator mode: plan in a child process, stripe across
    ``--num-workers`` spawned processes, merge journals into the one
    manifest.  This process initializes no JAX backend: a chip belongs
    to one process at a time, and the planner and workers need it."""
    import subprocess

    from repro.datastream import ShardedGraphDataset
    from repro.distributed.cluster import ClusterCoordinator, ClusterError
    from repro.distributed.launcher import child_env

    script = os.path.abspath(__file__)
    planner = ([sys.executable, script, "--plan-only"]
               + worker_flags(args, None, args.num_workers)
               + (["--resume"] if args.resume else []))
    rc = subprocess.run(planner, env=child_env()).returncode
    if rc != 0:
        return rc
    coord = ClusterCoordinator(
        args.out,
        lambda w, W: [sys.executable, script] + worker_flags(args, w, W),
        num_workers=args.num_workers,
        log=lambda msg: print(f"cluster: {msg}", file=sys.stderr))
    t0 = time.time()
    try:
        manifest = coord.run()
    except ClusterError as e:
        raise SystemExit(f"error: {e}")
    dt = time.time() - t0
    done = manifest.done_edges()
    rounds = coord.report["rounds"]
    print(f"cluster: materialized {len(manifest.done_ids())}/"
          f"{len(manifest.shards)} shards, {done:,} edges in {dt:.1f}s "
          f"({done / max(dt, 1e-9):,.0f} edges/s) across "
          f"{args.num_workers} worker(s), {len(rounds)} round(s), "
          f"{sum(r['deaths'] for r in rounds)} death(s)",
          file=sys.stderr)
    if args.trace is not None:
        print(f"traces: {args.out}/trace.w*.jsonl "
              f"(scripts/report_run.py trace.w0.jsonl trace.w1.jsonl ... "
              f"for the merged stall report)", file=sys.stderr)
    if args.verify or args.verify_deep:
        ds = ShardedGraphDataset(args.out)
        problems = ds.verify(deep=True)
        if problems:
            print("VERIFY FAILED:", *problems, sep="\n  ",
                  file=sys.stderr)
            return 1
        print("verify: ok (deep, streamed crc)", file=sys.stderr)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--fit", default="demo",
                    help="'demo', a KroneckerFit JSON, or a "
                         "fit_dataset.py output (fit + provenance)")
    ap.add_argument("--edges", default=None,
                    help="total edge count E, e.g. 1e7 (overrides fit.E)")
    ap.add_argument("--shard-edges", default="1e6",
                    help="max edges per shard (memory bound), e.g. 1e6")
    ap.add_argument("--out", required=True, help="output dataset directory")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--k-pref", type=int, default=None,
                    help="prefix levels (default: auto from shard size)")
    ap.add_argument("--noise", type=float, default=0.0,
                    help="App. 9 per-level θ-noise amplitude")
    ap.add_argument("--mode", choices=("chunks", "device_steps"),
                    default="chunks")
    ap.add_argument("--backend", default=None,
                    choices=("auto", "xla", "pallas_bits", "pallas_prng"),
                    help="edge-sampler engine backend (repro.core.sampler): "
                         "'xla' = jit reference (runs everywhere), "
                         "'pallas_bits' = Pallas kernel with HBM bit "
                         "streams (interpret on CPU, compiled on TPU), "
                         "'pallas_prng' = TPU-only VMEM-resident PRNG "
                         "kernel (fastest). Default/auto picks by device; "
                         "the choice is recorded in the manifest and "
                         "validated on --resume (streams differ per "
                         "backend)")
    ap.add_argument("--id-dtype", default=None, choices=("int32", "int64"),
                    help="node id width (default: auto from the fit — "
                         "int32 up to 2^31 ids, int64 up to 2^62; int64 "
                         "needs no jax x64 in chunks mode)")
    ap.add_argument("--workers", type=int, default=1,
                    help="worker queues in the plan (see --worker)")
    ap.add_argument("--worker", type=int, default=None,
                    help="only materialize this worker's shard queue")
    ap.add_argument("--num-workers", type=int, default=None,
                    help="multi-PROCESS generation: spawn this many "
                         "worker processes, each running one stripe of "
                         "the plan, and merge their journals into the "
                         "one manifest (repro.distributed.cluster). "
                         "Output is byte-identical to the single-process "
                         "run. With --worker-id, run one stripe instead "
                         "of spawning")
    ap.add_argument("--worker-id", type=int, default=None,
                    help="run ONE stripe of an existing plan as this "
                         "worker (0..K-1 of --num-workers K): appends "
                         "completions to journal.w{k}.jsonl and never "
                         "rewrites manifest.json — the building block "
                         "the cluster coordinator spawns")
    ap.add_argument("--plan-only", action="store_true",
                    help="write the plan (with --resume: validate the "
                         "existing one) and exit without generating; "
                         "the coordinator runs this as a child so that "
                         "it never takes a chip itself")
    ap.add_argument("--max-shards", type=int, default=None,
                    help="stop after N shards (incremental progress)")
    ap.add_argument("--resume", action="store_true",
                    help="continue an interrupted job in --out")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="shards queued per executor stage: 0 = serial "
                         "loop, >=1 overlaps device struct sampling with "
                         "host feature decode and writer flush (output is "
                         "byte-identical either way; memory scales with "
                         "depth). Default 2")
    ap.add_argument("--host-workers", type=int, default=1,
                    help="threads in the executor's host feature stage "
                         "(per-shard draws are independent pure "
                         "functions, so >1 stays deterministic)")
    ap.add_argument("--fused", action="store_true",
                    help="run each shard's R-MAT descent as one fused "
                         "jitted device program (and the feature decode "
                         "too when a traceable generator rides along). "
                         "Byte-identical to the staged path; recorded as "
                         "provenance, never validated on --resume")
    ap.add_argument("--serial", action="store_true",
                    help="fully serial generation: pipeline depth 0 plus "
                         "no chunk double buffering (debug/benchmark "
                         "baseline)")
    ap.add_argument("--verify", action="store_true",
                    help="deep-verify after generation: re-CRC every "
                         "column in streamed blocks (bounded memory even "
                         "for >RAM datasets)")
    ap.add_argument("--verify-deep", action="store_true",
                    help="alias of --verify (kept explicit so scripts can "
                         "name the deep semantics)")
    ap.add_argument("--trace", nargs="?", const="auto", default=None,
                    metavar="PATH",
                    help="record a span event log (crash-safe JSONL) of "
                         "the run; with no PATH it lands next to the "
                         "dataset manifest as OUT/trace.jsonl. Feed it to "
                         "scripts/report_run.py for a per-stage breakdown "
                         "or a Perfetto/chrome://tracing export")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the run's counters/gauges/histograms + "
                         "stage timings as a unified BENCH-schema JSON")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="additionally capture a jax.profiler device "
                         "trace into DIR (TensorBoard/Perfetto)")
    args = ap.parse_args(argv)
    if argv is None:
        # a command-line run keeps its compiled programs across runs
        from repro.utils import use_compile_cache
        use_compile_cache()
    if args.worker_id is not None and args.num_workers is None:
        ap.error("--worker-id needs --num-workers (the stripe count "
                 "the plan was made for)")
    if args.num_workers is not None:
        if args.num_workers < 1:
            ap.error(f"--num-workers {args.num_workers} < 1")
        if args.workers != 1 or args.worker is not None:
            ap.error("--num-workers (multi-process) and "
                     "--workers/--worker (in-process striping) are "
                     "mutually exclusive")
        if args.worker_id is not None \
                and not 0 <= args.worker_id < args.num_workers:
            ap.error(f"--worker-id {args.worker_id} outside "
                     f"0..{args.num_workers - 1}")

    import numpy as np

    from repro.datastream import DatasetJob, ShardedGraphDataset
    from repro.obs import JsonlSink, MetricsRegistry, Tracer, jaxprof, \
        write_bench

    fit = build_fit(args)
    if args.num_workers is not None and args.worker_id is None \
            and not args.plan_only:
        return run_cluster(args)
    tracer = Tracer()
    metrics = MetricsRegistry()
    trace_path = None
    if args.trace is not None and not args.plan_only:
        # a coordinator generates nothing — its workers each record
        # their own namespaced trace (trace.w{k}.jsonl)
        trace_path = (os.path.join(args.out, "trace.jsonl")
                      if args.trace == "auto" else args.trace)
        if args.worker_id is not None:
            trace_path = worker_path(trace_path, args.worker_id)
        os.makedirs(os.path.dirname(trace_path) or ".", exist_ok=True)
        tracer.add_sink(JsonlSink(trace_path))
    try:
        job = DatasetJob(fit, args.out,
                         shard_edges=parse_count(args.shard_edges),
                         seed=args.seed, k_pref=args.k_pref,
                         num_workers=(args.num_workers
                                      if args.num_workers is not None
                                      else args.workers),
                         double_buffered=not args.serial, mode=args.mode,
                         backend=args.backend, id_dtype=args.id_dtype,
                         pipeline_depth=(0 if args.serial
                                         else args.pipeline_depth),
                         host_workers=args.host_workers, fused=args.fused,
                         tracer=tracer, metrics=metrics)
    except (KeyError, ValueError) as e:
        raise SystemExit(f"error: {e}")
    print(f"plan: E={fit.E:,} edges, 2^{fit.n}×2^{fit.m} ids "
          f"({np.dtype(job.dtype).name}), "
          f"k_pref={job.k_pref}, {len(job.scheduler.shards)} shards "
          f"(max {job.scheduler.max_shard_edges:,} edges/shard), "
          f"mode={args.mode}, backend={job.backend}, "
          f"pipeline_depth={job.pipeline_depth}, "
          f"host_workers={job.host_workers}, fused={job.fused}",
          file=sys.stderr)
    if args.plan_only:
        return plan_only(args, job)
    t0 = time.time()
    try:
        with jaxprof.trace(args.jax_profile):
            if args.worker_id is not None:
                manifest = job.run_worker(args.worker_id,
                                          max_shards=args.max_shards)
            else:
                manifest = job.run(resume=args.resume,
                                   max_shards=args.max_shards,
                                   worker=args.worker)
    except FileExistsError:
        raise SystemExit(f"error: {args.out} already holds a dataset — "
                         "pass --resume to continue it, or choose a "
                         "different --out")
    except FileNotFoundError as e:
        raise SystemExit(f"error: {e}")
    except ValueError as e:
        raise SystemExit(f"error: {e}")
    finally:
        tracer.close()
    dt = time.time() - t0
    done = manifest.done_edges()
    t = job.timings
    print(f"materialized {len(manifest.done_ids())}/"
          f"{len(manifest.shards)} shards, {done:,} edges "
          f"in {dt:.1f}s ({done / max(dt, 1e-9):,.0f} edges/s)",
          file=sys.stderr)
    print(f"stages: struct {t['gen_struct_s']:.1f}s, "
          f"feat {t['gen_feat_s']:.1f}s, align {t['gen_align_s']:.1f}s, "
          f"write {t['write_s']:.1f}s busy over {t['wall_s']:.1f}s wall "
          f"(overlap {t['overlap']:.2f}x, stalled {t['stall_s']:.1f}s)",
          file=sys.stderr)
    if trace_path:
        print(f"trace: {trace_path} (scripts/report_run.py for a "
              f"breakdown, --perfetto for a timeline)", file=sys.stderr)
    if args.metrics_out:
        metrics_path = (worker_path(args.metrics_out, args.worker_id)
                        if args.worker_id is not None
                        else args.metrics_out)
        write_bench("generate_dataset",
                    {"timings": t, "registry": metrics.snapshot()},
                    metrics_path)
        print(f"metrics: {metrics_path}", file=sys.stderr)
    if args.worker_id is not None:
        # one stripe of a larger run: completeness, verification and the
        # manifest compaction belong to the coordinator
        return 0
    if manifest.is_complete():
        ds = ShardedGraphDataset(args.out)
        assert ds.total_edges == fit.E
        if args.verify or args.verify_deep:
            problems = ds.verify(deep=True)
            if problems:
                print("VERIFY FAILED:", *problems, sep="\n  ",
                      file=sys.stderr)
                return 1
            print("verify: ok (deep, streamed crc)", file=sys.stderr)
    elif not args.max_shards and args.worker is None:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
