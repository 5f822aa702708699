"""Multi-process cluster scaling: 1 vs 2 worker processes end-to-end.

Times ``scripts/generate_dataset.py`` materializing the same demo
dataset single-process and through the ``--num-workers 2`` cluster
coordinator (``repro.distributed.cluster``), byte-compares the two
outputs (the cluster must be a pure throughput change), and records
per-worker stage breakdowns parsed from each worker's
``--metrics-out`` file.  Results land in
``results/bench/BENCH_cluster.json`` under the schema-v2 envelope.

This process touches no JAX until both runs have exited (the envelope's
device fields are read last): on a TPU host the chip belongs to one
process at a time, and the runs' children need it.  There, the 2-worker
run needs two chips (the planner refuses more workers than chips).

Both runs pay the same per-process jax import + jit compile tax, so
the headline ``speedup`` is honest about coordination overhead — on a
shared/oversubscribed CPU it can sit below 1; the per-worker stage
rows tell whether the stripes actually ran concurrently.

    PYTHONPATH=src:. python benchmarks/cluster_scaling.py            # full
    PYTHONPATH=src:. python benchmarks/cluster_scaling.py --smoke    # CI
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

from benchmarks.common import emit_bench

SCRIPT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts", "generate_dataset.py")


def _cli(out: str, edges: int, shard_edges: int, *extra: str) -> float:
    """Run one generate_dataset.py invocation; returns wall seconds."""
    argv = [sys.executable, SCRIPT, "--fit", "demo",
            "--edges", str(edges), "--shard-edges", str(shard_edges),
            "--out", out, "--seed", "0", "--backend", "xla", *extra]
    t0 = time.perf_counter()
    subprocess.run(argv, check=True, stdout=subprocess.DEVNULL,
                   stderr=subprocess.DEVNULL)
    return time.perf_counter() - t0


def _file_hashes(root: str) -> dict:
    out = {}
    for name in sorted(os.listdir(root)):
        if name.endswith(".npy"):
            with open(os.path.join(root, name), "rb") as f:
                out[name] = hashlib.md5(f.read()).hexdigest()
    return out


def _worker_timings(root: str, num_workers: int) -> dict:
    """Per-worker stage breakdown from the metrics.w{k}.json files the
    workers wrote (BENCH envelope → ["metrics"]["timings"])."""
    out = {}
    for k in range(num_workers):
        path = os.path.join(root, f"metrics.w{k}.json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            out[f"w{k}"] = json.load(f)["metrics"]["timings"]
    return out


def run(fast: bool = True, smoke: bool = False) -> dict:
    shard_edges = 1 << 12 if smoke else (1 << 14 if fast else 1 << 18)
    n_shards = 6 if smoke else 8
    edges = n_shards * shard_edges
    root = tempfile.mkdtemp(prefix="bench_cluster_")
    result = {"edges": edges, "shard_edges": shard_edges, "smoke": smoke,
              "num_workers": 2}
    try:
        serial_out = os.path.join(root, "serial")
        cluster_out = os.path.join(root, "cluster")
        dt1 = _cli(serial_out, edges, shard_edges)
        result["serial"] = {"seconds": dt1, "rows_per_sec": edges / dt1}
        print(f"cluster_serial,{dt1:.2f}s,{edges / dt1:,.0f} rows/s")
        dt2 = _cli(cluster_out, edges, shard_edges,
                   "--num-workers", "2",
                   "--metrics-out", os.path.join(root, "metrics.json"))
        workers = _worker_timings(root, 2)
        result["cluster2"] = {"seconds": dt2,
                              "rows_per_sec": edges / dt2,
                              "workers": workers}
        print(f"cluster_2workers,{dt2:.2f}s,{edges / dt2:,.0f} rows/s")
        result["speedup"] = dt1 / dt2
        print(f"cluster_speedup,{result['speedup']:.3f},x")
        identical = _file_hashes(serial_out) == _file_hashes(cluster_out)
        result["byte_identical"] = identical
        print(f"cluster_byte_identical,{identical},")
        if not identical:
            raise AssertionError(
                "2-worker cluster output differs from the "
                "single-process run — placement changed bytes")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    emit_bench("cluster", result)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shards for CI (2^12-edge instead of 2^14)")
    args = ap.parse_args(argv)
    run(smoke=args.smoke)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
