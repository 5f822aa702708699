"""Shared benchmark helpers: timing, CSV row protocol, BENCH envelope.

Every table module exposes ``run(fast: bool) -> list[dict]`` with keys
``name, us_per_call, derived`` (derived = the table's headline quantity).
``benchmarks.run`` prints them as CSV and writes JSON under results/bench/.

``emit_bench`` writes the suites that CI trends across PRs
(``BENCH_*.json``) in the unified envelope from ``repro.obs.metrics``:
``{schema_version, suite, created_unix, env: {git_sha, host, platform,
device_kind, device_count, ...}, metrics: <payload>}`` — readers take
the payload from ``["metrics"]`` and the provenance from ``["env"]``.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List

from repro.obs.metrics import write_bench

BENCH_DIR = "results/bench"


def emit_bench(suite: str, metrics: Any,
               extra: Dict[str, Any] = None) -> Dict[str, Any]:
    """Write ``results/bench/BENCH_<suite>.json`` in the unified
    envelope (schema version + git SHA + host/device info wrapped
    around the suite's payload)."""
    path = os.path.join(BENCH_DIR, f"BENCH_{suite}.json")
    return write_bench(suite, metrics, path, extra)


def timeit(fn: Callable, repeats: int = 3, warmup: int = 1) -> float:
    """Median wall-time per call in microseconds."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    ts.sort()
    return ts[len(ts) // 2]


def row(name: str, us: float, derived) -> Dict:
    return {"name": name, "us_per_call": round(us, 1), "derived": derived}


def emit(rows: List[Dict], out_name: str):
    os.makedirs("results/bench", exist_ok=True)
    with open(f"results/bench/{out_name}.json", "w") as f:
        json.dump(rows, f, indent=2)
    for r in rows:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")
    return rows
