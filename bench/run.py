"""Run one benchmark cell once.

    python3 bench/run.py --workload graph500_s26.chunks --seed 7 \
        --seconds 10 --trace 0

Makes its inputs from ``--seed``, warms up, measures for ``--seconds``,
checks what the window committed against the plain reference, and prints
one JSON object as the last line of standard output; each compared
number and its limit go to the last lines of standard error.  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a device trace of a few
shards inside the window.  Exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

#: JAX's persistent compilation cache, inside the checkout at a fixed
#: path (the path is part of the cache key).  It wins over a directory
#: the environment names, so that two checkouts run on one machine share
#: no cache.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def use_cache() -> None:
    """Point JAX at ``CACHE_DIR``; JAX reads the variable when it is
    first imported, so this comes before any import of it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise SystemExit(f"error: JAX finds no TPU (devices: {devs})")
    if info["count"] < chips:
        raise SystemExit(f"error: the cell needs {chips} chips, JAX finds "
                         f"{info['count']}")
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import manifest
    bench = manifest.load(ROOT)
    cell = manifest.workload(bench, args.workload)
    config, traffic = manifest.config(bench, cell), manifest.traffic(cell)

    use_cache()
    from bench import cell as cell_mod
    from bench import peaks
    device = device_info(int(cell["chips"]))
    result = cell_mod.run(
        config, traffic, args.seed, args.seconds, bool(args.trace),
        T_START, manifest.readers(bench, cell) if args.trace else {},
        device, peaks.peaks(device["kind"]))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
