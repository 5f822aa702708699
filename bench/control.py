"""The control of a cell's check: the plain reference put in the program's
place, in the configuration's precision and in the one below it.

    python3 bench/control.py --workload graph500_s26.chunks \
        --seeds 11 12 13 --shards 4

For each seed it draws, with ``reference.sample_shard``, the first
``--shards`` shards that a window of the cell takes, in float32 (the
precision the kernel draws its uniforms in) and in bfloat16, and prints
the numbers the check compares beside their limits.  The float32 draw
has to pass and the bfloat16 draw has to fail: that is what shows the
check can tell a lower precision from the configured one.  The
benchmark's own runs never run this.
"""
import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

PRECISIONS = ("float32", "bfloat16")


def readings(config: dict, traffic: dict, seed: int, n_shards: int,
             dtype: str) -> dict:
    """The compared numbers for the reference's own draw of the first
    ``n_shards`` window shards, in ``dtype``."""
    import jax

    from bench import cell, order
    ref = importlib.import_module(f"bench.reference.{config['reference']}")
    kron = ref.Kronecker(**{k: config["kronecker"][k] for k in "abcdnmE"})
    pl = ref.plan(kron, config["job"]["shard_edges"])
    ids = order.shard_order(traffic["shard_order"],
                            len(pl.shards))[1:1 + n_shards]
    key = jax.random.PRNGKey(cell.job_seed(seed))
    shards = {}
    for sid in ids:
        src, dst = ref.sample_shard(pl, sid, key, dtype)
        shards[sid] = {"src": src, "dst": dst}
    numbers = ref.compare(pl, shards)
    numbers.update(shards_committed=len(shards), shards_missing=0,
                   shards_unreadable=0)
    ok, checks = cell.verdict(numbers, config["limits"])
    return {"seed": seed, "dtype": dtype, "shards": ids, "correct": ok,
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--shards", type=int, default=4)
    args = ap.parse_args(argv)

    from bench import manifest
    bench = manifest.load(ROOT)
    w = manifest.workload(bench, args.workload)
    config, traffic = manifest.config(bench, w), manifest.traffic(w)
    import jax
    print(f"device: {jax.devices()[0].device_kind}", file=sys.stderr)
    for seed in args.seeds:
        for dtype in PRECISIONS:
            print(json.dumps(readings(config, traffic, seed, args.shards,
                                      dtype)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
