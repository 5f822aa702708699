"""Faults planted under the timed path, to show that the check of a cell
sees each one: with the fault in place a run's ``correct`` has to come
out false.

    python3 bench/faults.py --workload graph500_s26.chunks \
        --fault level_copy --seeds 11 12 13 --seconds 10

runs the cell (as ``run.py`` does, on the chip, at the cell's own size)
with the fault planted, once per seed, and prints the numbers compared
beside their limits.  ``bench/tests/test_faults.py`` plants the same
faults at a size the CPU holds.  The benchmark's own runs never run
this.

* ``half_edges``: the source returns each shard with its second half
  left out (zeroed);
* ``id_flipped``: an id altered where it is drawn (the last suffix level's
  src bit);
* ``level_copy``: every suffix level of a chunk takes the first suffix
  level's bits, as a kernel would that drew one uniform for all levels;
* ``shared_key``: every chunk draws from one and the same key;
* ``torn_file``: the last word of a committed column file is overwritten.
"""
import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


@contextlib.contextmanager
def _patched(owner, name, make):
    orig = getattr(owner, name)
    setattr(owner, name, make(orig))
    try:
        yield
    finally:
        setattr(owner, name, orig)


def half_edges():
    from repro.datastream.source import ChunkShardSource

    def make(orig):
        def half(self, rec):
            arrays = orig(self, rec)
            for col in ("src", "dst"):
                arrays[col][len(arrays[col]) // 2:] = 0
            return arrays
        return half
    return _patched(ChunkShardSource, "generate", make)


def id_flipped():
    from repro.core import rmat

    def make(orig):
        def flipped(*a, **kw):
            src, dst = orig(*a, **kw)
            return src ^ 1, dst
        return flipped
    return _patched(rmat, "sample_chunk", make)


def level_copy():
    from repro.core import rmat

    def make(orig):
        def copied(key, fit, chunk, k_pref, *a, **kw):
            src, dst = orig(key, fit, chunk, k_pref, *a, **kw)

            def spread(ids, n_s):
                top = (ids >> (n_s - 1)) & 1
                return ((ids >> n_s) << n_s) | (top * ((1 << n_s) - 1))
            return (spread(src, fit.n - k_pref),
                    spread(dst, fit.m - k_pref))
        return copied
    return _patched(rmat, "sample_chunk", make)


def shared_key():
    import jax

    from repro.core import rmat
    one = jax.random.PRNGKey(12345)

    def make(orig):
        def same(_key, *a, **kw):
            return orig(one, *a, **kw)
        return same
    return _patched(rmat, "sample_chunk", make)


def torn_file():
    import numpy as np

    from repro.datastream import writer as writer_mod

    def make(orig):
        def torn(self, shard_id, arrays):
            rec = orig(self, shard_id, arrays)
            path = os.path.join(self.out_dir, rec.files["dst"])
            with open(path, "r+b") as f:
                f.seek(-4, os.SEEK_END)
                f.write(np.int32(-1).tobytes())
            return rec
        return torn
    return _patched(writer_mod.ShardWriter, "write_shard", make)


FAULTS = {f.__name__: f for f in (half_edges, id_flipped, level_copy,
                                  shared_key, torn_file)}


def main(argv=None) -> int:
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from bench import cell, manifest, peaks, run
    bench = manifest.load(ROOT)
    w = manifest.workload(bench, args.workload)
    config, traffic = manifest.config(bench, w), manifest.traffic(w)
    run.use_cache()
    device = run.device_info(int(w["chips"]))
    for seed in args.seeds:
        with FAULTS[args.fault]():
            res = cell.run(config, traffic, seed, args.seconds, False,
                           t_start, {}, device, peaks.peaks(device["kind"]))
        print(json.dumps({"fault": args.fault, "seed": seed,
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
