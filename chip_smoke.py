#!/usr/bin/env python
"""Prove that dataset generation runs on a TPU, through the entry points a
user calls, at realistic sizes.

    python chip_smoke.py              # one chip: phases a-d
    python chip_smoke.py --chips 4    # four chips: the device_steps mesh

Everything runs in this one process (a chip belongs to one process at a
time), and any failure ends the run with a non-zero exit code.  Phases on
one chip:

a. the device as JAX reports it; no TPU is an error;
b. structure only: ``scripts/generate_dataset.py`` materializes 2^27
   demo edges in 2^24-edge shards on the auto backend (which must be
   ``pallas_prng``) with deep verify, and the per-level θ fitted back
   from the files through ``DatasetFitSource`` + ``BitPairMLE`` is within
   ±0.02 of the demo θ (a distribution check: the TPU PRNG stream is not
   the ``xla`` one);
c. kernel parity: the compiled ``rmat_sample_bits`` equals the
   ``kernels/ref.py`` oracle bit for bit on the same bits, and the
   ``xla`` backend's stream digest is the same on the TPU and the CPU;
d. attributed transactions: a kronecker + GAN + GBDT-aligner pipeline
   fitted on ``tabformer_like`` streams >= 2^22 featured edges through
   the fused path with deep verify, and its first shard equals the
   staged path's byte for byte.

With ``--chips 4`` it runs only the ``device_steps`` path on a 1-D mesh
of the four chips (2^24 edges per step) and compares every step with a
one-device replay of each device's stream.

Each phase prints its result and wall time on a line of its own; the
last line of standard output is the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import zlib

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "scripts")]

DEMO_THETA = (0.45, 0.22, 0.2, 0.13)     # generate_dataset.py --fit demo
THETA_TOL = 0.02                          # README's streamed-fit tolerance
STRUCT_EDGES = 1 << 27
STRUCT_SHARD = 1 << 24
FEATURED_EDGES = 1 << 22
FEATURED_SHARD = 1 << 20
MESH_STEP_EDGES = 1 << 24
MESH_STEPS = 4


def run_phase(name: str, fn, *args, **kwargs):
    t0 = time.perf_counter()
    result = fn(*args, **kwargs)
    print(f"phase {name}: {result} ({time.perf_counter() - t0:.1f} s)",
          flush=True)
    return result


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise SystemExit(f"error: JAX finds no TPU (devices: {devs})")
    if info["count"] < chips:
        raise SystemExit(f"error: --chips {chips} but JAX finds "
                         f"{info['count']} TPU device(s)")
    return info


def same_files(dir_a: str, dir_b: str, stem: str) -> list:
    """The column files of shard ``stem``, asserted byte-identical."""
    names = sorted(f for f in os.listdir(dir_a) if f.startswith(stem + "."))
    assert names, f"no files of {stem} in {dir_a}"
    for f in names:
        with open(os.path.join(dir_a, f), "rb") as fa, \
                open(os.path.join(dir_b, f), "rb") as fb:
            assert fa.read() == fb.read(), f"{f} differs"
    return names


# -- b: structure only -------------------------------------------------------

def phase_structure(work: str, edges: int = STRUCT_EDGES,
                    shard_edges: int = STRUCT_SHARD,
                    expect_backend: str = "pallas_prng") -> dict:
    import numpy as np

    import generate_dataset
    from repro.core.fit_engine import BitPairMLE
    from repro.datastream import DatasetFitSource, Manifest

    out = os.path.join(work, "structure")
    rc = generate_dataset.main(["--fit", "demo", "--edges", str(edges),
                                "--shard-edges", str(shard_edges),
                                "--out", out, "--verify"])
    assert rc == 0, f"generate_dataset.py exited {rc}"
    man = Manifest.load(out)
    assert man.backend == expect_backend, (man.backend, expect_backend)
    assert man.is_complete() and man.done_edges() == edges
    source = DatasetFitSource(out, columns=("src", "dst"))
    mle = BitPairMLE(man.fit["n"], man.fit["m"])
    for chunk in source.chunks():
        mle.update(chunk.src, chunk.dst)
    assert mle.rows == edges, (mle.rows, edges)
    per_level = mle.counts / mle.counts.sum(axis=1, keepdims=True)
    err = float(np.abs(per_level - np.asarray(DEMO_THETA)).max())
    assert err <= THETA_TOL, f"per-level θ off by {err} > {THETA_TOL}"
    shutil.rmtree(out)
    return {"backend": man.backend, "edges": edges,
            "shards": len(man.shards), "levels": len(per_level),
            "max_theta_err": err}


# -- c: kernel parity and the xla stream -------------------------------------

def phase_parity(cases=((24, 24, 1 << 22), (34, 34, 1 << 20))) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import sampler
    from repro.kernels import ref

    bits_be = sampler.get_backend("pallas_bits")
    assert not bits_be.interpret() or jax.default_backend() != "tpu"
    checked = []
    for n, m, E in cases:
        L = max(n, m)
        dt = np.int64 if L > 31 else np.int32
        th = jnp.asarray(np.tile(DEMO_THETA, (L, 1)), jnp.float32)
        key = jax.random.PRNGKey(L)
        s, d = bits_be.sample(key, th, n, m, E, id_dtype=dt)
        block = sampler.choose_block(E)
        bits = bits_be.draw_bits(key, L, -(-E // block) * block)
        s_ref, d_ref = ref.rmat_ref(th, ref.bits_to_uniform_ref(bits), n, m,
                                    id_dtype=dt)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref)[:E])
        np.testing.assert_array_equal(np.asarray(d), np.asarray(d_ref)[:E])
        checked.append(f"L={L} E={E}")

    def xla_digest(device) -> int:
        # the stream tests/test_sampler.py pins as GOLDEN_XLA_CRC
        with jax.default_device(device):
            th = jnp.asarray(np.tile(DEMO_THETA, (12, 1)), jnp.float32)
            s, d = sampler.get_backend("xla").sample(
                jax.random.PRNGKey(3), th, 12, 10, 4096)
            return zlib.crc32(np.asarray(s).tobytes()
                              + np.asarray(d).tobytes()) & 0xFFFFFFFF

    on_chip = xla_digest(jax.devices()[0])
    on_cpu = xla_digest(jax.devices("cpu")[0])
    assert on_chip == on_cpu, (on_chip, on_cpu)
    return {"pallas_bits_eq_oracle": checked, "xla_digest": on_chip}


# -- d: attributed transactions ----------------------------------------------

def phase_featured(work: str, min_edges: int = FEATURED_EDGES,
                   shard_edges: int = FEATURED_SHARD) -> dict:
    import math

    from repro.core.pipeline import SyntheticGraphPipeline
    from repro.data.reference import tabformer_like
    from repro.datastream import DatasetJob, FeatureSpec

    g, cont, cat = tabformer_like(seed=0)
    t0 = time.perf_counter()
    pipe = SyntheticGraphPipeline(struct="kronecker", features="gan",
                                  aligner="xgboost").fit(g, cont, cat)
    fit_s = time.perf_counter() - t0
    # nodes ×2^k per side, edges ×2^k (linear scaling): the least k that
    # reaches min_edges
    scale = 2 ** math.ceil(math.log2(min_edges / pipe.struct.E))
    fused_out = os.path.join(work, "featured_fused")
    ds = pipe.generate_streamed(fused_out, seed=0, scale_nodes=scale,
                                density_preserving=False,
                                shard_edges=shard_edges, fused=True)
    assert ds.total_edges >= min_edges, (ds.total_edges, min_edges)
    problems = ds.verify(deep=True)
    assert not problems, problems
    first = ds.manifest.shards[0]
    staged_out = os.path.join(work, "featured_staged")
    staged = DatasetJob(pipe.struct.scaled(scale, False), staged_out,
                        shard_edges=shard_edges, seed=0,
                        features=FeatureSpec(pipe.features, pipe.aligner),
                        fused=False)
    staged.run(max_shards=1)
    assert staged.backend == ds.manifest.backend
    files = same_files(fused_out, staged_out, first.stem)
    t = pipe.timings
    return {"backend": ds.manifest.backend, "edges": ds.total_edges,
            "shards": len(ds.manifest.shards), "scale_nodes": scale,
            "fit_s": round(fit_s, 1), "gen_wall_s": round(t.gen_wall_s, 1),
            "struct_s": round(t.gen_struct_s, 1),
            "align_s": round(t.gen_align_s, 1),
            "fused_eq_staged": files}


# -- --chips 4: device_steps on the mesh -------------------------------------

def replay_step(seeds, thetas, n: int, m: int, edges_per_device: int,
                dtype):
    """One device_steps step replayed device by device on one device: the
    same threefry keys per device seed and the same law
    (``distributed_gen.device_block``) with the device index as the src
    prefix, outside any mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distributed_gen import device_block

    thetas = jnp.asarray(thetas, jnp.float32)
    k = int(np.log2(len(seeds)))

    @jax.jit
    def one(seed, prefix):
        keys = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(0), seed), max(n, m))
        return device_block(
            lambda ell: jax.random.uniform(keys[ell], (edges_per_device,),
                                           jnp.float32),
            thetas, prefix, n, m, k,
            lambda: jnp.zeros((edges_per_device,), jnp.int32), dtype)

    with jax.default_device(jax.devices()[0]):
        parts = [one(jnp.int32(s), jnp.int32(i)) for i, s in enumerate(seeds)]
        return (np.concatenate([np.asarray(p[0]) for p in parts]),
                np.concatenate([np.asarray(p[1]) for p in parts]))


def phase_mesh(work: str, chips: int = 4, step_edges: int = MESH_STEP_EDGES,
               steps: int = MESH_STEPS) -> dict:
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distributed_gen import step_seeds
    from repro.core.structure import KroneckerFit
    from repro.datastream import DatasetJob

    assert len(jax.devices()) == chips, (len(jax.devices()), chips)
    E = step_edges * steps
    n = max(4, math.ceil(math.log2(max(E // 8, 16))))   # the demo rule
    fit = KroneckerFit(*DEMO_THETA, n=n, m=n, E=E)
    out = os.path.join(work, "device_steps")
    job = DatasetJob(fit, out, shard_edges=step_edges, seed=0,
                     mode="device_steps")
    manifest = job.run()
    assert manifest.is_complete() and manifest.done_edges() == E
    problems = job.verify(deep=True)
    assert not problems, problems
    step, n_dev = job.source._setup()
    assert n_dev == chips
    n_loc = n - int(math.log2(chips))
    epd = step_edges // chips
    ds = job.dataset()
    prefixes = set()
    for i, rec in enumerate(manifest.shards):
        seeds = step_seeds(0, rec.shard_id, chips)
        if i == 0:
            src_dev, _ = step(jnp.asarray(seeds))
            spans = src_dev.sharding.device_set
            assert spans == set(jax.devices()), spans
        src_ref, dst_ref = replay_step(seeds, job.scheduler.thetas, fit.n,
                                       fit.m, epd, np.int32)
        shard = ds.load_shard(rec.shard_id)
        np.testing.assert_array_equal(shard.src, src_ref[: rec.n_edges])
        np.testing.assert_array_equal(shard.dst, dst_ref[: rec.n_edges])
        prefixes |= set(np.unique(shard.src >> n_loc).tolist())
    assert prefixes == set(range(chips)), prefixes
    return {"edges": E, "steps": len(manifest.shards), "devices": chips,
            "device_prefixes": sorted(prefixes),
            "replay_byte_identical": True}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-d on one chip; 4: only the "
                         "device_steps path on a four-chip mesh")
    args = ap.parse_args(argv)

    from repro.utils import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    device = run_phase("a device", device_info, args.chips)
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            run_phase("mesh device_steps", phase_mesh, work)
        else:
            run_phase("b structure", phase_structure, work)
            run_phase("c parity", phase_parity)
            run_phase("d featured", phase_featured, work)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
