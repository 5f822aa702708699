"""The unified edge-sampler engine (repro.core.sampler): registry and
auto-selection, backend parity against the kernels/ref.py oracle, wide
(64-bit) node ids end-to-end, overflow guards, the vectorized chunk plan,
and golden-seed chunked/streamed equivalence on rectangular and noisy
fits."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rmat, sampler
from repro.core.descend import LO_BITS, IdParts, combine_ids, descend
from repro.core.structure import KroneckerFit
from repro.kernels import ref

FIT34 = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=34, m=34, E=20_000)

#: crc32 of the xla backend's (src, dst) bytes for PRNGKey(3), the tiled
#: demo θ, n=12, m=10, E=4096 — pins the sample_edges stream.  Pinned
#: under jax 0.9.0, whose ``jax_threefry_partitionable`` defaults to True;
#: the value before it (3317847322) was the non-partitionable threefry
#: stream of older jax, which jax 0.9.0 gives only with the flag off.
GOLDEN_XLA_CRC = 1915872456


def _tiled_thetas(L, th=(0.45, 0.22, 0.2, 0.13)):
    return jnp.asarray(np.tile(th, (L, 1)), jnp.float32)


# -- registry ----------------------------------------------------------------

def test_registry_and_auto_selection():
    assert set(sampler.registered_backends()) == \
        {"xla", "pallas_bits", "pallas_prng"}
    assert "xla" in sampler.available_backends()
    assert "pallas_bits" in sampler.available_backends()
    with pytest.raises(KeyError, match="unknown edge-sampler"):
        sampler.get_backend("cuda")
    # CPU host: auto → xla; explicit names win
    if jax.default_backend() != "tpu":
        assert sampler.resolve_backend(None).name == "xla"
        assert sampler.resolve_backend("auto").name == "xla"
        assert "pallas_prng" not in sampler.available_backends()
        why = sampler.get_backend("pallas_prng").why_unavailable()
        assert "TPU" in why
        with pytest.raises(RuntimeError, match="unavailable"):
            sampler.get_backend("pallas_prng").sample(
                jax.random.PRNGKey(0), _tiled_thetas(8), 8, 8, 512)
    assert sampler.resolve_backend("pallas_bits").name == "pallas_bits"


def test_pallas_prng_interpret_smoke():
    """Run the TPU PRNG kernel under the TPU interpreter off-TPU: the
    per-block seed rows, grid and output envelope.  The interpreter's
    ``prng_random_bits`` yields zero bits, so this checks plumbing and
    determinism, not the stream; the chip run (chip_smoke.py) checks
    the stream's distribution."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import rmat_sample as rs
    n = m = 10
    E, block = 1024, 512
    seeds = rs.prng_block_seeds(jax.random.PRNGKey(3), E // block)
    assert seeds.shape == (2 * (E // block),) and seeds.dtype == jnp.int32
    th = _tiled_thetas(n)
    src, dst = rs.rmat_sample_prng(seeds, th, n, m, E, block=block,
                                   interpret=pltpu.InterpretParams())
    # narrow ids → single lo word, in range, deterministic
    assert src.hi is None and dst.hi is None
    s, d = np.asarray(src.lo), np.asarray(dst.lo)
    assert s.shape == d.shape == (E,)
    assert s.min() >= 0 and int(s.max()) < 2 ** n
    assert d.min() >= 0 and int(d.max()) < 2 ** m
    s2, d2 = rs.rmat_sample_prng(seeds, th, n, m, E, block=block,
                                 interpret=pltpu.InterpretParams())
    np.testing.assert_array_equal(s, np.asarray(s2.lo))
    np.testing.assert_array_equal(d, np.asarray(d2.lo))


def test_pallas_prng_block_seeds_are_disjoint():
    """Every grid block seeds the TPU PRNG with its own two-word row:
    rows differ across the blocks of one call and across the calls of
    different keys (the chunk keys of one shard), so no two blocks
    replay one stream."""
    from repro.kernels import rmat_sample as rs
    rows = []
    for k in range(8):
        seeds = np.asarray(rs.prng_block_seeds(jax.random.PRNGKey(k), 64))
        rows += [tuple(r) for r in seeds.reshape(-1, 2)]
    assert len(set(rows)) == len(rows) == 8 * 64
    np.testing.assert_array_equal(
        rs.prng_block_seeds(jax.random.PRNGKey(5), 4),
        rs.prng_block_seeds(jax.random.PRNGKey(5), 4))


def test_pallas_prng_forced_interpret_end_to_end():
    """Exercise ``pallas_prng`` END-TO-END through the public
    ``rmat.sample_graph`` entry point off-TPU: a
    ``PallasPrngBackend(force_interpret=True)`` instance replaces the
    registry entry so the full engine path (capacity guard → pad →
    per-block seeds → kernel → finalize) runs under the TPU interpreter,
    keeping the backend exercised (DEAD01)."""
    forced = sampler.PallasPrngBackend(force_interpret=True)
    assert forced.why_unavailable() is None
    fit = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=10, m=10, E=1024)
    orig = sampler._REGISTRY["pallas_prng"]
    sampler._REGISTRY["pallas_prng"] = forced
    try:
        s, d = rmat.sample_graph(jax.random.PRNGKey(5), fit,
                                 backend="pallas_prng")
        s, d = np.asarray(s), np.asarray(d)
        assert s.shape == d.shape == (fit.E,)
        assert s.min() >= 0 and int(s.max()) < 2 ** fit.n
        assert d.min() >= 0 and int(d.max()) < 2 ** fit.m
        s2, d2 = rmat.sample_graph(jax.random.PRNGKey(5), fit,
                                   backend="pallas_prng")
        np.testing.assert_array_equal(s, np.asarray(s2))
        np.testing.assert_array_equal(d, np.asarray(d2))
    finally:
        sampler._REGISTRY["pallas_prng"] = orig


@pytest.mark.parametrize("levels,prefixed", [(18, True), (18, False),
                                             (34, False)])
def test_pallas_prng_compiles_once_per_padded_shape(
        levels, prefixed, prng_chunk_program, prng_chunk_compiles):
    """A chunk's seeds, kernel and (narrow ids) prefix add are one
    program per padded shape: sizes that pad to the same
    ``(n_pad, block)`` reuse it, and its ids equal the eager composition
    it replaced, bit for bit (the interpreter's PRNG yields zero bits,
    so off-TPU this pins the plumbing; the chip pins the stream)."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import rmat_sample as rs
    forced = sampler.PallasPrngBackend(force_interpret=True)
    sizes = (5000, 6000, 5000, 8192)     # all one 8192-edge block
    assert {sampler._pad_edges(n, sampler.choose_block(n))
            for n in sizes} == {8192}
    th = _tiled_thetas(levels)
    keys = [jax.random.fold_in(jax.random.PRNGKey(7), i)
            for i in range(len(sizes))]
    # narrow ids only: a chunk's prefixes above its levels
    prefixes = [np.array([3 * i << levels, (5 * i + 1) << levels],
                         np.int32) if prefixed else None
                for i in range(len(sizes))]
    if prefixed:
        parts = [forced.sample_chunk_parts(k, th, levels, levels, n, p)
                 for k, n, p in zip(keys, sizes, prefixes)]
    else:
        parts = [forced.sample_parts(k, th, levels, levels, n)
                 for k, n in zip(keys, sizes)]
    jax.block_until_ready(parts)
    assert len(prng_chunk_compiles) == 1
    assert prng_chunk_program.total("_prng_chunk") == 1
    for k, p, (src, dst) in zip(keys[:2], prefixes, parts):
        want = rs.rmat_sample_prng(rs.prng_block_seeds(k, 1), th, levels,
                                   levels, 8192, block=8192,
                                   interpret=pltpu.InterpretParams())
        if prefixed:
            want = [w._replace(lo=w.lo + off) for w, off in zip(want, p)]
        assert (src.hi is None) == (levels <= LO_BITS)
        for got, ref_ in zip((*src, *dst), (*want[0], *want[1])):
            assert (got is None) == (ref_ is None)
            if got is not None:
                assert got.shape == (8192,) and got.dtype == jnp.int32
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(ref_))


def test_xla_backend_is_the_sample_edges_stream():
    """The engine's xla backend reproduces the PRE-ENGINE
    ``rmat.sample_edges`` stream bit-for-bit (the invariant that lets
    pre-engine datastream manifests resume as backend='xla').  Checked
    against an independent re-implementation of the old inline loop —
    not against the engine itself — plus a pinned golden digest."""
    import zlib
    th = _tiled_thetas(12)
    key = jax.random.PRNGKey(3)
    n, m, E = 12, 10, 4096
    # the seed repo's sample_edges, verbatim semantics
    lv_sq, L = min(n, m), max(n, m)
    keys = jax.random.split(key, L)
    src = jnp.zeros((E,), jnp.int32)
    dst = jnp.zeros((E,), jnp.int32)
    for ell in range(L):
        u = jax.random.uniform(keys[ell], (E,), jnp.float32)
        a, b, c = th[ell, 0], th[ell, 1], th[ell, 2]
        if ell < lv_sq:
            src = src * 2 + (u >= a + b).astype(jnp.int32)
            dst = dst * 2 + (((u >= a) & (u < a + b))
                             | (u >= a + b + c)).astype(jnp.int32)
        elif n > m:
            src = src * 2 + (u >= a + b).astype(jnp.int32)
        else:
            dst = dst * 2 + (u >= a + c).astype(jnp.int32)
    s2, d2 = sampler.get_backend("xla").sample(key, th, n, m, E)
    np.testing.assert_array_equal(np.asarray(src), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(dst), np.asarray(d2))
    # golden digest of the threefry stream itself: fails if jax's threefry
    # or the key-splitting order ever changes out from under resumes
    digest = zlib.crc32(np.asarray(s2).tobytes()
                        + np.asarray(d2).tobytes()) & 0xFFFFFFFF
    assert digest == GOLDEN_XLA_CRC, (digest, GOLDEN_XLA_CRC)


# -- backend parity vs the oracle -------------------------------------------

@pytest.mark.parametrize("n,m,E", [(12, 12, 5000), (12, 9, 3000)])
def test_pallas_bits_bit_identical_to_ref_oracle(n, m, E):
    """pallas_bits (interpret on CPU) == kernels/ref.py oracle, bit for
    bit, including the engine's pad-to-block and trim."""
    be = sampler.get_backend("pallas_bits")
    th = _tiled_thetas(max(n, m))
    key = jax.random.PRNGKey(n * 31 + m)
    s, d = be.sample(key, th, n, m, E)
    block = sampler.choose_block(E)
    E_pad = -(-E // block) * block
    bits = be.draw_bits(key, max(n, m), E_pad)
    s_ref, d_ref = ref.rmat_ref(th, ref.bits_to_uniform_ref(bits), n, m)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref)[:E])
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d_ref)[:E])


def test_pallas_bits_wide_parity_n34():
    """Wide (hi, lo) pair kernel outputs == oracle int64 ids at n=34."""
    be = sampler.get_backend("pallas_bits")
    th = _tiled_thetas(34)
    key = jax.random.PRNGKey(7)
    E = 700
    s, d = be.sample(key, th, 34, 33, E, id_dtype=np.int64)
    assert s.dtype == np.int64 and d.dtype == np.int64
    block = sampler.choose_block(E)
    bits = be.draw_bits(key, 34, -(-E // block) * block)
    s_ref, d_ref = ref.rmat_ref(th, ref.bits_to_uniform_ref(bits), 34, 33,
                                id_dtype=np.int64)
    np.testing.assert_array_equal(s, s_ref[:E])
    np.testing.assert_array_equal(d, d_ref[:E])
    assert int(s.max()) < 2 ** 34 and int(d.max()) < 2 ** 33


def test_pipeline_generate_backend_pallas_bits_bit_identical(rng):
    """Acceptance: SyntheticGraphPipeline.generate(backend='pallas_bits')
    produces edges bit-identical to the kernels/ref.py oracle (CPU
    interpret mode)."""
    from repro.core.pipeline import SyntheticGraphPipeline
    from repro.graph.ops import Graph
    src = rng.integers(0, 256, 4000).astype(np.int32)
    dst = rng.integers(0, 256, 4000).astype(np.int32)
    g = Graph(src, dst, 256, 256)
    cont = rng.normal(size=(4000, 2)).astype(np.float32)
    cat = rng.integers(0, 3, size=(4000, 1)).astype(np.int32)
    pipe = SyntheticGraphPipeline(features="kde", aligner="random")
    pipe.fit(g, cont, cat)
    g_syn, _, _ = pipe.generate(seed=5, backend="pallas_bits")

    fit = pipe.struct.scaled(1, True)
    key = jax.random.PRNGKey(5)
    th = jnp.asarray(rmat.derive_thetas(fit, key=key), jnp.float32)
    be = sampler.get_backend("pallas_bits")
    block = sampler.choose_block(fit.E)
    bits = be.draw_bits(key, max(fit.n, fit.m), -(-fit.E // block) * block)
    s_ref, d_ref = ref.rmat_ref(th, ref.bits_to_uniform_ref(bits),
                                fit.n, fit.m)
    np.testing.assert_array_equal(g_syn.src, np.asarray(s_ref)[:fit.E])
    np.testing.assert_array_equal(g_syn.dst, np.asarray(d_ref)[:fit.E])


# -- wide (64-bit) ids -------------------------------------------------------

def test_descend_wide_pair_matches_narrow_combination():
    """(hi, lo) split is pure bookkeeping: the combined int64 ids equal
    a direct int64 accumulation of the same bits."""
    L, E = 40, 256
    u = np.asarray(jax.random.uniform(jax.random.PRNGKey(0), (L, E)))
    th = np.tile([0.45, 0.22, 0.2, 0.13], (L, 1)).astype(np.float32)
    src, dst = descend(lambda ell: jnp.asarray(u[ell]),
                       lambda ell: (th[ell, 0], th[ell, 1], th[ell, 2]),
                       L, L, lambda: jnp.zeros((E,), jnp.int32))
    assert src.hi is not None and dst.hi is not None
    got = combine_ids(src, L, np.int64)
    # direct python-int accumulation oracle
    want = np.zeros(E, np.int64)
    a, b = th[0, 0], th[0, 1]
    for ell in range(L):
        want = want * 2 + (u[ell] >= a + b).astype(np.int64)
    np.testing.assert_array_equal(got, want)
    assert int(got.max()) < 2 ** 40


def test_xla_wide_ids_n34():
    th = _tiled_thetas(34)
    s, d = sampler.get_backend("xla").sample(
        jax.random.PRNGKey(0), th, 34, 34, 8192, id_dtype=np.int64)
    assert s.dtype == np.int64
    assert 0 <= int(s.min()) and int(s.max()) < 2 ** 34
    assert int(s.max()) > 2 ** 31          # ids actually leave int32 range


@pytest.mark.slow
def test_generate_streamed_n34_int64_roundtrip(tmp_path):
    """Acceptance: a 2^34-node fit generates via generate_streamed with
    id_dtype=int64 and ShardedGraphDataset.verify() passes, all ids in
    range — no jax x64 required."""
    from repro.core.pipeline import SyntheticGraphPipeline
    from repro.datastream import ShardedGraphDataset
    assert not jax.config.jax_enable_x64
    pipe = SyntheticGraphPipeline()
    pipe.struct = FIT34                    # inject the fitted structure
    ds = pipe.generate_streamed(str(tmp_path / "ds"), seed=0,
                                shard_edges=8192, include_features=False,
                                id_dtype=np.int64)
    assert isinstance(ds, ShardedGraphDataset)
    assert ds.manifest.dtype == "int64"
    assert ds.verify(deep=True) == []
    g = ds.to_graph()
    src = np.asarray(g.src)
    assert g.n_edges == FIT34.E and src.dtype == np.int64
    assert 0 <= src.min() and src.max() < 2 ** 34
    assert (src > 2 ** 31).any()
    # the streamed wide path (device id-words combined in flush) must
    # equal the in-memory chunked sampler edge-for-edge
    job = ds.manifest
    s, d = rmat.sample_graph_chunked(jax.random.PRNGKey(0), FIT34,
                                     k_pref=job.k_pref, dtype=np.int64)
    np.testing.assert_array_equal(np.sort(src), np.sort(np.asarray(s)))
    np.testing.assert_array_equal(np.sort(np.asarray(g.dst)),
                                  np.sort(np.asarray(d)))


# -- overflow guards (satellite) ---------------------------------------------

def test_sample_chunk_overflow_guard_n34():
    chunks = rmat.chunk_plan(FIT34, 2)
    with pytest.raises(ValueError, match="34 id bits.*int32"):
        rmat.sample_chunk(jax.random.PRNGKey(0), FIT34, chunks[0], 2)
    # int64 works and keeps the prefix intact past 2^31
    ck = chunks[-1]
    s, d = rmat.sample_chunk(jax.random.PRNGKey(0), FIT34, ck, 2,
                             dtype=np.int64)
    assert (np.asarray(s) >> (FIT34.n - 2) == ck.src_prefix).all()
    assert (np.asarray(d) >> (FIT34.m - 2) == ck.dst_prefix).all()


def test_device_generate_overflow_guard_n34():
    from jax.sharding import Mesh
    from repro.core.distributed_gen import device_generate
    mesh = Mesh(np.array(jax.devices()), ("d",))
    th = _tiled_thetas(34)
    seeds = jnp.zeros((mesh.size,), jnp.int32)
    with pytest.raises(ValueError, match="id bits.*int32"):
        device_generate(th, seeds, 34, 34, 256, mesh)
    if not jax.config.jax_enable_x64:      # wide device path needs x64
        with pytest.raises(ValueError, match="x64"):
            device_generate(th, seeds, 34, 34, 256, mesh, dtype=np.int64)


def test_device_steps_wide_fails_at_construction_without_x64(tmp_path):
    """No manifest may land on disk for a config this host can't run."""
    import os
    from repro.datastream import DatasetJob
    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled: device_steps wide ids are runnable")
    out = str(tmp_path / "ds")
    with pytest.raises(ValueError, match="x64"):
        DatasetJob(FIT34, out, shard_edges=8192, mode="device_steps")
    assert not os.path.exists(out)


def test_pipeline_generate_wide_ids(rng):
    """generate() (in-memory) supports id_dtype=int64 for wide fits."""
    from repro.core.pipeline import SyntheticGraphPipeline
    pipe = SyntheticGraphPipeline()
    pipe.struct = FIT34
    pipe.feat_kind = None                  # structure-only generate
    pipe._g_ref = None

    class _NoFeat:
        def sample(self, rng, n):
            return (np.zeros((n, 0), np.float32), np.zeros((n, 0), np.int32))

    class _NoAlign:
        def align(self, g, cont, cat, rng):
            return cont, cat

    pipe.features, pipe.aligner = _NoFeat(), _NoAlign()
    pipe.feature_kind = "edge"

    class _Ref:
        bipartite = False

    pipe._g_ref = _Ref()
    g, _, _ = pipe.generate(seed=0)        # id_dtype auto-widens
    src = np.asarray(g.src)
    assert src.dtype == np.int64 and src.max() < 2 ** 34
    assert (src > 2 ** 31).any()


def test_ops_wrappers_reject_wide_ids():
    from repro.kernels import ops
    th = _tiled_thetas(34)
    bits = jax.random.bits(jax.random.PRNGKey(0), (34, 512), jnp.uint32)
    with pytest.raises(ValueError, match="wide ids"):
        ops.rmat_edges_bits(th, bits, n=34, m=34, block=512)


def test_rmat_ref_wide_requires_wide_dtype():
    u = jax.random.uniform(jax.random.PRNGKey(0), (34, 256))
    with pytest.raises(ValueError, match="34 id bits"):
        ref.rmat_ref(_tiled_thetas(34), u, 34, 34)   # default int32


def test_id_dtype_hard_ceiling():
    with pytest.raises(ValueError, match="62"):
        sampler.get_backend("xla").sample(
            jax.random.PRNGKey(0), _tiled_thetas(63), 63, 63, 256,
            id_dtype=np.int64)


# -- vectorized chunk plan (satellite) ---------------------------------------

@pytest.mark.parametrize("k_pref", [0, 1, 3, 5])
def test_chunk_plan_vectorized_matches_loop_reference(k_pref):
    fit = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=12, m=9, E=33_333)
    got = rmat.chunk_plan(fit, k_pref)
    th = np.tile(np.array([fit.a, fit.b, fit.c, fit.d]), (fit.n, 1))
    probs = np.ones(1)
    for ell in range(k_pref):
        probs = np.kron(probs, th[ell])
    raw = probs * fit.E
    base = np.floor(raw).astype(np.int64)
    order = np.argsort(raw - base)[::-1]
    base[order[:fit.E - base.sum()]] += 1
    want = []
    for idx in range(4 ** k_pref):         # the former per-chunk loop
        sp = dp = 0
        for ell in range(k_pref):
            quad = (idx >> (2 * (k_pref - 1 - ell))) & 3
            sp = sp * 2 + (quad >> 1)
            dp = dp * 2 + (quad & 1)
        if base[idx] > 0:
            want.append(rmat.Chunk(sp, dp, int(base[idx]), idx))
    assert got == want
    assert sum(c.n_edges for c in got) == fit.E


def test_chunk_plan_int64_prefixes_beyond_int32():
    """Prefix arithmetic in the plan is int64-safe: a 2^34 fit's chunk
    ids and prefixes stay exact."""
    chunks = rmat.chunk_plan(FIT34, 8)
    assert sum(c.n_edges for c in chunks) == FIT34.E
    assert max(c.src_prefix for c in chunks) < 2 ** 8


# -- golden-seed equivalence: xla vs chunked vs streamed ---------------------

@pytest.mark.parametrize("fit", [
    KroneckerFit(a=0.45, b=0.25, c=0.2, d=0.1, n=12, m=9, E=30_000),
    KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=11, m=11, E=30_000,
                 noise=0.03),
], ids=["rectangular", "noisy"])
@pytest.mark.slow
def test_chunked_equals_streamed_golden_seed(fit, tmp_path):
    """Same seed ⇒ the in-memory chunked sampler and the datastream job
    produce identical edge multisets, on rectangular and noisy fits."""
    from repro.datastream import DatasetJob, ShardedGraphDataset
    out = str(tmp_path / "ds")
    job = DatasetJob(fit, out, shard_edges=8192, seed=0)
    job.run()
    g = ShardedGraphDataset(out).to_graph()
    s, d = rmat.sample_graph_chunked(jax.random.PRNGKey(0), fit,
                                     k_pref=job.k_pref)
    order_a = np.lexsort((np.asarray(g.dst), np.asarray(g.src)))
    order_b = np.lexsort((np.asarray(d), np.asarray(s)))
    np.testing.assert_array_equal(np.asarray(g.src)[order_a],
                                  np.asarray(s)[order_b])
    np.testing.assert_array_equal(np.asarray(g.dst)[order_a],
                                  np.asarray(d)[order_b])
    # and the one-shot xla path agrees distributionally (not bit-wise:
    # chunks consume per-chunk fold-in keys)
    s1, d1 = rmat.sample_graph(jax.random.PRNGKey(0), fit,
                               rng=np.random.default_rng(0))
    hi = max(int(np.asarray(s1).max()), int(np.asarray(s).max())) + 1
    cdf1 = np.cumsum(np.bincount(np.asarray(s1), minlength=hi)) / fit.E
    cdf2 = np.cumsum(np.bincount(np.asarray(s), minlength=hi)) / fit.E
    assert np.abs(cdf1 - cdf2).max() < 0.02


def test_datasetjob_records_and_validates_backend(tmp_path):
    """Resuming under a different engine backend must refuse (streams
    differ per backend ⇒ bytes would diverge)."""
    from repro.datastream import DatasetJob, Manifest
    fit = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=10, m=10, E=9000)
    out = str(tmp_path / "ds")
    DatasetJob(fit, out, shard_edges=4096, seed=0,
               backend="xla").run(max_shards=1)
    assert Manifest.load(out).backend == "xla"
    with pytest.raises(ValueError, match="backend"):
        DatasetJob(fit, out, shard_edges=4096, seed=0,
                   backend="pallas_bits").resume()
    DatasetJob(fit, out, shard_edges=4096, seed=0, backend="xla").resume()


def test_legacy_manifest_without_backend_resumes_as_xla(tmp_path):
    """Pre-engine manifests (no backend key) carried the bit-identical
    xla stream: they must keep resuming; device_steps records a stream
    marker instead, and an explicit backend there is an error."""
    import json
    import os

    from repro.datastream import DatasetJob, Manifest
    fit = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=10, m=10, E=9000)
    out = str(tmp_path / "ds")
    DatasetJob(fit, out, shard_edges=4096, seed=0).run(max_shards=1)
    path = os.path.join(out, "manifest.json")
    with open(path) as f:
        raw = json.load(f)
    del raw["backend"]                     # simulate the old format
    with open(path, "w") as f:
        json.dump(raw, f)
    m = DatasetJob(fit, out, shard_edges=4096, seed=0).resume()
    assert m.is_complete() and m.backend == "xla"
    # device_steps: marker recorded, explicit sampler backend refused
    from repro.datastream.service import _DEVICE_STREAM
    assert _DEVICE_STREAM == "device_descend_v3"
    job = DatasetJob(fit, str(tmp_path / "dev"), shard_edges=4096,
                     seed=0, mode="device_steps")
    assert job.backend == _DEVICE_STREAM
    # a dataset of the previous device stream refuses to resume
    job.run(max_shards=1)
    path = os.path.join(str(tmp_path / "dev"), "manifest.json")
    with open(path) as f:
        raw = json.load(f)
    raw["backend"] = "device_descend_v2"
    with open(path, "w") as f:
        json.dump(raw, f)
    with pytest.raises(ValueError, match="backend"):
        DatasetJob(fit, str(tmp_path / "dev"), shard_edges=4096, seed=0,
                   mode="device_steps").resume()
    with pytest.raises(ValueError, match="device_steps"):
        DatasetJob(fit, str(tmp_path / "dev2"), shard_edges=4096,
                   seed=0, mode="device_steps", backend="pallas_bits")


def test_datasetjob_guards_dtype_and_availability(tmp_path):
    """Resume must keep the planned id width, and an unavailable backend
    fails at construction (before a manifest lands on disk)."""
    from repro.datastream import DatasetJob
    fit = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=10, m=10, E=9000)
    out = str(tmp_path / "ds")
    DatasetJob(fit, out, shard_edges=4096, seed=0,
               id_dtype=np.int64).run(max_shards=1)
    with pytest.raises(ValueError, match="dtype"):
        DatasetJob(fit, out, shard_edges=4096, seed=0).resume()  # int32
    if jax.default_backend() != "tpu":
        import os
        with pytest.raises(ValueError, match="unavailable"):
            DatasetJob(fit, str(tmp_path / "nope"), shard_edges=4096,
                       backend="pallas_prng")
        assert not os.path.exists(str(tmp_path / "nope"))


def test_backend_threading_through_chunked_sampler(tmp_path):
    """sample_graph_chunked(backend='pallas_bits') == a DatasetJob run
    with the same backend — the engine is threaded end to end."""
    from repro.datastream import DatasetJob, ShardedGraphDataset
    fit = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=10, m=10, E=9000)
    out = str(tmp_path / "ds")
    job = DatasetJob(fit, out, shard_edges=4096, seed=0,
                     backend="pallas_bits")
    job.run()
    g = ShardedGraphDataset(out).to_graph()
    s, d = rmat.sample_graph_chunked(jax.random.PRNGKey(0), fit,
                                     k_pref=job.k_pref,
                                     backend="pallas_bits")
    np.testing.assert_array_equal(np.sort(np.asarray(g.src)),
                                  np.sort(np.asarray(s)))
    np.testing.assert_array_equal(np.sort(np.asarray(g.dst)),
                                  np.sort(np.asarray(d)))
    # different engines, different streams: xla bytes ≠ pallas_bits bytes
    s2, _ = rmat.sample_graph_chunked(jax.random.PRNGKey(0), fit,
                                      k_pref=job.k_pref, backend="xla")
    assert not np.array_equal(np.sort(np.asarray(s2)),
                              np.sort(np.asarray(s)))


# -- engine plumbing ---------------------------------------------------------

def test_choose_block_pads_sanely():
    assert sampler.choose_block(1 << 20) == 8192
    assert sampler.choose_block(8192) == 8192
    assert sampler.choose_block(1000) == 1024
    assert sampler.choose_block(37) == sampler.MIN_BLOCK
    for E in (37, 1000, 8192, 10_000):
        blk = sampler.choose_block(E)
        pad = -(-E // blk) * blk
        assert pad >= E and (pad < 2 * E or pad == sampler.MIN_BLOCK)


def test_idparts_narrow_has_no_hi():
    src, dst = descend(
        lambda ell: jax.random.uniform(jax.random.PRNGKey(ell), (64,)),
        lambda ell: (0.45, 0.22, 0.2), 8, 8,
        lambda: jnp.zeros((64,), jnp.int32))
    assert isinstance(src, IdParts) and src.hi is None and dst.hi is None
    assert int(src.lo.max()) < 2 ** 8
    assert LO_BITS == 31
