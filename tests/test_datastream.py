"""repro.datastream: scheduler determinism, streamed-vs-in-memory
equivalence, kill-and-resume byte identity, reader round-trips, per-shard
feature streaming."""
import dataclasses
import hashlib
import os

import jax
import numpy as np
import pytest

from repro.core import rmat
from repro.core.structure import KroneckerFit
from repro.datastream import (ChunkScheduler, DatasetJob, FeatureSpec,
                              Manifest, ShardedGraphDataset, auto_k_pref,
                              pump_chunks)

FIT = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=12, m=12, E=60_000)


def _file_hashes(path):
    return {f: hashlib.md5(open(os.path.join(path, f), "rb").read())
            .hexdigest()
            for f in sorted(os.listdir(path)) if f.endswith(".npy")}


def _ks_degree_distance(deg_a, deg_b):
    """Kolmogorov–Smirnov distance between two degree distributions."""
    hi = int(max(deg_a.max(), deg_b.max())) + 1
    cdf_a = np.cumsum(np.bincount(deg_a, minlength=hi) / len(deg_a))
    cdf_b = np.cumsum(np.bincount(deg_b, minlength=hi) / len(deg_b))
    return float(np.abs(cdf_a - cdf_b).max())


# -- scheduler ---------------------------------------------------------------

def test_scheduler_partition_is_exact_and_deterministic():
    s1 = ChunkScheduler(FIT, shard_edges=8192, num_workers=3, seed=7)
    s2 = ChunkScheduler(FIT, shard_edges=8192, num_workers=3, seed=7)
    assert s1.shards == s2.shards
    assert s1.theta_digest == s2.theta_digest
    # shards cover every chunk exactly once, edges sum exactly to E
    covered = [i for sh in s1.shards for i in sh.chunk_indices]
    assert sorted(covered) == sorted(c.index for c in s1.chunks)
    assert s1.total_edges == FIT.E
    # worker queues partition the shard set
    queues = [s1.worker_queue(w) for w in range(3)]
    assert sum(len(q) for q in queues) == len(s1.shards)
    assert all(sh.worker == w for w, q in enumerate(queues) for sh in q)
    # resumable progress: pending() drops exactly the done ids
    done = [s.shard_id for s in s1.shards[:2]]
    assert [s.shard_id for s in s1.pending(done)] == \
        [s.shard_id for s in s1.shards[2:]]


def test_auto_k_pref_bounds_chunk_size():
    k = auto_k_pref(FIT, shard_edges=4096)
    sched = ChunkScheduler(FIT, shard_edges=4096, k_pref=k)
    pmax = max(FIT.a, FIT.b, FIT.c, FIT.d)
    assert FIT.E * pmax ** k <= 4096 or k == min(FIT.n, FIT.m) - 1
    # realized max chunk stays near the expected bound
    assert max(c.n_edges for c in sched.chunks) <= int(4096 * 1.5)


def test_chunk_keys_are_index_stable():
    s = ChunkScheduler(FIT, shard_edges=8192, seed=3)
    ck = s.chunks[5]
    np.testing.assert_array_equal(
        s.key_for(ck), rmat.chunk_key(jax.random.PRNGKey(3), ck.index))


# -- seeding contract (satellite fix) ---------------------------------------

def test_sample_chunk_requires_explicit_theta_noise():
    noisy = dataclasses.replace(FIT, noise=0.02)
    chunks = rmat.chunk_plan(noisy, 2)
    with pytest.raises(ValueError, match="derive"):
        rmat.sample_chunk(jax.random.PRNGKey(0), noisy, chunks[0], 2)
    th = rmat.derive_thetas(noisy, key=jax.random.PRNGKey(0))
    rmat.sample_chunk(jax.random.PRNGKey(0), noisy, chunks[0], 2, th)


def test_noise_differs_across_keys_but_is_key_deterministic():
    noisy = dataclasses.replace(FIT, noise=0.02)
    t0 = rmat.derive_thetas(noisy, key=jax.random.PRNGKey(0))
    t0b = rmat.derive_thetas(noisy, key=jax.random.PRNGKey(0))
    t1 = rmat.derive_thetas(noisy, key=jax.random.PRNGKey(1))
    np.testing.assert_array_equal(t0, t0b)
    assert not np.array_equal(t0, t1)


# -- streamed == in-memory ---------------------------------------------------

@pytest.mark.slow
def test_streamed_equals_oneshot_distribution(tmp_path):
    out = str(tmp_path / "ds")
    job = DatasetJob(FIT, out, shard_edges=8192, seed=0)
    job.run()
    ds = ShardedGraphDataset(out)
    g = ds.to_graph()
    assert g.n_edges == FIT.E                        # exact edge count
    s1, d1 = rmat.sample_graph(jax.random.PRNGKey(0), FIT)
    deg_stream = np.bincount(np.asarray(g.src), minlength=2 ** FIT.n)
    deg_one = np.bincount(np.asarray(s1), minlength=2 ** FIT.n)
    assert _ks_degree_distance(deg_stream, deg_one) < 0.02
    deg_stream_in = np.bincount(np.asarray(g.dst), minlength=2 ** FIT.m)
    deg_one_in = np.bincount(np.asarray(d1), minlength=2 ** FIT.m)
    assert _ks_degree_distance(deg_stream_in, deg_one_in) < 0.02


def test_streamed_matches_chunked_sampler_exactly(tmp_path):
    out = str(tmp_path / "ds")
    job = DatasetJob(FIT, out, shard_edges=8192, seed=0)
    job.run()
    g = ShardedGraphDataset(out).to_graph()
    s, d = rmat.sample_graph_chunked(jax.random.PRNGKey(0), FIT,
                                     k_pref=job.k_pref)
    # same chunk keys + same θ ⇒ identical multisets of edges
    np.testing.assert_array_equal(np.sort(np.asarray(g.src)),
                                  np.sort(np.asarray(s)))
    np.testing.assert_array_equal(np.sort(np.asarray(g.dst)),
                                  np.sort(np.asarray(d)))


def test_serial_and_double_buffered_are_identical(tmp_path):
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    DatasetJob(FIT, a, shard_edges=8192, double_buffered=True).run()
    DatasetJob(FIT, b, shard_edges=8192, double_buffered=False).run()
    assert _file_hashes(a) == _file_hashes(b)


# -- kill and resume ---------------------------------------------------------

def test_kill_and_resume_is_byte_identical(tmp_path):
    full, part = str(tmp_path / "full"), str(tmp_path / "part")
    DatasetJob(FIT, full, shard_edges=8192, seed=0).run()
    # simulate preemption after 3 shards
    DatasetJob(FIT, part, shard_edges=8192, seed=0).run(max_shards=3)
    m = Manifest.load(part)
    assert len(m.done_ids()) == 3 and not m.is_complete()
    with pytest.raises(RuntimeError, match="incomplete"):
        ShardedGraphDataset(part)
    before = _file_hashes(part)
    m2 = DatasetJob(FIT, part, shard_edges=8192, seed=0).resume()
    assert m2.is_complete()
    after = _file_hashes(part)
    # finished shards untouched, and the whole dataset matches an
    # uninterrupted run byte for byte
    assert all(after[f] == h for f, h in before.items())
    assert after == _file_hashes(full)
    assert ShardedGraphDataset(part).verify(deep=True) == []


def test_resume_regenerates_corrupted_shard(tmp_path):
    out = str(tmp_path / "ds")
    DatasetJob(FIT, out, shard_edges=8192, seed=0).run(max_shards=2)
    victim = Manifest.load(out).shards[0].files["src"]
    os.remove(os.path.join(out, victim))
    m = DatasetJob(FIT, out, shard_edges=8192, seed=0).resume()
    assert m.is_complete()
    assert ShardedGraphDataset(out).verify(deep=True) == []


def test_resume_refuses_mismatched_config(tmp_path, rng):
    out = str(tmp_path / "ds")
    DatasetJob(FIT, out, shard_edges=8192, seed=0).run(max_shards=1)
    with pytest.raises(ValueError, match="different"):
        DatasetJob(FIT, out, shard_edges=8192, seed=1).resume()
    # a resumed job must produce the same columns: features on/off mismatch
    spec, _ = _fitted_feature_spec(rng)
    with pytest.raises(ValueError, match="features"):
        DatasetJob(FIT, out, shard_edges=8192, seed=0,
                   features=spec).resume()
    # a different feature jit batch is a different feature stream for
    # engine-batched generators — the recorded batch must refuse to
    # resume too (numpy-only specs like KDE skip the pin entirely)
    from repro.core.features import GANFeatureGenerator
    r = np.random.default_rng(0)
    cont = r.normal(size=(200, 1)).astype(np.float32)
    cat = r.integers(0, 2, size=(200, 1)).astype(np.int32)
    from repro.tabular.schema import infer_schema
    gan = GANFeatureGenerator(infer_schema(cont, cat)).fit(cont, cat,
                                                           steps=3)
    out_f = out + "_feat"
    DatasetJob(FIT, out_f, shard_edges=8192, seed=0,
               features=FeatureSpec(gan)).run(max_shards=1)
    assert Manifest.load(out_f).features["batch"] == 8192
    with pytest.raises(ValueError, match="features"):
        DatasetJob(FIT, out_f, shard_edges=8192, seed=0,
                   features=FeatureSpec(gan, batch=4096)).resume()
    # device_steps resumption depends on the mesh size
    m = Manifest.load(out)
    m.mode, m.n_dev = "device_steps", 4
    m.save(out)
    with pytest.raises(ValueError, match="n_dev"):
        DatasetJob(FIT, out, shard_edges=8192, seed=0,
                   mode="device_steps").resume()
    with pytest.raises(FileExistsError):
        DatasetJob(FIT, out, shard_edges=8192, seed=0).run()  # no resume


def test_journal_replay_recovers_uncompacted_progress(tmp_path):
    """A crash before manifest compaction loses nothing: per-shard
    completions live in progress.jsonl and Manifest.load replays them."""
    from repro.datastream.writer import JOURNAL_NAME, ShardWriter
    out = str(tmp_path / "ds")
    job = DatasetJob(FIT, out, shard_edges=8192, seed=0)
    manifest = job.plan()
    writer = ShardWriter(out, manifest, checkpoint_every=10_000)
    rec = manifest.shards[0]
    writer.write_shard(0, job.source.generate(rec))
    # no compaction yet: on-disk manifest.json is stale, journal is not
    import json as _json
    raw = _json.load(open(os.path.join(out, "manifest.json")))
    assert all(s["status"] == "pending" for s in raw["shards"])
    assert os.path.getsize(os.path.join(out, JOURNAL_NAME)) > 0
    assert Manifest.load(out).done_ids() == [0]       # replayed
    before = _file_hashes(out)
    m2 = DatasetJob(FIT, out, shard_edges=8192, seed=0).resume()
    assert m2.is_complete()
    after = _file_hashes(out)
    assert all(after[f] == h for f, h in before.items())
    # resume compacted: journal truncated, manifest current
    assert os.path.getsize(os.path.join(out, JOURNAL_NAME)) == 0
    assert ShardedGraphDataset(out).verify(deep=True) == []


# -- reader ------------------------------------------------------------------

def test_reader_batches_and_verify(tmp_path):
    out = str(tmp_path / "ds")
    DatasetJob(FIT, out, shard_edges=8192, seed=0).run()
    ds = ShardedGraphDataset(out)
    assert ds.total_edges == FIT.E and len(ds) >= 2
    sizes = []
    seen = 0
    for src, dst, cont, cat in ds.batches(10_000):
        assert len(src) == len(dst)
        assert cont is None and cat is None
        sizes.append(len(src))
        seen += len(src)
    assert seen == FIT.E
    assert all(s == 10_000 for s in sizes[:-1])
    assert ds.verify(deep=True) == []


def test_device_steps_multidevice(tmp_path):
    """device_steps on a >1-device mesh: the top k = log2(n_dev) src
    levels are the device index (uniform over the devices), so the
    per-device prefixes cover the id space; the k dst prefix levels are
    drawn conditioned on those src bits from θ rows 0..k-1, and every
    later level draws its src and dst bits together from its own θ row
    (noise on, so a row read at the wrong level would show); the dataset
    verifies."""
    import subprocess
    import sys
    script = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.core.structure import KroneckerFit
from repro.datastream import DatasetJob, ShardedGraphDataset
fit = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=10, m=10, E=20000,
                   noise=0.03)
job = DatasetJob(fit, {str(tmp_path / 'ds')!r}, shard_edges=8192, seed=0,
                 mode="device_steps")
job.run()
ds = ShardedGraphDataset({str(tmp_path / 'ds')!r})
assert ds.manifest.n_dev == 4, ds.manifest.n_dev
assert ds.verify(deep=True) == []
g = ds.to_graph()
assert g.n_edges == fit.E
src = np.asarray(g.src)
assert src.max() < 2 ** fit.n
assert sorted(np.unique(src >> (fit.n - 2)).tolist()) == [0, 1, 2, 3]

# step 0 drawn again from the same uniforms by the law, in numpy
import jax
from repro.core.distributed_gen import step_seeds
th = np.asarray(job.scheduler.thetas, np.float32)
shard = ds.load_shard(0)
epd = 8192 // 4
for d, seed in enumerate(step_seeds(0, 0, 4)):
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(0),
                                               int(seed)), fit.n)
    s_id, d_id = d, 0
    for ell in range(fit.n):
        u = np.asarray(jax.random.uniform(keys[ell], (epd,)))
        a, b, c, dd = th[ell]
        if ell < 2:
            sb = (d >> (1 - ell)) & 1
            p0 = a / (a + b) if sb == 0 else c / (c + dd)
            d_id = d_id * 2 + (u >= p0)
        else:
            s_id = s_id * 2 + (u >= a + b)
            d_id = d_id * 2 + (((u >= a) & (u < a + b)) | (u >= a + b + c))
    rows = slice(d * epd, (d + 1) * epd)
    np.testing.assert_array_equal(shard.src[rows], s_id)
    np.testing.assert_array_equal(shard.dst[rows], d_id)
print("multidevice ok")
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"


def test_device_steps_mode(tmp_path):
    out = str(tmp_path / "ds")
    job = DatasetJob(FIT, out, shard_edges=16_384, seed=0,
                     mode="device_steps")
    job.run()
    ds = ShardedGraphDataset(out)
    g = ds.to_graph()
    assert g.n_edges == FIT.E
    assert ds.verify(deep=True) == []
    assert np.asarray(g.src).max() < 2 ** FIT.n


def test_device_steps_compile_and_fetch_spans_nest_in_the_step(tmp_path):
    """The mesh step compiles inside the first ``struct.device_step``
    (booked as ``compile.*`` spans under its ``struct.dispatch``, no
    set-up span pretends to time it), each step's call is one
    ``struct.dispatch`` and its copy back one ``struct.fetch``, and
    ``struct.steps`` counts the steps."""
    from repro.obs import MemorySink, MetricsRegistry, Tracer

    sink, metrics = MemorySink(), MetricsRegistry()
    job = DatasetJob(FIT, str(tmp_path / "ds"), shard_edges=16_384, seed=0,
                     mode="device_steps", tracer=Tracer([sink]),
                     metrics=metrics)
    manifest = job.run(max_shards=2)
    by_id = {e["id"]: e for e in sink.spans()}
    steps = sink.spans("struct.device_step")
    assert len(steps) == 2 and not sink.spans("struct.compile")
    backend = sink.spans("compile.backend")
    assert any(by_id[e["parent"]]["name"] == "struct.dispatch"
               for e in backend)
    for name in ("struct.dispatch", "struct.fetch"):
        assert [by_id[e["parent"]]["name"] for e in sink.spans(name)] \
            == ["struct.device_step"] * 2, name
    fetches = sink.spans("struct.fetch")
    dispatches = sink.spans("struct.dispatch")
    assert all(d["ts"] + d["dur"] <= f["ts"]
               for d, f in zip(dispatches, fetches))
    assert metrics.counter("struct.steps").value == 2
    assert sorted(e["args"]["shard"] for e in fetches) == sorted(
        r.shard_id for r in manifest.shards if r.status == "done")
    assert metrics.counter("struct.bytes_fetched").value == sum(
        e["args"]["bytes"] for e in fetches) > 0


# -- per-shard features ------------------------------------------------------

def _fitted_feature_spec(rng):
    from repro.core.aligner import RandomAligner
    from repro.core.features import KDEFeatureGenerator
    from repro.tabular.schema import infer_schema
    cont = rng.normal(size=(500, 2)).astype(np.float32)
    cat = rng.integers(0, 3, size=(500, 1)).astype(np.int32)
    schema = infer_schema(cont, cat)
    gen = KDEFeatureGenerator(schema).fit(cont, cat)
    return FeatureSpec(gen, RandomAligner(schema)), schema


def test_feature_streaming_bounded_per_shard(tmp_path, rng):
    spec, schema = _fitted_feature_spec(rng)
    out = str(tmp_path / "ds")
    job = DatasetJob(FIT, out, shard_edges=8192, seed=0, features=spec)
    job.run()
    ds = ShardedGraphDataset(out)
    assert ds.has_features
    # pure-numpy spec (KDE + RandomAligner): no engine batch/device pin,
    # so these datasets stay resumable across hosts
    assert ds.manifest.features == {"n_cont": 2, "cat_cards": [3]}
    total = 0
    for blk in ds:
        assert blk.cont.shape == (blk.n_edges, 2)
        assert blk.cat.shape == (blk.n_edges, 1)
        assert blk.cat.max() < 3
        total += blk.n_edges
    assert total == FIT.E
    # feature draw is a pure function of (seed, shard_id): resume after
    # deleting a shard reproduces identical features
    files = Manifest.load(out).shards[1].files
    before = _file_hashes(out)
    os.remove(os.path.join(out, files["cont"]))
    DatasetJob(FIT, out, shard_edges=8192, seed=0,
               features=spec).resume()
    assert _file_hashes(out) == before


def test_pipeline_generate_streamed(tmp_path, rng):
    from repro.core.pipeline import SyntheticGraphPipeline
    from repro.graph.ops import Graph
    src = rng.integers(0, 256, 4000).astype(np.int32)
    dst = rng.integers(0, 256, 4000).astype(np.int32)
    g = Graph(src, dst, 256, 256)
    cont = rng.normal(size=(4000, 2)).astype(np.float32)
    cat = rng.integers(0, 3, size=(4000, 1)).astype(np.int32)
    pipe = SyntheticGraphPipeline(features="kde", aligner="random")
    pipe.fit(g, cont, cat)
    ds = pipe.generate_streamed(str(tmp_path / "ds"), seed=0,
                                shard_edges=2048)
    assert ds.total_edges == pipe.struct.E
    assert ds.has_features
    assert ds.verify(deep=True) == []
    # per-stage timing split: feature/align wall-time is no longer lumped
    # into gen_struct_s
    t = pipe.timings
    assert t.gen_struct_s > 0 and t.gen_feat_s > 0 and t.gen_align_s > 0
    # structure-only stream leaves the feature/align stages at zero
    pipe2 = SyntheticGraphPipeline(features="kde", aligner="random")
    pipe2.fit(g, cont, cat)
    pipe2.generate_streamed(str(tmp_path / "ds2"), seed=0, shard_edges=2048,
                            include_features=False)
    assert pipe2.timings.gen_struct_s > 0
    assert pipe2.timings.gen_feat_s == 0.0
    assert pipe2.timings.gen_align_s == 0.0


# -- pipelined executor ------------------------------------------------------

def _manifest_sans_executor(path):
    import json as _json
    with open(os.path.join(path, "manifest.json")) as f:
        d = _json.load(f)
    d.pop("executor", None)
    return d


def test_pipelined_golden_equals_serial_chunks_with_features(tmp_path, rng):
    """Golden-seed byte identity: the pipelined executor (overlapped
    struct/feature/IO stages, parallel host workers) must produce the
    exact bytes of the serial loop — shards AND manifest (modulo the
    executor provenance knobs, which are recorded but byte-transparent)."""
    spec, _ = _fitted_feature_spec(rng)
    a, b = str(tmp_path / "serial"), str(tmp_path / "pipe")
    DatasetJob(FIT, a, shard_edges=8192, seed=0, features=spec,
               pipeline_depth=0).run()
    DatasetJob(FIT, b, shard_edges=8192, seed=0, features=spec,
               pipeline_depth=3, host_workers=2).run()
    assert _file_hashes(a) == _file_hashes(b)
    assert _manifest_sans_executor(a) == _manifest_sans_executor(b)
    assert ShardedGraphDataset(b).verify(deep=True) == []


def test_pipelined_golden_equals_serial_device_steps(tmp_path):
    a, b = str(tmp_path / "serial"), str(tmp_path / "pipe")
    DatasetJob(FIT, a, shard_edges=16_384, seed=0, mode="device_steps",
               pipeline_depth=0).run()
    DatasetJob(FIT, b, shard_edges=16_384, seed=0, mode="device_steps",
               pipeline_depth=2).run()
    assert _file_hashes(a) == _file_hashes(b)
    assert _manifest_sans_executor(a) == _manifest_sans_executor(b)


# -- fused device-resident generation ----------------------------------------

#: small fit for the fused golden tests: the fused program compiles once
#: per distinct shard chunk-shape, so keep the shard count low.  E is NOT
#: a multiple of shard_edges ⇒ the last shard is ragged.
FIT_FUSED = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=10, m=10,
                         E=14_000)


def _gan_gbdt_spec(rng, batch=None):
    """A fitted GAN generator + GBDT aligner: the fully fusable feature
    stage (``GANFeatureGenerator.block_draw`` is traceable, so ``fused``
    runs R-MAT descent AND Gumbel-max feature decode in one jitted
    program per block; the GBDT alignment stays on the host stage)."""
    from repro.core.aligner import AlignerConfig, GBDTAligner
    from repro.core.features import GANConfig, GANFeatureGenerator
    from repro.core.gbdt import GBDTConfig
    from repro.graph.ops import Graph
    from repro.tabular.schema import infer_schema
    cont = rng.normal(size=(400, 2)).astype(np.float32)
    cat = rng.integers(0, 3, size=(400, 1)).astype(np.int32)
    schema = infer_schema(cont, cat)
    gen = GANFeatureGenerator(schema, GANConfig(batch=64)).fit(
        cont, cat, steps=5, seed=0)
    g = Graph(rng.integers(0, 64, 400).astype(np.int32),
              rng.integers(0, 64, 400).astype(np.int32), 64, 64)
    al = GBDTAligner(schema, AlignerConfig(
        gbdt=GBDTConfig(n_rounds=4, max_depth=3)), kind="edge").fit(
            g, cont, cat)
    return FeatureSpec(gen, al, batch=batch)


def test_fused_golden_equals_staged_chunks_with_features(tmp_path, rng):
    """Tentpole golden-seed byte identity: fused device-resident
    generation (one jitted program per block running struct descent +
    feature decode) must produce the exact bytes of the staged path —
    shards AND manifest, modulo the provenance-only executor knobs."""
    spec = _gan_gbdt_spec(rng, batch=1024)
    a, b = str(tmp_path / "staged"), str(tmp_path / "fused")
    DatasetJob(FIT_FUSED, a, shard_edges=4096, seed=0, features=spec).run()
    DatasetJob(FIT_FUSED, b, shard_edges=4096, seed=0, features=spec,
               fused=True).run()
    assert _file_hashes(a) == _file_hashes(b)
    assert _manifest_sans_executor(a) == _manifest_sans_executor(b)
    assert ShardedGraphDataset(b).verify(deep=True) == []


def test_fused_golden_equals_staged_device_steps(tmp_path, rng):
    spec = _gan_gbdt_spec(rng, batch=1024)
    a, b = str(tmp_path / "staged"), str(tmp_path / "fused")
    DatasetJob(FIT_FUSED, a, shard_edges=4096, seed=0,
               mode="device_steps", features=spec).run()
    DatasetJob(FIT_FUSED, b, shard_edges=4096, seed=0,
               mode="device_steps", features=spec, fused=True).run()
    assert _file_hashes(a) == _file_hashes(b)
    assert _manifest_sans_executor(a) == _manifest_sans_executor(b)
    assert ShardedGraphDataset(b).verify(deep=True) == []


def test_fused_padded_tail_blocks(tmp_path, rng):
    """No shard size divides the feature batch: every fused block run
    ends in a padded tail (4096 % 1000, ragged final shard % 1000), and
    the trimmed rows must still match the staged path byte-for-byte."""
    spec = _gan_gbdt_spec(rng, batch=1000)
    a, b = str(tmp_path / "staged"), str(tmp_path / "fused")
    DatasetJob(FIT_FUSED, a, shard_edges=4096, seed=0, features=spec).run()
    DatasetJob(FIT_FUSED, b, shard_edges=4096, seed=0, features=spec,
               fused=True).run()
    assert _file_hashes(a) == _file_hashes(b)


def test_pipelined_overlap_reported(tmp_path):
    job = DatasetJob(FIT, str(tmp_path / "ds"), shard_edges=8192,
                     pipeline_depth=2)
    job.run()
    t = job.timings
    assert t["wall_s"] > 0 and t["gen_struct_s"] > 0 and t["write_s"] > 0
    # busy time is accounted per stage; overlap = busy/wall is >= ~1 when
    # the pipeline engages (equality would mean fully serial behaviour)
    assert t["overlap"] == pytest.approx(
        (t["gen_struct_s"] + t["gen_feat_s"] + t["gen_align_s"]
         + t["write_s"]) / t["wall_s"])


class _FlakyGen:
    """Wraps a fitted generator; raises on the ``fail_at``-th draw."""

    def __init__(self, inner, fail_at):
        self.inner = inner
        self.schema = inner.schema
        self.fail_at = fail_at
        self.calls = 0
        self._lock = __import__("threading").Lock()

    def sample(self, rng, n):
        with self._lock:
            self.calls += 1
            boom = self.calls == self.fail_at
        if boom:
            raise RuntimeError("injected feature-stage failure")
        return self.inner.sample(rng, n)


def test_pipelined_resume_under_preemption_with_features(tmp_path, rng):
    """Kill mid-pipeline with shards queued but uncommitted: the journal
    must stay a clean prefix (no duplicate/missing records), and resume
    must complete byte-identical to an uninterrupted run."""
    spec, schema = _fitted_feature_spec(rng)
    full, part = str(tmp_path / "full"), str(tmp_path / "part")
    DatasetJob(FIT, full, shard_edges=8192, seed=0, features=spec,
               pipeline_depth=0).run()
    n_shards = len(Manifest.load(full).shards)
    assert n_shards >= 4
    flaky = FeatureSpec(_FlakyGen(spec.generator, fail_at=4), spec.aligner)
    with pytest.raises(RuntimeError, match="injected"):
        DatasetJob(FIT, part, shard_edges=8192, seed=0, features=flaky,
                   pipeline_depth=2, host_workers=2).run()
    m = Manifest.load(part)
    done = m.done_ids()
    # in-order commits ⇒ the done set is a contiguous prefix, each shard
    # recorded exactly once, and nothing past the failure was journaled
    assert done == list(range(len(done)))
    assert 0 < len(done) < n_shards
    before = _file_hashes(part)
    m2 = DatasetJob(FIT, part, shard_edges=8192, seed=0, features=spec,
                    pipeline_depth=2, host_workers=2).resume()
    assert m2.is_complete()
    assert sorted(m2.done_ids()) == list(range(n_shards))
    after = _file_hashes(part)
    assert all(after[f] == h for f, h in before.items())  # prefix untouched
    assert after == _file_hashes(full)                    # byte-identical
    assert ShardedGraphDataset(part).verify(deep=True) == []


def test_device_steps_worker_striping(tmp_path):
    """device_steps shards stripe across worker queues; formerly any
    worker id != 0 silently skipped every shard."""
    out = str(tmp_path / "ds")
    job = DatasetJob(FIT, out, shard_edges=8192, seed=0,
                     mode="device_steps", num_workers=2)
    job.run(worker=0)
    m = Manifest.load(out)
    assert 0 < len(m.done_ids()) < len(m.shards)
    job2 = DatasetJob(FIT, out, shard_edges=8192, seed=0,
                      mode="device_steps", num_workers=2)
    job2.run(resume=True, worker=1)
    assert Manifest.load(out).is_complete()
    with pytest.raises(ValueError, match="worker"):
        job2.run(resume=True, worker=5)


def test_resume_restripes_across_different_worker_count(tmp_path):
    """Worker queues follow the *running* job's num_workers: a dataset
    planned single-process can be finished by N resuming processes."""
    out = str(tmp_path / "ds")
    DatasetJob(FIT, out, shard_edges=8192, seed=0).run(max_shards=2)
    jobs = [DatasetJob(FIT, out, shard_edges=8192, seed=0, num_workers=2)
            for _ in range(2)]
    m0 = jobs[0].run(resume=True, worker=0)
    assert not m0.is_complete()          # worker 0's queue only
    jobs[1].run(resume=True, worker=1)
    assert Manifest.load(out).is_complete()
    assert ShardedGraphDataset(out).verify(deep=True) == []


# -- streamed deep verify ----------------------------------------------------

def test_crc32_stream_matches_oneshot():
    from repro.datastream.writer import _crc32, _crc32_stream
    arr = np.arange(10_007, dtype=np.int64)
    assert _crc32_stream(arr, block_rows=64) == _crc32(arr)
    assert _crc32_stream(arr, block_rows=1 << 30) == _crc32(arr)
    assert _crc32_stream(arr[:0], block_rows=64) == _crc32(arr[:0])


@pytest.mark.parametrize("arr", [
    np.arange(10_007, dtype=np.int64),
    np.arange(33, dtype=np.int32).reshape(11, 3) * 7,      # 2-D, small
    np.zeros((0,), np.float32),                            # empty
    np.random.default_rng(0).normal(size=(5000, 4)).astype(np.float32),
], ids=["int64-1d", "int32-2d", "empty", "f32-2d"])
def test_fused_save_crc_matches_legacy_bytes_and_digest(tmp_path, arr):
    """The fused single-pass save+crc (which replaced the np.save +
    .tobytes() staging copy + crc triple pass) must stay byte-identical
    on disk and digest-identical to the legacy path, across dtypes,
    shapes, empties, and block boundaries."""
    from repro.datastream.writer import (_atomic_save_npy,
                                         _atomic_save_npy_crc, _crc32)
    legacy, fused = str(tmp_path / "legacy.npy"), str(tmp_path / "f.npy")
    _atomic_save_npy(legacy, arr)
    # tiny block size forces the multi-block chaining path
    crc = _atomic_save_npy_crc(fused, arr, block_bytes=64)
    assert open(fused, "rb").read() == open(legacy, "rb").read()
    assert crc == _crc32(arr)
    np.testing.assert_array_equal(np.load(fused), arr)
    assert not os.path.exists(fused + ".tmp")              # atomic rename


def test_deep_verify_streams_blocks_and_catches_corruption(
        tmp_path, monkeypatch):
    from repro.datastream import writer as writer_mod
    out = str(tmp_path / "ds")
    DatasetJob(FIT, out, shard_edges=8192, seed=0).run()
    # force many blocks per shard so the streamed path really chains
    monkeypatch.setattr(writer_mod, "CRC_BLOCK_ROWS", 1000)
    assert ShardedGraphDataset(out).verify(deep=True) == []
    victim = Manifest.load(out).shards[0].files["src"]
    path = os.path.join(out, victim)
    with open(path, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        last = f.read(1)[0]
        f.seek(-1, os.SEEK_END)
        f.write(bytes([last ^ 0xFF]))
    # shallow verify can't see a bit flip; streamed deep verify must
    ds = ShardedGraphDataset(out)
    assert ds.verify(deep=False) == []
    assert any("shard 0" in p for p in ds.verify(deep=True))


# -- pump --------------------------------------------------------------------

def test_chunk_job_spans_and_counts_each_fetch(tmp_path, monkeypatch):
    """Every device-to-host copy of the chunked struct stage is one
    ``struct.fetch`` span under ``struct``; ``struct.chunks`` counts the
    chunks dispatched and ``struct.bytes_fetched`` what the copies
    returned."""
    from repro.obs import MemorySink, MetricsRegistry, Tracer

    returned = []
    device_get = jax.device_get

    def counting_get(tree):
        host = device_get(tree)
        returned.append(sum(x.nbytes for x in jax.tree.leaves(host)))
        return host

    monkeypatch.setattr(jax, "device_get", counting_get)
    sink, metrics = MemorySink(), MetricsRegistry()
    job = DatasetJob(FIT, str(tmp_path / "ds"), shard_edges=8192, seed=0,
                     backend="xla", tracer=Tracer([sink]), metrics=metrics)
    manifest = job.run(max_shards=3)
    done = [r for r in manifest.shards if r.status == "done"]
    n_chunks = sum(len(r.chunk_indices) for r in done)
    assert len(done) == 3 and n_chunks > 3
    assert metrics.counter("struct.chunks").value == n_chunks
    fetched = metrics.counter("struct.bytes_fetched").value
    assert fetched == sum(returned) == 8 * sum(r.n_edges for r in done)
    by_id = {e["id"]: e for e in sink.spans()}
    fetches = sink.spans("struct.fetch")
    assert len(fetches) == n_chunks
    assert all(by_id[e["parent"]]["name"] == "struct" for e in fetches)
    assert sorted(e["args"]["chunk"] for e in fetches) == sorted(
        i for r in done for i in r.chunk_indices)
    assert sum(e["args"]["bytes"] for e in fetches) == fetched


def test_staged_pallas_prng_traces_once_per_padded_shape(
        tmp_path, monkeypatch, prng_chunk_program):
    """The staged chunk source compiles ``pallas_prng``'s chunk program
    once per padded shape, a second job over the same shards compiles
    nothing, and the shards are byte-identical to the eager composition
    the program replaced."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.core import sampler
    from repro.kernels import rmat_sample as rs

    class EagerPrng(sampler.PallasPrngBackend):
        # the prefix added by an eager op after the kernel's own dispatch
        sample_chunk_parts = sampler.EdgeSamplerBackend.sample_chunk_parts

        def sample_parts(self, key, thetas, n, m, n_edges):
            block = sampler.choose_block(n_edges)
            n_pad = sampler._pad_edges(n_edges, block)
            return rs.rmat_sample_prng(
                rs.prng_block_seeds(key, n_pad // block),
                jax.numpy.asarray(thetas, jax.numpy.float32), n, m, n_pad,
                block=block, interpret=pltpu.InterpretParams())

    fit = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=10, m=10, E=6000)
    monkeypatch.setitem(sampler._REGISTRY, "pallas_prng",
                        sampler.PallasPrngBackend(force_interpret=True))

    def run(name):
        job = DatasetJob(fit, str(tmp_path / name), shard_edges=2048,
                         seed=3, backend="pallas_prng")
        job.run()
        return job

    job = run("first")
    sched = job.scheduler
    shapes = {(sampler.choose_block(ck.n_edges),
               sampler._pad_edges(ck.n_edges,
                                  sampler.choose_block(ck.n_edges)))
              for ck in sched.chunks}
    assert len(sched.shards) > 1 and 1 < len(shapes) < len(
        {ck.n_edges for ck in sched.chunks})
    assert prng_chunk_program.total("_prng_chunk") == len(shapes)
    traced = prng_chunk_program.total()
    # the benchmark's warm-up route, rmat.sample_chunk, reaches the same
    # compiled programs, and trims and prefixes the ids on device to
    # the values the shards hold
    ds = ShardedGraphDataset(str(tmp_path / "first"))
    for rec in sched.shards[:2]:
        cols = [rmat.sample_chunk(sched.key_for(sched.chunk(i)), fit,
                                  sched.chunk(i), sched.k_pref,
                                  sched.thetas, backend="pallas_prng")
                for i in rec.chunk_indices]
        got = ds.load_shard(rec.shard_id)
        np.testing.assert_array_equal(
            got.src, np.concatenate([np.asarray(s) for s, _ in cols]))
        np.testing.assert_array_equal(
            got.dst, np.concatenate([np.asarray(d) for _, d in cols]))
    assert prng_chunk_program.total("_prng_chunk") == len(shapes)
    run("second")
    assert prng_chunk_program.total() == traced
    monkeypatch.setitem(sampler._REGISTRY, "pallas_prng",
                        EagerPrng(force_interpret=True))
    run("eager")
    first = _file_hashes(str(tmp_path / "first"))
    assert len(first) == 2 * len(sched.shards)
    assert first == _file_hashes(str(tmp_path / "second")) \
        == _file_hashes(str(tmp_path / "eager"))


def test_pump_chunks_order_and_completeness():
    items = list(range(7))
    for dbl in (True, False):
        flushed = []
        n = pump_chunks(items, dispatch=lambda i: np.full(3, i),
                        flush=lambda i, host: flushed.append((i, host.sum())),
                        double_buffered=dbl)
        assert n == 7
        assert [i for i, _ in flushed] == items
        assert [s for _, s in flushed] == [3 * i for i in items]
