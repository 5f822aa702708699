"""``ShardSource``: one shard's structure as a pure function.

``DatasetJob`` used to braid two generation modes through its own method
bodies; this module extracts them behind one contract so the executor
(``repro.datastream.executor``) and the sources are independently
testable:

* ``ChunkShardSource`` — the θ-weighted chunk plan (``mode="chunks"``):
  one shard = a run of id-disjoint prefix chunks, sampled through the
  ``repro.core.sampler`` engine backend and pumped double-buffered from
  the device.  Full distributional fidelity (every src/dst level is
  θ-distributed).
* ``DeviceStepShardSource`` — pod-scale device steps
  (``mode="device_steps"``): one shard = one mesh-wide generation step
  with step-indexed seeds (paper App. 10's zero-collective design).
  Maximum throughput, but every device emits the same edge count under
  its own src prefix, so the top ``k = log2(n_dev)`` src levels are
  uniform rather than θ-distributed.  Given that prefix the law is
  Kronecker's: the k dst prefix levels are drawn conditioned on the
  device's src bits, and every later level draws its src and dst bits
  jointly from its θ row.

Either way ``generate(rec)`` is a pure function of
``(fit, seed, shard_id)`` — byte-identical on regeneration, which is
what makes kill/resume and the pipelined executor's golden-seed
equivalence hold.  ``generate`` owns the device: it must be called from
a single thread (the executor's struct stage); the returned arrays are
freshly allocated per shard, never reused buffers.

``FeatureSpec`` (the per-shard feature/alignment draw) lives here too —
it is the other pure per-shard function, consumed by the executor's host
stage, possibly from several worker threads at once (its stage timers
accumulate under a lock).
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rmat
from repro.core.descend import check_id_capacity, combine_ids, narrow_ids
from repro.core.sampler import get_backend
from repro.core.structure import KroneckerFit
from repro.datastream.scheduler import ChunkScheduler
from repro.datastream.writer import ShardRecord, pump_chunks
from repro.graph.ops import compact_subgraph
from repro.obs.trace import NULL_TRACER
from repro.utils import call_with_optional_kwargs

_FEATURE_SALT = 0xFEA7


@dataclasses.dataclass
class FeatureSpec:
    """Per-shard feature generation: a *fitted* generator (+ optional
    fitted aligner).  Only edge features stream (node features would need
    cross-shard node identity; see reader.batches for training access).

    ``batch`` fixes the padded jit batch size of the batched feature
    engine (GAN sample + decode, packed GBDT inference) — ``None`` lets
    the caller (``DatasetJob``) derive it from ``shard_edges`` so every
    shard reuses one compiled shape.  ``feat_s``/``align_s`` accumulate
    wall-time so the pipeline can report feature/align cost separately
    from structure generation; the executor's host stage may draw several
    shards concurrently, so the accumulation is lock-guarded."""
    generator: Any                      # .sample(rng, n) -> (cont, cat)
    aligner: Any = None                 # .align(g, cont, cat, rng)
    batch: Optional[int] = None
    feat_s: float = 0.0
    align_s: float = 0.0
    tracer: Any = NULL_TRACER           # set by the executor's _adopt_obs
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    def describe(self) -> dict:
        schema = getattr(self.generator, "schema", None)
        if schema is None:
            return {"n_cont": None, "cat_cards": None}
        return {"n_cont": int(schema.n_cont),
                "cat_cards": [int(c) for c in schema.cat_cards]}

    def _push_tracer(self) -> None:
        """Propagate this spec's tracer into the aligner (and through it
        the per-column GBDT models) so ``gbdt.scan`` spans land on the
        run timeline.  Duck-typed aligners without the attribute are
        left alone."""
        if (self.aligner is not None
                and getattr(self.aligner, "tracer", None)
                not in (self.tracer,)):
            try:
                self.aligner.tracer = self.tracer
            except AttributeError:
                pass

    def block_draw(self, batch: int):
        """The generator's fused traceable per-block draw (see
        ``GANFeatureGenerator.block_draw``), or ``None`` for host-only
        generators (KDE/Random) — in which case the fused sources fall
        back to struct-only fusion + the staged host feature stage."""
        fn = getattr(self.generator, "block_draw", None)
        return fn(batch) if callable(fn) else None

    def feature_key_int(self, seed: int, shard_id: int) -> int:
        """The 63-bit seed the staged path's ``generator.sample`` draws
        first for this shard — the fused program must consume the exact
        same value so its device-side feature stream matches byte for
        byte."""
        rng = np.random.default_rng([seed, _FEATURE_SALT, shard_id])
        return int(rng.integers(2 ** 63))

    def sample_for_shard(self, seed: int, shard_id: int, src: np.ndarray,
                         dst: np.ndarray, bipartite: bool,
                         batch: Optional[int] = None):
        """Deterministic per-shard draw + shard-local alignment.

        Alignment uses structural features of the id-compacted shard
        subgraph (degrees/PageRank *within* the shard) — a bounded-memory
        approximation of the global §3.4 alignment.
        """
        self._push_tracer()
        rng = np.random.default_rng([seed, _FEATURE_SALT, shard_id])
        b = batch or self.batch
        # feat_s/align_s mirror the span durations so callers that only
        # read the attributes see the same numbers a trace sink records;
        # the perf_counter fallback covers the NULL_TRACER case (span
        # durations read 0 when tracing is disabled).
        t0 = time.perf_counter()
        with self.tracer.span("feat", shard=shard_id, rows=len(src)) as sp:
            cont, cat = call_with_optional_kwargs(self.generator.sample, rng,
                                                  len(src), batch=b)
        dt_feat = sp.dur or (time.perf_counter() - t0)
        dt_align = 0.0
        if self.aligner is not None and len(src):
            # id compaction is part of the alignment cost
            t0 = time.perf_counter()
            with self.tracer.span("align", shard=shard_id) as sp:
                g_local = compact_subgraph(src, dst, bipartite)
                cont, cat = call_with_optional_kwargs(
                    self.aligner.align, g_local, cont, cat, rng, batch=b)
            dt_align = sp.dur or (time.perf_counter() - t0)
        with self._lock:
            self.feat_s += dt_feat
            self.align_s += dt_align
        return cont, cat

    def align_for_shard(self, seed: int, shard_id: int, src: np.ndarray,
                        dst: np.ndarray, cont: np.ndarray, cat: np.ndarray,
                        bipartite: bool, batch: Optional[int] = None):
        """Host half of the *fused* path: the feature rows were already
        decoded on device inside the struct program (which consumed the
        shard's ``feature_key_int`` seed), so this replays the staged rng
        stream up to the alignment draw — burning the generator's one
        ``integers(2**63)`` — and runs alignment only.  Byte-identical to
        ``sample_for_shard`` on the same shard."""
        self._push_tracer()
        rng = np.random.default_rng([seed, _FEATURE_SALT, shard_id])
        if len(src):
            rng.integers(2 ** 63)   # consumed on-device by the fused draw
        b = batch or self.batch
        dt_align = 0.0
        if self.aligner is not None and len(src):
            t0 = time.perf_counter()
            with self.tracer.span("align", shard=shard_id) as sp:
                g_local = compact_subgraph(src, dst, bipartite)
                cont, cat = call_with_optional_kwargs(
                    self.aligner.align, g_local, cont, cat, rng, batch=b)
            dt_align = sp.dur or (time.perf_counter() - t0)
        with self._lock:
            self.align_s += dt_align
        return cont, cat


# NOTE: the shard-local id compaction moved to
# ``repro.graph.ops.compact_subgraph`` — the streamed fit path reuses it
# for sample subgraphs, so it is graph substrate, not datastream
# plumbing.


class ShardSource:
    """Contract: ``generate(rec)`` → ``{"src": ..., "dst": ...}``, a pure
    function of the construction arguments and ``rec.shard_id`` /
    ``rec.chunk_indices``.  Single-threaded: the executor calls it from
    its struct stage only."""

    name = "base"
    #: replaced per-instance by the executor's ``_adopt_obs`` so struct
    #: sub-spans (dispatch/fetch/combine/device_step) land in the run
    #: timeline and the ``struct.*`` counters in the run's registry
    tracer = NULL_TRACER
    metrics = None

    def generate(self, rec: ShardRecord) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    def _count(self, name: str, unit: str, n: float) -> None:
        if self.metrics is not None:
            self.metrics.counter(name, unit).inc(n)

    def _fetch(self, tree, **where):
        """``jax.device_get(tree)`` under a ``struct.fetch`` span that
        names the chunk or shard (``where``) and the bytes copied, which
        ``struct.bytes_fetched`` counts."""
        nbytes = sum(x.nbytes for x in jax.tree.leaves(tree))
        with self.tracer.span("struct.fetch", bytes=nbytes, **where):
            host = jax.device_get(tree)
        self._count("struct.bytes_fetched", "bytes", nbytes)
        return host


class ChunkShardSource(ShardSource):
    """θ-weighted prefix-chunk sampling through the engine backend.

    ``fused=True`` replaces the per-chunk dispatch/flush pump with ONE
    jitted program per shard *signature* (the tuple of chunk sizes +
    feature block count): every chunk's backend descent runs in a single
    trace, narrow ids are finalized and concatenated in-graph, and — when
    ``features`` carries a traceable generator (``block_draw``) — the
    Gumbel-max feature decode for the whole shard runs in the same
    program, so neither edge ids nor raw feature draws round-trip through
    host numpy between the struct and feature stages.  The emitted values
    are byte-identical to the staged path: per-chunk keys, feature seed,
    block shapes and op order are all replayed exactly.
    """

    name = "chunks"

    def __init__(self, scheduler: ChunkScheduler, backend: str,
                 dtype, double_buffered: bool = True, fused: bool = False,
                 features: Optional[FeatureSpec] = None, seed: int = 0,
                 feature_batch: Optional[int] = None):
        self.scheduler = scheduler
        self.fit: KroneckerFit = scheduler.fit
        self.backend = backend
        self.dtype = np.dtype(dtype)
        self.double_buffered = double_buffered
        self.fused = bool(fused)
        self.features = features
        self.seed = int(seed)
        self.feature_batch = feature_batch
        self._fused_cache: Dict[Any, Any] = {}   # signature -> jitted fn

    def generate(self, rec: ShardRecord) -> Dict[str, np.ndarray]:
        if self.fused:
            return self._generate_fused(rec)
        return self._generate_staged(rec)

    # -- fused: one program per shard signature -----------------------------
    def _feature_plan(self, n_rows: int):
        """(block_draw, batch, n_blocks) for the fused program — or
        ``(None, 0, 0)`` when there is no traceable generator (struct-only
        fusion; the executor's host stage keeps the staged feature draw)."""
        if self.features is None or n_rows == 0:
            return None, 0, 0
        b = int(self.feature_batch or self.features.batch or n_rows)
        draw = self.features.block_draw(b)
        if draw is None:
            return None, 0, 0
        return draw, b, -(-n_rows // b)

    def _build_fused(self, sizes, n_blocks: int, b: int, wide: bool):
        """Trace-once program for one shard signature.  Chunk prefixes
        vary per shard under one signature, so they enter as *traced*
        pre-shifted scalars, not trace constants."""
        sched, fit = self.scheduler, self.fit
        be = get_backend(self.backend)
        suffix_np = np.asarray(sched.thetas)[sched.k_pref:]
        n_s = fit.n - sched.k_pref
        m_s = fit.m - sched.k_pref
        dt = self.dtype
        draw = self.features.block_draw(b) if n_blocks else None

        def program(keys, spre, dpre, params, fkey):
            suffix = jnp.asarray(suffix_np, jnp.float32)
            srcs, dsts, parts = [], [], []
            for i, ne in enumerate(sizes):
                sp, dp = be.sample_parts(keys[i], suffix, n_s, m_s, ne)
                if wide:
                    # (hi, lo) words stay per-chunk; the host combines
                    # them without jax x64, exactly like the staged flush
                    parts.append((sp, dp))
                else:
                    srcs.append(narrow_ids(sp, ne, dt) + spre[i])
                    dsts.append(narrow_ids(dp, ne, dt) + dpre[i])
            edges = (tuple(parts) if wide
                     else (jnp.concatenate(srcs), jnp.concatenate(dsts)))
            if draw is None:
                return edges, None
            conts, cats = [], []
            for i in range(n_blocks):
                c, k = draw(params, jax.random.fold_in(fkey, i))
                conts.append(c)
                cats.append(k)
            return edges, (jnp.concatenate(conts), jnp.concatenate(cats))

        return jax.jit(program)

    def _generate_fused(self, rec: ShardRecord) -> Dict[str, np.ndarray]:
        sched = self.scheduler
        dt = self.dtype
        chunks = [sched.chunk(i) for i in rec.chunk_indices]
        sizes = tuple(ck.n_edges for ck in chunks)
        wide = dt.itemsize > 4
        n_s = self.fit.n - sched.k_pref
        m_s = self.fit.m - sched.k_pref
        draw, b, n_blocks = self._feature_plan(rec.n_edges)
        sig = (sizes, n_blocks, b, wide)
        fn = self._fused_cache.get(sig)
        if fn is None:
            fn = self._fused_cache[sig] = self._build_fused(
                sizes, n_blocks, b, wide)
        keys = tuple(sched.key_for(ck) for ck in chunks)
        if wide:
            spre = dpre = None
        else:
            check_id_capacity(self.fit.n, jnp.int32,
                              "_generate_fused: src prefix+level bits")
            check_id_capacity(self.fit.m, jnp.int32,
                              "_generate_fused: dst prefix+level bits")
            spre = jnp.asarray([ck.src_prefix << n_s for ck in chunks],
                               jnp.int32)
            dpre = jnp.asarray([ck.dst_prefix << m_s for ck in chunks],
                               jnp.int32)
        if n_blocks:
            fkey = jax.random.PRNGKey(
                self.features.feature_key_int(self.seed, rec.shard_id))
            params = self.features.generator.params["g"]
        else:
            fkey = params = None
        with self.tracer.span("struct.fused", shard=rec.shard_id,
                              chunks=len(chunks), feature_blocks=n_blocks):
            out = fn(keys, spre, dpre, params, fkey)
            self._count("struct.chunks", "chunks", len(chunks))
            edges, feats = self._fetch(out, shard=rec.shard_id)
            if wide:
                src_buf = np.empty(rec.n_edges, dt)
                dst_buf = np.empty(rec.n_edges, dt)
                off = 0
                for ck, (sp, dp) in zip(chunks, edges):
                    src_buf[off: off + ck.n_edges] = combine_ids(
                        sp, n_s, dt, prefix=ck.src_prefix)[: ck.n_edges]
                    dst_buf[off: off + ck.n_edges] = combine_ids(
                        dp, m_s, dt, prefix=ck.dst_prefix)[: ck.n_edges]
                    off += ck.n_edges
                arrays = {"src": src_buf, "dst": dst_buf}
            else:
                arrays = {"src": np.asarray(edges[0]),
                          "dst": np.asarray(edges[1])}
        if feats is not None:
            arrays["cont"] = np.asarray(feats[0])[: rec.n_edges]
            arrays["cat"] = np.asarray(feats[1])[: rec.n_edges]
        return arrays

    # -- staged: double-buffered per-chunk pump -----------------------------
    def _generate_staged(self, rec: ShardRecord) -> Dict[str, np.ndarray]:
        """Double-buffered chunk loop into a preallocated shard buffer.

        A chunk's dispatch is one call of the backend's compiled chunk
        program where it has one: its ids stay on the device, padded
        past the chunk (kernel blocks), and ``flush`` trims them as it
        copies them into the shard buffer.  Narrow ids get their prefix
        on the device; wide (int64) ids combine their ``(hi, lo)`` words
        and prefix in ``flush`` — combining inside dispatch would force a
        device sync per chunk and silently serialize the double-buffered
        pump."""
        sched = self.scheduler
        np_dtype = self.dtype
        src_buf = np.empty(rec.n_edges, np_dtype)
        dst_buf = np.empty(rec.n_edges, np_dtype)
        chunks = [sched.chunk(i) for i in rec.chunk_indices]
        offsets = dict(zip(rec.chunk_indices,
                           np.cumsum([0] + [c.n_edges for c in chunks])))
        wide = np_dtype.itemsize > 4
        if wide:
            be = get_backend(self.backend)
            suffix = rmat.suffix_thetas(sched.thetas, sched.k_pref)
            n_s = self.fit.n - sched.k_pref
            m_s = self.fit.m - sched.k_pref

        def dispatch(ck):
            # host span times dispatch only (the device call is async);
            # the copy back is the struct.fetch span
            self._count("struct.chunks", "chunks", 1)
            with self.tracer.span("struct.dispatch", chunk=ck.index):
                if wide:
                    return be.sample_parts(sched.key_for(ck), suffix,
                                           n_s, m_s, ck.n_edges)
                return rmat.sample_chunk(sched.key_for(ck), self.fit,
                                         ck, sched.k_pref,
                                         sched.thetas, dtype=np_dtype,
                                         backend=self.backend, padded=True)

        def flush(ck, host):
            off = offsets[ck.index]
            with self.tracer.span("struct.combine", chunk=ck.index):
                if wide:
                    sparts, dparts = host
                    s = combine_ids(sparts, n_s, np_dtype,
                                    prefix=ck.src_prefix)
                    d = combine_ids(dparts, m_s, np_dtype,
                                    prefix=ck.dst_prefix)
                else:
                    s, d = host
                # the backend may pad past n_edges
                src_buf[off: off + ck.n_edges] = s[: ck.n_edges]
                dst_buf[off: off + ck.n_edges] = d[: ck.n_edges]

        pump_chunks(chunks, dispatch, flush,
                    double_buffered=self.double_buffered,
                    fetch=lambda ck, bufs: self._fetch(bufs, chunk=ck.index))
        return {"src": src_buf, "dst": dst_buf}


class DeviceStepShardSource(ShardSource):
    """One mesh-wide ``device_generate`` step == one shard; the step index
    (== shard id) seeds the per-device streams, so any step can be
    regenerated in isolation.  A shard is the devices' blocks in device
    order; block ``d`` has src prefix ``d`` over the top ``k =
    log2(n_dev)`` src levels (uniform across blocks), dst prefix levels
    drawn conditioned on those src bits, and levels ``k`` and below drawn
    as joint (src, dst) quadrants from θ rows ``k`` on."""

    name = "device_steps"

    def __init__(self, fit: KroneckerFit, thetas: np.ndarray,
                 shard_edges: int, seed: int, dtype,
                 fused: bool = False,
                 features: Optional[FeatureSpec] = None,
                 feature_batch: Optional[int] = None):
        self.fit = fit
        self.thetas = np.asarray(thetas)
        self.shard_edges = int(shard_edges)
        self.seed = int(seed)
        self.dtype = np.dtype(dtype)
        self.fused = bool(fused)
        self.features = features
        self.feature_batch = feature_batch
        self._step = None
        self._fused_steps: Dict[int, Any] = {}   # n_blocks -> jitted step

    def _setup(self):
        """Build the mesh + jitted step function once per source: every
        step shares shapes, so the shard_map trace/compile is paid a
        single time (inside the first step's span) and steps differ only
        in their seed vector."""
        if self._step is None:
            self._step = self._build_step()
        return self._step

    def _build_step(self):
        from jax.sharding import Mesh

        from repro.core.distributed_gen import device_generate

        mesh = Mesh(np.array(jax.devices()), ("d",))
        n_dev = mesh.size
        epd = math.ceil(self.shard_edges / n_dev)
        # full θ rows, row ell = level ell (``device_block``), as the
        # chunk plan reads them
        thetas = jnp.asarray(self.thetas, jnp.float32)

        @jax.jit
        def step(seeds):
            return device_generate(thetas, seeds, self.fit.n, self.fit.m,
                                   epd, mesh, dtype=self.dtype)

        return (step, n_dev)

    def _feature_plan(self, n_rows: int):
        """Mirror of ``ChunkShardSource._feature_plan``: the fused step
        only engages for traceable generators."""
        if self.features is None or n_rows == 0:
            return None, 0, 0
        b = int(self.feature_batch or self.features.batch or n_rows)
        draw = self.features.block_draw(b)
        if draw is None:
            return None, 0, 0
        return draw, b, -(-n_rows // b)

    def _fused_step(self, n_blocks: int, b: int):
        """One jitted program per feature-block count (the struct shapes
        are step-invariant; only the ragged last shard re-traces): mesh
        ``device_generate`` + the whole shard's feature decode in a
        single trace.  The staged step is reused as a sub-program —
        jit-in-jit inlines — so the edge stream is unchanged."""
        fn = self._fused_steps.get(n_blocks)
        if fn is None:
            step, _ = self._setup()
            draw = self.features.block_draw(b)

            def fused(seeds, params, fkey):
                src, dst = step(seeds)
                conts, cats = [], []
                for i in range(n_blocks):
                    c, k = draw(params, jax.random.fold_in(fkey, i))
                    conts.append(c)
                    cats.append(k)
                return ((src.reshape(-1), dst.reshape(-1)),
                        (jnp.concatenate(conts), jnp.concatenate(cats)))

            fn = self._fused_steps[n_blocks] = jax.jit(fused)
        return fn

    def generate(self, rec: ShardRecord) -> Dict[str, np.ndarray]:
        from repro.core.distributed_gen import step_seeds

        step, n_dev = self._setup()
        draw, b, n_blocks = self._feature_plan(rec.n_edges) \
            if self.fused else (None, 0, 0)
        span = "struct.fused" if n_blocks else "struct.device_step"
        with self.tracer.span(span, shard=rec.shard_id):
            seeds = jnp.asarray(step_seeds(self.seed, rec.shard_id, n_dev))
            if n_blocks:
                fkey = jax.random.PRNGKey(
                    self.features.feature_key_int(self.seed, rec.shard_id))
                params = self.features.generator.params["g"]
                fn = self._fused_step(n_blocks, b)
                out = self._dispatch(fn, rec, seeds, params, fkey)
                (src, dst), (cont, cat) = self._fetch(out,
                                                      shard=rec.shard_id)
                return {"src": np.asarray(src)[: rec.n_edges],
                        "dst": np.asarray(dst)[: rec.n_edges],
                        "cont": np.asarray(cont)[: rec.n_edges],
                        "cat": np.asarray(cat)[: rec.n_edges]}
            src, dst = self._fetch(self._dispatch(step, rec, seeds),
                                   shard=rec.shard_id)
            src = np.asarray(src).reshape(-1)
            dst = np.asarray(dst).reshape(-1)
        return {"src": src[: rec.n_edges], "dst": dst[: rec.n_edges]}

    def _dispatch(self, fn, rec: ShardRecord, *args):
        """One mesh step's call under a ``struct.dispatch`` span (it holds
        the step's compile on the first call; the device work runs on
        after it returns), counted by ``struct.steps``."""
        with self.tracer.span("struct.dispatch", shard=rec.shard_id):
            out = fn(*args)
        self._count("struct.steps", "steps", 1)
        return out
