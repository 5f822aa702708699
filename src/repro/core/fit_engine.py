"""Streaming fit engine (the fit-side counterpart of ``repro.datastream``).

``pipeline.fit`` demands the whole graph and feature matrix in RAM; the
generate side has streamed sharded datasets since PR 1, so anything we
materialize at scale could not be fit on.  This module closes the loop:
composable **one-pass accumulators** consume ``(src, dst, cont, cat)``
chunks from any ``FitSource`` (``repro.datastream.fitsource``) and
reduce them to exactly the statistics the existing fitting code needs —
peak memory is bounded by the chunk size (plus fixed-size sketches),
never by the graph.

Accumulators (each one-pass, chunk-order invariant):

* :class:`BitPairMLE` — per-level bit-pair counts == the exact MLE of
  the quadrant distribution (paper §3.2.3).  Replaces the per-level
  numpy loop in ``structure.estimate_ratios_mle`` with one jit-batched
  device call per block; int64 node ids are split into the engine's
  ``(hi, lo)`` int32 words (``repro.core.descend``) so wide graphs fit
  without jax x64.  Counts are exact int64 sums → invariant under any
  chunk ordering.
* :class:`DegreeSketch` — bounded-memory degree histogram over a fixed
  id space: a dense per-node counter when ``n_nodes`` is small, an
  out-of-core bucketed spill (sort/merge per id-range bucket) when it is
  not.  Feeds ``structure.fit_marginals_hist`` unchanged.
* :class:`ReservoirSample` — order-invariant bottom-k *priority* sample
  (each global row index hashes to a fixed priority, the k smallest
  win), optionally stratified per chunk.  Unlike a classic reservoir it
  does not depend on stream order, which is what makes the fit JSON
  byte-identical across chunk orderings.  Feeds the existing
  VGM/GAN/GBDT-aligner fits; provenance (seed, k, rows seen) is
  recorded.
* :class:`Moments` — per-continuous-column count/mean/var/min/max.
  Per-chunk partial sums are combined with ``math.fsum`` (exactly
  rounded ⇒ order-independent), so streamed moments match to the last
  bit across chunk orderings.
* :class:`CatCards` — exact per-categorical-column cardinality (max+1).

``accumulate`` drives one pass over a source and returns
:class:`StreamFitStats`; ``fit_structure_streamed`` turns the stats into
a ``KroneckerFit`` via the same MLE → Eq. 6 marginals → candidate
calibration ladder as ``structure.fit_structure`` (candidates are
scored against the *sketched* histograms through
``metrics.degree_counts_similarity`` — no dense degree arrays).
``fit_to_json`` serializes (fit, provenance) deterministically
(sorted keys), the contract behind ``scripts/fit_dataset.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import math
import os
import tempfile
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.descend import LO_BITS
from repro.graph.ops import sparse_degree_histogram

#: jit block of the bit-pair counter — one trace per (n, m) fit shape
BITPAIR_BLOCK = 1 << 20

#: DegreeSketch stays dense below this many nodes (int64 counters:
#: 2^24 nodes == 128 MiB); larger id spaces spill per id-range bucket
DENSE_NODE_LIMIT = 1 << 24

#: rows loaded per block when replaying a bucket spill
SPILL_BLOCK_ROWS = 1 << 22


class FitChunk(NamedTuple):
    """One chunk of a fit stream.  ``start_row`` is the chunk's global
    row offset in the dataset's canonical order — accumulators key
    per-row randomness on it, which is what makes every accumulator
    invariant to the order chunks actually arrive in."""
    src: np.ndarray
    dst: np.ndarray
    cont: Optional[np.ndarray]
    cat: Optional[np.ndarray]
    start_row: int

    @property
    def n_rows(self) -> int:
        return int(len(self.src))


# ---------------------------------------------------------------------------
# Bit-pair MLE (jit-batched, wide-id capable)
# ---------------------------------------------------------------------------

def _split_id_words(ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host split of node ids into the engine's (hi, lo) int32 words —
    works for int64 inputs without jax x64 (cf. ``descend.combine_ids``,
    the inverse)."""
    a = np.asarray(ids)
    if a.dtype.itemsize <= 4:
        return np.zeros(0, np.int32), a.astype(np.int32, copy=False)
    a = a.astype(np.int64, copy=False)
    lo = (a & np.int64((1 << LO_BITS) - 1)).astype(np.int32)
    hi = (a >> np.int64(LO_BITS)).astype(np.int32)
    return hi, lo


@functools.lru_cache(maxsize=None)
def _bitpair_counts_fn(n: int, m: int, block: int):
    """Jit-compiled per-block bit-pair counter: (hi, lo) id words →
    (min(n,m), 4) int32 counts, padding rows excluded via the valid
    mask.  One trace per (n, m, block)."""
    lv = min(n, m)

    def bit_at(hi, lo, pos: int):
        if pos >= LO_BITS:
            return (hi >> (pos - LO_BITS)) & 1
        return (lo >> pos) & 1

    def f(s_hi, s_lo, d_hi, d_lo, n_valid):
        valid = jnp.arange(block, dtype=jnp.int32) < n_valid
        rows = []
        for ell in range(lv):
            sb = bit_at(s_hi, s_lo, n - 1 - ell)
            db = bit_at(d_hi, d_lo, m - 1 - ell)
            joint = jnp.where(valid, sb * 2 + db, 4)
            # length=5: padding counts into bin 4, sliced off — robust
            # whether out-of-range scatters drop or clip
            rows.append(jnp.bincount(joint, length=5)[:4])
        return jnp.stack(rows)

    return jax.jit(f)


class BitPairMLE:
    """One-pass per-level bit-pair counts == per-level quadrant MLE.

    ``counts[ell]`` holds the (a, b, c, d)-order joint counts of
    ``(src_bit_ell, dst_bit_ell)`` over every row seen; ``ratios()`` is
    the level-averaged frequency vector — numerically identical to the
    historical ``estimate_ratios_mle`` numpy loop (same integer counts).
    """

    def __init__(self, n: int, m: int, block: int = BITPAIR_BLOCK):
        self.n, self.m = int(n), int(m)
        self.lv = min(self.n, self.m)
        self.block = int(block)
        self.counts = np.zeros((max(self.lv, 1), 4), np.int64)
        self.rows = 0

    @staticmethod
    def _pad_to(w: np.ndarray, size: int) -> np.ndarray:
        if len(w) == size:
            return w
        return np.concatenate([w, np.zeros(size - len(w), np.int32)])

    def update(self, src, dst) -> "BitPairMLE":
        src = np.asarray(src)
        dst = np.asarray(dst)
        assert len(src) == len(dst), (len(src), len(dst))
        self.rows += len(src)
        if not self.lv or not len(src):
            return self
        for off in range(0, len(src), self.block):
            s_hi, s_lo = _split_id_words(src[off: off + self.block])
            d_hi, d_lo = _split_id_words(dst[off: off + self.block])
            n_valid = len(s_lo)
            # pad to the next power of two (≤ block): one trace per
            # size class, ≤2x padding waste on ragged chunks — a fixed
            # block would pay the full block for every small chunk
            size = min(self.block, 1 << max(n_valid - 1, 0).bit_length())
            fn = _bitpair_counts_fn(self.n, self.m, size)
            zeros = np.zeros(size, np.int32)
            out = fn(self._pad_to(s_hi, size) if len(s_hi) else zeros,
                     self._pad_to(s_lo, size),
                     self._pad_to(d_hi, size) if len(d_hi) else zeros,
                     self._pad_to(d_lo, size), n_valid)
            self.counts += np.asarray(out, np.int64)
        return self

    def ratios(self) -> np.ndarray:
        """Level-averaged (a, b, c, d) frequency — the MLE point."""
        total = self.counts.sum()
        return self.counts.sum(axis=0) / max(total, 1)


# ---------------------------------------------------------------------------
# Degree histogram sketch (dense / out-of-core bucketed)
# ---------------------------------------------------------------------------

class DegreeSketch:
    """Bounded-memory degree histogram over a fixed ``n_nodes`` id space.

    * ``n_nodes <= dense_limit``: exact dense per-node int64 counters,
      updated with unique-count per chunk (never allocates more than the
      chunk).
    * larger: ids spill to per-id-range bucket files (one bucket spans
      ``dense_limit`` ids); ``finalize`` replays each bucket either via
      unique-count (small spills) or a dense bucket array filled in
      ``SPILL_BLOCK_ROWS`` blocks — peak memory is one bucket, never the
      id space.

    Either path yields the exact ``degree_histogram(degrees, kmax)``
    (tail clipped into the ``kmax`` bin, zero-degree nodes in bin 0)
    plus the exact max degree.  Integer sums ⇒ chunk-order invariant.
    """

    def __init__(self, n_nodes: int, kmax: int = 2048,
                 dense_limit: int = DENSE_NODE_LIMIT):
        self.n_nodes = int(n_nodes)
        self.kmax = int(kmax)
        self.dense_limit = int(dense_limit)
        self.rows = 0
        self._finalized: Optional[Tuple[np.ndarray, int]] = None
        if self.n_nodes <= self.dense_limit:
            self.mode = "dense"
            self._deg = np.zeros(self.n_nodes, np.int64)
            self._tmp = None
        else:
            self.mode = "bucketed"
            self._deg = None
            self.n_buckets = math.ceil(self.n_nodes / self.dense_limit)
            self._tmp = tempfile.TemporaryDirectory(prefix="degsketch-")
            self._spill_rows = np.zeros(self.n_buckets, np.int64)

    def _bucket_path(self, b: int) -> str:
        return os.path.join(self._tmp.name, f"bucket-{b:06d}.i64")

    def update(self, ids) -> "DegreeSketch":
        ids = np.asarray(ids)
        self.rows += len(ids)
        if not len(ids):
            return self
        if self.mode == "dense":
            u, c = np.unique(ids, return_counts=True)
            self._deg[u] += c
            return self
        ids = np.sort(ids.astype(np.int64, copy=False))
        buckets = ids // self.dense_limit
        bounds = np.searchsorted(buckets, np.arange(self.n_buckets + 1))
        for b in np.unique(buckets):
            lo, hi = bounds[b], bounds[b + 1]
            with open(self._bucket_path(int(b)), "ab") as f:
                f.write(np.ascontiguousarray(ids[lo:hi]).tobytes())
            self._spill_rows[b] += hi - lo
        return self

    def _bucket_hist(self, b: int) -> Tuple[np.ndarray, int]:
        """Histogram + max degree of one bucket's spilled ids."""
        size = min(self.dense_limit,
                   self.n_nodes - b * self.dense_limit)
        n_sp = int(self._spill_rows[b])
        if n_sp == 0:
            h = np.zeros(self.kmax + 1, np.int64)
            h[0] = size
            return h, 0
        path = self._bucket_path(b)
        base = np.int64(b) * self.dense_limit
        if n_sp <= SPILL_BLOCK_ROWS:
            local = np.fromfile(path, np.int64) - base
            return self._hist_from_sparse(local, size)
        dense = np.zeros(size, np.int64)
        mm = np.memmap(path, np.int64, mode="r")
        for off in range(0, n_sp, SPILL_BLOCK_ROWS):
            blk = np.asarray(mm[off: off + SPILL_BLOCK_ROWS]) - base
            u, c = np.unique(blk, return_counts=True)
            dense[u] += c
        h = np.bincount(np.minimum(dense, self.kmax),
                        minlength=self.kmax + 1).astype(np.int64)
        return h, int(dense.max())

    def _hist_from_sparse(self, local_ids: np.ndarray, size: int
                          ) -> Tuple[np.ndarray, int]:
        hist, max_deg = sparse_degree_histogram(local_ids, size, self.kmax)
        return hist, max_deg

    def finalize(self) -> Tuple[np.ndarray, int]:
        """``(histogram (kmax+1,) int64, max_degree)``; idempotent."""
        if self._finalized is not None:
            return self._finalized
        if self.mode == "dense":
            hist = np.bincount(np.minimum(self._deg, self.kmax),
                               minlength=self.kmax + 1).astype(np.int64)
            max_deg = int(self._deg.max()) if self.n_nodes else 0
        else:
            hist = np.zeros(self.kmax + 1, np.int64)
            max_deg = 0
            for b in range(self.n_buckets):
                h, md = self._bucket_hist(b)
                hist += h
                max_deg = max(max_deg, md)
            self._tmp.cleanup()
        self._finalized = (hist, max_deg)
        return self._finalized


# ---------------------------------------------------------------------------
# Order-invariant row sampling + streaming moments
# ---------------------------------------------------------------------------

def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer — the fixed per-row-index priority hash."""
    x = x.astype(np.uint64, copy=True)
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


class ReservoirSample:
    """Bottom-k priority sample over global row indices.

    Every row's priority is ``_mix64(row_index XOR mix(seed))`` — a pure
    function of identity, not arrival order — and the k smallest
    priorities win, so the selected set is invariant to chunk order and
    to how the stream is chunked (streamed == in-memory exactly).

    ``stratified=True`` additionally caps each chunk's candidates at its
    proportional share ``ceil(k · chunk_rows / total_rows)`` (requires
    ``total_rows``), guaranteeing spread across the id-space/chunk
    structure for heavily skewed datasets; still order-invariant because
    the cap depends only on the chunk's own content.
    """

    def __init__(self, k: int, seed: int = 0, stratified: bool = False,
                 total_rows: Optional[int] = None):
        self.k = int(k)
        self.seed = int(seed)
        self.stratified = bool(stratified)
        self.total_rows = total_rows
        if stratified and not total_rows:
            raise ValueError("stratified sampling needs total_rows "
                             "(the proportional per-chunk quota)")
        self.rows_seen = 0
        self._pri = np.zeros(0, np.uint64)
        self._row = np.zeros(0, np.int64)
        self._cols: Dict[str, Optional[np.ndarray]] = {}
        self._seed_mix = _mix64(np.array([self.seed], np.uint64))[0]

    def update(self, chunk: FitChunk) -> "ReservoirSample":
        n = chunk.n_rows
        self.rows_seen += n
        if n == 0:
            return self
        rows = np.arange(chunk.start_row, chunk.start_row + n,
                         dtype=np.int64)
        pri = _mix64(rows.astype(np.uint64) ^ self._seed_mix)
        keep = np.lexsort((rows, pri))
        quota = (math.ceil(self.k * n / self.total_rows)
                 if self.stratified else self.k)
        keep = keep[: min(quota, self.k)]
        cols = {"src": np.asarray(chunk.src)[keep],
                "dst": np.asarray(chunk.dst)[keep],
                "cont": (np.asarray(chunk.cont)[keep]
                         if chunk.cont is not None else None),
                "cat": (np.asarray(chunk.cat)[keep]
                        if chunk.cat is not None else None)}
        if not self._cols:
            self._pri, self._row = pri[keep], rows[keep]
            self._cols = cols
            return self
        pri = np.concatenate([self._pri, pri[keep]])
        row = np.concatenate([self._row, rows[keep]])
        order = np.lexsort((row, pri))[: self.k]
        self._pri, self._row = pri[order], row[order]
        for name, cur in self._cols.items():
            add = cols[name]
            self._cols[name] = (np.concatenate([cur, add])[order]
                                if cur is not None else None)
        return self

    def finalize(self) -> Dict[str, Any]:
        """Sampled rows in global-row order + provenance."""
        if not self._cols:                  # empty stream
            self._cols = {"src": np.zeros(0, np.int64),
                          "dst": np.zeros(0, np.int64),
                          "cont": None, "cat": None}
        order = np.argsort(self._row, kind="stable")
        out = {name: (arr[order] if arr is not None else None)
               for name, arr in self._cols.items()}
        out["rows"] = self._row[order]
        out["provenance"] = {
            "kind": "stratified" if self.stratified else "uniform",
            "requested": self.k, "rows": int(len(self._row)),
            "seed": self.seed, "rows_seen": int(self.rows_seen)}
        return out


class Moments:
    """Streaming per-column count/mean/var/min/max for the continuous
    block.  Per-chunk partial sums are float64; the cross-chunk combine
    is ``math.fsum`` (exactly rounded), so the result is bit-identical
    under any chunk ordering of the same chunks."""

    def __init__(self, n_cols: int):
        self.n_cols = int(n_cols)
        self.count = 0
        self._sums: List[List[float]] = [[] for _ in range(n_cols)]
        self._sumsq: List[List[float]] = [[] for _ in range(n_cols)]
        self._min = np.full(n_cols, np.inf)
        self._max = np.full(n_cols, -np.inf)

    def update(self, cont: np.ndarray) -> "Moments":
        cont = np.asarray(cont, np.float64)
        if cont.shape[0] == 0 or self.n_cols == 0:
            self.count += cont.shape[0]
            return self
        assert cont.shape[1] == self.n_cols, (cont.shape, self.n_cols)
        self.count += cont.shape[0]
        for j in range(self.n_cols):
            col = cont[:, j]
            self._sums[j].append(float(col.sum()))
            self._sumsq[j].append(float((col * col).sum()))
        self._min = np.minimum(self._min, cont.min(axis=0))
        self._max = np.maximum(self._max, cont.max(axis=0))
        return self

    def finalize(self) -> List[Dict[str, float]]:
        out = []
        for j in range(self.n_cols):
            s = math.fsum(self._sums[j])
            sq = math.fsum(self._sumsq[j])
            n = max(self.count, 1)
            mean = s / n
            out.append({"count": self.count, "mean": mean,
                        "var": max(sq / n - mean * mean, 0.0),
                        "min": float(self._min[j]),
                        "max": float(self._max[j])})
        return out


class CatCards:
    """Exact categorical cardinalities (running per-column max + 1)."""

    def __init__(self, n_cols: int):
        self.n_cols = int(n_cols)
        self._max = np.full(n_cols, -1, np.int64)

    def update(self, cat: np.ndarray) -> "CatCards":
        cat = np.asarray(cat)
        if cat.shape[0] and self.n_cols:
            self._max = np.maximum(self._max, cat.max(axis=0))
        return self

    def cards(self) -> Tuple[int, ...]:
        return tuple(int(m) + 1 if m >= 0 else 1 for m in self._max)


# ---------------------------------------------------------------------------
# One-pass driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StreamFitStats:
    """Everything one pass over a ``FitSource`` reduces to."""
    n: int
    m: int
    n_src: int
    n_dst: int
    bipartite: bool
    rows: int
    n_chunks: int
    bitpair: np.ndarray                 # (min(n,m), 4) int64
    hist_out: np.ndarray                # (kmax+1,) int64
    hist_in: np.ndarray
    max_deg_out: int
    max_deg_in: int
    kmax: int
    sample: Dict[str, Any]              # ReservoirSample.finalize()
    moments: List[Dict[str, float]]
    n_cont: int
    cat_cards: Tuple[int, ...]
    has_features: bool
    source: Dict[str, Any]              # FitSource.describe()

    def ratios(self) -> np.ndarray:
        total = self.bitpair.sum()
        return self.bitpair.sum(axis=0) / max(total, 1)

    def _hist_digest(self, h: np.ndarray) -> str:
        return hashlib.sha256(
            np.ascontiguousarray(h, np.int64).tobytes()).hexdigest()[:16]

    def provenance(self) -> Dict[str, Any]:
        """JSON-native provenance block (deterministic content)."""
        return {
            "rows": int(self.rows), "n_chunks": int(self.n_chunks),
            "n": self.n, "m": self.m,
            "bitpair_counts": [[int(x) for x in row]
                               for row in self.bitpair],
            "theta_mle": [float(x) for x in self.ratios()],
            "degree_sketch": {
                "kmax": self.kmax,
                "max_deg_out": int(self.max_deg_out),
                "max_deg_in": int(self.max_deg_in),
                "hist_out_digest": self._hist_digest(self.hist_out),
                "hist_in_digest": self._hist_digest(self.hist_in)},
            "sample": self.sample.get("provenance", {}),
            "moments": self.moments,
            "n_cont": self.n_cont,
            "cat_cards": list(self.cat_cards),
            "source": self.source,
        }


def accumulate(source, sample_rows: int = 100_000, seed: int = 0,
               kmax: int = 2048, dense_limit: int = DENSE_NODE_LIMIT,
               stratified: bool = False, tracer=None) -> StreamFitStats:
    """One pass over ``source`` (anything with ``n_src``/``n_dst``/
    ``bipartite``/``total_rows``/``has_features``/``chunks()``/
    ``describe()`` — see ``repro.datastream.fitsource``) through every
    accumulator.  Memory: one chunk + the sketches.  ``tracer`` (a
    ``repro.obs`` tracer) records per-chunk ``fit.read``/``fit.update``
    spans and a ``fit.finalize`` span."""
    from repro.obs.trace import NULL_TRACER
    tracer = tracer if tracer is not None else NULL_TRACER

    n = max(1, math.ceil(math.log2(max(source.n_src, 2))))
    m = max(1, math.ceil(math.log2(max(source.n_dst, 2))))
    mle = BitPairMLE(n, m)
    sk_out = DegreeSketch(source.n_src, kmax, dense_limit)
    sk_in = DegreeSketch(source.n_dst, kmax, dense_limit)
    res = ReservoirSample(sample_rows, seed=seed, stratified=stratified,
                          total_rows=(source.total_rows if stratified
                                      else None))
    moments: Optional[Moments] = None
    cards: Optional[CatCards] = None
    n_chunks = 0
    chunk_iter = iter(source.chunks())
    while True:
        with tracer.span("fit.read", chunk=n_chunks):
            chunk = next(chunk_iter, None)
        if chunk is None:
            break
        n_chunks += 1
        with tracer.span("fit.update", chunk=n_chunks - 1,
                         rows=chunk.n_rows):
            mle.update(chunk.src, chunk.dst)
            sk_out.update(chunk.src)
            sk_in.update(chunk.dst)
            res.update(chunk)
            if chunk.cont is not None:
                if moments is None:
                    moments = Moments(chunk.cont.shape[1])
                moments.update(chunk.cont)
            if chunk.cat is not None:
                if cards is None:
                    cards = CatCards(chunk.cat.shape[1])
                cards.update(chunk.cat)
    with tracer.span("fit.finalize"):
        hist_out, max_out = sk_out.finalize()
        hist_in, max_in = sk_in.finalize()
        sample = res.finalize()
    return StreamFitStats(
        n=n, m=m, n_src=source.n_src, n_dst=source.n_dst,
        bipartite=source.bipartite, rows=mle.rows, n_chunks=n_chunks,
        bitpair=mle.counts[: mle.lv], hist_out=hist_out, hist_in=hist_in,
        max_deg_out=max_out, max_deg_in=max_in, kmax=kmax,
        sample=sample, moments=(moments.finalize() if moments else []),
        n_cont=(moments.n_cols if moments else 0),
        cat_cards=(cards.cards() if cards else ()),
        has_features=bool(source.has_features),
        source=dict(source.describe()))


# ---------------------------------------------------------------------------
# Structure fit from stats
# ---------------------------------------------------------------------------

def fit_structure_streamed(stats: StreamFitStats, noise: float = 0.0,
                           calibrate: bool = True):
    """``structure.fit_structure`` evaluated from one-pass stats: exact
    bit-pair MLE anchor, Eq. 6 marginal refinement on the sketched
    histograms, then the same candidate ladder — scored against the
    sketches via ``metrics.degree_counts_similarity`` with calibration
    samples histogrammed sparsely (no dense per-node arrays, so wide-id
    fits score without x64 or OOM).  Returns ``(KroneckerFit,
    provenance_dict)``."""
    from repro.core import rmat as rmat_mod
    from repro.core import structure as st
    from repro.core.descend import default_id_dtype
    from repro.core.metrics import degree_counts_similarity
    from repro.graph.ops import sparse_degree_histogram as sparse_hist

    E = stats.rows
    ratios = stats.ratios()

    def marginals(anchor):
        return st.fit_marginals_hist(
            stats.hist_out.astype(np.float64),
            stats.hist_in.astype(np.float64),
            E, stats.n, stats.m, kmax=stats.kmax, anchor=anchor)

    cand = st.candidate_fits(stats.n, stats.m, E, stats.bipartite, noise,
                             ratios, marginals, calibrate=calibrate)
    prov = stats.provenance()
    prov["candidates"] = [name for name, _ in cand]
    if len(cand) == 1:
        prov["chosen"] = cand[0][0]
        return cand[0][1], prov

    dt = default_id_dtype(max(stats.n, stats.m))
    scores = []
    best, best_score = None, -1.0
    for i, (name, fit) in enumerate(cand):
        e_cal = min(fit.E, 200_000)
        src, dst = rmat_mod.sample_graph(jax.random.PRNGKey(1234 + i), fit,
                                         n_edges=e_cal, dtype=dt)
        h_out, mx_out = sparse_hist(np.asarray(src), 2 ** stats.n,
                                    stats.kmax)
        h_in, mx_in = sparse_hist(np.asarray(dst), 2 ** stats.m,
                                  stats.kmax)
        score = degree_counts_similarity(
            stats.hist_out, stats.max_deg_out, stats.hist_in,
            stats.max_deg_in, h_out, mx_out, h_in, mx_in)
        scores.append({"candidate": name, "score": round(float(score), 6)})
        if score > best_score:
            best, best_score, best_name = fit, score, name
    prov["calibration"] = scores
    prov["chosen"] = best_name
    return best, prov


# ---------------------------------------------------------------------------
# Deterministic fit JSON
# ---------------------------------------------------------------------------

def fit_to_json(fit, provenance: Dict[str, Any]) -> str:
    """Serialize ``(KroneckerFit, provenance)`` deterministically: sorted
    keys, fixed separators, repr floats — identical stats in ⇒ identical
    bytes out (the round-trip/ordering acceptance contract)."""
    payload = {"fit": dataclasses.asdict(fit), "provenance": provenance}
    return json.dumps(payload, sort_keys=True, indent=1)


def fit_from_json(text: str):
    """Inverse of :func:`fit_to_json` → ``(KroneckerFit, provenance)``."""
    from repro.core.structure import KroneckerFit
    d = json.loads(text)
    return KroneckerFit(**d["fit"]), d.get("provenance", {})
