"""Plain reference of chunked stochastic-Kronecker (R-MAT) generation.

Written from the generator's definition (paper App. 10 and the Graph500
Kronecker generator), in numpy, importing nothing of the program under
test.  It gives the three things a run's committed shards are held to:

* the plan: the 4^k prefix chunks with their expected edge counts,
  rounded by largest remainder so they sum to E, packed first-fit in
  chunk order into shards of at most ``shard_edges`` edges;
* the read-back: each committed shard's column files, loaded with numpy
  and checked against the row count and CRC-32 the journal recorded;
* the statistics: within a chunk every suffix level draws one quadrant
  per edge, independently, with P = (a, b, c, d), and every chunk draws
  from a stream of its own.  A shard's per-level quadrant counts are
  compared with P, and its counts of the quadrant pairs of adjacent
  levels with P ⊗ P, as z-scores; and no run of edges may repeat, at a
  256-edge boundary, a run drawn anywhere else in the window.

``sample_shard`` is the reference sampler itself.  It does not reproduce
the program's PRNG stream (the TPU kernel draws its bits from the chip's
own generator); it exists to stand in the program's place as the control,
in float32 or in the precision below it.
"""
from __future__ import annotations

import dataclasses
import json
import os
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

#: prefix levels are capped so that planning stays cheap (4^8 chunks)
MAX_K_PREF = 8


@dataclasses.dataclass(frozen=True)
class Kronecker:
    a: float
    b: float
    c: float
    d: float
    n: int                    # src levels
    m: int                    # dst levels
    E: int                    # edges

    @property
    def probs(self) -> np.ndarray:
        return np.array([self.a, self.b, self.c, self.d], np.float64)


def auto_k_pref(k: Kronecker, shard_edges: int) -> int:
    """Fewest prefix levels at which the heaviest chunk's expected count
    fits one shard, keeping at least one suffix level."""
    cap = max(0, min(MAX_K_PREF, min(k.n, k.m) - 1))
    pmax = float(k.probs.max())
    levels = 0
    while levels < cap and k.E * pmax ** levels > shard_edges:
        levels += 1
    return levels


@dataclasses.dataclass
class Plan:
    """Nonzero chunks in chunk-index order, and the shards over them."""
    kron: Kronecker
    k_pref: int
    index: np.ndarray         # chunk index (quadrant digits, base 4)
    src_prefix: np.ndarray
    dst_prefix: np.ndarray
    n_edges: np.ndarray
    shards: List[Tuple[int, int]]   # [start, stop) into the chunk arrays

    def shard_edges(self, shard_id: int) -> int:
        lo, hi = self.shards[shard_id]
        return int(self.n_edges[lo:hi].sum())


def plan(k: Kronecker, shard_edges: int,
         k_pref: Optional[int] = None) -> Plan:
    if k_pref is None:
        k_pref = auto_k_pref(k, shard_edges)
    p = np.ones(1)
    for _ in range(k_pref):
        p = np.kron(p, k.probs)
    raw = p * k.E
    counts = np.floor(raw).astype(np.int64)
    short = k.E - int(counts.sum())
    # largest remainder; among equal remainders, the order numpy's
    # default argsort gives them, reversed (the plan's published rule)
    counts[np.argsort(raw - counts)[::-1][:short]] += 1
    index = np.flatnonzero(counts)
    sp = np.zeros(len(index), np.int64)
    dp = np.zeros(len(index), np.int64)
    for lvl in range(k_pref):
        quad = (index >> (2 * (k_pref - 1 - lvl))) & 3
        sp = sp * 2 + (quad >> 1)
        dp = dp * 2 + (quad & 1)
    n_edges = counts[index]
    shards, start, fill = [], 0, 0
    for i, ne in enumerate(n_edges):
        if i > start and fill + ne > shard_edges:
            shards.append((start, i))
            start, fill = i, 0
        fill += int(ne)
    shards.append((start, len(n_edges)))
    return Plan(k, k_pref, index, sp, dp, n_edges, shards)


# -- read-back ---------------------------------------------------------------

def committed_records(out_dir: str) -> Dict[int, dict]:
    """Shard records marked done, from ``manifest.json`` and then any
    journal lines appended after it (later lines win)."""
    with open(os.path.join(out_dir, "manifest.json")) as f:
        recs = {r["shard_id"]: r for r in json.load(f)["shards"]}
    journal = os.path.join(out_dir, "progress.jsonl")
    if os.path.exists(journal):
        with open(journal) as f:
            for line in f:
                line = line.strip()
                if line:
                    r = json.loads(line)
                    recs[r["shard_id"]] = r
    return {sid: r for sid, r in recs.items() if r.get("status") == "done"}


def read_column(path: str, n_rows: int, crc: int) -> Tuple[
        Optional[np.ndarray], Optional[str]]:
    """The column as written, or why it does not read back."""
    try:
        arr = np.load(path, mmap_mode="r")
    except (OSError, ValueError) as e:
        return None, f"{os.path.basename(path)}: {e}"
    if arr.shape[:1] != (n_rows,):
        return None, f"{os.path.basename(path)}: {arr.shape[0]} rows, " \
                     f"journal says {n_rows}"
    data = np.ascontiguousarray(arr)
    got = zlib.crc32(memoryview(data).cast("B")) & 0xFFFFFFFF
    if got != crc:
        return None, f"{os.path.basename(path)}: crc32 {got} != {crc}"
    return data, None


def read_shard(out_dir: str, rec: dict
               ) -> Tuple[Dict[str, np.ndarray], List[str]]:
    """A committed shard's id columns, and what did not read back."""
    arrays, problems = {}, []
    for col in ("src", "dst"):
        fname = rec.get("files", {}).get(col)
        if fname is None:
            problems.append(f"shard {rec['shard_id']}: no {col} file")
            continue
        arr, why = read_column(os.path.join(out_dir, fname),
                               int(rec["n_edges"]), rec["crc32"].get(col))
        if why:
            problems.append(f"shard {rec['shard_id']}: {why}")
        else:
            arrays[col] = arr
    return arrays, problems


# -- the comparison ----------------------------------------------------------

def outside_chunk(pl: Plan, shard_id: int, src: np.ndarray,
                  dst: np.ndarray) -> int:
    """Edges whose ids lie outside their chunk's prefix block."""
    lo, hi = pl.shards[shard_id]
    reps = pl.n_edges[lo:hi]
    n_s, m_s = pl.kron.n - pl.k_pref, pl.kron.m - pl.k_pref
    want_s = np.repeat(pl.src_prefix[lo:hi], reps)
    want_d = np.repeat(pl.dst_prefix[lo:hi], reps)
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    bad = ((src >> n_s) != want_s) | ((dst >> m_s) != want_d) \
        | (src < 0) | (dst < 0)
    return int(bad.sum())


def _expected(k: Kronecker, kind: str) -> np.ndarray:
    if kind == "quad":
        return k.probs
    p0 = k.a + k.b if kind == "src" else k.a + k.c
    return np.array([p0, 1.0 - p0])


def level_codes(pl: Plan, src: np.ndarray, dst: np.ndarray) -> List[
        Tuple[str, np.ndarray]]:
    """Per suffix level, its kind and each edge's code there: ("quad",
    2·src bit + dst bit) where both ids take a bit, or ("src"/"dst", the
    one bit) where only one does (the marginal levels of a rectangular
    fit)."""
    n_s, m_s = pl.kron.n - pl.k_pref, pl.kron.m - pl.k_pref
    out = []
    for lvl in range(max(n_s, m_s)):
        sb = ((src >> (n_s - 1 - lvl)) & 1) if lvl < n_s else None
        db = ((dst >> (m_s - 1 - lvl)) & 1) if lvl < m_s else None
        if sb is not None and db is not None:
            out.append(("quad", 2 * sb + db))
        elif sb is not None:
            out.append(("src", sb))
        else:
            out.append(("dst", db))
    return out


def level_counts(k: Kronecker, codes: List[Tuple[str, np.ndarray]]
                 ) -> List[Tuple[str, np.ndarray]]:
    """Per suffix level, its kind and the counts of each code there."""
    return [(kind, np.bincount(c, minlength=len(_expected(k, kind))))
            for kind, c in codes]


def _widest_z(counts: np.ndarray, p: np.ndarray) -> float:
    n = float(counts.sum())
    return float(np.max(np.abs(counts - n * p) / np.sqrt(n * p * (1 - p))))


def pair_z(k: Kronecker, codes: List[Tuple[str, np.ndarray]]) -> float:
    """Widest |z| of one shard's counts of the code pairs of adjacent
    suffix levels against the product of their distributions: levels
    drawn independently of each other give P ⊗ P."""
    widest = 0.0
    for (k1, c1), (k2, c2) in zip(codes, codes[1:]):
        p1, p2 = _expected(k, k1), _expected(k, k2)
        joint = np.bincount(c1 * len(p2) + c2, minlength=len(p1) * len(p2))
        widest = max(widest, _widest_z(joint, np.outer(p1, p2).ravel()))
    return widest


def z_scores(k: Kronecker, per_shard: List[List[Tuple[str, np.ndarray]]]
             ) -> Tuple[float, float]:
    """(widest |z| of any level of any shard, widest |z| of the counts
    pooled over every level and shard), over quadrants (or bits)."""
    widest = 0.0
    pooled: Dict[str, np.ndarray] = {}
    for levels in per_shard:
        for kind, c in levels:
            widest = max(widest, _widest_z(c, _expected(k, kind)))
            pooled[kind] = pooled.get(kind, 0) + c
    wide_pool = max(_widest_z(c, _expected(k, kind))
                    for kind, c in pooled.items())
    return widest, wide_pool


#: runs of edges are compared at every multiple of this offset in a chunk
#: (the smallest block the sampler launches), this many edges long
RUN_STRIDE, RUN_EDGES = 256, 4


def repeated_runs(pl: Plan, shards: Dict[int, Tuple[np.ndarray,
                                                   np.ndarray]]) -> int:
    """Runs of ``RUN_EDGES`` suffix pairs, taken at every ``RUN_STRIDE``
    offset of every chunk of ``shards``, that equal a run taken earlier.
    Chunks draw from streams of their own, so two runs agree by chance
    with probability (Σ p²)^(levels · RUN_EDGES), under 10^-28 at 18
    levels: any repeat is a stream drawn twice."""
    n_s, m_s = pl.kron.n - pl.k_pref, pl.kron.m - pl.k_pref
    runs = []
    for sid, (src, dst) in sorted(shards.items()):
        suffix = np.stack([np.asarray(src, np.int64) & ((1 << n_s) - 1),
                           np.asarray(dst, np.int64) & ((1 << m_s) - 1)])
        lo, hi = pl.shards[sid]
        starts = np.concatenate([[0], np.cumsum(pl.n_edges[lo:hi])[:-1]])
        for at, ne in zip(starts, pl.n_edges[lo:hi]):
            offs = np.arange(0, int(ne) - RUN_EDGES + 1, RUN_STRIDE)
            if not len(offs):
                continue
            idx = at + offs[:, None] + np.arange(RUN_EDGES)
            runs.append(np.concatenate([suffix[0, idx], suffix[1, idx]], 1))
    if not runs:
        return 0
    allr = np.concatenate(runs)
    return int(len(allr) - len(np.unique(allr, axis=0)))


def compare(pl: Plan, shards: Dict[int, Dict[str, np.ndarray]]
            ) -> Dict[str, float]:
    """The numbers compared for ``shards`` ({shard_id: {"src", "dst"}}),
    each read back from what the window committed."""
    off_plan = outside = 0
    per_shard, z_pair, on_plan = [], 0.0, {}
    for sid, cols in sorted(shards.items()):
        src, dst = np.asarray(cols["src"]), np.asarray(cols["dst"])
        if not (0 <= sid < len(pl.shards)) \
                or len(src) != pl.shard_edges(sid) or len(dst) != len(src):
            off_plan += 1
            continue
        outside += outside_chunk(pl, sid, src, dst)
        codes = level_codes(pl, src, dst)
        per_shard.append(level_counts(pl.kron, codes))
        z_pair = max(z_pair, pair_z(pl.kron, codes))
        on_plan[sid] = (src, dst)
    if not per_shard:
        return {"shards_off_plan": off_plan, "edges_outside_chunk": outside,
                "quadrant_z_level": float("inf"),
                "quadrant_z_pooled": float("inf"),
                "pair_z_level": float("inf"), "repeated_runs": 0}
    z_level, z_pooled = z_scores(pl.kron, per_shard)
    return {"shards_off_plan": off_plan, "edges_outside_chunk": outside,
            "quadrant_z_level": z_level, "quadrant_z_pooled": z_pooled,
            "pair_z_level": z_pair,
            "repeated_runs": repeated_runs(pl, on_plan)}


# -- the reference sampler (the control stands it in the program's place) ----

def _quadrant_ids(n_levels: int, cuts: Tuple[float, float, float],
                  dtype: str):
    """The jitted draw of one chunk's suffix ids from its uniforms."""
    import jax
    import jax.numpy as jnp

    key = (n_levels, cuts, dtype)
    if key not in _ID_PROGRAMS:
        dt = jnp.dtype(dtype)

        def ids(u):
            t = jnp.asarray(cuts, jnp.float32).astype(dt)
            u = u.astype(dt)
            q = ((u >= t[0]).astype(jnp.int32) + (u >= t[1]).astype(
                jnp.int32) + (u >= t[2]).astype(jnp.int32))
            pw = (2 ** jnp.arange(n_levels - 1, -1, -1,
                                  dtype=jnp.int32))[:, None]
            return jnp.sum((q >> 1) * pw, 0), jnp.sum((q & 1) * pw, 0)

        _ID_PROGRAMS[key] = jax.jit(ids)
    return _ID_PROGRAMS[key]


_ID_PROGRAMS: Dict[tuple, object] = {}


def sample_shard(pl: Plan, shard_id: int, key, dtype: str = "float32"
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """One shard drawn by definition: per chunk, per suffix level, one
    uniform per edge picks the quadrant by the cumulative thresholds
    a, a+b, a+b+c.  ``dtype`` is the precision of the uniforms and the
    thresholds (``float32``, or ``bfloat16`` for the control)."""
    import jax
    import jax.numpy as jnp

    k = pl.kron
    if k.n != k.m:
        raise ValueError("the reference sampler draws square fits only")
    n_s = k.n - pl.k_pref
    ids = _quadrant_ids(n_s, tuple(float(x) for x in
                                   np.cumsum(k.probs)[:3]), dtype)
    lo, hi = pl.shards[shard_id]
    srcs, dsts = [], []
    for i in range(lo, hi):
        ne = int(pl.n_edges[i])
        u = jax.random.uniform(jax.random.fold_in(key, int(pl.index[i])),
                               (n_s, ne), jnp.float32)
        s, d = jax.device_get(ids(u))
        srcs.append((int(pl.src_prefix[i]) << n_s) + s.astype(np.int64))
        dsts.append((int(pl.dst_prefix[i]) << n_s) + d.astype(np.int64))
    return np.concatenate(srcs), np.concatenate(dsts)
