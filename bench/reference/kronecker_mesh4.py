"""Plain reference of stochastic-Kronecker (R-MAT) generation on a mesh of
four chips, one src quarter per chip (paper App. 10, Graph500 Kronecker
generator), written from the definition and importing nothing of the
program under test.  It gives the three things a run's committed shards
are held to:

* the plan: ⌈E / shard_edges⌉ steps; a step is ``N_DEV`` blocks of
  ``ceil(shard_edges / N_DEV)`` edges in device order, the last step cut
  to the edges left;
* the read-back: each committed shard's column files, loaded with numpy
  and checked against the row count and CRC-32 the journal recorded
  (``kronecker.read_shard``);
* the statistics of the law.  Block ``d``'s top ``K_DEV`` src levels are
  ``d``'s bits (uniform over the blocks, one src quarter per chip); its
  ``K_DEV`` dst prefix levels are drawn conditioned on those src bits,
  P(dst bit = 0 | s) = θ[s, 0] / (θ[s, 0] + θ[s, 1]); every later level
  draws its (src, dst) quadrant with P = (a, b, c, d), independently of
  the others; and every block draws from a stream of its own.  Per step
  the quadrant counts of each later level are compared with P, and the
  counts of the quadrant pairs of adjacent later levels with P ⊗ P; per
  block the counts of the dst prefix codes are compared with the
  conditional law; all as z-scores.  No run of edges may repeat, at a
  256-edge boundary of a block, a run drawn anywhere else in the window.

The counts are integer sums, taken in one jitted program per block shape
(on whatever device JAX has), so a check of a window of 2^24-edge steps
takes seconds.  ``sample_shard`` is the reference sampler: it does not
reproduce the program's stream, it stands in the program's place as the
control, in float32 or in the precision below it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import numpy as np

from bench.reference.kronecker import (RUN_EDGES, RUN_STRIDE, Kronecker,
                                       _widest_z, committed_records,
                                       read_shard)

__all__ = ["Kronecker", "Plan", "plan", "committed_records", "read_shard",
           "compare", "sample_shard", "N_DEV", "K_DEV"]

#: the deployment's chips: one four-chip host
N_DEV = 4
#: src levels the device index takes
K_DEV = 2


@dataclasses.dataclass
class Plan:
    kron: Kronecker
    block_edges: int            # edges a device draws per step
    shards: List[int]           # edges per step

    def blocks(self, shard_id: int) -> List[Tuple[int, int]]:
        """``[start, stop)`` of each device's block in step ``shard_id``,
        in device order; blocks past the step's edges are empty."""
        ne, be = self.shards[shard_id], self.block_edges
        return [(min(d * be, ne), min((d + 1) * be, ne))
                for d in range(N_DEV)]


def plan(k: Kronecker, shard_edges: int) -> Plan:
    if k.n != k.m or not K_DEV < k.n <= 31:
        raise ValueError("the mesh reference holds square fits of "
                         f"{K_DEV + 1} to 31 levels only")
    steps = -(-k.E // shard_edges)
    return Plan(k, math.ceil(shard_edges / N_DEV),
                [min(shard_edges, k.E - s * shard_edges)
                 for s in range(steps)])


def prefix_law(k: Kronecker, d: int) -> np.ndarray:
    """P of each dst prefix code (the ``K_DEV`` dst bits, level 0 first)
    in block ``d``: a product over the prefix levels of P(dst bit | the
    device's src bit there)."""
    rows = {0: np.array([k.a, k.b]) / (k.a + k.b),
            1: np.array([k.c, k.d]) / (k.c + k.d)}
    p = np.ones(1)
    for lvl in range(K_DEV):
        p = np.kron(p, rows[(d >> (K_DEV - 1 - lvl)) & 1])
    return p


# -- the counts --------------------------------------------------------------

_COUNT_PROGRAMS: Dict[tuple, object] = {}


def _counter(n: int, rows: int):
    """The jitted counts of one block of ``rows`` edges: per later level
    the 4 quadrant counts, per adjacent pair of later levels the 16 pair
    counts, the dst prefix code counts, and the rows outside the block's
    id range."""
    key = (n, rows)
    if key not in _COUNT_PROGRAMS:
        import jax
        import jax.numpy as jnp

        lv = jnp.arange(n - 1 - K_DEV, -1, -1, dtype=jnp.int32)[:, None]

        def counts(src, dst, d):
            code = ((src[None] >> lv) & 1) * 2 + ((dst[None] >> lv) & 1)
            quad = jnp.stack([(code == q).sum(1) for q in range(4)], 1)
            pc = code[:-1] * 4 + code[1:]
            pair = jnp.stack([(pc == q).sum(1) for q in range(16)], 1)
            pre = dst >> (n - K_DEV)
            prefix = jnp.stack([(pre == c).sum()
                                for c in range(1 << K_DEV)])
            outside = ((src >> (n - K_DEV)) != d) | (src < 0) \
                | (dst < 0) | (pre >= (1 << K_DEV))
            return quad, pair, prefix, outside.sum()

        _COUNT_PROGRAMS[key] = jax.jit(counts)
    return _COUNT_PROGRAMS[key]


def _runs(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Rows of ``RUN_EDGES`` suffix pairs (the levels past the prefix),
    one at every ``RUN_STRIDE`` offset of the block."""
    offs = np.arange(0, len(src) - RUN_EDGES + 1, RUN_STRIDE)
    idx = offs[:, None] + np.arange(RUN_EDGES)
    mask = (1 << (n - K_DEV)) - 1
    pairs = ((src[idx].astype(np.int64) & mask) << (n - K_DEV)) \
        | (dst[idx].astype(np.int64) & mask)
    return pairs


def repeated(runs: List[np.ndarray]) -> int:
    """Rows that equal a row taken earlier.  Blocks draw from streams of
    their own, so two runs agree by chance with probability
    (Σ p²)^(levels · RUN_EDGES), under 10^-30 at 24 levels: any repeat
    is a stream drawn twice."""
    runs = [r for r in runs if len(r)]
    if not runs:
        return 0
    allr = np.ascontiguousarray(np.concatenate(runs))
    rows = allr.view(np.dtype((np.void, allr.itemsize * allr.shape[1])))
    return int(len(rows) - len(np.unique(rows)))


def compare(pl: Plan, shards: Dict[int, Dict[str, np.ndarray]]
            ) -> Dict[str, float]:
    """The numbers compared for ``shards`` ({shard_id: {"src", "dst"}}),
    each read back from what the window committed."""
    k, n = pl.kron, pl.kron.n
    p = k.probs
    pp = np.outer(p, p).ravel()
    off_plan = outside = 0
    z_level = z_pair = z_prefix = 0.0
    pooled = np.zeros(4, np.int64)
    runs = []
    for sid, cols in sorted(shards.items()):
        src, dst = np.asarray(cols["src"]), np.asarray(cols["dst"])
        if not (0 <= sid < len(pl.shards)) or len(src) != pl.shards[sid] \
                or len(dst) != len(src):
            off_plan += 1
            continue
        src, dst = src.astype(np.int32), dst.astype(np.int32)
        quad = pair = 0
        for d, (lo, hi) in enumerate(pl.blocks(sid)):
            if hi == lo:
                continue
            q, pr, pre, out = (np.asarray(x) for x in _counter(n, hi - lo)(
                src[lo:hi], dst[lo:hi], np.int32(d)))
            quad, pair = quad + q, pair + pr
            outside += int(out)
            z_prefix = max(z_prefix, _widest_z(pre, prefix_law(k, d)))
            runs.append(_runs(n, src[lo:hi], dst[lo:hi]))
        z_level = max(z_level, max(_widest_z(c, p) for c in quad))
        if len(pair):
            z_pair = max(z_pair, max(_widest_z(c, pp) for c in pair))
        pooled += quad.sum(0)
    if not pooled.sum():
        return {"shards_off_plan": off_plan, "edges_outside_device": outside,
                "quadrant_z_level": float("inf"),
                "quadrant_z_pooled": float("inf"),
                "pair_z_level": float("inf"), "prefix_z": float("inf"),
                "repeated_runs": 0}
    return {"shards_off_plan": off_plan, "edges_outside_device": outside,
            "quadrant_z_level": z_level,
            "quadrant_z_pooled": _widest_z(pooled, p),
            "pair_z_level": z_pair, "prefix_z": z_prefix,
            "repeated_runs": repeated(runs)}


# -- the reference sampler (the control stands it in the program's place) ----

_ID_PROGRAMS: Dict[tuple, object] = {}


def _block_ids(n: int, k: Kronecker, dtype: str):
    """The jitted draw of one block's ids from its uniforms (one row per
    level) and its device index."""
    key = (n, k, dtype)
    if key not in _ID_PROGRAMS:
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(dtype)
        cuts = np.cumsum(k.probs)[:3]
        cond = (k.a / (k.a + k.b), k.c / (k.c + k.d))

        def ids(u, d):
            u = u.astype(dt)
            src = jnp.zeros(u.shape[1], jnp.int32) + d
            dst = jnp.zeros(u.shape[1], jnp.int32)
            for lvl in range(n):
                if lvl < K_DEV:
                    sb = (d >> (K_DEV - 1 - lvl)) & 1
                    t = jnp.where(sb == 0, jnp.float32(cond[0]),
                                  jnp.float32(cond[1])).astype(dt)
                    dst = dst * 2 + (u[lvl] >= t).astype(jnp.int32)
                    continue
                t = jnp.asarray(cuts, jnp.float32).astype(dt)
                q = ((u[lvl] >= t[0]).astype(jnp.int32)
                     + (u[lvl] >= t[1]).astype(jnp.int32)
                     + (u[lvl] >= t[2]).astype(jnp.int32))
                src = src * 2 + (q >> 1)
                dst = dst * 2 + (q & 1)
            return src, dst

        _ID_PROGRAMS[key] = jax.jit(ids)
    return _ID_PROGRAMS[key]


def sample_shard(pl: Plan, shard_id: int, key, dtype: str = "float32"
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """One step drawn by definition: per block, per level, one uniform per
    edge picks the dst bit of a prefix level by the conditional threshold,
    or the quadrant of a later level by the cumulative thresholds a, a+b,
    a+b+c.  ``dtype`` is the precision of the uniforms and the thresholds
    (``float32``, or ``bfloat16`` for the control)."""
    import jax
    import jax.numpy as jnp

    n = pl.kron.n
    ids = _block_ids(n, pl.kron, dtype)
    srcs, dsts = [], []
    for d, (lo, hi) in enumerate(pl.blocks(shard_id)):
        if hi == lo:
            continue
        u = jax.random.uniform(
            jax.random.fold_in(jax.random.fold_in(key, shard_id), d),
            (n, hi - lo), jnp.float32)
        s, t = jax.device_get(ids(u, jnp.int32(d)))
        srcs.append(s.astype(np.int64))
        dsts.append(t.astype(np.int64))
    return np.concatenate(srcs), np.concatenate(dsts)
