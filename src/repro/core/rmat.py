"""Edge sampling for the generalized stochastic Kronecker generator.

All sampling routes through the unified engine in ``repro.core.sampler``
(one shared level-descend core, pluggable xla / pallas_bits / pallas_prng
backends).  ``sample_edges`` is the ``xla`` backend's contract (kept as
the stable reference API); ``chunk_plan`` + ``sample_chunk`` implement
the paper's App. 10 chunked generation: θ is split ``θ_pref ⊗ θ_gen``;
prefix sampling is replaced by its expectation ``E_i = E · P(prefix = i)``
so chunks are id-disjoint, deterministic in count, and embarrassingly
parallel (each chunk only needs its own PRNG key).

Node ids follow the engine's dtype contract: int32 up to 31 bits, int64
(``(hi, lo)`` pair descend + host combine — no jax x64 needed) up to 62.
"""
from __future__ import annotations

import functools
import math
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sampler as sampler_mod
from repro.core.descend import check_id_capacity, narrow_ids
from repro.core.structure import KroneckerFit, noisy_thetas


def sample_edges(key, thetas, n: int, m: int, n_edges: int,
                 dtype=jnp.int32, backend: Optional[str] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sample ``n_edges`` edges of a 2^n × 2^m adjacency.

    thetas: (max(n,m), 4) per-level (a,b,c,d) — rows beyond min(n,m) use
    only their marginals (p = a+b row-zero prob, q = a+c col-zero prob).
    ``backend=None`` keeps the ``xla`` reference stream (bit-stable across
    repo versions); pass a registry name or ``'auto'`` to switch engines.
    """
    return _engine(backend, n_edges).sample(key, thetas, n, m, n_edges,
                                            id_dtype=dtype)


def _engine(backend: Optional[str], n_edges: int):
    return sampler_mod.get_backend("xla") if backend is None \
        else sampler_mod.resolve_backend(backend, n_edges)


_NOISE_SALT = 0x5eed


def _noise_rng_from_key(key) -> np.random.Generator:
    """Deterministic numpy Generator derived from a JAX key — distinct keys
    get distinct θ-noise, the same key always gets the same noise."""
    seed = int(jax.random.randint(jax.random.fold_in(key, _NOISE_SALT), (),
                                  0, np.iinfo(np.int32).max))
    return np.random.default_rng(seed)


def derive_thetas(fit: KroneckerFit,
                  rng: Optional[np.random.Generator] = None,
                  key=None) -> np.ndarray:
    """Canonical (levels, 4) θ derivation — the ONE place θ-noise is drawn.

    With ``fit.noise == 0`` the result is the deterministic tiled base and no
    RNG state is consumed.  With noise, the per-level draw comes from ``rng``
    (or a Generator derived from ``key``) — callers must derive θ once and
    thread it through repeated ``sample_chunk`` calls; deriving inside each
    call would silently reuse identical noise across chunks.
    """
    if fit.noise <= 0:
        return np.tile(np.array([fit.a, fit.b, fit.c, fit.d]),
                       (max(fit.n, fit.m), 1))
    if rng is None:
        if key is None:
            raise ValueError("fit.noise > 0: pass rng= or key= so θ-noise "
                             "is derived explicitly (no hidden default rng)")
        rng = _noise_rng_from_key(key)
    return noisy_thetas(fit, rng)


def chunk_key(key, chunk_index: int):
    """Index-stable per-chunk PRNG key: depends only on (key, chunk.index),
    never on how many chunks the plan produced or the order they run in —
    the property datastream resumption relies on."""
    return jax.random.fold_in(key, chunk_index)


def sample_graph(key, fit: KroneckerFit, n_edges: Optional[int] = None,
                 rng: Optional[np.random.Generator] = None,
                 dtype=jnp.int32, backend: Optional[str] = None):
    """One-shot (unchunked) generation from a fit."""
    thetas = jnp.asarray(derive_thetas(fit, rng=rng, key=key), jnp.float32)
    E = n_edges if n_edges is not None else fit.E
    return sample_edges(key, thetas, fit.n, fit.m, E, dtype, backend)


# ---------------------------------------------------------------------------
# Chunked generation (paper App. 10)
# ---------------------------------------------------------------------------

class Chunk(NamedTuple):
    src_prefix: int
    dst_prefix: int
    n_edges: int
    index: int


def chunk_plan(fit: KroneckerFit, k_pref: int,
               thetas: Optional[np.ndarray] = None) -> List[Chunk]:
    """Enumerate the 4^k_pref prefix chunks with expected edge counts.

    Uses the first ``k_pref`` (square) levels of θ; expected counts are
    rounded with largest-remainder so they sum exactly to E.  Fully
    vectorized (numpy bit de-interleave over the nonzero chunks) — the
    former per-chunk Python loop dominated plan time at k_pref ≥ 8.
    """
    assert k_pref <= min(fit.n, fit.m), (k_pref, fit.n, fit.m)
    if thetas is None:
        thetas = np.tile(np.array([fit.a, fit.b, fit.c, fit.d]),
                         (max(fit.n, fit.m), 1))
    probs = np.ones(1)
    for ell in range(k_pref):
        probs = np.kron(probs, thetas[ell])
    raw = probs * fit.E
    base = np.floor(raw).astype(np.int64)
    rem = fit.E - base.sum()
    order = np.argsort(raw - base)[::-1]
    base[order[:rem]] += 1
    # quadrant index sequence -> (src_prefix, dst_prefix): de-interleave
    # the 2k_pref-bit chunk index into odd (src) and even (dst) bits
    nz = np.flatnonzero(base)
    sp = np.zeros(len(nz), np.int64)
    dp = np.zeros(len(nz), np.int64)
    for ell in range(k_pref):
        quad = (nz >> (2 * (k_pref - 1 - ell))) & 3
        sp = sp * 2 + (quad >> 1)
        dp = dp * 2 + (quad & 1)
    return [Chunk(int(s), int(d), int(e), int(i))
            for s, d, e, i in zip(sp, dp, base[nz], nz)]


def suffix_thetas(thetas, k_pref: int):
    """θ's suffix levels (rows ``k_pref`` on) as a device float32 array,
    made once per distinct θ: a chunk call then copies no θ to the
    device, and every caller hands the compiled chunk programs the same
    array."""
    host = np.asarray(thetas, np.float32)[k_pref:]
    return _device_rows(host.tobytes(), len(host))


@functools.lru_cache(maxsize=8)
def _device_rows(raw: bytes, rows: int):
    return jnp.asarray(np.frombuffer(raw, np.float32).reshape(rows, 4))


def sample_chunk(key, fit: KroneckerFit, chunk: Chunk, k_pref: int,
                 thetas=None, dtype=jnp.int32,
                 backend: Optional[str] = None, padded: bool = False):
    """Sample one chunk: suffix levels from θ_gen, prefix bits prepended.
    Guaranteed id-disjoint across chunks (distinct prefixes).

    ``thetas`` must be derived ONCE by the caller (``derive_thetas``) and
    threaded through every chunk of a generation; for noiseless fits it is
    optional (the deterministic base is used).

    Narrow ids come back as device int32 arrays with the prefix added on
    device, in the backend's one dispatch where it can; ``padded=True``
    leaves them as long as the backend made them (kernel blocks past
    ``chunk.n_edges``), for a caller that trims them on the host after
    the copy.  Wide ids come back as host numpy arrays.
    """
    # prefix bits + suffix level bits must fit the id dtype — raise
    # instead of wrapping (int32 silently capped ids at 2^31 before)
    check_id_capacity(fit.n, dtype, "sample_chunk: src prefix+level bits")
    check_id_capacity(fit.m, dtype, "sample_chunk: dst prefix+level bits")
    if thetas is None:
        if fit.noise > 0:
            raise ValueError(
                "fit.noise > 0: derive θ once with derive_thetas() in the "
                "caller and pass thetas= — a per-call default rng would "
                "silently reuse identical θ-noise across chunks")
        thetas = derive_thetas(fit)
    suffix = suffix_thetas(thetas, k_pref)
    n_s, m_s = fit.n - k_pref, fit.m - k_pref
    dt = np.dtype(dtype)
    if dt.itemsize > 4:
        # int64 prefix arithmetic happens in host numpy (x64-independent)
        src, dst = sample_edges(key, suffix, n_s, m_s, chunk.n_edges, dtype,
                                backend)
        return (np.asarray(src) + dt.type(chunk.src_prefix << n_s),
                np.asarray(dst) + dt.type(chunk.dst_prefix << m_s))
    prefix = np.array([chunk.src_prefix << n_s, chunk.dst_prefix << m_s],
                      np.int32)
    src, dst = _engine(backend, chunk.n_edges).sample_chunk_parts(
        key, suffix, n_s, m_s, chunk.n_edges, prefix)
    if padded:
        return src.lo, dst.lo
    return (narrow_ids(src, chunk.n_edges, dt),
            narrow_ids(dst, chunk.n_edges, dt))


def sample_graph_chunked(key, fit: KroneckerFit, k_pref: int = 2,
                         rng: Optional[np.random.Generator] = None,
                         thetas: Optional[np.ndarray] = None,
                         dtype=jnp.int32, backend: Optional[str] = None):
    """Full graph via chunk concatenation (memory-bounded generation).

    θ-noise is derived exactly once (from ``rng`` or, failing that, from
    ``key``) and threaded through every chunk; per-chunk keys are
    index-stable ``chunk_key`` fold-ins, so this matches the streamed
    ``repro.datastream`` path chunk-for-chunk.
    """
    if thetas is None:
        thetas = derive_thetas(fit, rng=rng, key=key)
    # pin 'auto' once for the whole plan: per-chunk resolution could mix
    # engines (sub-block chunks fall back to xla on TPU) and break the
    # chunked == streamed golden-seed equivalence
    if backend is not None:
        backend = sampler_mod.resolve_backend(backend, fit.E).name
    chunks = chunk_plan(fit, k_pref, thetas)
    srcs, dsts = [], []
    for ck in chunks:
        s, d = sample_chunk(chunk_key(key, ck.index), fit, ck, k_pref,
                            thetas, dtype, backend)
        srcs.append(s)
        dsts.append(d)
    if np.dtype(dtype).itemsize > 4:    # host-resident wide ids
        return np.concatenate(srcs), np.concatenate(dsts)
    return jnp.concatenate(srcs), jnp.concatenate(dsts)


# ---------------------------------------------------------------------------
# Erdős–Rényi baseline (paper §4.1 'random')
# ---------------------------------------------------------------------------

def sample_erdos_renyi(key, n_src: int, n_dst: int, n_edges: int,
                       dtype=jnp.int32):
    k1, k2 = jax.random.split(key)
    src = jax.random.randint(k1, (n_edges,), 0, n_src, dtype)
    dst = jax.random.randint(k2, (n_edges,), 0, n_dst, dtype)
    return src, dst
