"""The unified edge-sampler engine: one descend core, pluggable backends.

Every production generation path (``rmat.sample_graph*``,
``datastream.DatasetJob``, ``SyntheticGraphPipeline.generate*``,
``scripts/generate_dataset.py``) routes through this registry; the Pallas
fast paths are no longer a side gallery.  All backends share the single
level-descend core (``repro.core.descend.descend``) and one contract::

    backend = get_backend("pallas_bits")          # or resolve_backend()
    src, dst = backend.sample(key, thetas, n, m, n_edges,
                              id_dtype=np.int64)

========================  ===========================================
backend                   what it is
========================  ===========================================
``xla``                   jit reference: one threefry uniform per edge
                          per level (the historical ``sample_edges``
                          stream, bit-for-bit).  Runs everywhere.
``pallas_bits``           Pallas kernel, uint32 bits streamed from HBM
                          and converted in-VMEM.  Interpret mode on
                          CPU/GPU (correctness path), compiled on TPU.
``pallas_prng``           Pallas kernel, bits generated *in VMEM* by
                          the TPU PRNG — HBM traffic drops ~L× to the
                          edge output.  TPU-only (no interpret rule).
========================  ===========================================

Selection (``resolve_backend(None)``): TPU → ``pallas_prng``, the one
TPU sampler, everything else → ``xla`` (interpret-mode Pallas is a
correctness tool, not a fast path).  Tiny batches (< one kernel block)
stay on ``xla`` regardless — the pad-to-block waste would exceed the
work.

Id dtypes: ``int32`` ids cap at 31 bits; ``int64`` ids are produced via
the ``(hi, lo)`` int32-pair descend (native int64 is unsupported on TPU
and in un-x64 jax) and combined on host — up to 62 bits, with or without
``JAX_ENABLE_X64``.  Backends differ in their PRNG streams, so a given
``(backend, key)`` is deterministic but streams are not interchangeable
across backends — resumable jobs record the backend name in their
manifest.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from repro.core.descend import (LO_BITS, IdParts, check_id_capacity,
                                combine_ids, descend, narrow_ids)
from repro.kernels import rmat_sample as rs

#: smallest Pallas block the engine will launch (lane-width friendly)
MIN_BLOCK = 256


def choose_block(n_edges: int, block: int = rs.DEFAULT_BLOCK) -> int:
    """Largest power-of-two block ≤ ``block`` that doesn't over-pad tiny
    batches (pad waste stays < 2× down to MIN_BLOCK)."""
    while block > MIN_BLOCK and block >= 2 * n_edges:
        block //= 2
    return block


def _pad_edges(n_edges: int, block: int) -> int:
    return -(-n_edges // block) * block


def _check_capacity(n: int, m: int, id_dtype, who: str) -> np.dtype:
    dt = np.dtype(id_dtype)
    check_id_capacity(n, dt, f"{who} (src levels)")
    check_id_capacity(m, dt, f"{who} (dst levels)")
    return dt


def _finalize(src: IdParts, dst: IdParts, n: int, m: int, dt: np.dtype,
              n_edges: int):
    """Trim kernel padding and materialize the contract dtype.

    Narrow ids stay device-resident int32 (cast only if asked for a
    different narrow dtype); wide ids are combined on the host so the
    path needs no jax x64.
    """
    if dt.itemsize <= 4:
        return narrow_ids(src, n_edges, dt), narrow_ids(dst, n_edges, dt)
    return (combine_ids(src, n, dt)[:n_edges],
            combine_ids(dst, m, dt)[:n_edges])


def _add_prefix(parts: Tuple[IdParts, IdParts], prefix
                ) -> Tuple[IdParts, IdParts]:
    src, dst = parts
    return (src._replace(lo=src.lo + prefix[0]),
            dst._replace(lo=dst.lo + prefix[1]))


class EdgeSamplerBackend:
    """One way of turning ``(key, thetas, n, m, n_edges)`` into edges."""

    name: str = "?"

    def available(self) -> bool:
        return True

    def why_unavailable(self) -> Optional[str]:
        return None

    def sample_parts(self, key, thetas, n: int, m: int, n_edges: int
                     ) -> Tuple[IdParts, IdParts]:
        """Device-resident ``(src, dst)`` id words, possibly padded past
        ``n_edges`` (kernel blocks).  Stays asynchronous — callers that
        overlap device generation with host I/O (``pump_chunks``) fetch
        and ``descend.combine_ids`` these on their own schedule."""
        raise NotImplementedError

    def sample_chunk_parts(self, key, thetas, n: int, m: int,
                           n_edges: int, prefix) -> Tuple[IdParts, IdParts]:
        """``sample_parts`` of one chunk of narrow ids, with ``prefix`` —
        the chunk's int32 ``(src, dst)`` id prefixes, already shifted past
        the ``n`` and ``m`` level bits — added to the low words."""
        return _add_prefix(self.sample_parts(key, thetas, n, m, n_edges),
                           prefix)

    def sample(self, key, thetas, n: int, m: int, n_edges: int,
               id_dtype=np.int32) -> Tuple[np.ndarray, np.ndarray]:
        """thetas: (max(n,m), 4) per-level (a,b,c,d).  Returns ids of
        ``id_dtype`` — device arrays for int32, host numpy for int64."""
        dt = _check_capacity(n, m, id_dtype, f"{self.name} sampler")
        src, dst = self.sample_parts(key, thetas, n, m, n_edges)
        return _finalize(src, dst, n, m, dt, n_edges)


# ---------------------------------------------------------------------------
# xla: the jit reference path
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "m", "n_edges"))
def _xla_parts(key, thetas, n: int, m: int, n_edges: int):
    keys = jax.random.split(key, max(n, m))
    return descend(
        lambda ell: jax.random.uniform(keys[ell], (n_edges,), jnp.float32),
        lambda ell: (thetas[ell, 0], thetas[ell, 1], thetas[ell, 2]),
        n, m, lambda: jnp.zeros((n_edges,), jnp.int32))


class XlaBackend(EdgeSamplerBackend):
    name = "xla"

    # NOTE: n_edges is a static jit arg, so each distinct size compiles
    # once (cached).  Padding to shape buckets would amortize that, but
    # threefry bit streams are not prefix-stable across shapes — padding
    # would silently change every emitted edge and break both the
    # historical sample_edges stream and resume of pre-engine datasets.
    # Jobs with thousands of distinct chunk sizes belong on the Pallas
    # backends, whose block padding already pins the compiled shapes.
    def sample_parts(self, key, thetas, n, m, n_edges):
        return _xla_parts(key, jnp.asarray(thetas, jnp.float32),
                          n, m, n_edges)


# ---------------------------------------------------------------------------
# pallas_bits: HBM bits → in-VMEM conversion → shared descend
# ---------------------------------------------------------------------------

class PallasBitsBackend(EdgeSamplerBackend):
    name = "pallas_bits"

    @staticmethod
    def interpret() -> bool:
        return jax.default_backend() != "tpu"

    @staticmethod
    def draw_bits(key, L: int, n_edges: int):
        """The exact bit stream the kernel consumes (exposed so parity
        tests can replay it through the ``kernels/ref.py`` oracle)."""
        return jax.random.bits(key, (L, n_edges), jnp.uint32)

    def sample_parts(self, key, thetas, n, m, n_edges):
        block = choose_block(n_edges)
        bits = self.draw_bits(key, max(n, m), _pad_edges(n_edges, block))
        return rs.rmat_sample_bits(jnp.asarray(thetas, jnp.float32),
                                   bits, n, m, block=block,
                                   interpret=self.interpret())


# ---------------------------------------------------------------------------
# pallas_prng: bits generated in VMEM (TPU-only)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "m", "n_pad", "block",
                                             "interpret"))
def _prng_chunk(key, thetas, prefix, *, n: int, m: int, n_pad: int,
                block: int, interpret: bool):
    """All of one chunk's device work: per-block seeds, the kernel, and
    (``prefix`` not None) the chunk's id prefixes.  One program per
    padded shape: the chunk's exact size is not an argument, so every
    chunk that pads to ``(n_pad, block)`` reuses it (an eager
    ``pallas_call`` compiles anew on every call)."""
    src, dst = rs.rmat_sample_prng(
        rs.prng_block_seeds(key, n_pad // block), thetas, n, m, n_pad,
        block=block, interpret=pltpu.InterpretParams() if interpret
        else False)
    return (src, dst) if prefix is None else _add_prefix((src, dst), prefix)


class PallasPrngBackend(EdgeSamplerBackend):
    name = "pallas_prng"

    def __init__(self, force_interpret: bool = False):
        #: opt-in plumbing coverage off-TPU: run the kernel under the TPU
        #: interpreter, whose PRNG yields zero bits (every edge takes
        #: quadrant a).  Never the registered default.
        self.force_interpret = bool(force_interpret)

    def available(self) -> bool:
        return self.why_unavailable() is None

    def why_unavailable(self) -> Optional[str]:
        if jax.default_backend() != "tpu" and not self.force_interpret:
            return ("pltpu.prng_* generates zero bits off-TPU — "
                    "TPU-only backend")
        return None

    def sample_parts(self, key, thetas, n, m, n_edges):
        return self._chunk(key, thetas, n, m, n_edges, None)

    def sample_chunk_parts(self, key, thetas, n, m, n_edges, prefix):
        return self._chunk(key, thetas, n, m, n_edges, prefix)

    def _chunk(self, key, thetas, n, m, n_edges, prefix):
        reason = self.why_unavailable()
        if reason is not None:
            raise RuntimeError(f"backend 'pallas_prng' unavailable: "
                               f"{reason}; use 'pallas_bits' or 'xla'")
        block = choose_block(n_edges)
        return _prng_chunk(key, jnp.asarray(thetas, jnp.float32), prefix,
                           n=n, m=m, n_pad=_pad_edges(n_edges, block),
                           block=block,
                           interpret=jax.default_backend() != "tpu")


# ---------------------------------------------------------------------------
# registry + auto-selection
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, EdgeSamplerBackend] = {}


def register_backend(backend: EdgeSamplerBackend) -> EdgeSamplerBackend:
    _REGISTRY[backend.name] = backend
    return backend


register_backend(XlaBackend())
register_backend(PallasBitsBackend())
register_backend(PallasPrngBackend())


def registered_backends() -> List[str]:
    """Every registered backend name (available on this host or not)."""
    return list(_REGISTRY)


def available_backends() -> List[str]:
    return [n for n, b in _REGISTRY.items() if b.available()]


def get_backend(name: str) -> EdgeSamplerBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown edge-sampler backend {name!r}; "
                       f"registered: {sorted(_REGISTRY)}") from None


def resolve_backend(name: Optional[str] = None,
                    n_edges: Optional[int] = None) -> EdgeSamplerBackend:
    """Pick a backend by device/size: explicit names win (``'auto'`` and
    ``None`` both auto-select); TPU gets the VMEM-resident PRNG kernel,
    sub-block batches and non-TPU hosts get the jit reference path."""
    if name is not None and name != "auto":
        return get_backend(name)
    if jax.default_backend() != "tpu" or (n_edges is not None
                                          and n_edges < MIN_BLOCK):
        return _REGISTRY["xla"]
    return _REGISTRY["pallas_prng"]
