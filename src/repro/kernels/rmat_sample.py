"""Pallas TPU kernel: stochastic-Kronecker (R-MAT) edge sampling.

This is the paper's performance hot spot (Fig. 8: their CUDA sampler beats
TrillionG/FastSGG by >10×).  TPU-native adaptation (DESIGN.md §2): edges are
tiled into VMEM blocks; the per-level bit decision is a vectorized
predicated add over 8×128 lanes — no gathers, no divergence.  Uniform
layout is ``(L, BLK)`` so each level reads one contiguous VMEM row.

The decision logic is the repo-wide shared core
(``repro.core.descend.descend``); three kernel variants differ only in
where their uniforms come from:

* ``rmat_sample_uniforms``   — uniforms streamed from HBM (memory-bound
  baseline: 4·L bytes/edge).  Validated in interpret mode vs ``ref.py``.
* ``rmat_sample_bits``       — raw uint32 bits from HBM, converted in-VMEM
  (validates the bit→uniform conversion used by the PRNG variant).
* ``rmat_sample_prng``       — TPU-only: ``pltpu.prng_random_bits``
  generates bits in VMEM (§Perf optimized variant: HBM traffic drops ~L×
  to the edge output).  Each grid block seeds the core PRNG with its own
  two-word seed row (Mosaic accepts at most two seed values).  The TPU
  interpreter's ``prng_random_bits`` returns zeros, so off-TPU this
  variant checks plumbing only; its post-bits logic is exactly
  ``rmat_sample_bits``'s.

Node ids above 31 bits: TPUs have no native int64, so each wide id is
emitted as an ``IdParts(hi, lo)`` pair of int32 output refs and combined
outside the kernel (``repro.core.descend.combine_ids``).  All variants
return ``(src, dst)`` as ``IdParts`` — narrow callers read ``.lo``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.descend import LO_BITS, IdParts, descend

DEFAULT_BLOCK = 8192
#: most grid blocks one ``rmat_sample_prng`` call may seed: the seed rows
#: sit whole in SMEM (1 MiB on v5e), 8 bytes per block
MAX_PRNG_BLOCKS = 1 << 16


def _bits_to_uniform(bits):
    """uint32 -> U[0,1) float32 via mantissa trick (TPU-friendly, no div).
    Signed words are refused: their right shift is arithmetic, which
    turns every draw with the top bit set into NaN."""
    assert bits.dtype == jnp.uint32, bits.dtype
    mant = jnp.right_shift(bits, jnp.uint32(9))
    one = jnp.uint32(0x3F800000)
    f = jax.lax.bitcast_convert_type(jnp.bitwise_or(mant, one), jnp.float32)
    return f - 1.0


def _theta_at(theta_ref):
    return lambda ell: (theta_ref[ell, 0], theta_ref[ell, 1],
                        theta_ref[ell, 2])


def _run_descend(get_u, theta_ref, n, m, block, out_refs):
    """Shared core + scatter of the (hi?, lo) words into the output refs."""
    src, dst = descend(get_u, _theta_at(theta_ref), n, m,
                       lambda: jnp.zeros((block,), jnp.int32))
    vals = [v for v in (src.hi, src.lo, dst.hi, dst.lo) if v is not None]
    for ref, val in zip(out_refs, vals):
        ref[:] = val


def _kernel_uniforms(theta_ref, u_ref, *out_refs, n, m, block):
    _run_descend(lambda ell: u_ref[ell, :], theta_ref, n, m, block, out_refs)


def _kernel_bits(theta_ref, bits_ref, *out_refs, n, m, block):
    _run_descend(lambda ell: _bits_to_uniform(bits_ref[ell, :]),
                 theta_ref, n, m, block, out_refs)


def _kernel_prng(seeds_ref, theta_ref, *out_refs, n, m, block):
    """TPU-only: bits generated in VMEM.  Block ``i`` seeds the PRNG with
    its own row ``seeds_ref[2i : 2i + 2]`` — two 32-bit words, the most
    Mosaic accepts.  The rows are split keys of the call's key, so block
    streams are disjoint across blocks and across calls; seeding with a
    call-level word plus the block index would let the seed ranges of
    different calls overlap."""
    pid = pl.program_id(0)
    pltpu.prng_seed(seeds_ref[2 * pid], seeds_ref[2 * pid + 1])
    L = max(n, m)
    # the TPU PRNG yields int32 words
    bits = pltpu.bitcast(pltpu.prng_random_bits((L, block)), jnp.uint32)
    _run_descend(lambda ell: _bits_to_uniform(bits[ell, :]),
                 theta_ref, n, m, block, out_refs)


def _out_layout(n: int, m: int, E: int, block: int):
    """(specs, shapes, packer) for the 2–4 int32 id-word outputs."""
    wide_src, wide_dst = n > LO_BITS, m > LO_BITS
    k = 2 + wide_src + wide_dst
    specs = [pl.BlockSpec((block,), lambda i: (i,)) for _ in range(k)]
    shapes = [jax.ShapeDtypeStruct((E,), jnp.int32) for _ in range(k)]

    def pack(outs) -> Tuple[IdParts, IdParts]:
        it = iter(outs)
        src_hi = next(it) if wide_src else None
        src_lo = next(it)
        dst_hi = next(it) if wide_dst else None
        dst_lo = next(it)
        return IdParts(src_hi, src_lo), IdParts(dst_hi, dst_lo)

    return specs, shapes, pack


def rmat_sample_uniforms(thetas, uniforms, n: int, m: int,
                         block: int = DEFAULT_BLOCK, interpret: bool = True
                         ) -> Tuple[IdParts, IdParts]:
    """thetas: (L,4) f32; uniforms: (L, E) f32.  E % block == 0."""
    L, E = uniforms.shape
    assert E % block == 0, (E, block)
    grid = (E // block,)
    kern = functools.partial(_kernel_uniforms, n=n, m=m, block=block)
    specs, shapes, pack = _out_layout(n, m, E, block)
    outs = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((L, 4), lambda i: (0, 0)),
            pl.BlockSpec((L, block), lambda i: (0, i)),
        ],
        out_specs=specs,
        out_shape=shapes,
        interpret=interpret,
        name="rmat_sample_uniforms",
    )(thetas, uniforms)
    return pack(outs)


def rmat_sample_bits(thetas, bits, n: int, m: int,
                     block: int = DEFAULT_BLOCK, interpret: bool = True
                     ) -> Tuple[IdParts, IdParts]:
    """thetas: (L,4) f32; bits: (L, E) uint32."""
    L, E = bits.shape
    assert E % block == 0, (E, block)
    kern = functools.partial(_kernel_bits, n=n, m=m, block=block)
    specs, shapes, pack = _out_layout(n, m, E, block)
    outs = pl.pallas_call(
        kern,
        grid=(E // block,),
        in_specs=[
            pl.BlockSpec((L, 4), lambda i: (0, 0)),
            pl.BlockSpec((L, block), lambda i: (0, i)),
        ],
        out_specs=specs,
        out_shape=shapes,
        interpret=interpret,
        name="rmat_sample_bits",
    )(thetas, bits)
    return pack(outs)


def prng_block_seeds(key, n_blocks: int):
    """The ``(2 * n_blocks,)`` int32 seed words ``rmat_sample_prng``
    takes: the key data of ``jax.random.split(key, n_blocks)``, one
    two-word row per grid block."""
    words = jax.random.key_data(jax.random.split(key, n_blocks))
    return jax.lax.bitcast_convert_type(
        words.reshape(n_blocks, -1)[:, -2:].astype(jnp.uint32),
        jnp.int32).reshape(-1)


def rmat_sample_prng(seeds, thetas, n: int, m: int, n_edges: int,
                     block: int = DEFAULT_BLOCK, interpret=False
                     ) -> Tuple[IdParts, IdParts]:
    """TPU-only fast path (no HBM uniform traffic).  seeds: the
    ``(2 * n_edges // block,)`` int32 words of ``prng_block_seeds``.

    ``interpret`` is passed to ``pallas_call``: ``pltpu.InterpretParams()``
    runs the TPU interpreter, whose PRNG yields zero bits."""
    L = max(n, m)
    assert n_edges % block == 0
    n_blocks = n_edges // block
    assert seeds.shape == (2 * n_blocks,), (seeds.shape, n_blocks)
    assert n_blocks <= MAX_PRNG_BLOCKS, \
        f"{n_blocks} blocks > MAX_PRNG_BLOCKS={MAX_PRNG_BLOCKS}: split the call"
    kern = functools.partial(_kernel_prng, n=n, m=m, block=block)
    specs, shapes, pack = _out_layout(n, m, n_edges, block)
    outs = pl.pallas_call(
        kern,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((L, 4), lambda i: (0, 0)),
        ],
        out_specs=specs,
        out_shape=shapes,
        interpret=interpret,
        name="rmat_sample_prng",
    )(seeds, thetas)
    return pack(outs)
