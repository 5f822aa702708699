"""Compile the main path's device programs for a TPU v5e that is described,
not attached: the R-MAT kernels at real widths and the four-chip
generation step.  Nothing runs; the TPU compiler refuses here what it
would refuse on the chip (unsupported kernel ops, VMEM/SMEM overruns,
unaligned blocks), at no chip time.

``repro.kernels.rmat_sample`` is imported first, on purpose: it must
import on its own, with no ``repro`` module loaded before it.
"""
import functools
import os
import re

from repro.kernels import rmat_sample as rs  # noqa: I001 — first, see above

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.core.distributed_gen import build_generation_cell

BLOCK = 8192
#: one 2^24-edge shard: the size ``chip_smoke.py`` runs per kernel call
N_EDGES = 1 << 24
COLLECTIVE = re.compile(r"(all-gather|all-reduce|reduce-scatter|all-to-all"
                        r"|collective-permute)")


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host.  The persistent compilation cache is off
    while it is in use: a program compiled for a described chip is
    written to the cache but cannot be read back without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any reason it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _n_outputs(levels):
    return 4 if levels > 31 else 2      # (hi, lo) words per wide id


@pytest.mark.parametrize("levels", [30, 34])
def test_rmat_sample_prng_compiles_for_v5e(one_chip, levels):
    fn = functools.partial(rs.rmat_sample_prng, n=levels, m=levels,
                           n_edges=N_EDGES, block=BLOCK)
    compiled = jax.jit(fn).lower(
        _abstract((2 * N_EDGES // BLOCK,), jnp.int32, one_chip),
        _abstract((levels, 4), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= \
        _n_outputs(levels) * 4 * N_EDGES


@pytest.mark.parametrize("levels", [18, 34])
def test_prng_chunk_program_compiles_for_v5e(one_chip, levels):
    """``pallas_prng``'s per-chunk program (seeds, kernel and, for narrow
    ids, the prefix add in one) at the Graph500 cell's suffix depth and
    at wide ids."""
    from repro.core import sampler
    prefix = (None if levels > 31
              else _abstract((2,), jnp.int32, one_chip))
    compiled = sampler._prng_chunk.lower(
        _abstract((2,), jnp.uint32, one_chip),
        _abstract((levels, 4), jnp.float32, one_chip), prefix,
        n=levels, m=levels, n_pad=N_EDGES, block=BLOCK,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= \
        _n_outputs(levels) * 4 * N_EDGES


@pytest.mark.parametrize("levels", [30, 34])
def test_rmat_sample_bits_compiles_for_v5e(one_chip, levels):
    fn = functools.partial(rs.rmat_sample_bits, n=levels, m=levels,
                           block=BLOCK, interpret=False)
    compiled = jax.jit(fn).lower(
        _abstract((levels, 4), jnp.float32, one_chip),
        _abstract((levels, N_EDGES), jnp.uint32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= \
        _n_outputs(levels) * 4 * N_EDGES


def test_generation_cell_compiles_for_four_chips_without_collectives(topo):
    """One device_steps step on the 2x2 mesh: every chip samples its own
    prefix, so the compiled step holds no collective."""
    mesh = Mesh(np.array(topo.devices).reshape(-1), ("d",))
    assert mesh.size == 4
    cell = build_generation_cell(mesh)
    args = tuple(_abstract(a.shape, a.dtype, sh)
                 for a, sh in zip(cell.args, cell.in_shardings))
    compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                       out_shardings=cell.out_shardings).lower(
        *args).compile()
    hlo = compiled.as_text()
    assert not COLLECTIVE.search(hlo), COLLECTIVE.findall(hlo)[:5]
    # per chip: 2^24 edges × (src, dst) int32
    assert compiled.memory_analysis().output_size_in_bytes >= \
        2 * 4 * cell.meta["edges"] // mesh.size
