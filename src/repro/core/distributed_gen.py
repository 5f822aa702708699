"""Distributed chunked generation over a TPU mesh (paper App. 10 at pod
scale).

Each device owns one src id range, the top ``k = log2(n_dev)`` src levels
being its index; one ``generation step`` produces ``edges_per_device``
edges on every device simultaneously with ZERO collectives (the roofline
collective term of this step is ~0 by construction — the paper's linear
multi-GPU scaling claim, reproduced as a property of the lowered HLO).
Those k src levels are uniform (every device makes the same number of
edges); everything else follows the Kronecker law given the device's src
prefix: the k dst prefix levels are drawn conditioned on the device's src
bits, and every later src level is drawn jointly with the dst level of
the same index (``device_block``).

The shard_map body contains no level-descend logic of its own: it drives
the repo-wide shared core (``repro.core.descend.descend``) for the levels
past the prefix and composes the prefixes with ``combine_ids_device``.

``build_generation_cell`` returns the lowering target used by
``launch/dryrun.py --graphgen``: one streaming step of the trillion-edge
configuration (2^30 × 2^30 nodes, 2^24 edges/device/step ⇒ 8.6e9 edges per
512-chip step; 1e12 edges in ~117 steps).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.descend import (check_id_capacity, combine_ids_device,
                                descend)


def step_seeds(base_seed: int, step: int, n_dev: int) -> np.ndarray:
    """Step-indexed per-device seeds (splitmix64 finalizer, int32 range).

    Deterministic in ``(base_seed, step)`` and disjoint across devices and
    steps: generation step *s* can be (re)run in isolation — after a crash,
    on a different worker, in any order — and produce the same edges, which
    is what ``datastream.DatasetJob`` resumption relies on.
    """
    with np.errstate(over="ignore"):   # uint64 wraparound is the point
        mix = (np.uint64(base_seed) * np.uint64(0x9E3779B97F4A7C15)
               + np.uint64(step) * np.uint64(0xBF58476D1CE4E5B9)
               + np.arange(n_dev, dtype=np.uint64) *
               np.uint64(0x94D049BB133111EB))
        mix ^= mix >> np.uint64(30)
        mix *= np.uint64(0xBF58476D1CE4E5B9)
        mix ^= mix >> np.uint64(27)
        mix *= np.uint64(0x94D049BB133111EB)
        mix ^= mix >> np.uint64(31)
    return (mix & np.uint64(0x7FFFFFFF)).astype(np.int32)


def device_block(get_u, thetas, didx, n: int, m: int, k: int, zeros,
                 dtype):
    """One device's block of a mesh step: the Kronecker law restricted to
    src prefix ``didx``, the top ``k`` of the ``n`` src levels.

    Level ``ell`` reads θ row ``ell`` (the ``ChunkScheduler`` convention)
    and ``get_u(ell)`` (one uniform per edge per level, ``max(n, m)`` in
    all).  At a prefix level ``ell < k`` the src bit is the device's own
    bit and the dst bit is drawn conditioned on it, P(dst bit = 0 | s) =
    θ[ell][s, 0] / (θ[ell][s, 0] + θ[ell][s, 1]); past the prefix the
    shared ``descend`` draws src bit ``ell`` and dst bit ``ell`` jointly
    from θ row ``ell``, and levels beyond ``min(n, m)`` from its
    marginals.  Returns the (src, dst) ids in ``dtype``."""
    m_pref = min(k, m)                # prefix levels that carry a dst bit
    dst_pre = None
    for ell in range(m_pref):
        sb = (didx >> (k - 1 - ell)) & 1
        a, b, c, d = (thetas[ell, i] for i in range(4))
        p0 = jnp.where(sb == 0, a / (a + b), c / (c + d))
        db = (get_u(ell) >= p0).astype(jnp.int32)
        dst_pre = db if dst_pre is None else dst_pre * 2 + db
    src, dst = descend(
        lambda ell: get_u(ell + k),
        lambda ell: (thetas[ell + k, 0], thetas[ell + k, 1],
                     thetas[ell + k, 2]),
        n - k, m - m_pref, zeros)
    return (combine_ids_device(src, n - k, dtype, prefix=didx),
            combine_ids_device(dst, m - m_pref, dtype, prefix=dst_pre))


def device_generate(thetas, seeds, n: int, m: int, edges_per_device: int,
                    mesh, dtype=jnp.int32, uniforms=None):
    """shard_map over every mesh axis: device ``d`` draws
    ``edges_per_device`` edges of the ``n``-by-``m``-level graph under src
    prefix ``d`` (its top ``log2(n_dev)`` src levels), with its own
    fold-in key and the law of ``device_block``.

    ``thetas`` holds ``max(n, m)`` rows.  ``uniforms`` (n_dev, max(n, m),
    E) switches to the paper-faithful GPU-port mode where pre-generated
    uniforms stream from HBM (the §Perf baseline); the default generates
    threefry bits on-device."""
    axes = tuple(mesh.axis_names)
    n_dev = mesh.size
    k_pref = int(np.log2(n_dev))  # device index becomes a src-prefix
    if 2 ** k_pref != n_dev or k_pref > n:
        raise ValueError(f"device_generate: {n_dev} devices must be a power "
                         f"of two of at most 2^n = 2^{n}")
    dt = np.dtype(dtype)
    # prefix + level bits must fit the id dtype — raise instead of
    # wrapping (``didx << n`` silently overflowed for n ≥ 31)
    check_id_capacity(n, dt,
                      "device_generate: device prefix + src level bits")
    check_id_capacity(m, dt, "device_generate: dst level bits")
    if dt.itemsize > 4 and not jax.config.jax_enable_x64:
        raise ValueError(
            "device_generate with int64 ids composes ids on-device; "
            "enable jax x64 (JAX_ENABLE_X64=1) or use the host-combining "
            "chunks path (datastream mode='chunks')")
    L = max(n, m)

    def local(thetas, seed, u_in):
        if u_in is None:
            keys = jax.random.split(
                jax.random.fold_in(jax.random.PRNGKey(0), seed[0]), L)
            get_u = lambda ell: jax.random.uniform(         # noqa: E731
                keys[ell], (edges_per_device,), jnp.float32)
        else:
            get_u = lambda ell: u_in[0, ell]                # noqa: E731
        didx = jnp.zeros((), jnp.int32)
        for ax in axes:
            didx = didx * mesh.shape[ax] + jax.lax.axis_index(ax)
        src_ids, dst_ids = device_block(
            get_u, thetas, didx, n, m, k_pref,
            lambda: jnp.zeros((edges_per_device,), jnp.int32), dt)
        return src_ids[None], dst_ids[None]

    if uniforms is not None:
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(axes), P(axes)),
            out_specs=(P(axes), P(axes)),
            check_vma=False)
        return fn(thetas, seeds, uniforms)
    fn = jax.shard_map(
        lambda t, s: local(t, s, None), mesh=mesh,
        in_specs=(P(), P(axes)),
        out_specs=(P(axes), P(axes)),
        check_vma=False)
    return fn(thetas, seeds)


class GenCell(NamedTuple):
    fn: Any
    args: tuple
    in_shardings: tuple
    out_shardings: Any
    meta: dict


def build_generation_cell(mesh, scale: str = "1t",
                          edges_per_device: int = 1 << 24,
                          mode: str = "threefry") -> GenCell:
    """Lowering target for the trillion-edge dry run.

    mode='threefry': bits generated on-device (TPU-native).
    mode='hbm_uniforms': pre-generated uniforms stream from HBM — the
    faithful port of the paper's GPU sampler structure (§Perf baseline).

    The device prefix is part of the 2^30 src id space (top ``log2(n_dev)``
    src levels = device index, sampled suffix = the rest), so ids fit
    int32 on any mesh."""
    n = m = 30      # 2^30 nodes per partite (total, across the mesh)
    L = max(n, m)
    thetas_abs = jax.ShapeDtypeStruct((L, 4), jnp.float32)
    seeds_abs = jax.ShapeDtypeStruct((mesh.size,), jnp.int32)
    axes = tuple(mesh.axis_names)
    total = {"1t": 1.0e12, "100b": 1.0e11}.get(scale, 1.0e12)
    step_edges = edges_per_device * mesh.size
    meta = {"edges": step_edges, "target_edges": total,
            "steps_needed": int(np.ceil(total / step_edges)), "mode": mode}

    if mode == "hbm_uniforms":
        u_abs = jax.ShapeDtypeStruct((mesh.size, L, edges_per_device),
                                     jnp.float32)

        def step(thetas, seeds, uniforms):
            return device_generate(thetas, seeds, n, m, edges_per_device,
                                   mesh, uniforms=uniforms)

        in_sh = (NamedSharding(mesh, P()), NamedSharding(mesh, P(axes)),
                 NamedSharding(mesh, P(axes)))
        out_sh = (NamedSharding(mesh, P(axes)), NamedSharding(mesh, P(axes)))
        return GenCell(step, (thetas_abs, seeds_abs, u_abs), in_sh, out_sh,
                       meta)

    def step(thetas, seeds):
        return device_generate(thetas, seeds, n, m, edges_per_device, mesh)

    in_sh = (NamedSharding(mesh, P()), NamedSharding(mesh, P(axes)))
    out_sh = (NamedSharding(mesh, P(axes)), NamedSharding(mesh, P(axes)))
    return GenCell(step, (thetas_abs, seeds_abs), in_sh, out_sh, meta)
