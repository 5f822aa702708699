"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; everything else sees the real device count.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _make_mesh((data, model), ("data", "model"))


# TPU v5e hardware model used for the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link
