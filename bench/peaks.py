"""Published peaks of the accelerators the benchmark runs on, keyed by
``device_kind`` as JAX reports it.  A device that is not here is an
error, never a default."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    # one TPU v5e chip
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(cloud.google.com/tpu/docs/v5e)",
    },
}


def peaks(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
