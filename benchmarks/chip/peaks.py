"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` string JAX reports.  A device that is not in the
table is an error, never a default: a share of an unknown peak means
nothing.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,      # FLOP/s, dense bf16 on the MXUs
        "hbm_bytes_s": 819e9,      # HBM bandwidth, bytes/s
        "hbm_bytes": 16e9,         # HBM capacity, bytes
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> Dict[str, object]:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
