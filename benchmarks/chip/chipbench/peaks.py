"""Peak rates of each chip the benchmark may run on, keyed by JAX's
``device_kind``.  A kind with no row is an error, never a default.

The v5e row is copied from the program's ``analysis/hlo_cost.py``
``DEVICE_PEAKS`` table, where it carries the same source."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB of HBM at 819 GB/s",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak rates for device kind {device_kind!r}; "
                         f"known kinds: {sorted(PEAKS)}") from None
