"""Published peaks of each accelerator the benchmark may run on.

Keyed by ``jax.Device.device_kind``. A device that is not in the table is
an error, never a default: a share of a peak is only as good as the peak.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s,
1,600 Gbit/s of chip-to-chip interconnect, per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; raises for an unknown one."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
