"""Chip peaks and the work of an IVF probe, for roofline shares.

PEAKS is keyed by `jax.devices()[0].device_kind`. Source: Google Cloud
documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s). A device kind not in the table is an error.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}

ID_BYTES = 4          # int32 bucket id per row
SQNORM_BYTES = 4      # float32 squared norm per row


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}: add them to bench/roofline.py "
                         f"PEAKS with their source")
    return PEAKS[device_kind]


def probe_work(rows: int, dim: int, store_bytes: int = 4):
    """(flops, bytes) of scanning `rows` real bucket rows of width `dim`:
    each row's vector, squared norm and id read once, and one dot product
    (2 * dim operations) against its query. Padding rows are not work."""
    flops = 2.0 * dim * rows
    nbytes = float(rows) * (dim * store_bytes + SQNORM_BYTES + ID_BYTES)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    p = peaks(device_kind)
    return max(flops / p["flops"], nbytes / p["hbm_bytes_per_s"])
