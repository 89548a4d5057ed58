"""Bytes each kernel call needs, from its shapes, and its roofline share.

The bytes are what the call must move at the least: every input it reads
and every output it writes, at their unpadded sizes (the wrappers' lane
padding is the kernel's own cost, not the algorithm's). Both kernels
here do too little arithmetic per byte for the compute peak to bound
them, so the least time is bytes over the HBM bandwidth.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; a chip not in the table is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}: add them with their source")
    return table[device_kind]


def pq_adc_bytes(b: int, c: int, m: int, k: int) -> int:
    """``pq_adc`` over ``b`` queries x ``c`` candidates, ``m`` subspaces of
    ``k`` centroids: the LUT [b, m, k] f32, the gathered code rows
    [b, c, m] u8 and the ids [b, c] i32 in, the distances [b, c] f32 out."""
    return b * (m * k * 4 + c * m + c * 4 + c * 4)


def row_gather_bytes(b: int, w: int, r: int) -> int:
    """``row_gather`` of ``w`` frontier rows of degree ``r`` for ``b``
    queries: the slots [b, w] i32 and the rows read [b, w, r] i32 in, the
    rows [b, w, r] i32 out."""
    return b * w * 4 + 2 * b * w * r * 4


def share(total_bytes: float, seconds: float, device_kind: str):
    """Percent of the HBM roofline: least time over kernel time. None
    where there was no kernel time to read."""
    if seconds <= 0:
        return None
    least = total_bytes / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / seconds
