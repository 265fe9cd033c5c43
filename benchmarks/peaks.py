"""The table of peaks and the functions that count a kernel's least bytes.
One place, keyed by `device_kind` as JAX reports it; a device that is not in
the table is an error, never a default.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB of HBM at 819 GB/s, for one chip.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"add it to benchmarks/peaks.py with its source")
    return PEAKS[device_kind]


def rows_count(i: int, a: int, le: int) -> int:
    """Rows of the docs-minor buffer for per-document dims (ops, actors,
    list-element slots): eight op bands and one clock band per actor over
    the ops, five element bands, one actor-hash band."""
    return 8 * i + a * i + 5 * le + a


def rows_hash_min_bytes(rows: int, lanes: int) -> int:
    """Least bytes a fused reconcile-and-hash over an int32 `[rows, lanes]`
    buffer must move through HBM: the buffer read once and one uint32 hash
    a lane written. Taken from the call's shapes and nothing of the
    implementation, so a rewrite of the kernel is read against the same
    work. The kernel is int32 vector work with no matrix product, so this
    byte bound is the roofline used."""
    return 4 * rows * lanes + 4 * lanes
