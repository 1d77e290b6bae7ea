"""The table of peaks and the functions that compute a kernel's bytes from its
shapes. Kept with the benchmark; keyed by `device_kind`; a kind that is not in
the table is an error, never a default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "int8_ops_per_s": 393e12,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str, key: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to peaks.PEAKS with its source")
    return float(PEAKS[device_kind][key])


def rs_apply_bytes(rows_in: int, rows_out: int, width: int) -> int:
    """Bytes a GF(2^8) matrix apply must move through HBM: every input row read
    once, every output row written once (the matrix itself is 140 bytes)."""
    return (rows_in + rows_out) * width


def rs_apply_min_seconds(device_kind: str, rows_in: int, rows_out: int, width: int) -> float:
    """The least time the chip could take: the apply is bound by HBM (14 bytes
    moved per 40 GF multiply-adds; as the int8 bit-plane matmul the program
    uses, 8*rows_in x 8*rows_out MACs per column = 5,120 int8 ops per 14 bytes,
    0.76x the HBM time at the published peaks — the larger bound is HBM)."""
    return rs_apply_bytes(rows_in, rows_out, width) / peak(device_kind, "hbm_bytes_per_s")
