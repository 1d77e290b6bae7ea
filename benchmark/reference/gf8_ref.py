"""Plain numpy GF(2^8) reference for RS(10+4): a copy of the golden in
`seaweedfs_tpu/ops/gf8.py` (tables, `parity_matrix`, `gf_mat_vec`) kept with
the benchmark so that the comparison deciding `correct` does not move with the
program. Field: polynomial 0x11D, generator 2; systematic Vandermonde
generator as klauspost/reedsolomon's default (what upstream SeaweedFS uses).
Imports nothing of the program."""

from __future__ import annotations

import numpy as np

GF_POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[255:] = exp[: 512 - 255]
    mul = np.zeros((256, 256), dtype=np.uint8)
    for a in range(1, 256):
        mul[a, 1:] = exp[log[a] + log[1:256]]
    return exp, log, mul


GF_EXP, GF_LOG, GF_MUL_TABLE = _tables()


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(2^8) inverse of zero")
    return int(GF_EXP[255 - GF_LOG[a]])


def gf_exp(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(GF_EXP[(GF_LOG[a] * n) % 255])


def gf_mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    prods = GF_MUL_TABLE[a[:, :, None], b[None, :, :]]
    return np.bitwise_xor.reduce(prods, axis=1)


def gf_mat_inv(m: np.ndarray) -> np.ndarray:
    n = m.shape[0]
    aug = np.concatenate([m.copy(), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = next((r for r in range(col, n) if aug[r, col]), None)
        if pivot is None:
            raise ValueError("singular matrix over GF(2^8)")
        if pivot != col:
            aug[[col, pivot]] = aug[[pivot, col]]
        aug[col] = GF_MUL_TABLE[gf_inv(int(aug[col, col]))][aug[col]]
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= GF_MUL_TABLE[int(aug[r, col])][aug[col]]
    return aug[:, n:].copy()


def generator_matrix(data_shards: int, total_shards: int) -> np.ndarray:
    """Vandermonde(total, data) times the inverse of its top square: identity
    on top, the parity generator below."""
    vm = np.zeros((total_shards, data_shards), dtype=np.uint8)
    for r in range(total_shards):
        for c in range(data_shards):
            vm[r, c] = gf_exp(r, c)
    return gf_mat_mul(vm, gf_mat_inv(vm[:data_shards, :data_shards]))


def parity_matrix(data_shards: int = 10, parity_shards: int = 4) -> np.ndarray:
    return generator_matrix(data_shards, data_shards + parity_shards)[data_shards:]


def gf_mat_vec(a: np.ndarray, x: np.ndarray) -> np.ndarray:
    """(m,k) matrix over (k, n) bytes -> (m, n) bytes."""
    a = np.asarray(a, dtype=np.uint8)
    x = np.asarray(x, dtype=np.uint8)
    out = np.zeros((a.shape[0],) + x.shape[1:], dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in range(a.shape[1]):
            c = a[i, j]
            if c:
                out[i] ^= GF_MUL_TABLE[c][x[j]]
    return out
