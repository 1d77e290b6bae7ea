"""JAX/XLA Reed-Solomon kernels — the TPU replacement for the reference codec's
SIMD assembly (klauspost/reedsolomon galois_amd64.s PSHUFB nibble tables
[VERIFY: reference mount empty, SURVEY.md §2.2]).

Formulation (SURVEY.md §7.2): GF(2^8) multiply-by-constant is linear over
GF(2), so an (R x C) GF(2^8) coding matrix lifts to an (R*8 x C*8) binary
matrix B. Unpack data bytes into little-endian bit-planes, then

    out_bits = (B @ in_bits) mod 2

is the exact GF(2^8) matrix product — one int8 matmul on the MXU with an
int32 accumulator (K = C*8 <= 112*8 < 2^31, no overflow) and a final `& 1`.
Encode, reconstruct, and verify all reduce to this one kernel with different
(host-built, cached) matrices. Arithmetic intensity is fixed (~R*8 int8
MACs/byte), so the design problem is feeding the MXU — callers batch tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from seaweedfs_tpu.ops import gf8
from seaweedfs_tpu.utils.devices import setup_compile_cache

setup_compile_cache()


def bytes_to_bits(x: jax.Array) -> jax.Array:
    """(..., C, N) uint8 -> (..., C*8, N) int8 little-endian bit-planes."""
    *lead, c, n = x.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[..., :, None, :] >> shifts[None, :, None]) & jnp.uint8(1)
    return bits.reshape(*lead, c * 8, n).astype(jnp.int8)


def bits_to_bytes(bits: jax.Array) -> jax.Array:
    """(..., R*8, N) int -> (..., R, N) uint8, little-endian bit-planes."""
    *lead, r8, n = bits.shape
    b = bits.reshape(*lead, r8 // 8, 8, n).astype(jnp.uint8)
    out = b[..., 0, :]
    for i in range(1, 8):
        out = out | (b[..., i, :] << np.uint8(i))
    return out


def _gf_apply_impl(b_bits: jax.Array, data: jax.Array) -> jax.Array:
    bits = bytes_to_bits(data)
    if data.ndim == 2:
        acc = jax.lax.dot_general(
            b_bits,
            bits,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
    else:
        acc = jnp.einsum(
            "rk,bkn->brn", b_bits, bits, preferred_element_type=jnp.int32
        )
    return bits_to_bytes(acc & 1)


@jax.jit
def gf_apply(b_bits: jax.Array, data: jax.Array) -> jax.Array:
    """Apply a lifted GF(2^8) matrix to byte shards.

    b_bits: (R*8, C*8) int8 binary matrix (from gf8.gf_matrix_to_bits).
    data:   (C, N) or (batch, C, N) uint8 input shards.
    Returns (R, N) / (batch, R, N) uint8 output shards.
    """
    return _gf_apply_impl(b_bits, data)


# Donated twin: the data argument's device buffer is donated to XLA. The
# (C, N) input cannot alias the smaller (R<=4, N) output (XLA aliasing
# requires matching shape+dtype), so this is NOT output aliasing — it is a
# deterministic early-release hint: the batch's input HBM is freed as soon
# as the dispatch consumes it rather than when host-side references die,
# bounding a depth-N pipeline's inflight footprint. Only
# selected off-CPU — XLA CPU ignores donation and warns.
_gf_apply_donated = jax.jit(_gf_apply_impl, donate_argnums=(1,))


def _gf_apply_tiled_impl(b_tiles: jax.Array, data: jax.Array) -> jax.Array:
    """One lifted matrix per column tile: b_tiles (T, R*8, C*8) int8, data
    (C, T*w) uint8 -> (R, T*w) uint8, tile t of the output being matrix t
    applied to tile t of the input. What a packed rebuild batch runs: its
    signature groups' decode matrices side by side in one program, whose
    shape does not say where one group's columns end."""
    tiles, r8, _ = b_tiles.shape
    c, n = data.shape
    # tiles lead, as the matrices' do: the v5e compiler then fuses the
    # unpack into the batched matmul, as it does in the flat program
    by_tile = jnp.moveaxis(data.reshape(c, tiles, n // tiles), 1, 0)  # (T, C, w)
    acc = jax.lax.dot_general(
        b_tiles,
        bytes_to_bits(by_tile),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )  # (T, R*8, w)
    out = bits_to_bytes(acc & 1)  # (T, R, w)
    return jnp.moveaxis(out, 0, 1).reshape(r8 // 8, n)


gf_apply_tiled = jax.jit(_gf_apply_tiled_impl)
_gf_apply_tiled_donated = jax.jit(_gf_apply_tiled_impl, donate_argnums=(1,))


def _run_counted(fn, *args) -> jax.Array:
    """Call a jitted program; a call that grew its jit cache traced and
    compiled (or loaded) a program for a shape this process had not run:
    `weedtpu_codec_programs_compiled_total` counts those. (A jax whose
    jitted functions do not say their cache's size counts nothing.)"""
    size = getattr(fn, "_cache_size", None)
    if size is None:
        return fn(*args)
    before = size()
    out = fn(*args)
    grew = size() - before
    if grew > 0:
        from seaweedfs_tpu import stats

        stats.CodecProgramsCompiled.inc(grew)
    return out


@functools.lru_cache(maxsize=1)
def donation_supported() -> bool:
    """Buffer donation is a no-op (plus a warning per dispatch) on the XLA
    CPU backend; only the accelerator paths should request it."""
    return jax.devices()[0].platform != "cpu"


@functools.lru_cache(maxsize=256)
def _lifted_host(matrix_key) -> np.ndarray:
    rows = np.array(matrix_key, dtype=np.uint8)
    return gf8.gf_matrix_to_bits(rows).astype(np.int8)


@functools.lru_cache(maxsize=256)
def _lifted(matrix_key) -> jax.Array:
    return jnp.asarray(_lifted_host(matrix_key), dtype=jnp.int8)


def _matrix_key(m: np.ndarray) -> tuple:
    m = np.asarray(m, dtype=np.uint8)
    return tuple(tuple(int(v) for v in row) for row in m)


def lifted_matrix(m: np.ndarray) -> jax.Array:
    """Device int8 binary lift of a GF(2^8) matrix, cached by value."""
    return _lifted(_matrix_key(m))


def encode_parity(data: jax.Array, parity_m: np.ndarray) -> jax.Array:
    """data: (D, N) or (B, D, N) uint8 -> parity (P, N) / (B, P, N)."""
    return gf_apply(lifted_matrix(parity_m), data)


def apply_matrix(m: np.ndarray, shards: jax.Array, donate: bool = False) -> jax.Array:
    """Apply an arbitrary GF(2^8) matrix (e.g. a cached decode matrix) — or
    a (T, R, C) stack of them, matrix t to the t-th of T equal column tiles
    of the (C, N) shards: ONE program and one crossing of the shards to the
    device, whatever the matrices are and wherever one gives way to the next
    (what a packed rebuild batch runs; a matrix padded with zeros ignores
    the rows it has no survivor in). Every apply of the codec on the device
    comes through here.

    donate=True routes through the donated jit so the input's device buffer
    is released the moment the dispatch consumes it (streaming pipelines
    dispatch hundreds of same-shaped batches; the early release keeps the
    inflight HBM footprint at depth x (in + out) instead of trusting
    host-side GC timing — see the donated-twin note above for why this is
    a release hint, not output aliasing). The host array is explicitly
    device_put first so the donated buffer is one jax owns — never a
    zero-copy alias of caller memory."""
    m = np.asarray(m, dtype=np.uint8)
    if m.ndim == 3:
        b = jnp.asarray(np.stack([_lifted_host(_matrix_key(t)) for t in m]))
        plain, donated = gf_apply_tiled, _gf_apply_tiled_donated
    else:
        b, plain, donated = lifted_matrix(m), gf_apply, _gf_apply_donated
    if donate and donation_supported():
        return _run_counted(donated, b, jax.device_put(jnp.asarray(shards)))
    return _run_counted(plain, b, shards)
