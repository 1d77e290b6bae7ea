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
import math

import jax
import jax.numpy as jnp
import numpy as np

from seaweedfs_tpu import stats
from seaweedfs_tpu.obs import trace as trace_mod
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


def crossing_chunks(rows: int) -> int:
    """Into how many chunks each row of a `rows`-row uint8 slot is cut for the
    slot to cross to the device in the bytes it has. The TPU stores a 2-D
    uint8 array four rows to a word and eight words to a tile, so ten rows
    are held (and handed over) as sixteen and one row as four; with each row
    cut into k = 32 / gcd(rows, 32) chunks the `(rows * k, N / k)` view of
    the same contiguous bytes is whole tiles: 16 for ten rows, 8 for twelve.
    Chunks of a row are column ranges, and a GF apply is independent from
    column to column, so the programs below read the chunk index as a batch
    axis and give `(rows out * k, N / k)` back: the rows of the result cut
    the same way, which a reshape of the synced array undoes (a view)."""
    return 32 // math.gcd(int(rows), 32)


def _chunks_in(rows: int, c: int) -> int:
    """How a 2-D input of `rows` rows meets a matrix of `c` columns: 1 as
    `(C, N)`, k as the exact crossing's `(C * k, N / k)` view; anything else
    is a caller's mistake, not a shape to compute on."""
    k = crossing_chunks(c)
    if rows not in (c, c * k):
        raise ValueError(f"{rows} rows for a matrix of {c} columns: neither (C, N) nor (C * {k}, N / {k})")
    return rows // c


def _gf_apply_impl(b_bits: jax.Array, data: jax.Array) -> jax.Array:
    if data.ndim == 2 and (k := _chunks_in(data.shape[0], b_bits.shape[1] // 8)) > 1:
        # the exact crossing's view: the chunk index is the batch axis of the
        # branch below, and the result is (R * k, N / k)
        by_chunk = jnp.moveaxis(data.reshape(-1, k, data.shape[1]), 1, 0)  # (k, C, N / k)
        return jnp.moveaxis(_gf_apply_impl(b_bits, by_chunk), 0, 1).reshape(-1, data.shape[1])
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
    tiles, c = b_tiles.shape[0], b_tiles.shape[2] // 8
    rows, n = data.shape  # (C, T*w), or the exact crossing's (C * k, T*w / k):
    _chunks_in(rows, c)  # its chunks are whole runs of tiles, so the tiles are there
    # tiles lead, as the matrices' do: the v5e compiler then fuses the
    # unpack into the batched matmul, as it does in the flat program
    by_tile = jnp.moveaxis(data.reshape(c, tiles, rows * n // (c * tiles)), 1, 0)  # (T, C, w)
    acc = jax.lax.dot_general(
        b_tiles,
        bytes_to_bits(by_tile),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )  # (T, R*8, w)
    out = bits_to_bytes(acc & 1)  # (T, R, w)
    return jnp.moveaxis(out, 0, 1).reshape(-1, n)  # (R, T*w), or (R * k, T*w / k)


gf_apply_tiled = jax.jit(_gf_apply_tiled_impl)
_gf_apply_tiled_donated = jax.jit(_gf_apply_tiled_impl, donate_argnums=(1,))


def run_counted(fn, *args) -> jax.Array:
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


def _exact_chunks(m: np.ndarray, shards) -> int:
    """The k of the exact crossing (`crossing_chunks`) where `shards` can
    take it and it has been seen to gain, from what is at hand: a
    C-contiguous 2-D uint8 host array (the `(C * k, N / k)` view is then a
    reshape, no copy) of two rows or more whose chunks would be whole
    128-column tiles of the device (and whole tiles of a stack of matrices),
    for a result of ONE row. 0 otherwise, and the shards cross as they are:
    a strided column range of a wider slot, an odd width, one row in, a
    batch axis, an array on the device already, a result of two rows or
    more. One row out is the result the device pads most (four rows for
    one) and the one whose program keeps no temporaries on the device (the
    v5e compiler's `memory_analysis()`: 0 bytes, 235-604 MB from two rows
    on; `tests/test_tpu_compile.py` holds the 0). Programs with temporaries
    fall into the runtime allocator's slow mode far more often once their
    slot shrinks, which cost the 10+4 encode 7-15% of a command on the chip
    (PERF.md section 6, PR 38); until that is understood (ROADMAP S14 (c))
    the crossing is kept to where a pipeline was measured with it and
    nothing is left to explain."""
    if not (
        isinstance(shards, np.ndarray)
        and shards.ndim == 2
        and shards.dtype == np.uint8
        and shards.flags.c_contiguous
        and shards.shape[0] == m.shape[-1] >= 2
        and m.shape[-2] == 1
    ):
        return 0
    k = crossing_chunks(shards.shape[0])
    grid = 128 * k if m.ndim == 2 else math.lcm(128, shards.shape[1] // m.shape[0]) * k
    return k if k > 1 and shards.shape[1] % grid == 0 else 0


def apply_matrix(m: np.ndarray, shards: jax.Array, donate: bool = False) -> jax.Array:
    """Apply an arbitrary GF(2^8) matrix (e.g. a cached decode matrix) — or
    a (T, R, C) stack of them, matrix t to the t-th of T equal column tiles
    of the (C, N) shards: ONE program and one crossing of the shards to the
    device, whatever the matrices are and wherever one gives way to the next
    (what a packed rebuild batch runs; a matrix padded with zeros ignores
    the rows it has no survivor in). Every apply of the codec on the device
    comes through here.

    The crossing: where `_exact_chunks` says so (a one-row result of a
    contiguous slot), the shards go up as their `(C * k, N / k)` view and
    the result comes back as `(R * k, N / k)`, so that neither is padded on
    the device or on the way (`crossing_chunks`); the synced array's
    `reshape(R, N)` is the answer either way (the codec's lazy handle does
    it). Which it was is counted, `weedtpu_codec_crossings_total{form=
    "exact"|"as_is"}`, and said on the ambient span (`form=`: the
    pipelines' `*.dispatch`).

    donate=True routes through the donated jit so the input's device buffer
    is released the moment the dispatch consumes it (streaming pipelines
    dispatch hundreds of same-shaped batches; the early release keeps the
    inflight HBM footprint at depth x (in + out) instead of trusting
    host-side GC timing — see the donated-twin note above for why this is
    a release hint, not output aliasing). The host array is explicitly
    device_put first so the donated buffer is one jax owns — never a
    zero-copy alias of caller memory."""
    m = np.asarray(m, dtype=np.uint8)
    k = _exact_chunks(m, shards)
    if k:
        shards = shards.reshape(shards.shape[0] * k, -1)
    form = "exact" if k else "as_is"
    stats.CodecCrossings.labels(form).inc()
    trace_mod.annotate(form=form)
    if m.ndim == 3:
        b = jnp.asarray(np.stack([_lifted_host(_matrix_key(t)) for t in m]))
        plain, donated = gf_apply_tiled, _gf_apply_tiled_donated
    else:
        b, plain, donated = lifted_matrix(m), gf_apply, _gf_apply_donated
    if donate and donation_supported():
        return run_counted(donated, b, jax.device_put(jnp.asarray(shards)))
    return run_counted(plain, b, shards)
