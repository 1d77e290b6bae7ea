"""Fused Pallas TPU kernel for GF(2^8) coding — the performance path.

The pure-XLA route (rs_jax.gf_apply) materializes the 8x bit-plane expansion
and an int32 accumulator in HBM; this kernel keeps both in VMEM:

    per grid step (one batch element x one stripe tile of T bytes):
      load   data tile (C, T) uint8                  HBM -> VMEM
      unpack bits (8*C, T) int8, PLANE-major         VPU (block concat — no
             (row j*C+ci = bit j of byte-row ci)     per-byte interleave;
                                                     B's columns are pre-
                                                     permuted to match)
      matmul acc = B_pm @ bits -> (R*8, T) int32     MXU
      mod-2  acc & 1
      pack   out[r] = sum_i acc[r*8+i] << i          VPU (7 shifted ORs —
                                                     cheaper than a tiny
                                                     M=R pack-matmul)
    store  out tile (R, T)                           VMEM -> HBM

HBM traffic is exactly C+R bytes/byte-position — the algorithmic minimum —
vs ~(9C + 5R) for the unfused path. Replaces the reference codec's AVX2/GFNI
galois kernels (klauspost/reedsolomon galois_gen_amd64.s [VERIFY: mount
empty]) as SURVEY.md §2.2 prescribes.

The kernel is a staged FAMILY of variants (all byte-exact vs the gf8
golden, all compiled for the v5e by tests/test_tpu_compile.py, selected by
the `mxu` argument). Every variant widens the tile to int32 before it
extracts bits: the v5e compiler has no 8-bit shift or compare.

  int8    8 shift+mask unpacks, one (R*8, C*8) int8 MXU matmul.
  bf16    same unpack, bf16 MXU matmul (exact: partial sums <= 80 < 256).
  u8      shift-free unpack — bit j is extracted as a mask+compare
          ((x & (1<<j)) != 0) instead of the shift chain.
  mplane  multi-plane ACCUMULATION: 8 small K=C matmuls, one per bit
          plane, summed into a single int32 accumulator — the (8C, T)
          concatenated bit matrix is never materialized in VMEM.
  dma     manual DOUBLE-BUFFERED tile DMA: the data operand stays in HBM
          (pl.ANY) and the kernel streams (C, chunk) sub-tiles through a
          2-slot VMEM scratch ring with make_async_copy, overlapping the
          HBM load of chunk k+1 with the MXU/VPU work on chunk k inside
          one big grid step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from seaweedfs_tpu.ops import gf8
from seaweedfs_tpu.utils.devices import setup_compile_cache

setup_compile_cache()

#: bytes of one stripe tile per grid step at the small end: the floor the
#: streaming tests straddle. The auto chooser below scales the tile up to
#: the VMEM budget, since per-grid-step overhead is material at 8 KiB.
DEFAULT_TILE = 8192

#: VMEM the auto tile chooser may plan against: half of the 16 MiB the v5e
#: compiler gives one kernel's stack (probed: mplane at RS(10+4) compiles at
#: tile 32768 = 11.5 MiB by the model below and is refused at 49152 =
#: 17.3 MiB, "Ran out of memory in memory space vmem").
DEFAULT_VMEM_BUDGET = 8 << 20

#: snap grid for auto tiles — large power-of-two-ish strides keep the
#: HBM windows aligned and the grid-step count predictable
_TILE_STEPS = (65536, 49152, 32768, 24576, 16384, 8192, 4096, 2048, 1024, 512, 256, 128)


#: bytes of one DMA chunk for the `dma` variant — the unit the manual
#: double buffer streams through VMEM. Small enough that two slots plus
#: the per-chunk bit expansion stay far under budget, large enough that
#: each chunk's matmul amortizes the copy-start overhead.
DMA_CHUNK = 2048


def auto_tile(
    c: int, rows: int, mxu: str = "int8", vmem_budget: int = DEFAULT_VMEM_BUDGET
) -> int:
    """Largest tile whose per-grid-step VMEM working set fits the budget.

    Per byte-position of tile, for the kernels as the v5e compiler accepts
    them: the double-buffered uint8 data and output windows, whose rows pad
    to the 32-row uint8 tiling; the int32 copy of the tile the unpack
    shifts (4C: the target has no 8-bit shift); the bit stack the matmul
    reads (8C at the MXU dtype's width) and the int32 accumulator (32R).
    `mplane` holds one widened plane instead of the stack but two live
    accumulators (the running sum and the plane's product), which is what
    the compiler's VMEM limit was probed against. `dma` streams DMA_CHUNK
    columns at a time, so only its output window grows with the tile.
    tests/test_tpu_compile.py compiles every variant at the tile this
    picks for every registered geometry."""
    win_in, win_out = 2 * _round_up(c, 32), 2 * _round_up(rows, 32)
    if mxu == "dma":
        per_byte = win_out + 1
    elif mxu == "mplane":
        per_byte = win_in + win_out + 4 * c + 5 * c + 2 * 32 * rows
    else:
        bits_width = 2 if mxu == "bf16" else 1
        per_byte = win_in + win_out + 4 * c + 8 * c * bits_width + 32 * rows
    cap = max(128, vmem_budget // per_byte)
    for t in _TILE_STEPS:
        if t <= cap:
            return t
    return 128


def _bit_planes(data, dtype=jnp.int8):
    """(C, T) uint8 tile -> the 8 (C, T) 0/1 bit planes, little-endian.

    The bytes are widened to int32 before the shift: the v5e Mosaic
    compiler has no 8-bit vector shift ("failed to legalize operation
    'arith.shrsi'" on vector<8x128x4xi8>) and no uint8 elementwise ops at
    all, so the unpack runs on 32-bit lanes and each plane is narrowed to
    the matmul dtype afterwards. uint8 -> int32 zero-extends, so
    (x >> j) & 1 is bit j for every j < 8."""
    d32 = data.astype(jnp.int32)
    return [((d32 >> j) & 1).astype(dtype) for j in range(8)]


def _pack_planes(acc):
    """(8*R, T) int32 plane-major 0/1 rows -> (R, T) uint8 bytes.

    With plane-major rows each plane is a CONTIGUOUS (R, T) block
    (sublane stride 1); a byte-major pack would read with sublane
    stride 8, which Mosaic lowers to per-sublane shuffles."""
    rows8, t = acc.shape
    acc3 = acc.reshape(8, rows8 // 8, t)
    out = acc3[0]
    for i in range(1, 8):
        out = out | (acc3[i] << i)
    return out.astype(jnp.uint8)


def _matmul_mod2(b, bits):
    """(B @ bits) & 1 with an int32 accumulator — the GF(2) product."""
    acc = jax.lax.dot_general(
        b, bits, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    return acc & 1


def _kernel(b_ref, data_ref, out_ref):
    # Plane-major bit layout ON BOTH SIDES:
    #   input  row j*C + ci = bit j of input byte-row ci
    #   output row i*R + r  = bit i of output byte-row r
    # Concatenating whole (C, T) blocks keeps every plane in its natural
    # VMEM layout — a byte-major stack(axis=1).reshape forces a per-byte
    # sublane interleave Mosaic must shuffle for. The lifted matrix's
    # columns AND rows are pre-permuted host-side to match (free).
    bits = jnp.concatenate(_bit_planes(data_ref[0]), axis=0)
    out_ref[0] = _pack_planes(_matmul_mod2(b_ref[...], bits))


def _kernel_bf16(b_ref, data_ref, out_ref):
    """Same plane-major layout as `_kernel`, but the MXU matmul runs in
    bf16: products are 0/1 and K = C*8 <= 80 for RS(10+4), so every partial
    sum <= 80 < 256 is exactly representable in bf16's 8-bit significand
    (f32 accumulate is exact a fortiori) — int8 matmul on some TPU
    generations is emulated at a fraction of bf16 rate, so this can win."""
    bits = jnp.concatenate(_bit_planes(data_ref[0], jnp.bfloat16), axis=0)
    acc = jax.lax.dot_general(
        b_ref[...].astype(jnp.bfloat16),
        bits,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    out_ref[0] = _pack_planes(acc & 1)


def _kernel_u8(b_ref, data_ref, out_ref):
    """Shift-free unpack: bit j extracted as a VPU mask+compare
    ((x & (1<<j)) != 0) instead of the 8-deep shift chain of `_kernel`.
    The compare runs on int32 lanes like the shift does: the v5e target
    refuses an 8-bit `arith.cmpi` ("Target does not support this
    comparison")."""
    d32 = data_ref[0].astype(jnp.int32)
    planes = [
        ((d32 & (1 << j)) != 0).astype(jnp.int32).astype(jnp.int8)
        for j in range(8)
    ]
    bits = jnp.concatenate(planes, axis=0)
    out_ref[0] = _pack_planes(_matmul_mod2(b_ref[...], bits))


def _kernel_mplane(b_ref, data_ref, out_ref):
    """Multi-plane accumulation: instead of materializing the (8C, T)
    concatenated bit matrix and one K=8C matmul, run 8 small K=C matmuls
    — one per bit plane of the lifted matrix (B's columns are plane-major,
    so plane j is the contiguous column block [j*C, (j+1)*C)) — summed
    into ONE int32 accumulator. All 8 planes fold into a single grid
    pass with an 8x smaller unpack working set; mod-2 commutes with the
    sum (acc = sum_j B_j @ bits_j over Z, & 1 at the end)."""
    d32 = data_ref[0].astype(jnp.int32)
    c = d32.shape[0]
    acc = None
    for j in range(8):
        plane = ((d32 >> j) & 1).astype(jnp.int8)  # one plane live at a time
        part = jax.lax.dot_general(
            b_ref[:, j * c : (j + 1) * c],
            plane,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        acc = part if acc is None else acc + part
    out_ref[0] = _pack_planes(acc & 1)


def _make_dma_kernel(chunk: int):
    """Manual double-buffered HBM->VMEM streaming: the data operand stays
    in HBM (pl.ANY BlockSpec) and the kernel DMAs (C, chunk) sub-tiles
    into a 2-slot VMEM scratch ring, starting the copy of chunk k+1
    before computing on chunk k — HBM loads overlap MXU/VPU work inside
    one large grid step instead of relying on Mosaic's window pipelining
    across many small steps. The caller pads C to the 8-row tiling (the
    target refuses a DMA slice of 10 rows)."""

    def kernel(b_ref, data_ref, out_ref):
        bi = pl.program_id(0)
        ti = pl.program_id(1)
        c = data_ref.shape[1]
        tile = out_ref.shape[2]
        nchunks = tile // chunk

        def body(scratch, sem):
            def chunk_dma(slot, k):
                return pltpu.make_async_copy(
                    data_ref.at[bi, :, pl.ds(ti * tile + k * chunk, chunk)],
                    scratch.at[slot],
                    sem.at[slot],
                )

            chunk_dma(0, 0).start()

            def loop(k, carry):
                slot = k % 2

                @pl.when(k + 1 < nchunks)
                def _():
                    chunk_dma((k + 1) % 2, k + 1).start()

                chunk_dma(slot, k).wait()
                bits = jnp.concatenate(_bit_planes(scratch[slot]), axis=0)
                out_ref[0, :, pl.ds(k * chunk, chunk)] = _pack_planes(
                    _matmul_mod2(b_ref[...], bits)
                )
                return carry

            jax.lax.fori_loop(0, nchunks, loop, 0)

        pl.run_scoped(
            body,
            scratch=pltpu.VMEM((2, c, chunk), jnp.uint8),
            sem=pltpu.SemaphoreType.DMA((2,)),
        )

    return kernel


_KERNELS = {
    "int8": _kernel,
    "bf16": _kernel_bf16,
    "u8": _kernel_u8,
    "mplane": _kernel_mplane,
    "dma": None,  # built per tile/chunk by _make_dma_kernel
}

#: the staged fused-kernel family, in sweep order. The canonical name
#: tuple lives jax-free in rs_codec (evidence parsing in bench's parent
#: must not import this module); the kernel table here must match it.
from seaweedfs_tpu.ops.rs_codec import FUSED_VARIANTS as VARIANTS  # noqa: E402

assert tuple(_KERNELS) == VARIANTS, (
    f"kernel table {tuple(_KERNELS)} drifted from rs_codec.FUSED_VARIANTS {VARIANTS}"
)


def _plane_major_columns(b_bits: np.ndarray) -> np.ndarray:
    """Permute the lifted matrix's columns from byte-major (ci*8 + j) to
    plane-major (j*C + ci), AND its rows from byte-major (r*8 + i) to
    plane-major (i*R + r) — both sides of the kernel's bit layout."""
    rows8, cols8 = b_bits.shape
    c = cols8 // 8
    r = rows8 // 8
    col_perm = [(k % c) * 8 + (k // c) for k in range(cols8)]
    row_perm = [(k % r) * 8 + (k // r) for k in range(rows8)]
    return np.asarray(b_bits)[np.ix_(row_perm, col_perm)]


def _require_tpu() -> None:
    """The compiled kernel exists for the TPU only. Off the chip the
    interpreter is something a caller asks for (`interpret=True`: tests
    and `kernel_sweep --smoke`), never something this module infers."""
    from seaweedfs_tpu.utils.devices import is_tpu_device

    d = jax.devices()[0]
    if not is_tpu_device(d):
        raise RuntimeError(
            f"the pallas backend needs a TPU, found platform {d.platform!r} "
            "(pass interpret=True to run the kernel in the Pallas interpreter)"
        )


def _dma_chunk(tile: int) -> int:
    """Largest chunk <= DMA_CHUNK dividing the tile (tiles are always
    multiples of 128, so 128 is the floor)."""
    for ch in (DMA_CHUNK, 1024, 512, 256, 128):
        if ch <= tile and tile % ch == 0:
            return ch
    return 128


def _pad_rows_to_tiling(b_pm, data):
    """`dma` only: zero-pad the C input rows to the 8-row tiling the
    target demands of a DMA slice, and B's plane-major columns
    (j*C + ci -> j*Cp + ci) to match. Zero rows contribute nothing."""
    batch, c, n = data.shape
    cp = _round_up(c, 8)
    if cp == c:
        return b_pm, data
    data = jnp.pad(data, ((0, 0), (0, cp - c), (0, 0)))
    b3 = b_pm.reshape(b_pm.shape[0], 8, c)
    b3 = jnp.pad(b3, ((0, 0), (0, 0), (0, cp - c)))
    return b3.reshape(b_pm.shape[0], 8 * cp), data


def _apply_padded_impl(b_pm, data, tile: int, interpret: bool, mxu: str):
    rows = b_pm.shape[0] // 8
    if mxu == "dma":
        # the data operand never gets a Mosaic-managed VMEM window: it
        # stays in HBM and the kernel streams it through its own 2-slot
        # scratch ring (chunk k+1's copy overlaps chunk k's compute)
        b_pm, data = _pad_rows_to_tiling(b_pm, data)
        kernel = _make_dma_kernel(_dma_chunk(tile))
        data_spec = pl.BlockSpec(memory_space=pl.ANY)
    else:
        kernel = _KERNELS[mxu]
        data_spec = pl.BlockSpec((1, data.shape[1], tile), lambda b, i: (b, 0, i))
    batch, _c, n = data.shape
    return pl.pallas_call(
        kernel,
        grid=(batch, n // tile),
        in_specs=[
            pl.BlockSpec((b_pm.shape[0], b_pm.shape[1]), lambda b, i: (0, 0)),
            data_spec,
        ],
        out_specs=pl.BlockSpec((1, rows, tile), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((batch, rows, n), jnp.uint8),
        interpret=interpret,
        # every grid step is independent (disjoint tiles): telling Mosaic
        # so unlocks unconstrained pipelining of the HBM<->VMEM windows
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
    )(b_pm, data)


_STATIC = ("tile", "interpret", "mxu")
_apply_padded_jit = jax.jit(_apply_padded_impl, static_argnames=_STATIC)
# donated twin: the (large) data buffer's HBM is released as soon as the
# dispatch consumes it — an early-release hint, not output aliasing (the
# (B, C, N) input cannot alias the smaller (B, R, N) output; see the
# rs_jax donated-twin note). No-op + warning on CPU, so callers gate on
# rs_jax.donation_supported().
_apply_padded_donated = jax.jit(
    _apply_padded_impl, static_argnames=_STATIC, donate_argnums=(1,)
)


def _apply_pm(
    b_pm: jax.Array,
    data: jax.Array,
    tile: int | None,
    mxu: str = "int8",
    donate: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Shared pad/tile/squeeze plumbing over an already-plane-major matrix."""
    if mxu not in _KERNELS:
        raise ValueError(f"unknown mxu dtype {mxu!r} (want {sorted(_KERNELS)})")
    if not interpret:
        _require_tpu()
    squeeze = data.ndim == 2
    if squeeze:
        data = data[None]
    batch, c, n = data.shape
    rows = b_pm.shape[0] // 8
    if n == 0:
        out = jnp.zeros((batch, rows, 0), jnp.uint8)
        return out[0] if squeeze else out
    if tile is None:
        tile = auto_tile(c, rows, mxu)
    t = min(tile, _round_up(max(n, 128), 128))
    n_pad = _round_up(n, t)
    if n_pad != n:
        data = jnp.pad(data, ((0, 0), (0, 0), (0, n_pad - n)))
    if donate and not interpret:
        out = _apply_padded_donated(b_pm, jax.device_put(data), t, False, mxu)
    else:
        out = _apply_padded_jit(b_pm, data, t, interpret, mxu)
    if n_pad != n:
        out = out[..., :n]
    return out[0] if squeeze else out


def gf_apply_fused(
    b_bits: jax.Array,
    data: jax.Array,
    tile: int | None = None,
    mxu: str = "int8",
    interpret: bool = False,
) -> jax.Array:
    """Fused equivalent of rs_jax.gf_apply for TPU.

    b_bits: (R*8, C*8) int8 lifted matrix; data (C, N) or (B, C, N) uint8.
    Handles any N by zero-padding to the tile size (zero bytes encode to
    zero bytes, so padding never corrupts real lanes). Off a TPU this
    raises unless `interpret=True` asks for the Pallas interpreter (tests
    only). tile=None picks the largest tile whose working set fits the
    VMEM budget (`auto_tile`); mxu selects the staged kernel variant
    (`VARIANTS`: "int8", "bf16", "u8", "mplane", "dma" — see the module
    docstring for strategies).
    """
    return _apply_pm(
        _lifted_plane_major(b_bits), data, tile, mxu, interpret=interpret
    )


@functools.lru_cache(maxsize=256)
def _plane_major_cached(key) -> jax.Array:
    rows8, cols8, flat = key
    arr = np.frombuffer(bytes(flat), dtype=np.int8).reshape(rows8, cols8)
    return jnp.asarray(_plane_major_columns(arr))


@functools.lru_cache(maxsize=256)
def _lift_pm_cached(key) -> jax.Array:
    rows, cols, flat = key
    m = np.frombuffer(bytes(flat), dtype=np.uint8).reshape(rows, cols)
    lifted = gf8.gf_matrix_to_bits(m).astype(np.int8)
    return jnp.asarray(_plane_major_columns(lifted))


def plane_major_matrix(m: np.ndarray) -> jax.Array:
    """Host-side: lifted + column-permuted device matrix for the kernel,
    cached by GF-matrix value — both the bit-lift (Python GF math) and the
    permutation happen once per matrix, and the hot path (apply_matrix)
    never round-trips an already-uploaded matrix through the host."""
    a = np.asarray(m, dtype=np.uint8)
    return _lift_pm_cached((a.shape[0], a.shape[1], a.tobytes()))


# id-keyed memo for the b_bits (device array) compat path: np.asarray on a
# device array is a blocking D2H transfer, so it must happen once per
# matrix object, not once per call. Entries
# self-evict when their source array is collected (weakref callback), so
# the memo cannot pin dead device buffers for the life of the process.
_pm_by_id: dict[int, tuple] = {}


def _lifted_plane_major(b_bits) -> jax.Array:
    import weakref

    k = id(b_bits)
    hit = _pm_by_id.get(k)
    if hit is not None and hit[0]() is b_bits:
        return hit[1]
    a = np.asarray(b_bits, dtype=np.int8)
    pm = _plane_major_cached((a.shape[0], a.shape[1], a.tobytes()))
    try:
        ref = weakref.ref(b_bits, lambda _r, _k=k: _pm_by_id.pop(_k, None))
        _pm_by_id[k] = (ref, pm)
    except TypeError:  # non-weakrefable input (plain ndarray): value cache hit anyway
        pass
    return pm


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def apply_matrix(
    m: np.ndarray,
    shards,
    tile: int | None = None,
    mxu: str = "int8",
    donate: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """GF(2^8) matrix application via the fused kernel: the hot path —
    lift + permute host-side once per matrix value, no device round-trip.
    donate=True releases the input's device buffer at dispatch-consume
    time (streaming pipelines). interpret=True runs the Pallas interpreter
    instead of the compiled kernel (tests only); without it a non-TPU
    platform raises."""
    return _apply_pm(
        plane_major_matrix(m), jnp.asarray(shards), tile, mxu,
        donate=donate, interpret=interpret,
    )
