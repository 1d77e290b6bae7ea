"""Reed-Solomon codec facade — the `reedsolomon.Encoder`-shaped seam.

Mirrors the API surface the reference consumes from klauspost/reedsolomon
(`New(d, p)`, `Encode`, `Reconstruct`, `ReconstructData`, `Verify`,
`Split`/`Join` [VERIFY: reference mount empty — upstream API, SURVEY.md §2.1])
with three backends behind one factory, the same seam SURVEY.md §1 identifies
for backend selection:

  * "numpy"  — host CPU golden path (table-driven GF(2^8)), the correctness
    oracle and fallback when no accelerator is present.
  * "jax"    — pure-XLA bit-plane path (rs_jax); any accelerator.
  * "pallas" — the TPU path: the fused VMEM-resident kernel (rs_pallas).

Per-loss-pattern decode matrices are built host-side by GF Gaussian
elimination and cached — the role of the reference codec's inversion tree
(`inversion_tree.go`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import threading
from typing import Optional, Sequence

import numpy as np

from seaweedfs_tpu.ops import gf8
from seaweedfs_tpu.utils import config

#: committed on-chip measurement evidence older than this many days no
#: longer flips the auto backend away from its conservative default: the
#: kernels under measurement keep changing round to round, so an ancient
#: number says nothing about today's binary.
EVIDENCE_MAX_AGE_DAYS = config.env("WEEDTPU_EVIDENCE_MAX_AGE_DAYS")

#: the staged fused-kernel family (rs_pallas re-exports this as VARIANTS
#: and asserts its kernel table matches). Lives HERE, jax-free, so
#: evidence parsing (parse_fused_variant) and jax-free tools need no
#: rs_pallas/jax import.
FUSED_VARIANTS = ("int8", "bf16", "u8", "mplane", "dma")

_BACKENDS = ("numpy", "native", "xorsched", "jax", "pallas", "mesh")
#: the backends whose codec runs on a device jax holds (the rest are CPU floors)
DEVICE_BACKENDS = ("jax", "pallas", "mesh")


# -- code-family registry (the geometry-flexible seam) ------------------------
#
# Geometry (k, m, generator family) is a first-class Encoder parameter, no
# longer pinned at the legacy 10+4. Each registered family names one
# (data_shards, parity_shards, matrix_kind) triple; the `.eci` sidecar
# records a volume's family so mounts, rebuilds, and scrubs agree on the
# layout, and `ec.convert` re-encodes a volume from one family to another
# without ever materializing the .dat (see seaweedfs_tpu/ec/convert.py).


@dataclasses.dataclass(frozen=True)
class CodeGeometry:
    """One registered erasure-code geometry."""

    family: str
    data_shards: int
    parity_shards: int
    matrix_kind: str  # gf8.generator_matrix dispatch: vandermonde | cauchy

    @property
    def total_shards(self) -> int:
        return self.data_shards + self.parity_shards

    @property
    def overhead(self) -> float:
        """Storage overhead factor (total/data) — the tiering cost signal
        conversions optimize: colder data wants a smaller factor."""
        return self.total_shards / self.data_shards


#: the registered code families. `rs_10_4` is the legacy wire-default
#: (klauspost-compatible Vandermonde 10+4 — what every pre-geometry .eci
#: implies); `cauchy_12_3` is the wider, cheaper cold-tier code (overhead
#: 1.25 vs 1.4, Cauchy parity rows are provably MDS for any k+m <= 256);
#: `merge_20_4` is the 10+4 -> 20+4 stripe-merge layout (two source data
#: rows regroup into one target row; overhead 1.2).
CODE_FAMILIES: dict[str, CodeGeometry] = {
    g.family: g
    for g in (
        CodeGeometry("rs_10_4", 10, 4, "vandermonde"),
        CodeGeometry("cauchy_12_3", 12, 3, "cauchy"),
        CodeGeometry("merge_20_4", 20, 4, "cauchy"),
    )
}

DEFAULT_FAMILY = "rs_10_4"


def geometry_for(family: str) -> CodeGeometry:
    """The registered geometry behind a family name; unknown names raise
    (a typo'd conversion target must fail loudly, not encode garbage)."""
    geom = CODE_FAMILIES.get(str(family))
    if geom is None:
        raise ValueError(
            f"unknown code family {family!r} (registered: "
            f"{sorted(CODE_FAMILIES)})"
        )
    return geom


def family_of(
    data_shards: int, parity_shards: int, matrix_kind: str
) -> Optional[str]:
    """Reverse lookup: the registered family name for a geometry triple,
    or None for an unregistered ad-hoc geometry (tests use scaled ones)."""
    for name, g in CODE_FAMILIES.items():
        if (g.data_shards, g.parity_shards, g.matrix_kind) == (
            int(data_shards), int(parity_shards), str(matrix_kind),
        ):
            return name
    return None

#: LRU cap on cached decode matrices. A long-lived volume server whose
#: shard-loss patterns churn (peers flapping, rolling repairs) sees an
#: unbounded stream of (survivors, wanted) keys — C(14,10) x wanted sets is
#: thousands of patterns — so the memo must evict, not grow for the life of
#: the process. Matrices are tiny; the cap bounds the GF-elimination *keys*.
DECODE_MATRIX_CACHE_SIZE = config.env("WEEDTPU_DECODE_MATRIX_CACHE")


@functools.lru_cache(maxsize=max(16, DECODE_MATRIX_CACHE_SIZE))
def _reconstruction_matrix(
    kind: str,
    data_shards: int,
    parity_shards: int,
    survivors: tuple,
    wanted: tuple,
) -> np.ndarray:
    """(len(wanted) x data_shards) matrix mapping survivor shards to wanted
    shards. `survivors` must be exactly `data_shards` present shard ids."""
    gen = gf8.generator_matrix(kind, data_shards, data_shards + parity_shards)
    sub = gen[list(survivors), :]  # (D, D)
    inv = gf8.gf_mat_inv(sub)  # survivors -> data
    rows = []
    for w in wanted:
        if w < data_shards:
            rows.append(inv[w])
        else:
            rows.append(gf8.gf_mat_mul(gen[w : w + 1], inv)[0])
    out = np.stack(rows).astype(np.uint8)
    out.setflags(write=False)
    return out


def decode_matrix_cache_info():
    """The decode-matrix memo's (hits, misses, maxsize, currsize) — lets
    operators/tests assert the cache stays bounded under loss-pattern churn."""
    return _reconstruction_matrix.cache_info()


def clear_decode_matrix_cache() -> None:
    _reconstruction_matrix.cache_clear()


class _FusedBlocks:
    """Lazy handle for a block-diagonal fused decode on a non-xorsched
    backend: per-block device dispatches stay in flight until np.asarray()
    (the one sync point per staging batch, mirroring reconstruct_lazy's
    contract).  Rows past a block's own output count inside its columns
    are unspecified, like the materialized form."""

    def __init__(self, shape: tuple[int, int], parts: list):
        self.shape = shape
        self._parts = parts  # (rows, col_start, width, backend handle)
        self._out: Optional[np.ndarray] = None

    def __array__(self, dtype=None, copy=None):
        if self._out is None:
            out = np.empty(self.shape, dtype=np.uint8)
            for rows, c0, w, h in self._parts:
                out[:rows, c0:c0 + w] = np.asarray(h)[:rows]
            self._out = out
            self._parts = []
        if dtype is not None and dtype != self._out.dtype:
            return self._out.astype(dtype)
        return self._out


class _Crossed:
    """Lazy handle for an apply of the jax backend: the device array stays
    in the shape it crossed in (`rs_jax.apply_matrix`: `(R * k, N / k)` for
    the exact crossing) until np.asarray(), the one sync point, which gives
    `shape`, the `(R, N)` of the matrix's rows and the input's width, back:
    a reshape of the synced array (a view; of another size, an error)."""

    def __init__(self, out, shape: tuple):
        self._out, self._shape = out, shape

    def __array__(self, dtype=None, copy=None):
        self._out = np.asarray(self._out).reshape(self._shape)
        if dtype is not None and dtype != self._out.dtype:
            return self._out.astype(dtype)
        return self._out


class Encoder:
    """RS(d+p) encoder/reconstructor over GF(2^8).

    All shards in one call must share a length (like the reference codec);
    striping/padding policy lives a layer up in `ec.stripe`.

    Reconstructs on the jax/pallas backends are PAD-AND-MASKED to a fixed
    bucket set of shard lengths: XLA caches compiles per shape, so without
    bucketing every new interval size pays a fresh compile on the
    degraded-read serving path (r3 bench: 26x cold/warm gap). Zero padding
    is exact — GF matmul maps zero columns to zero columns — and the pad is
    sliced off before returning (SURVEY.md §7.3.5).
    """

    #: shard-length buckets for small-shape reconstructs (serving-path
    #: intervals are needle records: ~KBs; block-sized reads cap at 1 MiB)
    RECONSTRUCT_BUCKETS = (4 << 10, 64 << 10, 1 << 20)

    def __init__(
        self,
        data_shards: int = 10,
        parity_shards: int = 4,
        matrix_kind: str = "vandermonde",
        backend: str = "numpy",
        pallas_mxu: str = "int8",
        pallas_tile: Optional[int] = None,
        mesh_shape: Optional[Sequence[int]] = None,
        mesh_rebuild: Optional[str] = None,
        pallas_interpret: bool = False,
    ):
        if data_shards <= 0 or parity_shards <= 0:
            raise ValueError("shard counts must be positive")
        if data_shards + parity_shards > 256:
            raise ValueError("GF(2^8) supports at most 256 total shards")
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.total_shards = data_shards + parity_shards
        if backend not in _BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r} (want one of {_BACKENDS})"
            )
        self.matrix_kind = matrix_kind
        #: registered family name when the (k, m, kind) triple matches one
        #: (None for ad-hoc geometries, e.g. tests' scaled shard counts)
        self.family = family_of(data_shards, parity_shards, matrix_kind)
        self.backend = backend
        # fused-kernel variant config (pallas backend only): which staged
        # kernel (rs_pallas.VARIANTS) and tile the dispatches use — set by
        # new_encoder("auto") from the winning committed measurement
        self.pallas_mxu = pallas_mxu
        self.pallas_tile = pallas_tile
        # tests only: run the kernel in the Pallas interpreter. Without it
        # the pallas backend raises off a TPU — it never interprets unasked
        self.pallas_interpret = bool(pallas_interpret)
        # mesh backend config: dp x sp axis shape and distributed-rebuild
        # variant (None = resolve from WEEDTPU_MESH_SHAPE / committed
        # MULTICHIP evidence / the all-devices default at first dispatch)
        self.mesh_shape = tuple(int(v) for v in mesh_shape) if mesh_shape else None
        self.mesh_rebuild = mesh_rebuild
        self._mesh_obj = None
        #: how this encoder's backend was chosen (new_encoder fills it;
        #: direct construction is an explicit choice)
        self.selection: dict = {"backend": backend, "source": "explicit"}
        self.gen_matrix = gf8.generator_matrix(matrix_kind, data_shards, self.total_shards)
        self.parity_matrix = np.ascontiguousarray(self.gen_matrix[data_shards:])

    # -- kernel dispatch ----------------------------------------------------

    def _mesh_dispatch(self):
        """The lazily-built mesh state (imports jax; builds the Mesh and
        exports the weedtpu_ec_mesh_devices gauge on first use)."""
        if self._mesh_obj is None:
            from seaweedfs_tpu.parallel import backend as mesh_backend

            self._mesh_obj = mesh_backend.MeshDispatch(
                shape=self.mesh_shape, rebuild=self.mesh_rebuild
            )
        return self._mesh_obj

    @property
    def width_align(self) -> int:
        """Staging-width multiple the streaming pipelines should round
        their spans to so every steady-state batch dispatches pad-free
        (1 on single-device backends; dp*sp on the mesh backend)."""
        if self.backend != "mesh":
            return 1
        return self._mesh_dispatch().width_align

    #: columns of one tile of the jax backend's packed-batch program
    BLOCK_TILE = 65536

    def block_tile(self, width: int) -> int:
        """The column grid on which a packed batch of `width` columns has to
        start its blocks for `reconstruct_block` to run it as ONE device
        program (one decode matrix per tile, `rs_jax.apply_matrix`): the part
        of `BLOCK_TILE` that divides `width` on the jax backend; 1 elsewhere,
        where a block may start at any column (xorsched stitches blocks of
        any width into one pass; the other backends apply block by block)."""
        if self.backend != "jax":
            return 1
        return math.gcd(int(width), self.BLOCK_TILE)

    def _count_dispatch(self) -> None:
        try:
            from seaweedfs_tpu import stats

            stats.EcDispatchTotal.labels(self.backend).inc()
        except Exception:  # noqa: BLE001 — metrics must never break dispatch
            pass

    def _apply_lazy(self, m: np.ndarray, shards: np.ndarray, donate: bool = False):
        """Apply GF matrix m without forcing the result to the host: the
        jax/pallas backends return a device array (async dispatch), numpy/
        native an ndarray. The ONE backend dispatch point — _apply and
        encode_parity_lazy are both defined in terms of it. donate=True
        (jax/pallas, off-CPU only) releases the input's device buffer at
        dispatch-consume time so a streaming pipeline's inflight HBM stays
        bounded (an early-release hint — see rs_jax's donated-twin note).
        The mesh backend is not asked: its dispatcher owns the device copy
        of every batch (parallel/backend.py says how it is released)."""
        self._count_dispatch()
        if self.backend == "mesh":
            return self._mesh_dispatch().apply(m, shards)
        if self.backend == "pallas":
            from seaweedfs_tpu.ops import rs_pallas

            return rs_pallas.apply_matrix(
                m, shards, tile=self.pallas_tile, mxu=self.pallas_mxu,
                donate=donate, interpret=self.pallas_interpret,
            )
        if self.backend == "jax":
            from seaweedfs_tpu.ops import rs_jax

            out = rs_jax.apply_matrix(m, shards, donate=donate)
            return _Crossed(out, (*shards.shape[:-2], m.shape[0], shards.shape[-1]))
        if self.backend == "native":
            out = self._apply_native(m, shards)
            if out is not None:
                return out
            # library unavailable/unbuildable: numpy keeps serving
        if self.backend == "xorsched":
            return self._apply_xorsched(m, shards)
        if shards.ndim == 3:
            return np.moveaxis(gf8.gf_mat_vec(m, np.moveaxis(shards, 0, 1)), 1, 0)
        return gf8.gf_mat_vec(m, shards)

    @staticmethod
    def _apply_native(m: np.ndarray, shards: np.ndarray):
        """C++ AVX2 PSHUFB apply (utils/native, all cores) — ~30x the
        numpy table path on CPU-only volume servers. None when the
        library can't load (caller falls back to numpy)."""
        from seaweedfs_tpu.utils import native as native_mod

        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        if shards.ndim == 2:
            outs = native_mod.gf_matrix_apply_native(
                m, list(shards), shards.shape[1], threads=0
            )
            return None if outs is None else np.stack(outs)
        # batched: one library call with per-element slice pointers — one
        # worker pool for the whole flush and zero host-side repacking
        return native_mod.gf_matrix_apply_batch_native(m, shards, threads=0)

    @staticmethod
    def _apply_xorsched(m: np.ndarray, shards: np.ndarray) -> np.ndarray:
        """Compiled XOR-schedule apply (ops/xorsched): the GF(2^8) matrix is
        lowered once to a binary bit-plane XOR program (bounded LRU keyed by
        matrix bytes + tile geometry) and replayed over the shard widths.
        Never returns None — the numpy bulk-XOR interpreter inside xorsched
        is the always-available floor when libweedtpu.so lacks the
        weedtpu_xor_schedule_apply entry point."""
        from seaweedfs_tpu.ops import xorsched

        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        if shards.ndim == 2:
            out = np.stack(xorsched.apply_matrix(m, list(shards)))
        else:
            out = np.stack(
                [np.stack(xorsched.apply_matrix(m, list(b))) for b in shards]
            )
        try:
            from seaweedfs_tpu import stats

            for event, v in xorsched.schedule_cache_info().items():
                stats.XorschedCache.labels(event).set(v)
        except Exception:  # noqa: BLE001 — metrics must never break dispatch
            pass
        return out

    def _apply(self, m: np.ndarray, shards: np.ndarray) -> np.ndarray:
        """Apply GF matrix m (R x C) to a shard stack (C, N) -> (R, N) or a
        batched stack (B, C, N) -> (B, R, N), materialized on the host."""
        return np.asarray(self._apply_lazy(m, shards))

    # -- public API (reedsolomon.Encoder parity) ----------------------------

    def encode(self, shards: Sequence[np.ndarray]) -> list[np.ndarray]:
        """Fill parity shards from data shards.

        `shards` holds `data_shards` equal-length uint8 arrays (extra entries
        beyond data_shards are ignored/overwritten). Returns the full list of
        `total_shards` arrays (data passed through, parity computed).
        """
        data = np.stack([np.asarray(s, dtype=np.uint8) for s in shards[: self.data_shards]])
        parity = self._apply(self.parity_matrix, data)
        return [data[i] for i in range(self.data_shards)] + [
            parity[i] for i in range(self.parity_shards)
        ]

    def encode_batch(self, data: np.ndarray) -> np.ndarray:
        """Batched encode: (B, data_shards, N) -> (B, total_shards, N).

        One device dispatch for the whole batch — the TPU-first replacement
        for the reference's per-segment goroutine loop (SURVEY.md §2.5)."""
        return np.concatenate(
            [np.asarray(data, dtype=np.uint8),
             np.asarray(self.encode_parity_lazy(data))],
            axis=1,
        )

    def encode_parity_lazy(self, data: np.ndarray, donate: bool = False):
        """Batched parity WITHOUT forcing the result to the host:
        (B, data_shards, N) -> (B, parity_shards, N) — or the flat 2-D form
        (data_shards, N) -> (parity_shards, N), which streaming pipelines
        prefer (one wide matmul, no batch axis) — as a device array (jax/
        pallas backends) or ndarray (numpy). JAX's async dispatch returns
        immediately, so the caller can overlap the NEXT batch's disk reads
        with this batch's device compute (SURVEY §7.1 double buffering);
        np.asarray() on the result is the synchronization point. donate=True
        releases the batch's device buffer at dispatch-consume time
        (jax/pallas, off-CPU; an early-release hint, see `_apply_lazy`)."""
        data = np.asarray(data, dtype=np.uint8)
        if data.ndim == 2:
            if data.shape[0] != self.data_shards:
                raise ValueError(f"want ({self.data_shards}, N), got {data.shape}")
        elif data.ndim != 3 or data.shape[1] != self.data_shards:
            raise ValueError(f"want (B, {self.data_shards}, N), got {data.shape}")
        return self._apply_lazy(self.parity_matrix, data, donate=donate)

    # -- delta parity maintenance (the small-write/inline-ingest seam) -------

    def parity_delta(self, shard_index: int, old_block, new_block):
        """The parity CHANGE for a single data shard's byte change:
        (parity_shards, n) rows to XOR into the stored parity columns
        covering the same byte range — parity' = parity ⊕ delta rows.

        GF(2^8) linearity makes a small overwrite a rank-1 update instead
        of a stripe re-encode (gf8.gf_delta_parity is the numpy golden
        this is tested byte-exact against): the generator-matrix COLUMN
        for `shard_index` is applied to (old ⊕ new) through the same
        backend dispatch the bulk encode runs, so inline-ingest delta
        updates ride whatever kernel the encode path measured fastest."""
        if not 0 <= int(shard_index) < self.data_shards:
            raise ValueError(
                f"shard_index {shard_index} out of range 0..{self.data_shards - 1}"
            )
        old = np.asarray(old_block, dtype=np.uint8).ravel()
        new = np.asarray(new_block, dtype=np.uint8).ravel()
        if old.shape != new.shape:
            raise ValueError(
                f"old/new blocks disagree on length: {old.shape} vs {new.shape}"
            )
        delta = old ^ new
        col = np.ascontiguousarray(
            self.parity_matrix[:, int(shard_index) : int(shard_index) + 1]
        )  # (P, 1)
        return np.asarray(self._apply_lazy(col, delta[None, :]))

    def update_parity(
        self, parity, shard_index: int, old_block, new_block
    ) -> np.ndarray:
        """Delta parity update: given the stored parity columns `parity`
        ((parity_shards, n) uint8, covering the SAME byte range as the
        blocks), return the parity of the stripe with data shard
        `shard_index`'s bytes changed old -> new — byte-exact vs a full
        re-encode of the updated stripe, at O(changed bytes) instead of
        O(stripe). The caller rewrites only the touched parity ranges."""
        parity = np.asarray(parity, dtype=np.uint8)
        old = np.asarray(old_block, dtype=np.uint8).ravel()
        if parity.ndim != 2 or parity.shape[0] != self.parity_shards:
            raise ValueError(
                f"want ({self.parity_shards}, n) parity, got {parity.shape}"
            )
        if parity.shape[1] != old.size:
            raise ValueError(
                f"parity covers {parity.shape[1]} bytes but the block "
                f"changes {old.size}"
            )
        return parity ^ self.parity_delta(shard_index, old, new_block)

    def _pick_survivors(self, shards: Sequence[Optional[np.ndarray]]) -> list[int]:
        present = [i for i, s in enumerate(shards) if s is not None]
        if len(present) < self.data_shards:
            raise ValueError(
                f"too few shards to reconstruct: {len(present)} < {self.data_shards}"
            )
        # Deterministically use the first `data_shards` present shards, like
        # the reference codec's Reconstruct.
        return present[: self.data_shards]

    def reconstruct(
        self,
        shards: Sequence[Optional[np.ndarray]],
        data_only: bool = False,
        wanted: Optional[Sequence[int]] = None,
    ) -> list[np.ndarray]:
        """Recompute missing shards in place-semantics: returns a full list
        where every previously-None entry (or only missing data entries when
        `data_only`) is filled. `wanted` restricts to specific shard ids."""
        shards = list(shards)
        if len(shards) != self.total_shards:
            raise ValueError(f"expected {self.total_shards} entries, got {len(shards)}")
        if wanted is None:
            limit = self.data_shards if data_only else self.total_shards
            wanted = [i for i in range(limit) if shards[i] is None]
        else:
            for w in wanted:
                if not 0 <= w < self.total_shards:
                    raise ValueError(f"wanted shard id {w} out of range 0..{self.total_shards - 1}")
            wanted = [i for i in wanted if shards[i] is None]
        if not wanted:
            return shards
        survivors = self._pick_survivors(shards)
        m = _reconstruction_matrix(
            self.matrix_kind,
            self.data_shards,
            self.parity_shards,
            tuple(survivors),
            tuple(wanted),
        )
        stack = np.stack([np.asarray(shards[i], dtype=np.uint8) for i in survivors])
        out = self._apply_bucketed(m, stack)
        for k, w in enumerate(wanted):
            shards[w] = out[k]
        return shards

    # -- batched reconstruct (the repair-path mirror of encode_parity_lazy) --

    def reconstruction_matrix(
        self, survivors: Sequence[int], wanted: Sequence[int]
    ) -> np.ndarray:
        """The fused decode matrix (len(wanted) x data_shards) mapping a
        survivor stack to the wanted shards — ONE matrix for any mix of
        data and parity losses, built once per loss pattern via the cached
        GF Gaussian elimination. `survivors` must be exactly `data_shards`
        distinct present shard ids; stack rows must follow its order."""
        survivors = tuple(int(s) for s in survivors)
        wanted = tuple(int(w) for w in wanted)
        if len(survivors) != self.data_shards or len(set(survivors)) != len(survivors):
            raise ValueError(
                f"survivors must be {self.data_shards} distinct shard ids, got {survivors}"
            )
        if not wanted:
            raise ValueError("wanted must name at least one shard id")
        for i in survivors + wanted:
            if not 0 <= i < self.total_shards:
                raise ValueError(f"shard id {i} out of range 0..{self.total_shards - 1}")
        return _reconstruction_matrix(
            self.matrix_kind, self.data_shards, self.parity_shards, survivors, wanted
        )

    def repair_projection_plan(
        self, survivors: Sequence[int], wanted: Sequence[int]
    ) -> dict[int, np.ndarray]:
        """Per-survivor coefficient columns of the fused decode matrix:
        shard id -> (len(wanted),) uint8 coefficients. The trace-repair
        wire plan: a holder of local survivor set L ships the projection
          row w = XOR_{s in L} plan[s][w] * shard_s
        and XORing the holders' projections reproduces the decode matrix
        applied to the full survivor stack EXACTLY (GF addition is XOR,
        and matrix-vector products split column-wise), so trace rebuilds
        are byte-identical to slab rebuilds on the same survivor set."""
        m = self.reconstruction_matrix(survivors, wanted)
        return {
            int(s): np.ascontiguousarray(m[:, i])
            for i, s in enumerate(survivors)
        }

    def project(self, coeffs: np.ndarray, stack: np.ndarray) -> np.ndarray:
        """Survivor-side repair projection: apply an arbitrary (R, C)
        GF(2^8) coefficient matrix to a (C, N) local-survivor stack
        -> (R, N) host ndarray, through this encoder's backend (the same
        bit-plane matmul the encode/decode paths run — gf8.gf_project is
        the numpy golden it is tested byte-exact against). C is the
        holder's LOCAL shard count, not data_shards."""
        coeffs = np.asarray(coeffs, dtype=np.uint8)
        stack = np.asarray(stack, dtype=np.uint8)
        if coeffs.ndim != 2 or stack.ndim != 2:
            raise ValueError(
                f"want (R, C) coeffs and (C, N) stack, got {coeffs.shape} "
                f"and {stack.shape}"
            )
        if coeffs.shape[1] != stack.shape[0]:
            raise ValueError(
                f"coeff cols {coeffs.shape[1]} != stack rows {stack.shape[0]}"
            )
        return np.asarray(self._apply_lazy(coeffs, stack))

    def project_lazy(self, coeffs: np.ndarray, stack: np.ndarray, donate: bool = False):
        """`project` without forcing the result to the host — the trace
        rebuild pipeline's combine step (XOR of holder projections IS a
        GF matmul by an all-ones row) rides the same async-dispatch
        contract as encode_parity_lazy/reconstruct_lazy; np.asarray() on
        the result is the synchronization point."""
        coeffs = np.asarray(coeffs, dtype=np.uint8)
        stack = np.asarray(stack, dtype=np.uint8)
        if coeffs.ndim != 2 or stack.ndim != 2 or coeffs.shape[1] != stack.shape[0]:
            raise ValueError(
                f"want (R, C) coeffs and (C, N) stack, got {coeffs.shape} "
                f"and {stack.shape}"
            )
        return self._apply_lazy(coeffs, stack, donate=donate)

    def reconstruct_lazy(
        self,
        stack: np.ndarray,
        survivors: Sequence[int],
        wanted: Sequence[int],
        donate: bool = False,
    ):
        """Batched repair WITHOUT forcing the result to the host: a
        (B, data_shards, N) survivor stack (rows in `survivors` order)
        -> (B, len(wanted), N) — or the flat 2-D (data_shards, N) ->
        (len(wanted), N) form streaming rebuilds prefer — as a device
        array (jax/pallas) or ndarray (numpy/native). ONE device dispatch
        for the whole batch, the `encode_parity_lazy` contract mirrored
        for the repair path; np.asarray() on the result is the
        synchronization point. donate=True releases the stack's device
        buffer at dispatch-consume time (jax/pallas, off-CPU; an
        early-release hint, see `_apply_lazy`)."""
        stack = np.asarray(stack, dtype=np.uint8)
        if stack.ndim == 2:
            if stack.shape[0] != self.data_shards:
                raise ValueError(f"want ({self.data_shards}, N), got {stack.shape}")
        elif stack.ndim != 3 or stack.shape[1] != self.data_shards:
            raise ValueError(f"want (B, {self.data_shards}, N), got {stack.shape}")
        if self.backend == "mesh":
            # the bulk-repair path rides the DISTRIBUTED formulations
            # (ring ppermute / all_to_all over the mesh) rather than the
            # generic column-sharded apply — same bytes, pod bandwidth
            self._count_dispatch()
            return self._mesh_dispatch().reconstruct(
                self.reconstruction_matrix(survivors, wanted), stack
            )
        return self._apply_lazy(
            self.reconstruction_matrix(survivors, wanted), stack, donate=donate
        )

    def reconstruct_batch(
        self,
        stack: np.ndarray,
        survivors: Sequence[int],
        wanted: Sequence[int],
        bucketed: bool = False,
    ) -> np.ndarray:
        """Materialized batched repair: (B, data_shards, N) survivor stack
        -> (B, len(wanted), N) host ndarray. `bucketed` pads N to the
        serving-path shard-length buckets (jax/pallas only) so degraded
        reads of odd interval sizes never pay a fresh XLA compile."""
        stack = np.asarray(stack, dtype=np.uint8)
        if stack.ndim != 3 or stack.shape[1] != self.data_shards:
            raise ValueError(f"want (B, {self.data_shards}, N), got {stack.shape}")
        m = self.reconstruction_matrix(survivors, wanted)
        if bucketed:
            return self._apply_bucketed(m, stack)
        return np.asarray(self._apply_lazy(m, stack))

    def reconstruct_block(
        self,
        staging: np.ndarray,
        blocks: Sequence[dict],
    ):
        """Block-diagonal decode of a staging batch that packs MANY signature
        groups' survivor columns side by side: one call, one sync point.

        `staging` is a (max_k, W) uint8 matrix; block g is a dict with
        `survivors` / `wanted` (shard-id sequences), `col_start` / `width`
        (its column range of the staging batch, disjoint across blocks) and
        an optional `encoder` (its geometry; defaults to self — this is how
        converted volumes join the same dispatch).  Block g's survivor rows
        occupy staging[:k_g, col_start:col_start+width] and its decoded
        shards land at the same columns of the returned (max_m, W) array,
        rows [0, len(wanted_g)).  Rows past len(wanted_g) inside a block's
        columns, and columns that no block covers, are UNSPECIFIED (never
        zeroed — the composite's zero blocks are structural, not
        materialized).

        GF matmul is column-independent, so packing different volumes'
        columns into one batch is byte-exact; each block keeps its own
        LRU'd decode matrix. What runs, by backend:

        - jax: ONE device program over the whole (max_k, W) batch, which
          crosses to the device once, donated: W is cut into tiles of
          `block_tile(W)` columns and every tile is decoded by the matrix
          of the block it lies in (`rs_jax.apply_matrix` of the stack of
          them, padded with zeros to the batch's largest geometry). The
          program's shape
          is (W / tile, max_m, max_k, W): it does not change with where
          blocks begin and end, so a pipeline of such batches compiles
          once. That needs every block to start on the tile grid (a block
          covers the columns up to the next block's start, so padding
          before a start decodes with its left neighbour's matrix and is
          never read); blocks that do not are applied one by one, as below.
        - xorsched: one stitched native (or interpreter) pass over the flat
          (block, width-tile) task list, each block its own compiled XOR
          program.
        - pallas, mesh, native, numpy: one apply per block over its column
          range (on the device backends asynchronous, so the blocks overlap
          in flight; each a program of its own width).

        Host backends return the materialized ndarray; device backends
        return a lazy handle whose np.asarray() is the synchronization
        point, like reconstruct_lazy."""
        staging = np.asarray(staging, dtype=np.uint8)
        if staging.ndim != 2:
            raise ValueError(f"want a 2-D (max_k, W) staging batch, got {staging.shape}")
        if not blocks:
            raise ValueError("blocks must name at least one signature group")
        max_k, width_total = staging.shape
        spans = []
        for g, b in enumerate(blocks):
            enc = b.get("encoder") or self
            c0, w = int(b["col_start"]), int(b["width"])
            if w <= 0 or c0 < 0 or c0 + w > width_total:
                raise ValueError(
                    f"block {g} columns [{c0}, {c0 + w}) outside staging width {width_total}"
                )
            if enc.data_shards > max_k:
                raise ValueError(
                    f"block {g} needs {enc.data_shards} survivor rows, staging has {max_k}"
                )
            m = enc.reconstruction_matrix(b["survivors"], b["wanted"])
            spans.append((enc, m, c0, w))
        by_col = sorted(spans, key=lambda s: s[2])
        for (_, _, a0, aw), (_, _, b0, _bw) in zip(by_col, by_col[1:]):
            if a0 + aw > b0:
                raise ValueError("block column ranges overlap")
        max_m = max(m.shape[0] for _, m, _, _ in spans)
        if self.backend == "xorsched":
            return self._reconstruct_block_xorsched(staging, spans, max_m)
        tile = self.block_tile(width_total)
        if self.backend == "jax" and all(c0 % tile == 0 for _, _, c0, _ in by_col):
            # one program: a block's matrix from its first tile up to the next
            # block's (the first block's from tile 0: nobody reads what lies
            # left of a block), each padded with zeros to the batch's largest
            from seaweedfs_tpu.ops import rs_jax

            self._count_dispatch()
            tiles = np.zeros((width_total // tile, max_m, staging.shape[0]), dtype=np.uint8)
            starts = [0] + [c0 // tile for _, _, c0, _ in by_col[1:]]
            for (_, m, _, _), first in zip(by_col, starts):
                tiles[first:] = 0
                tiles[first:, : m.shape[0], : m.shape[1]] = m
            return _Crossed(rs_jax.apply_matrix(tiles, staging, donate=True), (max_m, width_total))
        # other backends: per-block dispatches (async on device backends,
        # so blocks overlap in flight; _apply_lazy counts each), one sync
        # point for the whole batch via the lazy wrapper
        parts = []
        for enc, m, c0, w in spans:
            sub = staging[: enc.data_shards, c0:c0 + w]
            if self.backend == "mesh":
                self._count_dispatch()
                h = self._mesh_dispatch().apply(m, sub)
            else:
                h = self._apply_lazy(m, sub, donate=False)
            parts.append((m.shape[0], c0, w, h))
        return _FusedBlocks((max_m, width_total), parts)

    def _reconstruct_block_xorsched(
        self, staging: np.ndarray, spans: Sequence[tuple], max_m: int
    ) -> np.ndarray:
        """The stitched path: one native (or interpreter) pass over the
        flat (block, width-tile) task list, each block writing its row
        slices of the fused output in place."""
        from seaweedfs_tpu.ops import xorsched

        self._count_dispatch()
        staging = np.ascontiguousarray(staging)
        out = np.empty((max_m, staging.shape[1]), dtype=np.uint8)
        progs, ins, outs = [], [], []
        for enc, m, c0, w in spans:
            progs.append(xorsched.get_schedule(m))
            ins.append([staging[r, c0:c0 + w] for r in range(enc.data_shards)])
            outs.append([out[r, c0:c0 + w] for r in range(m.shape[0])])
        xorsched.apply_blocks(progs, ins, outputs_per_block=outs)
        try:
            from seaweedfs_tpu import stats

            for event, v in xorsched.schedule_cache_info().items():
                stats.XorschedCache.labels(event).set(v)
        except Exception:  # noqa: BLE001 — metrics must never break dispatch
            pass
        return out

    def _bucket_for(self, n: int) -> Optional[int]:
        if self.backend in ("numpy", "native", "xorsched") or n == 0:
            return None  # host backends have no compile cache to miss —
            # padding would only make the AVX2 kernel chew dead bytes
        for b in self.RECONSTRUCT_BUCKETS:
            if n <= b:
                return b
        return None

    def _apply_bucketed(self, m: np.ndarray, stack: np.ndarray) -> np.ndarray:
        n = stack.shape[-1]
        b = self._bucket_for(n)
        if b is None or b == n:
            return self._apply(m, stack)
        padded = np.zeros(stack.shape[:-1] + (b,), dtype=np.uint8)
        padded[..., :n] = stack
        return self._apply(m, padded)[..., :n]

    def warm_reconstruct(
        self,
        wanted_counts: Sequence[int] = (1,),
        buckets: Optional[Sequence[int]] = None,
    ) -> int:
        """Pre-compile the bucketed reconstruct shapes so the first degraded
        read never pays an XLA compile (jit caches key on shapes only — any
        GF matrix of the right shape covers every decode matrix). Returns
        the number of shapes compiled (0 on the host backends)."""
        if self.backend in ("numpy", "native", "xorsched"):
            return 0  # no XLA compile cache to warm (xorsched's schedule
            # LRU fills on first dispatch; compiles are ~100ms host-side)
        count = 0
        for L in wanted_counts:
            m = self.gen_matrix[: max(1, L), : self.data_shards]
            for b in buckets or self.RECONSTRUCT_BUCKETS:
                self._apply(m, np.zeros((self.data_shards, b), dtype=np.uint8))
                count += 1
        return count

    def warm_decode_matrices(self, local_shards: Sequence[int] = ()) -> int:
        """Pre-build decode matrices for the dominant serving-path loss
        patterns: one shard lost, all 13 others reachable (survivors are
        picked in shard-id order, so the pattern per lost shard is
        deterministic). The GF Gaussian elimination these need was the
        bulk of r3's 4.4 ms cold reconstruct. Returns patterns built."""
        count = 0
        for lost in range(self.total_shards):
            if lost in local_shards:
                continue  # a locally-present shard never needs reconstructing
            survivors = [s for s in range(self.total_shards) if s != lost]
            _reconstruction_matrix(
                self.matrix_kind,
                self.data_shards,
                self.parity_shards,
                tuple(survivors[: self.data_shards]),
                (lost,),
            )
            count += 1
        return count

    def reconstruct_data(self, shards):
        """reedsolomon.ReconstructData: only repair data shards."""
        return self.reconstruct(shards, data_only=True)

    def verify(self, shards: Sequence[np.ndarray]) -> bool:
        """True iff parity shards match the data shards."""
        if len(shards) != self.total_shards:
            raise ValueError(f"expected {self.total_shards} shards")
        data = np.stack([np.asarray(s, dtype=np.uint8) for s in shards[: self.data_shards]])
        parity = self._apply(self.parity_matrix, data)
        for i in range(self.parity_shards):
            if not np.array_equal(parity[i], np.asarray(shards[self.data_shards + i])):
                return False
        return True

    def split(self, data: bytes | np.ndarray) -> list[np.ndarray]:
        """Split a byte blob into data_shards equal arrays (zero-padded).

        Empty input raises, matching the reference codec's ErrShortData."""
        buf = np.frombuffer(bytes(data), dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else np.asarray(data, dtype=np.uint8)
        if len(buf) == 0:
            raise ValueError("short data: cannot split an empty blob")
        per = -(-len(buf) // self.data_shards)
        padded = np.zeros(per * self.data_shards, dtype=np.uint8)
        padded[: len(buf)] = buf
        return list(padded.reshape(self.data_shards, per))

    def join(self, shards: Sequence[np.ndarray], out_size: int) -> bytes:
        return np.concatenate([np.asarray(s, dtype=np.uint8) for s in shards[: self.data_shards]]).tobytes()[:out_size]


def _cpu_backend() -> str:
    """Best CPU path: the C++ AVX2 library when it loads, else numpy."""
    try:
        from seaweedfs_tpu.utils import native as native_mod

        return "native" if native_mod.load() is not None else "numpy"
    except Exception:  # noqa: BLE001 — any loader surprise: numpy serves
        return "numpy"


# -- on-chip measurement evidence (the auto-backend decision input) ----------


def _artifacts_dir() -> str:
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        "artifacts",
    )


def load_device_evidence(art_dir: Optional[str] = None) -> Optional[dict]:
    """Newest committed `DEVICE_MEASUREMENT_r*.json` (lexically latest
    round), with `_file` recording its provenance. None when no readable
    measurement artifact exists."""
    art_dir = art_dir or _artifacts_dir()
    try:
        names = sorted(
            f
            for f in os.listdir(art_dir)
            if f.startswith("DEVICE_MEASUREMENT_") and f.endswith(".json")
        )
    except OSError:
        return None
    for name in reversed(names):
        try:
            import json

            with open(os.path.join(art_dir, name), encoding="utf-8") as f:
                ev = json.load(f)
            if isinstance(ev, dict):
                ev["_file"] = name
                return ev
        except (OSError, ValueError):
            continue  # an unreadable newest artifact must not hide older ones
    return None


def _evidence_age_days(ev: dict) -> Optional[float]:
    """Days since the measurement's `when` stamp; None when unparseable."""
    import datetime

    when = str(ev.get("when", ""))
    for fmt in ("%Y-%m-%dT%H:%M:%SZ", "%Y-%m-%dT%H:%MZ", "%Y-%m-%d"):
        try:
            t = datetime.datetime.strptime(when, fmt)
            return (datetime.datetime.utcnow() - t).total_seconds() / 86400.0
        except ValueError:
            continue
    return None


def parse_fused_variant(label: str) -> tuple[str, Optional[int]]:
    """Map a measurement key / sweep variant name to (mxu, tile) kernel
    config: 'pallas-bf16-16384' -> ('bf16', 16384), 'pallas_tile8192_
    steady_gbps' -> ('int8', 8192), 'pallas-auto'/'pallas_steady_gbps'
    -> ('int8', None), 'pallas-dma-65536' -> ('dma', 65536)."""
    s = label.replace("_steady_gbps", "").replace("_", "-")
    mxu, tile = "int8", None
    for tok in s.split("-"):
        if not tok or tok in ("pallas", "rebuild", "auto"):
            continue
        if tok in FUSED_VARIANTS:
            mxu = tok
        else:
            digits = tok[4:] if tok.startswith("tile") else tok
            if digits.isdigit():
                tile = int(digits)
    return mxu, tile


def _best_fused(ev: dict) -> tuple[Optional[str], float]:
    """Best committed fused-kernel ENCODE number in a measurement dict:
    scans both the stage-1 `pallas*_steady_gbps` keys and the assembled
    sweep section (`sweep.encode`: variant name -> steady GB/s). Rebuild-
    path numbers never pick the encode backend."""
    best_label, best = None, 0.0
    for k, v in ev.items():
        if (
            k.startswith("pallas")
            and k.endswith("_steady_gbps")
            and isinstance(v, (int, float))
            and v > best
        ):
            best_label, best = k, float(v)
    sweep = ev.get("sweep") or {}
    for name, v in (sweep.get("encode") or {}).items():
        if (
            str(name).startswith("pallas")
            and isinstance(v, (int, float))
            and v > best
        ):
            best_label, best = str(name), float(v)
    return best_label, best


def pick_device_backend(art_dir: Optional[str] = None) -> tuple[str, dict]:
    """The auto-backend decision ON TPU: flip to the fused Pallas kernel
    ONLY when a committed on-chip measurement shows a fused variant
    beating the XLA steady-state; otherwise the XLA bit-plane path. The
    returned decision dict (also exported through stats and reported by
    bench.py) names the evidence file, both numbers, and the reason, so
    the selection is auditable rather than folklore."""
    ev = load_device_evidence(art_dir)
    if ev is None:
        return "jax", {
            "backend": "jax",
            "reason": "no committed on-chip measurement evidence",
        }
    decision: dict = {"evidence_file": ev.get("_file")}
    xla = ev.get("xla_steady_gbps") or 0.0
    rm = ev.get("remeasured") or {}
    if isinstance(rm, dict) and rm.get("xla_steady_gbps"):
        xla = max(xla, rm["xla_steady_gbps"])
    # a sweep-only record carries its XLA anchor in the sweep table, not
    # the stage-1 keys
    sweep_xla = ((ev.get("sweep") or {}).get("encode") or {}).get("xla")
    if isinstance(sweep_xla, (int, float)):
        xla = max(xla, sweep_xla)
    label, fused = _best_fused(ev)
    decision["xla_steady_gbps"] = xla
    decision["fused_steady_gbps"] = fused or None
    decision["fused_variant"] = label
    age = _evidence_age_days(ev)
    if "tpu" not in str(ev.get("platform", "")).lower():
        decision.update(backend="jax", reason="evidence is not an on-chip measurement")
        return "jax", decision
    if age is None:
        # conservative default: evidence whose age cannot be established
        # must not flip production (a hand-edited or malformed `when`
        # would otherwise count as fresh forever)
        decision.update(
            backend="jax",
            reason=f"evidence age unparseable (when={ev.get('when')!r}): treated as stale",
        )
        return "jax", decision
    if age > EVIDENCE_MAX_AGE_DAYS:
        decision.update(
            backend="jax",
            reason=f"evidence stale ({age:.0f}d > {EVIDENCE_MAX_AGE_DAYS:.0f}d)",
        )
        return "jax", decision
    if label is not None and xla and fused > xla:
        mxu, tile = parse_fused_variant(label)
        decision.update(
            backend="pallas",
            pallas_mxu=mxu,
            pallas_tile=tile,
            reason=f"committed on-chip {label}={fused} beats xla_steady={xla}",
        )
        return "pallas", decision
    decision.update(
        backend="jax",
        reason=(
            f"no fused number beats xla_steady={xla}"
            if xla
            else "evidence lacks an XLA steady-state to beat"
        ),
    )
    return "jax", decision


# -- committed mesh evidence (the pod-scale promotion input) -----------------


def _multichip_dir() -> str:
    """MULTICHIP_r*.json artifacts live at the repo root (beside
    BENCH_r*.json), not under artifacts/."""
    return os.path.dirname(_artifacts_dir())


def load_mesh_evidence(art_dir: Optional[str] = None) -> Optional[dict]:
    """Newest committed `MULTICHIP_r*.json` (lexically latest round), with
    `_file` recording provenance. None when no readable artifact exists."""
    art_dir = art_dir or _multichip_dir()
    try:
        names = sorted(
            f
            for f in os.listdir(art_dir)
            if f.startswith("MULTICHIP_r") and f.endswith(".json")
        )
    except OSError:
        return None
    for name in reversed(names):
        try:
            import json

            with open(os.path.join(art_dir, name), encoding="utf-8") as f:
                ev = json.load(f)
            if isinstance(ev, dict):
                ev["_file"] = name
                return ev
        except (OSError, ValueError):
            continue  # an unreadable newest artifact must not hide older ones
    return None


def _evidence_round(ev: dict) -> Optional[int]:
    r = ev.get("round")
    if isinstance(r, int):
        return r
    name = str(ev.get("_file", ""))
    digits = "".join(c for c in name if c.isdigit())
    return int(digits) if digits else None


def pick_mesh_backend(
    n_devices: int, art_dir: Optional[str] = None
) -> tuple[bool, dict]:
    """The pod-scale promotion decision: flip `auto` to the mesh backend
    ONLY when a committed `MULTICHIP_r*.json` carries fresh ON-CHIP
    per-mesh-shape measurements (the PR-4 evidence rule generalized from
    per-kernel to per-mesh-shape) in which an achievable shape's encode
    beats the single-device number recorded beside it. Absent, stale,
    off-chip, or losing evidence keeps the current backend. The decision
    dict names the evidence file/round, the winning shape, and both
    numbers, so the selection stays auditable."""
    ev = load_mesh_evidence(art_dir)
    if ev is None:
        return False, {
            "reason": "no committed mesh evidence (MULTICHIP_r*.json)",
        }
    decision: dict = {
        "evidence_file": ev.get("_file"),
        "evidence_round": _evidence_round(ev),
    }
    shapes = ev.get("shapes")
    if not isinstance(shapes, dict) or not shapes:
        decision["reason"] = "evidence has no per-mesh-shape measurements"
        return False, decision
    if "tpu" not in str(ev.get("platform", "")).lower():
        decision["reason"] = "mesh evidence is not an on-chip measurement"
        return False, decision
    age = _evidence_age_days(ev)
    if age is None:
        decision["reason"] = (
            f"mesh evidence age unparseable (when={ev.get('when')!r}): treated as stale"
        )
        return False, decision
    if age > EVIDENCE_MAX_AGE_DAYS:
        decision["reason"] = (
            f"mesh evidence stale ({age:.0f}d > {EVIDENCE_MAX_AGE_DAYS:.0f}d)"
        )
        return False, decision
    single = (ev.get("single_device") or {}).get("encode_gbps")
    single = float(single) if isinstance(single, (int, float)) else 0.0
    best_label, best = None, 0.0
    for label, rec in shapes.items():
        if not isinstance(rec, dict):
            continue
        # parse `DPxSP` locally — this function runs in jax-free parents
        # (bench), so it must not import the parallel package
        parts = str(label).lower().split("x")
        if len(parts) != 2 or not all(p.isdigit() and int(p) > 0 for p in parts):
            continue
        dims = (int(parts[0]), int(parts[1]))
        if dims[0] * dims[1] > int(n_devices):
            continue  # shape not achievable on this pod
        if rec.get("match") is not True or rec.get("error"):
            # only a shape that COMPLETED byte-verification is evidence —
            # a missing `match` (e.g. a rebuild variant crashed after the
            # encode measurement landed) must not promote
            continue
        gbps = rec.get("encode_gbps")
        if not isinstance(gbps, (int, float)) or gbps <= 0:
            continue
        if single and gbps <= single:
            continue  # aggregate number must beat the single-device one
        if gbps > best:
            best_label, best = str(label), float(gbps)
    if best_label is None:
        decision["reason"] = (
            "no achievable mesh shape beats the single-device number"
            if single
            else "no achievable mesh shape with a usable encode measurement"
        )
        return False, decision
    rec = shapes[best_label]
    ring = rec.get("rebuild_ring_gbps")
    a2a = rec.get("rebuild_alltoall_gbps")
    variant = "ring"
    if isinstance(a2a, (int, float)) and (
        not isinstance(ring, (int, float)) or a2a > ring
    ):
        variant = "alltoall"
    decision.update(
        mesh_shape=best_label,
        mesh_rebuild=variant,
        encode_gbps=best,
        single_device_gbps=single or None,
        reason=(
            f"committed on-chip mesh evidence: {best_label} encode={best} "
            f"beats single-device {single}"
        ),
    )
    return True, decision


# -- committed CPU bench evidence (the xorsched promotion input) --------------


def _host_fingerprint() -> dict:
    """Identity of THIS host for same-host evidence matching: cpu model
    string + logical core count. Hostnames are ephemeral in the fleet;
    the model+cores pair is what decides whether a committed BENCH number
    was measured on silicon equivalent to the one now selecting."""
    model = ""
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    if not model:
        import platform

        model = platform.processor() or platform.machine() or ""
    return {"cpu": model, "cores": int(os.cpu_count() or 0)}


def load_cpu_bench_evidence(art_dir: Optional[str] = None) -> Optional[dict]:
    """Newest committed `BENCH_r*.json` (repo root, beside MULTICHIP_r*)
    whose payload carries an `xor` section, unwrapped from the round
    wrapper ({"n", "cmd", "rc", "tail", "parsed"}) when present, with
    `_file` recording provenance. Rounds without an xor section are
    skipped rather than treated as de-promoting evidence: most bench
    rounds measure other subsystems, and the newest XOR measurement is
    the current truth for the xorsched decision. None when no readable
    artifact carries one."""
    art_dir = art_dir or _multichip_dir()
    try:
        names = sorted(
            f
            for f in os.listdir(art_dir)
            if f.startswith("BENCH_r") and f.endswith(".json")
        )
    except OSError:
        return None
    for name in reversed(names):
        try:
            import json

            with open(os.path.join(art_dir, name), encoding="utf-8") as f:
                ev = json.load(f)
            if not isinstance(ev, dict):
                continue
            if isinstance(ev.get("parsed"), dict):
                ev = dict(ev["parsed"], n=ev.get("n"))
            if isinstance(ev.get("xor"), dict):
                ev["_file"] = name
                return ev
        except (OSError, ValueError):
            continue  # an unreadable newest artifact must not hide older ones
    return None


def pick_cpu_backend(art_dir: Optional[str] = None) -> tuple[str, dict]:
    """The CPU-floor promotion decision: flip `auto`'s plain-CPU pick from
    the AVX2 library to the compiled XOR-schedule backend ONLY when a
    committed `BENCH_r*.json` carries a fresh SAME-HOST byte-verified
    measurement in which xorsched's encode beats the native number
    recorded in the SAME run (shared boxes are noisy — both numbers move
    with the noise together, so the committed ratio is the evidence;
    cross-host or cross-run comparisons never are). Absent, stale,
    other-host, unverified, or losing evidence keeps `_cpu_backend()`'s
    pick, and so does a libweedtpu.so predating the xor executor entry
    point — the pure-numpy interpreter cannot beat AVX2, only the
    GFNI/AVX2 transpose path can. The decision dict mirrors
    pick_device_backend's: evidence file/round, both numbers, reason."""
    base = _cpu_backend()
    ev = load_cpu_bench_evidence(art_dir)
    if ev is None:
        return base, {
            "backend": base,
            "reason": "no committed CPU bench evidence with an xor section",
        }
    xor = ev["xor"]
    decision: dict = {
        "evidence_file": ev.get("_file"),
        "evidence_round": _evidence_round(ev),
    }
    age = _evidence_age_days(xor)
    if age is None:
        decision.update(
            backend=base,
            reason=(
                f"xor evidence age unparseable (when={xor.get('when')!r}): "
                "treated as stale"
            ),
        )
        return base, decision
    if age > EVIDENCE_MAX_AGE_DAYS:
        decision.update(
            backend=base,
            reason=f"xor evidence stale ({age:.0f}d > {EVIDENCE_MAX_AGE_DAYS:.0f}d)",
        )
        return base, decision
    host = xor.get("host") or {}
    here = _host_fingerprint()
    if (
        str(host.get("cpu", "")) != here["cpu"]
        or int(host.get("cores") or 0) != here["cores"]
    ):
        decision.update(
            backend=base,
            reason=(
                f"evidence measured on a different host "
                f"({host.get('cpu')!r} x{host.get('cores')}): not transferable"
            ),
        )
        return base, decision
    if xor.get("match") is not True:
        # only a run that COMPLETED byte-verification against the numpy
        # oracle is evidence — a fast-but-wrong executor must not promote
        decision.update(
            backend=base,
            reason="xor evidence did not complete byte-verification",
        )
        return base, decision
    enc = xor.get("encode") or {}
    xs = enc.get("xorsched_gbps")
    nat = enc.get("native_gbps")
    decision["xorsched_gbps"] = float(xs) if isinstance(xs, (int, float)) else None
    decision["native_gbps"] = float(nat) if isinstance(nat, (int, float)) else None
    if (
        not isinstance(xs, (int, float))
        or not isinstance(nat, (int, float))
        or nat <= 0
    ):
        decision.update(
            backend=base,
            reason="xor evidence lacks a same-run xorsched/native encode pair",
        )
        return base, decision
    if xs <= nat:
        decision.update(
            backend=base,
            reason=(
                f"committed xorsched encode {xs} does not beat "
                f"same-run native {nat}"
            ),
        )
        return base, decision
    try:
        from seaweedfs_tpu.ops import xorsched as _xs_mod

        native_ok = _xs_mod.native_available()
    except Exception:  # noqa: BLE001 — a broken probe must not break auto
        native_ok = False
    if not native_ok:
        decision.update(
            backend=base,
            reason=(
                "libweedtpu.so lacks weedtpu_xor_schedule_apply "
                "(stale binary: run make -C native): library path keeps serving"
            ),
        )
        return base, decision
    decision.update(
        backend="xorsched",
        reason=(
            f"committed same-host bench: xorsched encode {xs} beats "
            f"same-run native {nat}"
        ),
    )
    return "xorsched", decision


def _export_selection(selection: dict) -> None:
    """Mirror the factory's decision into the Prometheus registry: the
    previously-selected label (if any) drops to 0 so a scrape shows ONE
    current backend (read-modify-write under a lock: concurrent factories
    must not leave two label-sets at 1)."""
    try:
        from seaweedfs_tpu import stats

        global _last_selection_labels
        backend = str(selection.get("backend", ""))
        source = str(selection.get("source", ""))
        with _selection_lock:
            prev = _last_selection_labels
            if prev is not None and prev != (backend, source):
                stats.EcBackendSelected.labels(*prev).set(0)
            stats.EcBackendSelected.labels(backend, source).set(1)
            _last_selection_labels = (backend, source)
    except Exception:  # noqa: BLE001 — metrics must never break the factory
        pass


_last_selection_labels: Optional[tuple] = None
_selection_lock = threading.Lock()


def new_encoder(
    data_shards: int = 10,
    parity_shards: int = 4,
    backend: str = "auto",
    matrix_kind: str = "vandermonde",
    family: Optional[str] = None,
) -> Encoder:
    """Encoder factory — the backend-selection seam (SURVEY.md §1, §7.1 step 5).

    `family` names a registered code geometry (CODE_FAMILIES) and overrides
    data_shards/parity_shards/matrix_kind — the geometry-flexible entry
    point `ec.convert` and geometry-recording `.eci` mounts use. Without
    it the explicit shard counts apply (legacy default: the 10+4
    Vandermonde wire geometry).

    backend: "auto" picks the measured-fastest device path on TPU, the XLA
    path on other accelerators, and the C++ AVX2 library (numpy if it can't
    load) on plain CPU — the reference's SIMD role; explicit values force a
    path. `WEEDTPU_BACKEND` overrides an "auto" request (operator seam;
    explicit callers are never overridden).

    On TPU the decision is EVIDENCE-BASED: `pick_device_backend` reads the
    newest committed `artifacts/DEVICE_MEASUREMENT_r*.json` and flips to
    the fused Pallas kernel (with the winning variant's tile/mxu config)
    only when a committed on-chip steady-state number beats the XLA path's;
    absent, stale, or losing evidence keeps the XLA default — the path
    chip_smoke.py proves. The decision lands on
    `encoder.selection`, in the `weedtpu_ec_backend_selected` stats gauge,
    and in bench.py output. backend="pallas" still forces the fused kernel.

    POD promotion: with more than one device, `pick_mesh_backend` extends
    the same rule to per-mesh-shape measurements in the committed
    `MULTICHIP_r*.json` artifact — fresh on-chip evidence of an achievable
    dp x sp shape beating the single-device encode flips `auto` to the
    mesh backend (shape + ring/all_to_all rebuild variant from the
    evidence); absent/stale/off-chip mesh evidence keeps whatever the
    per-chip decision chose. backend="mesh" forces the mesh path with
    `WEEDTPU_MESH_SHAPE`/`WEEDTPU_MESH_REBUILD` (or evidence/default)
    config; the selection audit records the mesh shape and evidence round.

    CPU promotion: on plain-CPU hosts `pick_cpu_backend` extends the same
    evidence rule to the compiled XOR-schedule backend — a fresh committed
    `BENCH_r*.json` xor section measured on THIS host (cpu model + cores
    fingerprint) in which xorsched's byte-verified encode beats the native
    AVX2 number from the same run flips `auto` to "xorsched"; absent,
    stale, other-host, or losing evidence keeps the AVX2 library (numpy
    when it can't load).
    """
    if family is not None:
        geom = geometry_for(family)
        data_shards, parity_shards = geom.data_shards, geom.parity_shards
        matrix_kind = geom.matrix_kind
    selection: dict = {"requested": backend}
    pallas_kwargs: dict = {}
    if backend == "auto":
        env = config.env("WEEDTPU_BACKEND").strip().lower()
        if env and env != "auto":
            if env not in _BACKENDS:
                raise ValueError(
                    f"WEEDTPU_BACKEND={env!r} is not one of {('auto',) + _BACKENDS}"
                )
            backend = env
            selection.update(backend=backend, source="env:WEEDTPU_BACKEND")
    if backend == "auto":
        # an exception from here — no jax, a chip another process holds,
        # libtpu's lock taken — propagates: a server that cannot get its
        # device says so and stops, it never carries on from a CPU backend.
        # A host where jax initialises cleanly on the CPU still gets the
        # CPU backend: that is selection by platform.
        import jax

        from seaweedfs_tpu.utils.devices import (
            describe_devices,
            is_tpu_device,
            setup_compile_cache,
        )

        setup_compile_cache()
        devs = jax.devices()
        d, n_dev = devs[0], len(devs)
        selection["device"] = describe_devices(devs)
        if is_tpu_device(d):
            backend, decision = pick_device_backend()
            selection.update(decision)
            # provenance must be honest: absent evidence is a default,
            # not an evidence-based decision
            selection["source"] = (
                "on-chip-evidence"
                if decision.get("evidence_file")
                else "tpu-default-no-evidence"
            )
            if backend == "pallas":
                pallas_kwargs = {
                    "pallas_mxu": decision.get("pallas_mxu", "int8"),
                    "pallas_tile": decision.get("pallas_tile"),
                }
            # pod promotion: >1 device + committed per-mesh-shape
            # evidence outranks any per-chip kernel choice (the
            # aggregate number is the one the rebuild target is
            # stated against)
            if n_dev > 1:
                mesh_ok, mesh_dec = pick_mesh_backend(n_dev)
                selection["mesh"] = mesh_dec
                if mesh_ok:
                    backend = "mesh"
                    dims = tuple(
                        int(p) for p in mesh_dec["mesh_shape"].split("x")
                    )
                    pallas_kwargs = {
                        "mesh_shape": dims,
                        "mesh_rebuild": mesh_dec["mesh_rebuild"],
                    }
                    selection.update(
                        backend="mesh",
                        source="mesh-evidence",
                        reason=mesh_dec["reason"],
                    )
        elif d.platform != "cpu":
            backend = "jax"
            selection.update(
                backend="jax", source="platform",
                reason=f"non-TPU accelerator ({d.platform}): XLA path",
            )
        else:
            backend, cpu_dec = pick_cpu_backend()
            selection.update(cpu_dec)
            # provenance must be honest: promotion (or an explicit
            # keep-native verdict) backed by a committed artifact is
            # evidence; everything else is the platform default
            selection["source"] = (
                "cpu-bench-evidence"
                if cpu_dec.get("evidence_file")
                else "platform"
            )
        if n_dev > 1 and "mesh" not in selection:
            # audit-only on non-TPU multi-device hosts: the decision
            # dict records WHY the pod path is not promoted here, so
            # `ec.backend` can print it (off-chip hosts never promote
            # even when committed evidence would qualify)
            mesh_ok, mesh_dec = pick_mesh_backend(n_dev)
            if mesh_ok:
                mesh_dec = dict(
                    mesh_dec,
                    reason="qualifying evidence exists but this host "
                    "is not a TPU pod: not promoted",
                )
            selection["mesh"] = mesh_dec
    else:
        selection.setdefault("backend", backend)
        selection.setdefault("source", "explicit")
    enc = Encoder(
        data_shards, parity_shards, matrix_kind=matrix_kind, backend=backend,
        **pallas_kwargs,
    )
    if enc.backend in DEVICE_BACKENDS and "device" not in selection:
        # a forced device backend reports what it will run on, like auto
        from seaweedfs_tpu.utils.devices import describe_devices

        selection["device"] = describe_devices()
    if enc.backend == "mesh":
        # audit must name the ACTUAL mesh (explicit/env requests resolve
        # their shape inside MeshDispatch) — build it now so a mesh
        # encoder that cannot construct its mesh fails at the factory,
        # not mid-stream
        md = enc._mesh_dispatch()
        selection.setdefault("mesh_shape", md.shape_str())
        selection.setdefault("mesh_rebuild", md.rebuild_variant)
        selection["mesh_devices"] = md.n_devices
        selection["audit"] = (
            f"mesh {md.shape_str()} ({md.n_devices} devices, "
            f"rebuild={md.rebuild_variant}, evidence="
            f"r{selection.get('mesh', {}).get('evidence_round', '-')})"
        )
    enc.selection = selection
    _export_selection(selection)
    return enc
