"""Mesh dispatch — the production adapter between the flat (shards, width)
forms the streaming pipelines in `ec/stripe` dispatch and the dp x sp
shard_map formulations in `parallel/sharded` + `parallel/ring`.

The streaming encode/rebuild pipelines stage every batch as ONE wide
(shards, W) host slab; GF(2^8) matmul is column-independent, so W *is*
the batch axis laid out flat. `MeshDispatch` shards it:

  * encode / generic apply — `sharded.make_matrix_apply_fn`: W splits
    over the FULL device set (zero communication), so host->device
    transfers of a staging batch land on all chips concurrently and each
    chip matmuls its own column tile.
  * distributed rebuild — the flat (S, W) survivor stack is viewed as dp
    column-slice "volumes" of width W/dp and handed SHARD-major to
    `ring.make_ring_rebuild_fn` (ppermute rotation, one resident block
    per chip — the measured-faster formulation, MULTICHIP_r05: 1.21 s vs
    1.54 s on 64 MiB shards) or `sharded.make_distributed_rebuild_fn`
    (one all_to_all layout flip), selected by `WEEDTPU_MESH_REBUILD`.

Who owns the device copy: the dispatcher, always. Every dispatch
`device_put`s the host batch itself (the staging slot it was handed goes
back to its ring untouched), and on an accelerator the compiled programs
donate that copy, so it is released the moment the program has consumed
it; on the CPU nothing is donated. A caller has no say in it and is
asked for none: `apply` and `reconstruct` take no `donate`.

The way back: every program's result is a column partition of the flat
(rows, W) result, whatever the variant: `cols` leaves device d columns
[d * W/n, (d + 1) * W/n) (`P(None, ("dp", "sp"))`), `ring` and `alltoall`
leave device (i, j) columns [i * W/dp + j * W/(dp*sp), ... + W/(dp*sp))
(`P("dp", None, "sp")` over the (dp, rows, W/dp) volumes). So a restore
(`MeshDispatch._restore`) starts the fetch of every addressable shard,
takes ONE host result of the host-facing shape and copies each fetched
shard straight into its columns, which it reads off the shard's own
`index`: one pass over the result's bytes, pad columns never copied, a
batched (B, rows, N) result split at its N boundaries on the way. It
never asks `np.asarray` of the global array: jax would assemble the
shards into a host array of the DEVICE layout (and keep it on the array),
and re-laying that takes a second full copy. Large results come from a
small pool of kept buffers (`_ResultPool`), so a run of batches writes
into pages it has touched before; a buffer comes home by itself when the
result handed out, and every view of it, has died.

What a dispatch says of itself: `form=mesh-ring | mesh-alltoall |
mesh-cols` on the ambient `*.dispatch` span, a `mesh.put` span under it
(host layout + device_put) and a `mesh.restore` span under the `*.sync`
that fetches the result (the way back: `pieces=` shards copied, `copied=`
host bytes written, `kept=` whether the result's buffer had been used
before), both in `weedtpu_ec_mesh_seconds_total{stage}`;
`weedtpu_ec_mesh_restore_bytes_total{kind}` counts a restore's `result`
bytes and the host bytes it `copied` for them (1 to 1);
`weedtpu_ec_mesh_batches_total{variant, devices}` counts the batches by
the devices they lay on.

Byte-identity contract: a column partition never changes any output byte
(matmul columns are independent; zero pad columns map to zero columns and
are sliced off before the host sees them), so every mesh path is
byte-identical to the single-device encoder / `rebuild_ec_files_serial`.
Fully testable off-TPU via `--xla_force_host_platform_device_count=8`.
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
import time
import weakref
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from seaweedfs_tpu import stats
from seaweedfs_tpu.obs import trace as trace_mod
from seaweedfs_tpu.ops import rs_jax
from seaweedfs_tpu.parallel import mesh as mesh_mod
from seaweedfs_tpu.parallel import ring as ring_mod
from seaweedfs_tpu.parallel import sharded
from seaweedfs_tpu.utils import config

REBUILD_VARIANTS = ("ring", "alltoall")

#: cap on cached compiled dispatch functions per MeshDispatch. Decode
#: matrices churn with shard-loss patterns on a long-lived server (the
#: same churn WEEDTPU_DECODE_MATRIX_CACHE bounds for plain matrices), and
#: each entry here pins a compiled XLA executable — far heavier than a
#: matrix — so the cache must evict, not grow for the life of the process.
_COMPILED_CACHE_CAP = 64


def parse_mesh_shape(raw: str) -> Optional[Tuple[int, int]]:
    """`"4x2"` -> (4, 2); empty/`auto` -> None (resolve elsewhere).
    Malformed values raise — a typo'd shape must fail loudly, not fall
    back to a different mesh than the operator asked for."""
    s = str(raw or "").strip().lower()
    if not s or s == "auto":
        return None
    parts = s.split("x")
    if len(parts) != 2 or not all(p.isdigit() and int(p) > 0 for p in parts):
        raise ValueError(
            f"WEEDTPU_MESH_SHAPE must be `DPxSP` (e.g. 4x2) or auto, got {raw!r}"
        )
    return int(parts[0]), int(parts[1])


def default_mesh_shape(n_devices: int) -> Tuple[int, int]:
    """The dryrun's rule: (n/2 x 2) dp x sp for n >= 4, else (n x 1) —
    sp=2 keeps the ring/all_to_all collectives exercised while dp takes
    the bulk of the batch parallelism."""
    n = max(1, int(n_devices))
    if n >= 4:
        return n // 2, 2
    return n, 1


def _evidence_shape(n_devices: int) -> Optional[Tuple[int, int]]:
    """Best achievable mesh shape from committed MULTICHIP evidence, or
    None. Lazy rs_codec import: the evidence loader lives with the other
    artifact readers and must stay importable without jax."""
    try:
        from seaweedfs_tpu.ops import rs_codec

        ok, dec = rs_codec.pick_mesh_backend(n_devices)
        if ok:
            return parse_mesh_shape(dec["mesh_shape"])
    except Exception:  # noqa: BLE001 — unreadable evidence = no preference
        pass
    return None


class _LazyRestore:
    """An inflight mesh dispatch whose host form differs from the device
    layout: `np.asarray(handle)` (the pipelines' sync point) waits for the
    devices, then has `restore(shape, dev)` (`MeshDispatch._restore`: the
    `mesh.restore` span and counters) fetch the shards, each straight into
    its columns of one host result of `shape`. The global array is never
    read as a whole, so it keeps no host copy. Until then the dispatch
    stays async, exactly like a bare jax array."""

    def __init__(self, dev, shape, restore):
        self._dev = dev
        self._restore = restore
        #: host-facing shape (pad sliced off) — what np.asarray returns
        self.shape = tuple(shape)

    def __array__(self, dtype=None, copy=None):  # noqa: ARG002 — numpy 2.x kw
        self._dev.block_until_ready()  # the wait for the devices is the caller's own time
        out = self._restore(self.shape, self._dev)
        if dtype is not None:
            out = out.astype(dtype, copy=False)
        return out


#: what a `_ResultPool` keeps between restores, at most: three results (a
#: pipelined run holds depth + 1 = 3) of four rows of the widest slot, the
#: encode's (4, 6553600), rounded up to 32 MiB each
RESULT_POOL_MAX_BYTES = 3 * 32 * 1024 * 1024
#: results under this size are malloc's own business (the served reads)
RESULT_POOL_MIN_BYTES = 1 << 20


class _ResultPool:
    """Host results a mesh dispatcher keeps between restores, the staging
    pool's way (`ec/stripe._ring_for`): flat buffers, the smallest that
    fits serves a result, at most `max_bytes` are kept and the smallest go
    first. What is handed out is an array over a buffer's first bytes; the
    buffer is a `bytearray`, no ndarray, so numpy makes the ARRAY the
    `base` of every view of it (a `decoded[k, a:b]` on a lane's queue):
    when the array and its last view have died, its finalizer brings the
    buffer home. A result somebody keeps for good is ordinary garbage, as
    a fresh one would be, and the next restore allocates."""

    def __init__(self, max_bytes: int = RESULT_POOL_MAX_BYTES):
        self.max_bytes = max_bytes
        self._free: list[bytearray] = []  # smallest first
        self._lock = threading.Lock()

    def kept_bytes(self) -> int:
        with self._lock:
            return sum(map(len, self._free))

    def take(self, shape) -> tuple[np.ndarray, bool]:
        """(a C-contiguous writable uint8 array of `shape`, whether its
        buffer had been handed out before)."""
        n = math.prod(shape)
        if n < RESULT_POOL_MIN_BYTES:
            return np.empty(shape, dtype=np.uint8), False
        raw = None
        with self._lock:
            for i, b in enumerate(self._free):
                if len(b) >= n:
                    raw = self._free.pop(i)
                    break
        kept = raw is not None
        if raw is None:
            raw = bytearray(n)
        out = np.ndarray(shape, dtype=np.uint8, buffer=raw)
        weakref.finalize(out, self._give_back, raw)
        return out, kept

    def _give_back(self, raw: bytearray) -> None:
        # a finalizer may run wherever the collector does, under this very
        # lock too: it never waits, a buffer it cannot file is dropped
        if not self._lock.acquire(blocking=False):
            return
        try:
            self._free.append(raw)
            self._free.sort(key=len)
            over = sum(map(len, self._free)) - self.max_bytes
            while over > 0:
                over -= len(self._free.pop(0))
        finally:
            self._lock.release()


def _copy_columns(out: np.ndarray, c0: int, block: np.ndarray) -> int:
    """`block` (rows, m) holds flat columns [c0, c0 + m) of every row of a
    result: copy those that `out` has, (rows, w) or (b, rows, n) with flat
    column bi * n + ni at [bi, :, ni]; the columns past the last are pad
    and stay where they are. Returns the bytes written."""
    n = out.shape[-1]
    c1 = min(c0 + block.shape[-1], n if out.ndim == 2 else out.shape[0] * n)
    if c1 <= c0:
        return 0
    if out.ndim == 2:
        out[:, c0:c1] = block[:, : c1 - c0]
    else:
        for bi in range(c0 // n, -(-c1 // n)):
            lo, hi = max(c0, bi * n), min(c1, (bi + 1) * n)
            out[bi, :, lo - bi * n : hi - bi * n] = block[:, lo - c0 : hi - c0]
    return block.shape[0] * (c1 - c0)


class MeshDispatch:
    """One encoder's mesh state: the `jax.sharding.Mesh`, the jitted
    shard_map'd apply/rebuild functions (cached per GF matrix), and the
    padding rules that keep every dispatch byte-identical to the
    single-device path."""

    def __init__(
        self,
        shape: Optional[Sequence[int]] = None,
        rebuild: Optional[str] = None,
        devices=None,
    ):
        devices = list(devices if devices is not None else jax.devices())
        n = len(devices)
        if shape is None:
            shape = parse_mesh_shape(config.env("WEEDTPU_MESH_SHAPE"))
        if shape is None:
            shape = _evidence_shape(n) or default_mesh_shape(n)
        dp, sp = int(shape[0]), int(shape[1])
        if dp <= 0 or sp <= 0 or dp * sp > n:
            raise ValueError(
                f"mesh shape {dp}x{sp} needs {dp * sp} devices, have {n}"
            )
        self.mesh = mesh_mod.device_mesh(("dp", "sp"), shape=(dp, sp), devices=devices)
        self.dp, self.sp = dp, sp
        self.n_devices = dp * sp
        rebuild = rebuild or config.env("WEEDTPU_MESH_REBUILD")
        if rebuild not in REBUILD_VARIANTS:
            raise ValueError(
                f"mesh rebuild variant {rebuild!r} not in {REBUILD_VARIANTS}"
            )
        self.rebuild_variant = rebuild
        #: staging-width alignment: widths that are a multiple of dp*sp
        #: shard with zero padding (the streaming pipelines round their
        #: staging spans up to this so steady-state batches never pad)
        self.width_align = dp * sp
        self._donate = rs_jax.donation_supported()
        self._col_sharding = NamedSharding(self.mesh, P(None, ("dp", "sp")))
        self._apply_fns: dict = {}
        self._rebuild_fns: dict = {}
        self._lock = threading.Lock()
        self._results = _ResultPool()
        #: distinct devices the last dispatched batch lay on (_check_spread)
        self.last_spread = 0
        stats.EcMeshDevices.set(self.n_devices)

    def shape_str(self) -> str:
        return f"{self.dp}x{self.sp}"

    def _check_spread(self, x, variant: str) -> None:
        """A sharded batch really lies on every device of the mesh: a
        placement that quietly put everything on device 0 would still be
        byte-correct, and would never have been a mesh. Counted by what it
        lay on, before the verdict."""
        self.last_spread = len({s.device for s in x.addressable_shards})
        stats.EcMeshBatches.labels(variant, self.last_spread).inc()
        trace_mod.annotate(devices=self.last_spread)
        if self.last_spread != self.n_devices:
            raise RuntimeError(
                f"mesh {self.shape_str()} batch lies on {self.last_spread} "
                f"devices, want {self.n_devices}"
            )

    @contextlib.contextmanager
    def _timed(self, stage: str, sp):
        """One host-side half of a dispatch: its span `sp` (`mesh.put` /
        `mesh.restore`) and its seconds in `weedtpu_ec_mesh_seconds_total`."""
        t0 = time.perf_counter()
        try:
            with sp:
                yield
        finally:
            stats.EcMeshSeconds.labels(stage).inc(time.perf_counter() - t0)

    def _timed_put(self, variant: str):
        """`devices=` is what `_check_spread` finds, inside."""
        return self._timed("put", trace_mod.span("mesh.put", mesh=self.shape_str(), variant=variant))

    def _restore(self, variant: str, shape: tuple, dev) -> np.ndarray:
        """The way back of one result (module docstring): `dev` is the
        program's output, the flat (rows, W) result as (rows, Wp) (`cols`)
        or as (dp, rows, Wp/dp) volumes (the rebuilds), column-sharded;
        `shape` is what the host is owed, (rows, w) or (b, rows, n) with
        flat column bi * n + ni at [bi, :, ni]; columns from w = b * n on
        are pad. A shard's place is its own `index`."""
        shards = [s for s in dev.addressable_shards if s.replica_id == 0]
        with self._timed("restore", trace_mod.span(
                "mesh.restore", mesh=self.shape_str(), variant=variant,
                devices=len({s.device for s in shards}))):
            for s in shards:
                s.data.copy_to_host_async()
            out, kept = self._results.take(shape)
            wd = dev.shape[-1]
            pieces = copied = 0
            for s in shards:
                host = np.asarray(s.data)
                first = s.index[-1].start or 0
                if host.ndim == 2:
                    done = _copy_columns(out, first, host)
                else:  # volume v of the rebuilds' (dp, rows, wd) is columns [v * wd, (v + 1) * wd)
                    v0 = s.index[0].start or 0
                    done = sum(_copy_columns(out, (v0 + vi) * wd + first, block)
                               for vi, block in enumerate(host))
                pieces += done > 0
                copied += done
            if copied != out.size:
                raise RuntimeError(
                    f"mesh {self.shape_str()} {variant} result of {out.size} bytes: "
                    f"its shards cover {copied}"
                )
            stats.EcMeshRestoreBytes.labels("result").inc(out.size)
            stats.EcMeshRestoreBytes.labels("copied").inc(copied)
            trace_mod.annotate(pieces=pieces, copied=copied, kept=kept)
        return out

    # -- cached compiled functions -------------------------------------------

    @staticmethod
    def _cache_get(cache: dict, key, build):
        """LRU-ish bounded memo: move hits to the end, evict the oldest
        entry past _COMPILED_CACHE_CAP (dict preserves insertion order).
        Caller holds the dispatch lock."""
        fn = cache.pop(key, None)
        if fn is None:
            fn = build()
            while len(cache) >= _COMPILED_CACHE_CAP:
                cache.pop(next(iter(cache)))
        cache[key] = fn
        return fn

    def _apply_fn(self, m: np.ndarray):
        key = (m.shape, m.tobytes())
        with self._lock:
            return self._cache_get(
                self._apply_fns,
                key,
                lambda: sharded.make_matrix_apply_fn(self.mesh, m, donate=self._donate),
            )

    def _rebuild_fn(self, recon_m: np.ndarray):
        key = (recon_m.shape, recon_m.tobytes(), self.rebuild_variant)
        make = (
            ring_mod.make_ring_rebuild_fn
            if self.rebuild_variant == "ring"
            else sharded.make_distributed_rebuild_fn
        )
        with self._lock:
            return self._cache_get(
                self._rebuild_fns,
                key,
                lambda: make(self.mesh, recon_m, donate=self._donate),
            )

    # -- layout helpers -------------------------------------------------------

    def _pad_cols(self, flat: np.ndarray, align: int) -> tuple[np.ndarray, int]:
        """Zero-pad the column axis to a multiple of `align` (exact: GF
        matmul maps zero columns to zero columns; the pad is sliced off
        on restore). Aligned inputs pass through untouched — the
        streaming pipelines stage aligned widths so this is the tail-
        batch/serving-path case only."""
        w = flat.shape[-1]
        pad = -w % align
        if pad == 0:
            return flat, w
        out = np.zeros(flat.shape[:-1] + (w + pad,), dtype=np.uint8)
        out[..., :w] = flat
        return out, w

    @staticmethod
    def _flatten_batch(shards: np.ndarray) -> tuple[np.ndarray, tuple]:
        """(B, C, N) -> (C, B*N): per-batch matmuls ARE column-wise
        concatenation, so the batched apply is the flat apply on the
        transposed layout."""
        b, c, n = shards.shape
        return np.ascontiguousarray(np.moveaxis(shards, 0, 1)).reshape(c, b * n), (b, n)

    # -- dispatches -----------------------------------------------------------

    def apply(self, m: np.ndarray, shards: np.ndarray):
        """Generic mesh apply: (C, W) -> lazy (R, W), or (B, C, N) ->
        lazy (B, R, N). Columns shard over the full device set, so every
        chip receives its host slice concurrently and computes its own
        tile. The device copy is the dispatcher's (module docstring)."""
        m = np.ascontiguousarray(np.asarray(m, dtype=np.uint8))
        shards = np.asarray(shards, dtype=np.uint8)
        batched = shards.ndim == 3
        trace_mod.annotate(form="mesh-cols")
        with self._timed_put("cols"):
            if batched:
                flat, (b, n) = self._flatten_batch(shards)
            else:
                flat = shards
            padded, w = self._pad_cols(flat, self.width_align)
            x = jax.device_put(padded, self._col_sharding)
            self._check_spread(x, "cols")
        out = rs_jax.run_counted(self._apply_fn(m), x)
        r = m.shape[0]
        shape = (b, r, n) if batched else (r, w)
        return _LazyRestore(out, shape, functools.partial(self._restore, "cols"))

    def reconstruct(self, recon_m: np.ndarray, stack: np.ndarray):
        """Distributed rebuild of a flat survivor stack: (S, W) -> lazy
        (L, W) (or (B, S, N) -> lazy (B, L, N)) through the selected
        ring/all_to_all formulation. The stack's byte axis is viewed as
        dp column-slice volumes of width W/dp placed SHARD-major
        (P(dp, sp, None)) — each chip holds whole survivor rows of its
        slice, the collective does the layout work, and the output comes
        back byte-sharded over sp. The device copy is the dispatcher's
        (module docstring)."""
        recon_m = np.ascontiguousarray(np.asarray(recon_m, dtype=np.uint8))
        stack = np.asarray(stack, dtype=np.uint8)
        batched = stack.ndim == 3
        variant = self.rebuild_variant
        fn = self._rebuild_fn(recon_m)
        trace_mod.annotate(form="mesh-" + variant)
        with self._timed_put(variant):
            if batched:
                flat, (b, n) = self._flatten_batch(stack)
            else:
                flat = stack
            # W/dp must itself divide over sp, so align the flat width to dp*sp
            padded, w = self._pad_cols(flat, self.dp * self.sp)
            s, wp = padded.shape
            wd = wp // self.dp
            # (S, dp, wd) -> (dp, S, wd): volume k holds byte columns
            # [k*wd, (k+1)*wd) of every survivor — a pure column partition
            x = fn.place(padded.reshape(s, self.dp, wd).transpose(1, 0, 2))
            self._check_spread(x, variant)
        out = rs_jax.run_counted(fn.jitted, x)  # (dp, L, wd) device, async
        rows = recon_m.shape[0]
        shape = (b, rows, n) if batched else (rows, w)
        return _LazyRestore(out, shape, functools.partial(self._restore, variant))
