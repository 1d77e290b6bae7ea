"""Stripe engine — file-level EC encode/decode/rebuild with the exact layout
semantics of weed/storage/erasure_coding/ec_encoder.go + ec_decoder.go
[VERIFY: mount empty; upstream semantics per SURVEY.md §2.3].

Layout: a volume .dat is processed as block rows. While more than one full
large row (DATA_SHARDS x large_block) remains, encode large rows; the tail is
encoded as small rows, the last one zero-padded past EOF. Shard k's .ec{k:02d}
file is the concatenation of its column across rows. All 14 shard files end up
the same length.

TPU-first deviation from the reference's inner loop: the reference encodes
256 KiB buffer segments one at a time per goroutine; here segments are laid
out flat in a reused (shards, width) host staging buffer and dispatched as
ONE wide device matmul per batch (SURVEY.md §2.5 pipeline analog) — GF
matmul is column-independent, so the flat form is byte-identical to any
per-segment batching. The streaming paths run a configurable depth-N
inflight pipeline (double/triple buffering) over a ring of staging buffers:
batch K's parity/decode computes on-device while batches K+1..K+depth read
from disk, with no per-batch host allocation (reads land straight in the
staging ring, buffer donation releasing batch HBM early on device
backends) and the per-shard CRC32 folded over the same staged bytes as they
are written, so a shard's bytes are touched once per stage (read, write,
CRC) and no finished file is read back.

The host side of a batch runs per shard on shard lanes (`_ShardLanes`): the
pipeline's own thread lays a batch out, waits for its reads, dispatches and
syncs; each shard's slab read, file write and CRC fold are tasks on the
process's lane threads, one shard's tasks in submission order, different
shards at once. Local files are read positionally (`pread_padded_into`); a
source that is no file, or a `SlabSource` that does not say `lane_reads`,
is read on the pipeline's own thread. Staging runs ahead of the drain: a
batch's lane reads are queued before the sync that makes room for it in the
pipeline and run beside it. The staging ring is leased from a pool the
process keeps (`_ring_for`, at most STAGING_POOL_MAX_BYTES between runs), so
only a server's first bulk command faults its slots in.

There are two pipelined loops. `_encode_parts` is the encode's
(`write_ec_files_batch`, of which `write_ec_files` is the batch of one; the
inline-ingest and conversion builders through `_encode_rows`): its batches
write data rows from staging and parity rows from the device, and hold the
rows of as many volumes as fit. `_run_rebuild` is every pipelined
rebuild's: `rebuild_ec_files`, `rebuild_ec_files_from_sources`,
`rebuild_ec_files_from_projections` and `rebuild_ec_files_batch` each lay
their work out as a `_Plan` (which columns a batch holds, what fills a staging
row, the dispatch, where a decoded row goes) and hand it over.
`rebuild_ec_files_serial` is the one-thread oracle the tests compare with.

The engine is backend-agnostic through the Encoder seam: the same flat
(shards, width) dispatch shape serves the device paths (jax/pallas/mesh)
and the CPU floor — including the compiled XOR-schedule backend
(ops/xorsched), whose width-axis cache tiling happens INSIDE the dispatch,
so the staging-batch geometry here needs no backend-specific casing.
"""

from __future__ import annotations

import io
import json
import math
import os
import threading
import zlib
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from contextlib import ExitStack
from typing import Callable, NamedTuple, Optional, Sequence

import numpy as np

from seaweedfs_tpu import stats
from seaweedfs_tpu.ec.constants import (
    DATA_SHARDS_COUNT,
    EC_BUFFER_SIZE,
    ERASURE_CODING_LARGE_BLOCK_SIZE,
    ERASURE_CODING_SMALL_BLOCK_SIZE,
    MAX_SHARD_COUNT,
)
from seaweedfs_tpu.ops.rs_codec import (
    CodeGeometry,
    DEFAULT_FAMILY,
    Encoder,
    family_of,
    geometry_for,
    new_encoder,
)
from seaweedfs_tpu.obs import trace as trace_mod
from seaweedfs_tpu.storage import idx as idx_mod
from seaweedfs_tpu.storage import types
from seaweedfs_tpu.storage.needle_map import MemDb
from seaweedfs_tpu.utils import config


#: inflight depth of the streaming encode/rebuild pipelines: how many
#: batches may be in the read->device->write pipe at once. 1 restores the
#: pre-r6 behavior (one batch overlapped), 2 = double buffering, 3 = triple.
#: Deeper pipelines hide longer device latencies at the cost of
#: (depth+1) staging buffers of `max_batch_bytes` each.
DEFAULT_PIPELINE_DEPTH = config.env("WEEDTPU_PIPELINE_DEPTH")

#: how many batches AHEAD of the reading cursor the rebuild pipeline keeps
#: network-prefetched on remote slab sources (the third overlap stage: the
#: network fetches batch k+N while local readinto consumes batch k+1 and
#: the device decodes batch k). Defaults to the pipeline depth.
DEFAULT_PREFETCH_BATCHES = config.env("WEEDTPU_REBUILD_PREFETCH_BATCHES")

#: sub-range size for striped parallel range-fetches within one remote slab
#: window: a `max_batch_bytes`-sized window is split into stripes fetched
#: concurrently so one window's latency is holder-RTT + transfer/parallelism,
#: not a single serial stream.
DEFAULT_SLAB_STRIPE_BYTES = 4 * 1024 * 1024

#: concurrent sub-range fetches per remote source (slab or trace): the
#: striping fan-out that spreads one shard's windows across its replica
#: holders instead of pinning the first-sorted one.
DEFAULT_SLAB_FANOUT = config.env("WEEDTPU_SLAB_FANOUT")


def to_ext(shard_id: int) -> str:
    return f".ec{shard_id:02d}"


def shard_file_name(base_file_name: str, shard_id: int) -> str:
    return base_file_name + to_ext(shard_id)


def read_padded(f, offset: int, length: int) -> np.ndarray:
    """Read `length` bytes at `offset`, zero-padding past EOF."""
    f.seek(offset)
    raw = f.read(length)
    buf = np.zeros(length, dtype=np.uint8)
    if raw:
        buf[: len(raw)] = np.frombuffer(raw, dtype=np.uint8)
    return buf


def read_padded_into(f, offset: int, out: np.ndarray) -> None:
    """Read `out.size` bytes at `offset` straight into a contiguous uint8
    staging view, zero-filling past EOF — the zero-copy replacement for
    `read_padded` on the streaming paths (no bytes object, no frombuffer,
    no intermediate host copy per batch)."""
    f.seek(offset)
    got = f.readinto(memoryview(out)) or 0
    if got < out.size:
        out[got:] = 0


def pread_padded_into(fd: int, offset: int, out: np.ndarray) -> None:
    """`read_padded_into` on a descriptor at an explicit offset: no file
    position is read or moved, so any number of threads may read one file
    at once (the shard lanes do). Short reads are continued; what lies past
    EOF is zero-filled."""
    mv = memoryview(out).cast("B")
    got = 0
    while got < len(mv):
        if hasattr(os, "preadv"):
            n = os.preadv(fd, [mv[got:]], offset + got)
        else:  # no preadv on this platform: one copy through a bytes object
            piece = os.pread(fd, len(mv) - got, offset + got)
            n = len(piece)
            mv[got : got + n] = piece
        if n == 0:
            break
        got += n
    if got < out.size:
        out[got:] = 0


def _fd_of(f) -> Optional[int]:
    """The descriptor of `f` where it is a real OS file, else None: a shim
    (`convert._VirtualDat`, a BytesIO) keeps state behind `seek`/`readinto`
    and is read on the calling thread only."""
    if not isinstance(f, (io.FileIO, io.BufferedReader, io.BufferedRandom)):
        return None
    try:
        return f.fileno()
    except OSError:  # a buffered reader over something that is no file
        return None


#: the most host memory the staging pool keeps between runs: three slots of the
#: default 64 MiB batch budget, that is one ring at the default depth. The
#: encode's `(10, 6553600)` slots (65.5 MB) and the rebuild's `(10, 4194304)`
#: (41.9 MB) are views of the same three flat buffers, 196.6 MB on a server
#: that has run a bulk command. What runs at once beyond that is allocated
#: for its run and dropped after it.
STAGING_POOL_MAX_BYTES = 3 * 64 * 1024 * 1024

#: flat uint8 buffers that no run holds, smallest first
_pool_free: list = []
_pool_lock = threading.Lock()


class _StagingRing:
    """`slots` host staging buffers for a depth-N pipeline, leased from the
    process's pool (`_ring_for`) for one run and given back at its end.

    A slot is pinned from the moment its batch's reads are queued until its
    batch has drained. With slots = pipeline_depth + 1 the round-robin take()
    never hands back a buffer whose batch is still inflight, although batch
    i takes its slot BEFORE the drain that makes room for it: at most `depth`
    batches are inflight then, i - depth .. i - 1, whose slots are the
    `depth` others; the slot handed out was batch i - depth - 1's, drained
    while batch i - 1 was staged (its parity or decode synced, so the device
    has read the slot, and in the encode its data shards' writes joined).

    The buffers are flat and handed out as `shape` views of their first
    bytes, so that a run of any geometry can use a buffer that is large
    enough. A slot may hold a longer batch's bytes from its last tenant:
    nothing shows, because a batch dispatches and writes its own columns only
    and its pad columns are zeroed by the pipeline."""

    def __init__(self, flat: list, shape: tuple):
        self._flat = flat
        self._bufs = [b[: shape[0] * shape[1]].reshape(shape) for b in flat]
        self._next = 0

    def take(self) -> np.ndarray:
        buf = self._bufs[self._next]
        self._next = (self._next + 1) % len(self._bufs)
        return buf

    def give_back(self) -> None:
        """Return the buffers to the pool: the caller says that nothing can
        read or write a slot any more (lanes joined or aborted, every inflight
        dispatch synced or discarded). The pool keeps at most
        STAGING_POOL_MAX_BYTES, the largest buffers, which serve every smaller
        geometry; a ring that is never given back is ordinary garbage."""
        flat, self._flat, self._bufs = self._flat, [], None
        with _pool_lock:
            _pool_free.extend(flat)
            _pool_free.sort(key=lambda b: b.size)
            over = sum(b.size for b in _pool_free) - STAGING_POOL_MAX_BYTES
            while over > 0:
                over -= _pool_free.pop(0).size


def _ring_for(slots: int, shape: tuple) -> _StagingRing:
    """Lease a staging ring of `slots` buffers of `shape`: the one place a
    bulk run gets its ring. Buffers come from the process's pool where it has
    some that are large enough (the smallest such first), so that a server's
    second bulk command does not fault three fresh 65 MB slots in again, and
    are allocated where it has not; two runs at once never share a buffer,
    because a leased buffer is out of the pool until `give_back`. Says what
    happened: `weedtpu_staging_ring_leases_total{outcome}` and `ring=` on the
    ambient run span, `reused` when every slot came from the pool, `allocated`
    otherwise (and for the span, if any lease under it allocated)."""
    need = shape[0] * shape[1]
    with _pool_lock:
        fit = [i for i, b in enumerate(_pool_free) if b.size >= need][:slots]
        flat = [_pool_free.pop(i) for i in reversed(fit)]
    outcome = "reused" if len(flat) == slots else "allocated"
    flat += [np.empty(need, dtype=np.uint8) for _ in range(slots - len(flat))]
    stats.StagingRingLeases.labels(outcome).inc()
    run = trace_mod.current()
    if run is not None and (run.attrs or {}).get("ring") != "allocated":
        run.annotate(ring=outcome)
    return _StagingRing(flat, shape)


def _aligned(width: int, align: int) -> int:
    """Round a staged width up to the encoder's dispatch alignment (the
    mesh backend shards columns over dp*sp devices; single-device
    backends align to 1 and this is the identity)."""
    return -(-width // align) * align


def _abandon_future(fut) -> None:
    """Cancel an abandoned fetch future; if it is already running, attach a
    callback that observes (and drops) its outcome so late errors never
    surface as unretrieved-exception noise from a thread nobody waits on."""
    if not fut.cancel():
        fut.add_done_callback(_observe_and_drop)


def _observe_and_drop(fut) -> None:
    try:
        fut.result()
    except Exception:  # noqa: BLE001 — abandoned by design
        pass


def _discard_inflight(inflight: deque) -> None:
    """Failure path: force every pending async dispatch to completion and
    drop the results, so teardown never races device work still reading
    from staging buffers. Errors here are suppressed — the original
    failure propagates from the caller."""
    while inflight:
        handle = inflight.popleft()[0]
        try:
            np.asarray(handle)
        except Exception:  # noqa: BLE001 — discarding, not reporting
            pass


# -- shard lanes: a batch's per-shard host work on the host's cores -----------

#: the process's lane threads: made at the first run that wants them, kept
#: for the life of the process (idle they cost nothing, and the inline-ingest
#: path calls `_encode_rows` once per poll), shared by every run.
#: -> (executor, threads)
_lane_threads: Optional[tuple] = None
_lane_threads_lock = threading.Lock()


def _lane_pool() -> Optional[tuple]:
    """(executor, threads) for this host, or None where it has one or two
    cores: one thread fewer than `os.cpu_count()`, so that the calling
    thread and the server's own keep a core."""
    global _lane_threads
    threads = (os.cpu_count() or 1) - 1
    if threads < 2:
        return None
    pool = _lane_threads
    if pool is None or pool[1] != threads:  # the second: only a test changes the count
        with _lane_threads_lock:
            pool = _lane_threads
            if pool is None or pool[1] != threads:
                pool = _lane_threads = (
                    ThreadPoolExecutor(max_workers=threads, thread_name_prefix="ec-lane"),
                    threads,
                )
    return pool


class _LaneBatch:
    """What a `join` waits for: the tasks submitted under it and not finished."""

    __slots__ = ("left",)

    def __init__(self):
        self.left = 0


class _ShardLanes:
    """One pipeline run's per-shard host work (slab reads, file writes, CRC
    folds: calls that release the GIL), spread over the process's lane threads.

    What a lane guarantees: the tasks of one shard run one at a time, in the
    order they were submitted, on whichever thread owns the shard just then;
    tasks of different shards run at once, on at most `n` threads (one per
    shard, and no more than the host's cores less one). So a shard's file
    receives its batches in batch order and its CRC is folded in that order,
    whatever the other shards do. Runs share the threads and nothing else:
    each has its own queues, so two volumes encoded at once neither wait for
    one another's joins nor mix their order.

    `n` == 0 (a host of one or two cores) is the inline form: `submit` runs
    the task there and then on the calling thread, and raises what it raises.

    The first exception of a lane task is kept, the run's tasks that have not
    started are cancelled, and the next `join` raises it on the calling
    thread. `abort` is the failure path's: cancel, then wait for what runs."""

    def __init__(self, shards: int):
        pool = _lane_pool()
        self._pool = pool[0] if pool else None
        self.n = min(shards, pool[1]) if pool else 0
        self._cv = threading.Condition()
        self._queues: dict[int, deque] = {}  # shard -> its waiting tasks; present: a thread owns it
        self._open = 0  # tasks submitted and not finished
        self._error: Optional[BaseException] = None
        self._cancelled = False

    def submit(self, batch: _LaneBatch, shard: int, fn: Callable, *args) -> None:
        """Queue `fn(*args)` behind `shard`'s earlier tasks. Spans it records
        become children of the span that is ambient here."""
        if not self.n:
            fn(*args)
            return
        task = (batch, trace_mod.current(), fn, args)
        with self._cv:
            batch.left += 1
            self._open += 1
            queue = self._queues.get(shard)
            if queue is not None:
                queue.append(task)
                return
            self._queues[shard] = deque((task,))
        self._pool.submit(self._own, shard)

    def _own(self, shard: int) -> None:
        """On a lane thread: run `shard`'s tasks until none waits."""
        done = None
        while True:
            with self._cv:
                if done is not None:
                    done.left -= 1
                    self._open -= 1
                    if not done.left or not self._open:
                        self._cv.notify_all()
                queue = self._queues[shard]
                if not queue:
                    del self._queues[shard]
                    return
                done, parent, fn, args = queue.popleft()
                cancelled = self._cancelled
            if cancelled:
                continue
            try:
                with trace_mod.attach(parent):
                    fn(*args)
            except BaseException as e:  # noqa: BLE001 — kept for the calling thread
                with self._cv:
                    self._cancelled = True
                    if self._error is None:
                        self._error = e

    def join(self, *batches: _LaneBatch) -> None:
        """Block until every task of `batches` (of the whole run, where none
        is given) has finished; raise the run's first exception."""
        with self._cv:
            while any(b.left for b in batches) if batches else self._open:
                self._cv.wait()
            if self._error is not None:
                raise self._error

    def abort(self) -> None:
        """Cancel the run's tasks that have not started and wait for those
        that run: after it no lane touches the run's files or staging slots."""
        with self._cv:
            self._cancelled = True
            while self._open:
                self._cv.wait()


class _Rows(NamedTuple):
    """`n_rows` block rows of one source, as the encode loop takes them: row r's
    shard d is the `block_size` bytes at `start_offset + (r * k + d) *
    block_size` of `f`; shard s's bytes go to `outputs[s]`, in row order, and
    are folded into `crcs[s]` where `crcs` is given. `done`, where given, is
    called once every output has the part's last byte, on a lane of its own
    beside the batches of the parts that follow (never for a part of no rows)."""

    f: object
    outputs: Sequence
    start_offset: int
    block_size: int
    n_rows: int
    crcs: Optional[list]
    done: Optional[Callable[[], None]] = None


def _encode_rows(
    f,
    enc: Encoder,
    outputs: Sequence,
    start_offset: int,
    block_size: int,
    n_rows: int,
    buffer_size: int,
    max_batch_bytes: int,
    pipeline_depth: Optional[int] = None,
    crcs: Optional[list] = None,
) -> int:
    """`_encode_parts` over the rows of ONE source (the inline-ingest and
    conversion builders' entry; `buffer_size` is capped at the block)."""
    part = _Rows(f, outputs, start_offset, block_size, n_rows, crcs)
    return _encode_parts(
        [part], enc, min(buffer_size, block_size), max_batch_bytes, pipeline_depth
    )


def _encode_parts(
    parts: Sequence[_Rows],
    enc: Encoder,
    buffer_size: int,
    max_batch_bytes: int,
    pipeline_depth: Optional[int] = None,
) -> int:
    """Encode the rows of `parts`, in their order, as ONE stream of flat
    (DATA_SHARDS, width) device dispatches over reused staging buffers: the
    `buffer_size` segments of all the parts are packed into the batches one
    after the other, so a batch may hold the end of one part and the start of
    the next (of another volume: the GF matmul is column-independent, which
    source a column came from only decides which files it is read from and
    written to), and only the last batch of the call is narrower than the
    rest. Every part's output files receive its bytes in row-major order.
    Returns the number of batches dispatched.

    Depth-N pipeline: up to `pipeline_depth` batches' parity computes
    on-device (async dispatch) while the next batch's disk reads run;
    the np.asarray in drain_one() is the per-batch synchronization point,
    and drains happen FIFO so parity files receive bytes in order.

    Staging runs ahead of the drain. A batch takes its slot and queues its
    lane reads first, then the oldest dispatches drain until fewer than
    `depth` are inflight, then the calling thread waits for the reads, queues
    the data shards' writes and dispatches: the reads run beside the sync
    (device wait + D2H) and not after it. The slot is free before that drain
    (`_StagingRing`). Reads that run on the calling thread (a source that is
    no OS file, a host with no lanes) stay after the drain: before it they
    would only delay it.

    Who does what (`_ShardLanes`). The calling thread lays out a batch,
    waits for its reads, dispatches it, and syncs its parity; the per-shard
    work runs on the lanes. Where every part's `f` is a real OS file each
    data shard's slabs are read on a lane, positionally (`pread_padded_into`:
    no seek on a handle that two threads touch), from the file of the part
    each run of columns belongs to; a source that is no file (`convert.
    _VirtualDat`) is read on the calling thread, in row-run order, through
    `seek`/`readinto`. Each data shard's write and, when `crcs` is given,
    its CRC32 fold are queued behind its read and run beside the dispatch,
    the device and the next batch's reads (a lane's order stays read i, put
    i, read i + 1); the parity rows' follow their sync. A batch's staging
    slot is pinned until its data shards are written: drain_one() joins them
    before the slot can come round again, and the parity array of a drain
    lives until the next drain (or the end) has joined its writes. Data
    shards' bytes never cross the device, and every byte is still touched
    once per stage: one read into staging, one write from there, one CRC
    fold over the same memory; no second pass over a finished file. On
    return every lane task of the call has finished; on a failure the lanes
    are aborted before the inflight device work is discarded, so the caller
    may unlink.

    The staging ring is leased from the process's pool (`_ring_for`) and
    given back when nothing can touch a slot any more: after the last drain
    and the lanes' join, or on a failure after the lanes are aborted and the
    inflight device work is discarded.

    On a mesh-backend encoder the staging span is rounded up to the
    encoder's `width_align` (dp*sp) and each dispatch covers the aligned
    width (the gap zero-filled, written/CRC'd only to the true width), so
    every batch's host->device transfer splits evenly across the chips
    with no dispatcher-side pad copy."""
    lanes = _ShardLanes(len(parts[0].outputs))
    run_span = trace_mod.current()  # the caller's
    trace_mod.annotate(lanes=lanes.n)  # 0 = inline
    parts = [p for p in parts if p.n_rows > 0]
    if not parts:
        return 0
    for p in parts:
        if p.block_size % buffer_size:
            raise ValueError(f"block size {p.block_size} not a multiple of buffer {buffer_size}")
    depth = DEFAULT_PIPELINE_DEPTH if pipeline_depth is None else max(1, int(pipeline_depth))
    align = int(getattr(enc, "width_align", 1) or 1)
    k = enc.data_shards  # geometry-flexible: the encoder owns (k, m)
    n_out = len(parts[0].outputs)
    # how many (k x buffer) segments fit the device-batch budget
    batch_cap = max(1, max_batch_bytes // (k * buffer_size))
    span = _aligned(batch_cap * buffer_size, align)
    fds = [_fd_of(p.f) for p in parts]
    on_lanes = None not in fds  # else every read runs on this thread, in run order
    lane_reads = on_lanes and lanes.n > 0
    inflight: deque = deque()  # FIFO of (parity_handle, width, the batch's data-shard tasks, its pieces)
    parity_tasks = _LaneBatch()  # the last drain's parity writes; their args keep its array alive
    done_tasks = _LaneBatch()  # the parts' `done` calls, on the lane after the shards'
    unwritten = {id(p): n_out for p in parts if p.done}  # outputs that lack the part's last bytes
    unwritten_lock = threading.Lock()
    n_batches = 0

    def read_slabs(shards: Sequence[int], staging: np.ndarray, runs: list) -> None:
        # the runs' columns are 0..width: one span for what `shards` get of a batch
        with trace_mod.span("encode.read", bytes=len(shards) * runs[-1][3]):
            for pi, off, lo, hi in runs:
                fd, block = fds[pi], parts[pi].block_size
                for d in shards:
                    if fd is None:
                        read_padded_into(parts[pi].f, off + d * block, staging[d, lo:hi])
                    else:
                        pread_padded_into(fd, off + d * block, staging[d, lo:hi])

    def put(s: int, row: np.ndarray, pieces: list) -> None:
        # `pieces`: the columns each part has of the batch, (part, first, end,
        # whether they are the part's last)
        with trace_mod.span("encode.write", bytes=row.size):
            for p, lo, hi, _ in pieces:
                p.outputs[s].write(row[lo:hi])
        if any(p.crcs is not None for p, *_ in pieces):
            with trace_mod.span("encode.crc", bytes=row.size):
                for p, lo, hi, _ in pieces:
                    if p.crcs is not None:
                        p.crcs[s] = zlib.crc32(row[lo:hi], p.crcs[s])
        for p, _, _, last in pieces:
            if last and p.done is not None:
                with unwritten_lock:
                    unwritten[id(p)] -= 1
                    whole = not unwritten[id(p)]
                if whole:  # this shard was the last to get the part's end
                    with trace_mod.attach(run_span):  # a child of the run, not of this drain
                        lanes.submit(done_tasks, n_out, p.done)

    def drain_one() -> None:
        parity, width, data_tasks, pieces = inflight.popleft()
        with trace_mod.span("encode.drain", width=width):
            with trace_mod.span("encode.sync", bytes=(n_out - k) * width):
                parity_np = np.asarray(parity)  # sync point: device wait + D2H
            if k + parity_np.shape[0] != n_out:
                # a geometry-mismatched encoder must fail loudly, not leave
                # trailing .ecNN files silently empty
                raise ValueError(
                    f"encoder produced {parity_np.shape[0]} parity shards; "
                    f"layout wants {n_out - k}"
                )
            with trace_mod.span("encode.wait"):
                lanes.join(data_tasks, parity_tasks)
            for p in range(parity_np.shape[0]):
                lanes.submit(
                    parity_tasks, k + p, put, k + p,
                    np.ascontiguousarray(parity_np[p, :width]), pieces,
                )

    def flush(batch: list) -> None:
        nonlocal n_batches
        if not batch:
            return
        n_batches += 1
        width = len(batch) * buffer_size
        with trace_mod.span("encode.stage", width=width):
            staging = ring.take()  # free already: before the drain below
            # read runs of consecutive segments as one contiguous slab per
            # shard (k large sequential reads per row-run instead of one
            # seek per segment x shard — keeps readahead alive at 1 GiB
            # block strides): (part, shard 0's offset in its f, first
            # column, end); a part's runs are one piece of the batch
            runs = []
            pieces = []
            i = 0
            while i < len(batch):
                pi, row, seg0 = batch[i]
                j = i
                while j + 1 < len(batch) and batch[j + 1] == (pi, row, batch[j][2] + 1):
                    j += 1
                p = parts[pi]
                runs.append(
                    (
                        pi,
                        p.start_offset + row * p.block_size * k + seg0 * buffer_size,
                        i * buffer_size,
                        (j + 1) * buffer_size,
                    )
                )
                last = row == p.n_rows - 1 and batch[j][2] == p.block_size // buffer_size - 1
                if pieces and pieces[-1][0] is p:
                    pieces[-1] = (p, pieces[-1][1], (j + 1) * buffer_size, last)
                else:
                    pieces.append((p, i * buffer_size, (j + 1) * buffer_size, last))
                i = j + 1
            data_tasks = _LaneBatch()

            def read_batch() -> None:
                if not on_lanes:  # no OS file: here, run by run through seek/readinto
                    read_slabs(range(k), staging, runs)
                else:
                    for d in range(k):
                        lanes.submit(data_tasks, d, read_slabs, (d,), staging, runs)

            if lane_reads:  # queued now, they run beside the sync
                read_batch()
            while len(inflight) >= depth:
                drain_one()
            if not lane_reads:
                read_batch()
            if on_lanes:
                with trace_mod.span("encode.wait"):
                    lanes.join(data_tasks)
            view = staging[:, :width]
            for d in range(k):
                lanes.submit(data_tasks, d, put, d, view[d], pieces)
            aw = _aligned(width, align)  # <= span: roundup is monotone
            if aw > width:
                staging[:, width:aw] = 0  # tail batch: pad columns are zeros
        with trace_mod.span("encode.dispatch", bytes=k * aw):
            parity = enc.encode_parity_lazy(staging[:, :aw], donate=True)  # H2D + launch
        inflight.append((parity, width, data_tasks, pieces))

    ring = _ring_for(depth + 1, (k, span))
    try:
        # iterate segments in global order (part, then row-major, then segment in block)
        pending: list = []  # (part, row, seg)
        for pi, p in enumerate(parts):
            for row in range(p.n_rows):
                for seg in range(p.block_size // buffer_size):
                    pending.append((pi, row, seg))
                    if len(pending) >= batch_cap:
                        flush(pending)
                        pending = []
        flush(pending)
        while inflight:
            drain_one()
        with trace_mod.span("encode.wait"):
            lanes.join()
    except BaseException:
        lanes.abort()
        _discard_inflight(inflight)
        ring.give_back()
        raise
    ring.give_back()
    return n_batches


def stripe_layout(
    dat_size: int,
    large_block_size: int,
    small_block_size: int,
    data_shards: int = DATA_SHARDS_COUNT,
) -> tuple[int, int]:
    """(n_large, n_small) rows for a .dat of `dat_size` bytes — THE layout
    rule (WriteEcFiles semantics): while strictly more than one full large
    row remains, rows are large; the tail becomes small rows, the last one
    zero-padded past EOF. The ONE definition shared by the warm converter,
    the inline-ingest builder, and the geometry converter: their
    byte-identity contract is exactly this function agreeing with itself.
    `data_shards` is the row width in blocks (legacy default 10)."""
    large_row = large_block_size * data_shards
    small_row = small_block_size * data_shards
    n_large = 0
    remaining = dat_size
    while remaining > large_row:
        n_large += 1
        remaining -= large_row
    n_small = 0
    while remaining > 0:
        n_small += 1
        remaining -= small_row
    return n_large, n_small


def write_ec_files_batch(
    base_file_names: Sequence[str],
    large_block_size: int = ERASURE_CODING_LARGE_BLOCK_SIZE,
    small_block_size: int = ERASURE_CODING_SMALL_BLOCK_SIZE,
    buffer_size: int = EC_BUFFER_SIZE,
    encoder: Optional[Encoder] = None,
    max_batch_bytes: int = 64 * 1024 * 1024,
    pipeline_depth: Optional[int] = None,
) -> dict:
    """<base>.dat -> <base>.ec00 .. .ec13 + <base>.eci for MANY volumes of
    one geometry through ONE encode pipeline (`_encode_parts`): the volumes'
    rows are packed into the batches in the order given, so the pipeline
    fills and drains once and only the last batch of the call is narrower
    than the slot, where a loop over the volumes ends each in a tail batch of
    its own width. Each volume's shard files, CRC32s and .eci are its own and
    byte-identical to what `write_ec_files` writes for it alone.

    Each shard's CRC32 is folded over its staged bytes as they are written
    (on the shard's lane, in batch order — no read-back pass over a
    finished file) and recorded in the .eci sidecar for later shard
    verification.

    A volume is finished (its 15 files closed, its .eci written and fsynced)
    as soon as its last bytes are written, on a lane of its own beside the
    batches of the volumes after it (`encode.finish`): only the last volume's
    finish follows the pipeline.

    Failure semantics: a volume whose .dat cannot be opened is left out and
    the others run. A mid-stream failure, on this thread or on a lane, stops
    the lanes, drains the inflight device work and unlinks every partial
    .ecNN file of every volume that was not finished — a crashed encode never
    leaves a truncated shard set that a later rebuild would mistake for
    truth — and fails those (a finished volume is whole and stays); an
    interrupt is raised again after that clean-up.
    Returns {"errors": {base: the exception}, "batches": int}."""
    enc = encoder or new_encoder()
    k, total = enc.data_shards, enc.total_shards
    errors: dict[str, BaseException] = {}
    finished: dict[str, int] = {}  # base -> its .dat's bytes
    batches = 0
    with trace_mod.ensure("encode.run", klass="maint"), ExitStack() as stack:
        parts: list[_Rows] = []
        opened: list[str] = []
        for base in base_file_names:
            try:
                f = stack.enter_context(open(base + ".dat", "rb"))
                dat_size = os.fstat(f.fileno()).st_size
            except OSError as e:
                errors[base] = e
                continue
            opened.append(base)
            outputs = [
                stack.enter_context(open(shard_file_name(base, s), "wb")) for s in range(total)
            ]
            crcs = [0] * total

            def finish(base=base, files=(f, *outputs), dat_size=dat_size, crcs=crcs) -> None:
                with trace_mod.span("encode.finish"):
                    for h in files:  # every shard file, before its .eci says it is whole
                        h.close()
                    write_ec_info(
                        base, large_block_size, small_block_size, dat_size,
                        shard_crcs=crcs, geometry=geometry_of(enc),
                    )
                finished[base] = dat_size

            n_large, n_small = stripe_layout(dat_size, large_block_size, small_block_size, k)
            small_at = n_large * large_block_size * k
            parts += [
                _Rows(f, outputs, 0, large_block_size, n_large, crcs),
                _Rows(f, outputs, small_at, small_block_size, n_small, crcs, finish),
            ]
            if not n_small:  # an empty .dat: no row will ever end, it is whole already
                finish()
        try:
            # one run where the buffer fits both block sizes (every published
            # geometry); a block under the buffer is cut finer and runs after
            # the coarser parts, which are the same volumes' earlier rows
            for buf in sorted({min(buffer_size, p.block_size) for p in parts}, reverse=True):
                batches += _encode_parts(
                    [p for p in parts if min(buffer_size, p.block_size) == buf],
                    enc, buf, max_batch_bytes, pipeline_depth,
                )
        except BaseException as e:
            stack.close()
            for base in opened:
                if base not in finished:
                    errors[base] = e
                    for s in range(total):
                        try:
                            os.unlink(shard_file_name(base, s))
                        except OSError:
                            pass
            if not isinstance(e, Exception):
                raise
        trace_mod.annotate(
            batch=len(base_file_names), bytes=sum(finished.values()), batches=batches
        )
    return {"errors": errors, "batches": batches}


def write_ec_files(
    base_file_name: str,
    large_block_size: int = ERASURE_CODING_LARGE_BLOCK_SIZE,
    small_block_size: int = ERASURE_CODING_SMALL_BLOCK_SIZE,
    buffer_size: int = EC_BUFFER_SIZE,
    encoder: Optional[Encoder] = None,
    max_batch_bytes: int = 64 * 1024 * 1024,
    pipeline_depth: Optional[int] = None,
) -> None:
    """<base>.dat -> <base>.ec00 .. .ec13 (WriteEcFiles semantics): the
    batch of one (`write_ec_files_batch`), whose failure is raised."""
    res = write_ec_files_batch(
        [base_file_name], large_block_size, small_block_size, buffer_size,
        encoder, max_batch_bytes, pipeline_depth,
    )
    if res["errors"]:
        raise res["errors"][base_file_name]


def geometry_of(enc: Encoder) -> CodeGeometry:
    """The encoder's geometry as a CodeGeometry record (family name from
    the registry when the triple matches one, else a `custom_K_M` tag)."""
    fam = enc.family or f"custom_{enc.data_shards}_{enc.parity_shards}"
    return CodeGeometry(
        fam, enc.data_shards, enc.parity_shards, enc.matrix_kind
    )


_LEGACY_GEOMETRY = geometry_for(DEFAULT_FAMILY)


def geometry_from_info(info: Optional[dict]) -> CodeGeometry:
    """The code geometry an .eci sidecar records — the LEGACY default
    (10+4 Vandermonde) when the sidecar is absent or predates geometry
    recording, so every pre-conversion shard set keeps reading exactly as
    before. Malformed geometry keys raise rather than silently misread."""
    if not info or "data_shards" not in info:
        return _LEGACY_GEOMETRY
    k = int(info["data_shards"])
    m = int(info["parity_shards"])
    kind = str(info.get("matrix_kind", "vandermonde"))
    if k <= 0 or m <= 0 or k + m > MAX_SHARD_COUNT:
        raise ValueError(
            f".eci records an unusable geometry: {k}+{m} (max total "
            f"{MAX_SHARD_COUNT})"
        )
    fam = str(info.get("family") or family_of(k, m, kind) or f"custom_{k}_{m}")
    return CodeGeometry(fam, k, m, kind)


def encoder_for_info(
    info: Optional[dict], default: Optional[Encoder] = None
) -> Encoder:
    """An encoder matching the .eci-recorded geometry. The supplied
    `default` (typically the server's shared encoder) is returned when its
    geometry already matches; otherwise a same-backend sibling is built so
    geometry-flexible volumes keep riding whatever kernel/mesh selection
    the factory measured fastest."""
    geom = geometry_from_info(info)
    if default is not None:
        if (
            default.data_shards == geom.data_shards
            and default.parity_shards == geom.parity_shards
            and default.matrix_kind == geom.matrix_kind
        ):
            return default
        enc = Encoder(
            geom.data_shards,
            geom.parity_shards,
            matrix_kind=geom.matrix_kind,
            backend=default.backend,
            pallas_mxu=default.pallas_mxu,
            pallas_tile=default.pallas_tile,
            pallas_interpret=default.pallas_interpret,
            mesh_shape=default.mesh_shape,
            mesh_rebuild=default.mesh_rebuild,
        )
        enc.selection = dict(
            default.selection, geometry=geom.family, source="geometry-sibling"
        )
        return enc
    return new_encoder(
        geom.data_shards, geom.parity_shards, matrix_kind=geom.matrix_kind
    )


def encoder_for_base(
    base_file_name: str, default: Optional[Encoder] = None
) -> Encoder:
    """`encoder_for_info` keyed by shard-set base path."""
    return encoder_for_info(read_ec_info(base_file_name), default)


def write_ec_info(
    base_file_name: str,
    large_block_size: int,
    small_block_size: int,
    dat_size: int,
    shard_crcs: Optional[Sequence[int]] = None,
    geometry: Optional[CodeGeometry] = None,
) -> None:
    """Record the stripe geometry + true .dat size in an .eci sidecar.

    The reference needs no such file because its block sizes are compile-time
    constants; here they are parameters (tests use scaled-down geometry), and
    opening a shard set with the wrong geometry would silently mis-map
    intervals. Shard sets written by stock tooling (no .eci) still open fine
    with the default constants. `shard_crcs` (one CRC32 per shard file,
    computed inline by the streaming encode) rides along when available so
    rebuilds and fsck can verify shard integrity without a golden copy.

    `geometry` records the code family/(k, m)/matrix kind for
    geometry-flexible volumes; the LEGACY default geometry is left implicit
    (absent keys read as 10+4 Vandermonde) so default-geometry sidecars stay
    byte-identical across every writer — warm, inline, rebuild, convert."""
    info = {
        "large_block_size": large_block_size,
        "small_block_size": small_block_size,
        "dat_size": dat_size,
    }
    if geometry is not None and (
        geometry.data_shards,
        geometry.parity_shards,
        geometry.matrix_kind,
    ) != (
        _LEGACY_GEOMETRY.data_shards,
        _LEGACY_GEOMETRY.parity_shards,
        _LEGACY_GEOMETRY.matrix_kind,
    ):
        info.update(
            data_shards=geometry.data_shards,
            parity_shards=geometry.parity_shards,
            matrix_kind=geometry.matrix_kind,
            family=geometry.family,
        )
    if shard_crcs is not None:
        info["shard_crc32"] = [int(c) for c in shard_crcs]
    tmp = base_file_name + ".eci.tmp"
    with open(tmp, "w") as f:
        json.dump(info, f)
        f.flush()
        os.fsync(f.fileno())  # the .eci is load-bearing: geometry + dat_size
    os.replace(tmp, base_file_name + ".eci")


_ECI_KEYS = ("large_block_size", "small_block_size", "dat_size")


def read_ec_info(base_file_name: str) -> Optional[dict]:
    try:
        with open(base_file_name + ".eci") as f:
            info = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(info, dict) or not all(
        isinstance(info.get(k), int) for k in _ECI_KEYS
    ):
        return None
    return info


def write_sorted_file_from_idx(base_file_name: str, ext: str = ".ecx") -> None:
    """<base>.idx -> <base>.ecx: replay the index log, write entries sorted
    by needle id (WriteSortedFileFromIdx semantics)."""
    db = MemDb()
    db.load_from_idx(base_file_name + ".idx")
    db.save_to_idx(base_file_name + ext)


def generate_ec_files(
    base_file_name: str,
    **kwargs,
) -> None:
    """The VolumeEcShardsGenerate work: shards + sorted index."""
    write_ec_files(base_file_name, **kwargs)
    write_sorted_file_from_idx(base_file_name)


def find_local_shards(base_file_name: str, total: Optional[int] = None) -> list[int]:
    """Shard ids with a local .ecNN file. The scan covers the registry-wide
    MAX_SHARD_COUNT bound by default so geometry-flexible shard sets (e.g.
    a converted 20+4 volume's .ec14-.ec23) are discovered; pass `total` to
    pin a known geometry."""
    return [
        s
        for s in range(total if total is not None else MAX_SHARD_COUNT)
        if os.path.exists(shard_file_name(base_file_name, s))
    ]


def _check_rebuild_geometry(
    base_file_name: str, enc: Encoder
) -> tuple[list[int], list[int], int]:
    """Shared preflight for both rebuild paths: -> (present, missing,
    shard_size). Raises when fewer than the geometry's data_shards survive
    or survivors disagree on length (truncated shard)."""
    present = find_local_shards(base_file_name, enc.total_shards)
    missing = [s for s in range(enc.total_shards) if s not in present]
    if not missing:
        return present, missing, 0
    if len(present) < enc.data_shards:
        raise ValueError(
            f"cannot rebuild: only {len(present)} shards present, need {enc.data_shards}"
        )
    sizes = {s: os.path.getsize(shard_file_name(base_file_name, s)) for s in present}
    if len(set(sizes.values())) != 1:
        raise IOError(f"surviving shards disagree on length: {sizes} — truncated shard?")
    return present, missing, sizes[present[0]]


# -- slab sources: where the rebuild pipeline's survivor bytes come from -----


class SlabSource:
    """One survivor shard's slab supplier for the rebuild pipeline.

    The pipeline calls `prefetch(offset, length)` for windows it will want
    soon (a hint — sources may start the work asynchronously) and
    `read_into(offset, out)` when the bytes must land in a staging view.
    Reads past the shard's end zero-fill, exactly like `read_padded_into`,
    so every backend is byte-interchangeable under the decode.

    `lane_reads` says whether `read_into` may run on a shard lane of
    `_run_rebuild`, that is on another thread than the
    pipeline's, at the same time as other sources' reads. A source says yes
    only if its `read_into` shares no state with anything else that runs
    meanwhile (`LocalSlabSource`: a positional read of its own file). The
    base class says no, and such a source is read on the pipeline's own
    thread, one after another, after its `prefetch` hints: the order it has
    always seen."""

    lane_reads = False

    def prefetch(self, offset: int, length: int) -> None:  # noqa: B027 — hint
        pass

    def read_into(self, offset: int, out: np.ndarray) -> None:
        raise NotImplementedError

    def close(self) -> None:  # noqa: B027 — optional teardown
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class LocalSlabSource(SlabSource):
    """A local shard file, read positionally straight into the staging view:
    no seek, no position shared between calls, so it may be read on a lane."""

    lane_reads = True

    def __init__(self, path: str):
        # weedlint: ignore[open-no-ctx] handle owned by the source, closed in close()
        self._f = open(path, "rb")

    def read_into(self, offset: int, out: np.ndarray) -> None:
        pread_padded_into(self._f.fileno(), offset, out)

    def close(self) -> None:
        self._f.close()


class RemoteSlabSource(SlabSource):
    """Striped parallel range-fetches of one shard from its peer holders.

    `fetch(addr, offset, size) -> bytes` is the transport (injected by the
    cluster layer: the chunk-streamed, CRC-checked VolumeEcShardSlabRead
    RPC); it may return SHORT on EOF and must raise on any failure. A
    prefetched window is split into `stripe_bytes` sub-ranges submitted to
    the executor so the window's wall time is ~one holder round-trip, not a
    serial stream.

    Failover is per-holder and mid-rebuild: a failed fetch marks the
    holder dead and retries the range against the next holder (after a
    one-shot `refresh_holders()` re-lookup when all known holders are
    dead) WITHOUT disturbing other inflight ranges — the batch pipeline
    never restarts. Dead holders are recorded in `self.failovers` for
    observability. Raises IOError when no holder can serve a range.

    Multi-holder striping (the PR-3-named follow-up): up to `fanout`
    stripes run concurrently and each picks the live holder with the
    FEWEST inflight fetches (ties broken by per-stripe rotation), so a
    replicated shard's windows aggregate bandwidth across all its
    holders — and when one holder dies the load rebalances onto the
    rest instead of serializing behind a static modulo assignment."""

    def __init__(
        self,
        shard_id: int,
        holders: Sequence[str],
        fetch: Callable[[str, int, int], bytes],
        executor: Optional[ThreadPoolExecutor] = None,
        stripe_bytes: int = DEFAULT_SLAB_STRIPE_BYTES,
        refresh_holders: Optional[Callable[[], Sequence[str]]] = None,
        fetch_deadline: float = 120.0,
        fanout: Optional[int] = None,
    ):
        self.shard_id = shard_id
        self.failovers: list[str] = []
        #: payload bytes this source pulled over the network (the
        #: repair-bandwidth accounting input: moved-bytes, not
        #: repaired-bytes)
        self.bytes_fetched = 0
        self._holders = [str(h) for h in holders]
        self._dead: set[str] = set()
        self._fetch = fetch
        self._refresh = refresh_holders
        # bounded, not one-shot: a transient error may kill the only known
        # holder more than once over a GB-scale rebuild; each refresh
        # resurrects re-listed holders, while the bound still guarantees
        # termination against a genuinely dead cluster
        self._refreshes_left = 2
        self._stripe = max(64 * 1024, int(stripe_bytes))
        self._deadline = fetch_deadline
        self._lock = threading.Lock()
        # the rebuild's ambient span, captured at construction: fetches
        # run on pool threads, and the holder-bound RPCs must carry the
        # rebuild's trace id across the wire (ContextVars don't cross
        # executor submission)
        self._trace_parent = trace_mod.current()
        self._fanout = DEFAULT_SLAB_FANOUT if fanout is None else max(1, int(fanout))
        #: holder -> fetches currently running against it (striping load)
        self._inflight: dict[str, int] = {}
        self._own_executor = executor is None
        self._ex = executor or ThreadPoolExecutor(
            max_workers=self._fanout, thread_name_prefix=f"slab-fetch-{shard_id}"
        )
        #: offset -> (length, [(rel_offset, size, Future[bytes]), ...])
        self._pending: dict[int, tuple[int, list]] = {}

    def _live_holders(self) -> list[str]:
        with self._lock:
            live = [h for h in self._holders if h not in self._dead]
            if live or self._refresh is None or self._refreshes_left <= 0:
                return live
            self._refreshes_left -= 1
        try:
            fresh = list(self._refresh() or ())
        except Exception:  # noqa: BLE001 — a dead master is "no holders"
            fresh = []
        with self._lock:
            for h in fresh:
                if h not in self._holders:
                    self._holders.append(str(h))
                self._dead.discard(str(h))
            return [h for h in self._holders if h not in self._dead]

    def _pick_holder(self, live: list[str], offset: int) -> str:
        """Least-inflight live holder; per-stripe rotation breaks ties so
        an idle source still spreads consecutive windows across replicas
        instead of always re-picking the first-sorted holder."""
        with self._lock:
            rot = (offset // self._stripe) % len(live)
            order = live[rot:] + live[:rot]
            addr = min(order, key=lambda h: self._inflight.get(h, 0))
            self._inflight[addr] = self._inflight.get(addr, 0) + 1
            return addr

    def _fetch_range(self, offset: int, size: int) -> bytes:
        with trace_mod.attach(self._trace_parent):
            return self._fetch_range_inner(offset, size)

    def _fetch_range_inner(self, offset: int, size: int) -> bytes:
        while True:
            live = self._live_holders()
            if not live:
                raise IOError(
                    f"shard {self.shard_id}: no reachable holder for "
                    f"[{offset}, {offset + size}) — tried {self._holders}"
                )
            addr = self._pick_holder(live, offset)
            try:
                data = self._fetch(addr, offset, size)
            except Exception:  # noqa: BLE001 — holder down: fail over
                with self._lock:
                    self._inflight[addr] = max(0, self._inflight.get(addr, 1) - 1)
                    if addr not in self._dead:
                        self._dead.add(addr)
                        self.failovers.append(addr)
                continue
            with self._lock:
                self._inflight[addr] = max(0, self._inflight.get(addr, 1) - 1)
                self.bytes_fetched += len(data)
            if len(data) > size:
                raise IOError(
                    f"shard {self.shard_id}: holder {addr} over-answered "
                    f"({len(data)} > {size} bytes)"
                )
            return data

    def prefetch(self, offset: int, length: int) -> None:
        if length <= 0 or offset in self._pending:
            return
        futs = []
        for off in range(offset, offset + length, self._stripe):
            n = min(self._stripe, offset + length - off)
            futs.append((off - offset, n, self._ex.submit(self._fetch_range, off, n)))
        self._pending[offset] = (length, futs)

    def read_into(self, offset: int, out: np.ndarray) -> None:
        entry = self._pending.pop(offset, None)
        if entry is not None and entry[0] != out.size:
            for _, _, fut in entry[1]:  # stale window shape: refetch
                _abandon_future(fut)
            entry = None
        if entry is None:
            self.prefetch(offset, out.size)
            entry = self._pending.pop(offset)
        _, futs = entry
        # the wait must outlive failover: a holder that HANGS (no error
        # until the transport deadline) burns one full fetch_deadline
        # before the worker retries the next holder, so budget one
        # deadline per holder we could try, plus one for the refresh
        with self._lock:
            wait_budget = self._deadline * (len(self._holders) + 1)
        try:
            for rel, n, fut in futs:
                data = fut.result(timeout=wait_budget)
                got = len(data)
                if got:
                    out[rel : rel + got] = np.frombuffer(data, dtype=np.uint8)
                if got < n:  # EOF inside the window: zero-fill, like local
                    out[rel + got : rel + n] = 0
        except BaseException:
            for _, _, fut in futs:
                _abandon_future(fut)
            raise

    def close(self) -> None:
        for _, futs in self._pending.values():
            for _, _, fut in futs:
                _abandon_future(fut)
        self._pending.clear()
        if self._own_executor:
            self._ex.shutdown(wait=False, cancel_futures=True)


# -- trace-repair projection sources -----------------------------------------
#
# The repair-bandwidth lever (PAPERS.md: "Practical Considerations in
# Repairing Reed-Solomon Codes", regenerating-code helpers): a holder of
# several survivor shards ships the GF(2^8) PROJECTION of its local group
# through the decode matrix — `rows = len(missing)` projected rows per
# holder — instead of one full slab per survivor. XORing the holders'
# projections IS the fused decode (GF addition is XOR and matrix products
# split column-wise), so the rebuilt bytes are identical to the slab path
# while the wire moves holders x repaired-bytes, not survivors x shard-bytes.


class TraceSlabSource(SlabSource):
    """One holder group's repair-projection supplier.

    `fetch(offset, size) -> bytes` is the transport, already bound to the
    holder and its projection terms by the cluster layer (the projection
    mode of the CRC-framed VolumeEcShardSlabRead RPC); it returns the
    ROW-MAJOR (rows, actual) projected block for the window, where
    `actual = min(size, shard_len - offset)` — short on EOF exactly like
    a slab, and the client zero-fills (projections of zero columns are
    zero). Windows are split into `chunk_bytes` sub-ranges fetched in
    parallel (projection is per-byte-column, so sub-ranges concatenate
    exactly).

    NO in-source failover: the group's shards live on THIS holder, so a
    failed fetch propagates and the caller falls back to full-slab
    sources (capability negotiation and chaos both land there)."""

    def __init__(
        self,
        holder: str,
        shard_ids: Sequence[int],
        rows: int,
        fetch: Callable[[int, int], bytes],
        executor: Optional[ThreadPoolExecutor] = None,
        chunk_bytes: Optional[int] = None,
        fanout: Optional[int] = None,
    ):
        if rows <= 0:
            raise ValueError("projection rows must be positive")
        self.holder = str(holder)
        self.shard_ids = [int(s) for s in shard_ids]
        self.rows = int(rows)
        self.bytes_fetched = 0
        self._fetch = fetch
        self._chunk = max(
            64 * 1024,
            int(config.env("WEEDTPU_TRACE_CHUNK") if chunk_bytes is None else chunk_bytes),
        )
        self._lock = threading.Lock()
        # same bridge as RemoteSlabSource: projection fetches run on pool
        # threads but must ride the rebuild's trace id over the wire
        self._trace_parent = trace_mod.current()
        self._own_executor = executor is None
        workers = DEFAULT_SLAB_FANOUT if fanout is None else max(1, int(fanout))
        self._ex = executor or ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix=f"trace-fetch-{self.holder}"
        )
        #: window offset -> (per-shard length, [(rel, size, Future), ...])
        self._pending: dict[int, tuple[int, list]] = {}

    def _fetch_counted(self, offset: int, size: int) -> bytes:
        with trace_mod.attach(self._trace_parent):
            return self._fetch_counted_inner(offset, size)

    def _fetch_counted_inner(self, offset: int, size: int) -> bytes:
        data = self._fetch(offset, size)
        if len(data) % self.rows:
            raise IOError(
                f"trace group {self.holder}: projected stream length "
                f"{len(data)} is not a multiple of {self.rows} rows"
            )
        if len(data) > size * self.rows:
            raise IOError(
                f"trace group {self.holder}: over-answered "
                f"({len(data)} > {size * self.rows} bytes)"
            )
        with self._lock:
            self.bytes_fetched += len(data)
        return data

    def prefetch(self, offset: int, length: int) -> None:
        if length <= 0 or offset in self._pending:
            return
        futs = []
        for off in range(offset, offset + length, self._chunk):
            n = min(self._chunk, offset + length - off)
            futs.append(
                (off - offset, n, self._ex.submit(self._fetch_counted, off, n))
            )
        self._pending[offset] = (length, futs)

    def read_into(self, offset: int, out: np.ndarray) -> None:
        """Fill a flat (rows * width,) staging view with the window's
        projected block: row-major (rows, width), EOF zero-filled."""
        if out.size % self.rows:
            raise ValueError(
                f"staging view of {out.size} bytes is not {self.rows} rows"
            )
        width = out.size // self.rows
        entry = self._pending.pop(offset, None)
        if entry is not None and entry[0] != width:
            for _, _, fut in entry[1]:  # stale window shape: refetch
                _abandon_future(fut)
            entry = None
        if entry is None:
            self.prefetch(offset, width)
            entry = self._pending.pop(offset)
        _, futs = entry
        out2d = out.reshape(self.rows, width)
        try:
            for rel, n, fut in futs:
                data = fut.result()
                sub = len(data) // self.rows
                if sub:
                    out2d[:, rel : rel + sub] = np.frombuffer(
                        data, dtype=np.uint8
                    ).reshape(self.rows, sub)
                if sub < n:  # EOF inside the window: zero-fill, like local
                    out2d[:, rel + sub : rel + n] = 0
        except BaseException:
            for _, _, fut in futs:
                _abandon_future(fut)
            raise

    def close(self) -> None:
        for _, futs in self._pending.values():
            for _, _, fut in futs:
                _abandon_future(fut)
        self._pending.clear()
        if self._own_executor:
            self._ex.shutdown(wait=False, cancel_futures=True)


class LocalProjectionSource(SlabSource):
    """The rebuild target's own survivors as one projection group: reads
    the local shard windows and projects them through the group's decode
    coefficients with the SAME math the remote holders run server-side —
    so local and remote groups are interchangeable rows of the trace
    combine, and local survivors cost zero wire bytes."""

    def __init__(self, paths: Sequence[str], coeffs: np.ndarray, encoder):
        coeffs = np.asarray(coeffs, dtype=np.uint8)
        if coeffs.ndim != 2 or coeffs.shape[1] != len(paths):
            raise ValueError(
                f"want (rows, {len(paths)}) coeffs, got {coeffs.shape}"
            )
        self.holder = "local"
        self.rows = coeffs.shape[0]
        self.bytes_fetched = 0  # never leaves the machine
        self._coeffs = coeffs
        self._enc = encoder
        # weedlint: ignore[open-no-ctx] handles owned by the source, closed in close()
        self._files = [open(p, "rb") for p in paths]

    def read_into(self, offset: int, out: np.ndarray) -> None:
        if out.size % self.rows:
            raise ValueError(
                f"staging view of {out.size} bytes is not {self.rows} rows"
            )
        width = out.size // self.rows
        stack = np.empty((len(self._files), width), dtype=np.uint8)
        for i, f in enumerate(self._files):
            read_padded_into(f, offset, stack[i])
        out.reshape(self.rows, width)[:] = self._enc.project(self._coeffs, stack)

    def close(self) -> None:
        for f in self._files:
            f.close()


# -- the rebuild pipeline: one loop, and the plans its entry points hand it ---


class _Seg(NamedTuple):
    """One signature group's columns of one batch. A single-volume rebuild has
    one group and one segment a batch; a batch of `rebuild_ec_files_batch`
    holds a segment per group whose volumes reach into it."""

    group: int  # the failure domain: a failed read stops this group and no other
    #: what fills the slot: (source, offset, length, staging row, first column,
    #: end): `source.read_into(offset, slot[row, first:end])`, hinted before by
    #: `source.prefetch(offset, length)`
    fills: list
    #: where a decoded row goes: (decoded row, first column, end, output)
    puts: list
    #: the columns as a `reconstruct_block` block; None where the plan's
    #: dispatch needs none (projections)
    block: Optional[dict]


class _Batch(NamedTuple):
    #: shard bytes staged per row: a volume's tail, and a packed plan's last
    #: batch, are rounded up to `buffer_size`
    width: int
    valid: int  # ... of them the shards': what is written
    cols: int  # staging columns in use (projections fold `rows` shard bytes into each)
    segs: list


class _Plan(NamedTuple):
    """What differs between the pipelined rebuilds, as data: `_run_rebuild`
    owns everything else. Outputs are numbered in member order."""

    shape: tuple  # of a staging slot: (rows, columns)
    align: int  # dispatched columns are a multiple of it (the mesh's dp*sp)
    batches: list  # of _Batch, in the order the output files receive them
    members: list  # (base, group, shard ids to rebuild) per volume
    #: (staging slot, columns to dispatch, the live segments' blocks) -> a lazy
    #: handle: np.asarray of it is the sync point
    dispatch: Callable


def _windows(shard_size: int, span: int, buffer_size: int):
    """One volume's batches: (offset, valid bytes, staged width) per `span`
    columns of the shard, the staged width a whole number of buffers."""
    for off in range(0, shard_size, span):
        valid = min(span, shard_size - off)
        yield off, valid, -(-valid // buffer_size) * buffer_size


def _decode_blocks(staging: np.ndarray, cols: int, blocks: list):
    """The decode of a batch of survivor slabs. One signature from column 0 on
    (every batch of a single-volume rebuild) is one `reconstruct_lazy` over the
    dispatched columns, donated; several signatures side by side are one
    block-diagonal `reconstruct_block` over the same columns (on the jax
    backend one program too: the plan starts every block on its tile grid)."""
    first = blocks[0]
    enc = first["encoder"]
    if len(blocks) == 1 and first["col_start"] == 0:
        return enc.reconstruct_lazy(
            staging[: enc.data_shards, :cols], first["survivors"], first["wanted"], donate=True
        )  # async: H2D + launch
    trace_mod.annotate(blocks=len(blocks))
    return enc.reconstruct_block(staging[:, :cols], blocks)


def _run_rebuild(
    plan: _Plan, pipeline_depth: Optional[int], prefetch_batches: Optional[int]
) -> dict:
    """THE pipelined rebuild: every entry point below plans, this runs.

    Depth-N over a ring of `depth + 1` staging slots, leased from the
    process's pool (`_ring_for`): while `depth` batches decode on the device
    the next one is staged, and its staging runs ahead of the drain. Per
    batch: sources are told to prefetch `ahead` batches in front of the read
    cursor (the network runs ahead of the reads); a slot is taken, free
    already (`_StagingRing`), and the reads of sources that say `lane_reads`
    are queued on the shard lanes, all at once; the oldest dispatches drain
    until fewer than `depth` are inflight, the lanes reading beside the sync;
    the other sources are read on this thread, one after another (before the
    drain they would only delay it); the dispatch waits for all of them. A
    drain syncs (np.asarray: device wait + D2H), joins the previous drain's
    writes (their arguments keep its array alive) and queues each decoded
    row's write and CRC fold on the lane of its output file, so a file
    receives its batches in order whatever the other files do. The ring goes
    back to the pool when nothing can touch a slot any more: after the last
    drain and the lanes' join, or after the failure path below has aborted
    the lanes and discarded the inflight device work.

    Failure is scoped. A survivor read that raises fails its GROUP: the read
    task records the exception against the group instead of raising, the
    group's later segments stop staging, its drains stop writing (from the
    drain that runs beside the failed read on), its members' partial outputs
    are unlinked and each gets the exception; every
    other group flows on, and a run whose groups have all failed stops. A
    volume whose rebuilt CRCs disagree with its .eci record loses its own
    outputs only. Anything else (dispatch, sync, a lane's write) fails the
    RUN, in one order: abort the lanes, discard the inflight device work,
    close the files, unlink every output, re-raise.

    Returns {member index: the exception that failed it}; annotates the
    caller's run span with `lanes`, `bytes` and `batches`."""
    depth = DEFAULT_PIPELINE_DEPTH if pipeline_depth is None else max(1, int(pipeline_depth))
    ahead = (
        DEFAULT_PREFETCH_BATCHES if prefetch_batches is None else max(1, int(prefetch_batches))
    )
    batches = plan.batches
    paths = [shard_file_name(base, s) for base, _, shards in plan.members for s in shards]
    groups = {group for _, group, _ in plan.members}
    failed: dict[int, Exception] = {}  # group -> what its read raised
    crcs = [0] * len(paths)
    lanes = _ShardLanes(plan.shape[0] + len(paths))
    trace_mod.annotate(lanes=lanes.n)  # 0 = inline
    inflight: deque = deque()  # FIFO of (decoded handle, its batch)
    written = _LaneBatch()  # the last drain's writes; their args keep its array alive
    rebuilt_bytes = 0

    def drop(outputs) -> None:
        for o in outputs:
            try:
                os.unlink(paths[o])
            except OSError:
                pass

    def read(group: int, src: SlabSource, off: int, out: np.ndarray) -> None:
        if group in failed:
            return
        try:
            with trace_mod.span("rebuild.read", bytes=out.size):
                src.read_into(off, out)
        except Exception as e:  # noqa: BLE001 — the group's failure, not the run's
            failed.setdefault(group, e)

    def put(o: int, row: np.ndarray) -> None:
        with trace_mod.span("rebuild.write", bytes=row.size):
            files[o].write(row)
        with trace_mod.span("rebuild.crc", bytes=row.size):
            crcs[o] = zlib.crc32(row, crcs[o])

    def drain_one() -> None:
        nonlocal rebuilt_bytes
        lazy, batch = inflight.popleft()
        rows = [p for seg in batch.segs if seg.group not in failed for p in seg.puts]
        nbytes = sum(end - first for _, first, end, _ in rows)
        with trace_mod.span("rebuild.drain", width=batch.valid):
            with trace_mod.span("rebuild.sync", bytes=nbytes):
                decoded = np.asarray(lazy)  # sync point: device wait + D2H
            with trace_mod.span("rebuild.wait"):
                lanes.join(written)
            for k, first, end, o in rows:
                lanes.submit(written, o, put, o, decoded[k, first:end])
        rebuilt_bytes += nbytes

    def issue_prefetch(bi: int) -> None:
        if bi < len(batches):
            for seg in batches[bi].segs:
                if seg.group not in failed:
                    for src, off, length, _, _, _ in seg.fills:
                        src.prefetch(off, length)

    try:
        with ExitStack() as stack:
            files = [stack.enter_context(open(p, "wb")) for p in paths]
            ring = _ring_for(depth + 1, plan.shape)
            try:
                for j in range(min(ahead, len(batches))):
                    issue_prefetch(j)
                for bi, batch in enumerate(batches):
                    if len(failed) == len(groups):
                        break
                    issue_prefetch(bi + ahead)  # network runs ahead of reads
                    with trace_mod.span("rebuild.stage", width=batch.width):
                        staging = ring.take()  # free already: before the drain below
                        reads = _LaneBatch()
                        fills = [(seg.group, f) for seg in batch.segs for f in seg.fills]
                        # sources read on a lane are queued now: they run beside the sync
                        for group, (src, off, _, row, first, end) in fills:
                            if lanes.n and src.lane_reads:
                                lanes.submit(
                                    reads, src, read, group, src, off, staging[row, first:end]
                                )
                        while len(inflight) >= depth:
                            drain_one()
                        # ... and the calling thread's own after it, while the lanes read
                        for group, (src, off, _, row, first, end) in fills:
                            if not (lanes.n and src.lane_reads):
                                read(group, src, off, staging[row, first:end])
                        with trace_mod.span("rebuild.wait"):
                            lanes.join(reads)
                        # <= the slot's: monotone. A packed tail dispatches its
                        # rounded width, so that widths repeat from run to run
                        cols = _aligned(max(batch.cols, batch.width), plan.align)
                        if cols > batch.cols:
                            staging[:, batch.cols:cols] = 0  # tail: pad columns are zeros
                    # a read may have failed its group after an earlier segment staged
                    blocks = [seg.block for seg in batch.segs if seg.group not in failed]
                    if blocks:
                        with trace_mod.span("rebuild.dispatch", bytes=plan.shape[0] * cols):
                            inflight.append((plan.dispatch(staging, cols, blocks), batch))
                while inflight:
                    drain_one()
                with trace_mod.span("rebuild.wait"):
                    lanes.join()
            except BaseException:
                lanes.abort()
                _discard_inflight(inflight)
                ring.give_back()
                raise
            ring.give_back()
        errors: dict[int, Exception] = {}
        o = 0
        for mi, (base, group, shards) in enumerate(plan.members):
            outputs = range(o, o + len(shards))
            o += len(shards)
            error = failed.get(group)
            if error is None:
                try:
                    with trace_mod.span("rebuild.verify"):
                        _verify_rebuilt_crcs(base, {s: crcs[i] for s, i in zip(shards, outputs)})
                except Exception as e:  # noqa: BLE001 — this volume's, the others are good
                    error = e
            if error is not None:
                drop(outputs)
                errors[mi] = error
    except BaseException:
        drop(range(len(paths)))
        raise
    trace_mod.annotate(bytes=rebuilt_bytes, batches=len(batches))
    return errors


def rebuild_ec_files_from_projections(
    base_file_name: str,
    groups: Sequence[SlabSource],
    shard_size: int,
    missing: Sequence[int],
    encoder: Optional[Encoder] = None,
    buffer_size: int = 4 * 1024 * 1024,
    max_batch_bytes: int = 64 * 1024 * 1024,
    pipeline_depth: Optional[int] = None,
    prefetch_batches: Optional[int] = None,
) -> list[int]:
    """The trace-combine rebuild: every batch reads one (rows x width)
    projected block per holder group and reconstructs the missing shards with
    ONE fused combine dispatch — the XOR of the groups' partial projections,
    expressed as an all-ones GF(2^8) matrix applied to the
    (groups, rows*width) staging stack, so it rides `_run_rebuild` like the
    slab rebuilds. Output is byte-identical to `rebuild_ec_files_serial` on
    the same survivor set (the projection coefficients ARE the fused decode
    matrix, split column-wise across holders); CRC32 is folded in as bytes
    stream out and checked against the .eci record; any failure stops the
    lanes, drains inflight device work, unlinks the partial outputs and is
    raised as it was."""
    enc = encoder or encoder_for_base(base_file_name)
    missing = sorted(int(s) for s in missing)
    if not missing:
        return []
    if not groups:
        raise ValueError("trace rebuild needs at least one projection group")
    rows = len(missing)
    for g in groups:
        if getattr(g, "rows", None) != rows:
            raise ValueError(
                f"group {getattr(g, 'holder', g)!r} projects "
                f"{getattr(g, 'rows', None)} rows, want {rows}"
            )
    span = max(1, max_batch_bytes // (enc.data_shards * buffer_size)) * buffer_size
    combine = np.ones((1, len(groups)), dtype=np.uint8)  # GF sum == XOR
    batches = [
        _Batch(
            width,
            valid,
            rows * width,
            [
                _Seg(
                    0,
                    [(g, off, width, i, 0, rows * width) for i, g in enumerate(groups)],
                    # the combined (1, rows*width) row is (rows, width) row-major
                    [(0, k * width, k * width + valid, k) for k in range(rows)],
                    None,
                )
            ],
        )
        for off, valid, width in _windows(shard_size, span, buffer_size)
    ]
    plan = _Plan(
        (len(groups), rows * span),
        1,
        batches,
        [(base_file_name, 0, missing)],
        lambda staging, cols, _: enc.project_lazy(combine, staging[:, :cols], donate=True),
    )
    errors = _run_rebuild(plan, pipeline_depth, prefetch_batches)
    if errors:
        raise errors[0]
    return missing


def rebuild_ec_files_from_sources(
    base_file_name: str,
    sources: dict[int, SlabSource],
    shard_size: int,
    encoder: Optional[Encoder] = None,
    missing: Optional[Sequence[int]] = None,
    buffer_size: int = 4 * 1024 * 1024,
    max_batch_bytes: int = 64 * 1024 * 1024,
    pipeline_depth: Optional[int] = None,
    prefetch_batches: Optional[int] = None,
) -> list[int]:
    """The generalized (local OR remote survivor) rebuild of one volume.

    `sources` maps present shard id -> SlabSource; `missing` defaults to
    every shard id absent from it. Survivor selection is the first
    DATA_SHARDS of the sorted present ids — the same rule as
    `rebuild_ec_files_serial` on the same survivor set, so output bytes are
    identical regardless of where survivors live. Each batch is one flat
    (survivors, width) slab of the staging ring, the width a whole number of
    buffers rounded up to the encoder's `width_align`, decoded by ONE fused
    survivors->missing matrix in ONE dispatch (`_decode_blocks`). Triple
    overlap (`_run_rebuild`): remote sources are told to prefetch batch
    k+`prefetch_batches` (network) while batch k+1 fills staging (disk /
    prefetched-buffer copy) and batch k decodes on-device. Rebuilt shards
    stream to `<base>.ecNN` with CRC32 folded in and verified against the
    .eci record when present; any failure, on this thread or on a lane,
    stops the lanes, drains inflight device work, unlinks the partial
    outputs and is raised as it was."""
    enc = encoder or encoder_for_base(base_file_name)
    present = sorted(sources)
    if missing is None:
        missing = [s for s in range(enc.total_shards) if s not in sources]
    missing = sorted(missing)
    if not missing:
        return []
    if len(present) < enc.data_shards:
        raise ValueError(
            f"cannot rebuild: only {len(present)} shards present, need {enc.data_shards}"
        )
    survivors = present[: enc.data_shards]
    align = int(getattr(enc, "width_align", 1) or 1)
    chunks_per_batch = max(1, max_batch_bytes // (enc.data_shards * buffer_size))
    span = _aligned(chunks_per_batch * buffer_size, align)
    batches = [
        _Batch(
            width,
            valid,
            width,
            [
                _Seg(
                    0,
                    [(sources[s], off, width, i, 0, width) for i, s in enumerate(survivors)],
                    [(k, 0, valid, k) for k in range(len(missing))],
                    {
                        "encoder": enc,
                        "survivors": survivors,
                        "wanted": missing,
                        "col_start": 0,
                        "width": width,
                    },
                )
            ],
        )
        for off, valid, width in _windows(shard_size, span, buffer_size)
    ]
    plan = _Plan(
        (enc.data_shards, span), align, batches, [(base_file_name, 0, missing)], _decode_blocks
    )
    errors = _run_rebuild(plan, pipeline_depth, prefetch_batches)
    if errors:
        raise errors[0]
    return missing


def rebuild_ec_files_batch(
    jobs: list[dict],
    encoder: Optional[Encoder] = None,
    buffer_size: int = 4 * 1024 * 1024,
    max_batch_bytes: int = 64 * 1024 * 1024,
    pipeline_depth: Optional[int] = None,
    prefetch_batches: Optional[int] = None,
) -> dict:
    """MANY volumes' rebuilds through SHARED device dispatches — the
    fleet-repair batch engine (and the PR 9 residual: dp used to shard
    one volume's staging width, so a storm of small volumes paid a
    partial-width dispatch each).

    Each job is {"base", "sources" ({shard id -> SlabSource}),
    "shard_size", "missing" (optional)}. Jobs whose (survivor set,
    missing set, geometry) SIGNATURE matches share one fused decode
    matrix, and batches are WIDTH-PACKED across volume boundaries: a
    batch window fills with volume A's tail and volume B's head side by
    side (the GF matmul is column-independent, so which volume a column
    came from is purely a scatter concern at drain time). Small stripes
    therefore ride full-width dispatches instead of one shallow dispatch
    per volume.

    DIFFERENT signatures share batches too: the cohort runs group-major
    through ONE `_run_rebuild` pipeline, each group's columns of a batch
    consecutive, so a batch that holds several is one block-diagonal decode
    (`Encoder.reconstruct_block`: the composite's zero blocks never
    materialize) — dispatch_groups == 1 for any mix of geometries and loss
    patterns. Groups keep insertion order, so the caller's job order IS the
    block order.

    Failure semantics are GROUP-scoped: a survivor read that fails unlinks
    every partial output of that signature group's members and records the
    error per job; a CRC mismatch does so for its volume; other groups still
    complete. A failure of the run (dispatch, drain) fails every job.
    Returns
      {"rebuilt": {base: [shard ids]}, "errors": {base: str},
       "dispatch_groups": int, "signature_groups": int,
       "volumes_fused": int, "block_order": [base, ...]}."""
    groups: dict[tuple, list[dict]] = {}
    out: dict = {
        "rebuilt": {},
        "errors": {},
        "dispatch_groups": 0,
        "signature_groups": 0,
        "volumes_fused": 0,
        "block_order": [],
    }
    for job in jobs:
        enc = job.get("encoder") or encoder or encoder_for_base(job["base"])
        present = sorted(job["sources"])
        missing = job.get("missing")
        if missing is None:  # an explicit [] means "nothing to rebuild",
            # NOT "compute it" — a healed volume must come back rebuilt=[]
            missing = [s for s in range(enc.total_shards) if s not in job["sources"]]
        missing = sorted(missing)
        if not missing:
            out["rebuilt"][job["base"]] = []
            continue
        if len(present) < enc.data_shards:
            out["errors"][job["base"]] = (
                f"only {len(present)} shards present, need {enc.data_shards}"
            )
            continue
        survivors = tuple(present[: enc.data_shards])
        sig = (
            survivors,
            tuple(missing),
            enc.data_shards,
            enc.total_shards,
            getattr(enc, "matrix_kind", ""),
        )
        groups.setdefault(sig, []).append(
            {**job, "encoder": enc, "missing": missing, "survivors": survivors}
        )
    flat = [(gi, job) for gi, members in enumerate(groups.values()) for job in members]
    out["signature_groups"] = len(groups)
    out["block_order"] = [job["base"] for _, job in flat]
    out["volumes_fused"] = len(flat)
    if not flat:
        return out
    out["dispatch_groups"] = 1
    max_k = max(job["encoder"].data_shards for _, job in flat)
    align = max(int(getattr(job["encoder"], "width_align", 1) or 1) for _, job in flat)
    span = _aligned(max(1, max_batch_bytes // (max_k * buffer_size)) * buffer_size, align)
    # where a new signature's block may start: the codec's tile grid, so that
    # a batch of several blocks is one device program whose shape does not
    # depend on where a volume's tail fell (1 on host backends: packed tight)
    grid = max(job["encoder"].block_tile(span) for _, job in flat)
    # width-packed, group-major: a group's columns of a batch are one segment
    batches: list[_Batch] = []
    segs: list[_Seg] = []
    room = span
    output = 0  # of this job's first missing shard
    for gi, job in flat:
        srcs, survivors, missing = job["sources"], job["survivors"], job["missing"]
        size = int(job["shard_size"])
        off = 0
        while off < size:
            if segs and segs[-1].group != gi:
                room -= -(span - room) % grid  # the columns skipped are never read
                if room == 0:
                    batches.append(_Batch(span, span, span, segs))
                    segs, room = [], span
            take = min(room, size - off)
            col = span - room
            if not segs or segs[-1].group != gi:
                block = {
                    "encoder": job["encoder"],
                    "survivors": survivors,
                    "wanted": missing,
                    "col_start": col,
                    "width": 0,
                }
                segs.append(_Seg(gi, [], [], block))
            seg = segs[-1]
            seg.fills.extend(
                (srcs[s], off, take, i, col, col + take) for i, s in enumerate(survivors)
            )
            seg.puts.extend((k, col, col + take, output + k) for k in range(len(missing)))
            seg.block["width"] += take
            off += take
            room -= take
            if room == 0:
                batches.append(_Batch(span, span, span, segs))
                segs, room = [], span
        output += len(missing)
    if segs:
        used = span - room
        # the tail dispatches a whole number of buffers, as a volume's does
        # (`_windows`): the widths of a run's programs repeat in the next run
        width = min(span, _aligned(used, math.lcm(buffer_size, align, grid)))
        batches.append(_Batch(width, used, used, segs))
    plan = _Plan(
        (max_k, span),
        align,
        batches,
        [(job["base"], gi, job["missing"]) for gi, job in flat],
        _decode_blocks,
    )
    try:
        errors = _run_rebuild(plan, pipeline_depth, prefetch_batches)
    except BaseException as e:
        for _, job in flat:
            out["errors"][job["base"]] = f"{type(e).__name__}: {e}"[:300]
        if not isinstance(e, Exception):
            # KeyboardInterrupt/SystemExit: partials are cleaned, but the
            # interrupt must propagate, not be absorbed into error strings
            raise
        return out
    for mi, (_, job) in enumerate(flat):
        if mi in errors:
            out["errors"][job["base"]] = f"{type(errors[mi]).__name__}: {errors[mi]}"[:300]
        else:
            out["rebuilt"][job["base"]] = list(job["missing"])
    return out


def rebuild_ec_files(
    base_file_name: str,
    encoder: Optional[Encoder] = None,
    buffer_size: int = 4 * 1024 * 1024,
    max_batch_bytes: int = 64 * 1024 * 1024,
    pipeline_depth: Optional[int] = None,
) -> list[int]:
    """Reconstruct missing .ecNN files from >=10 survivors (RebuildEcFiles).

    The device-first repair path (`rebuild_ec_files_from_sources` over the
    local shard files): each batch is one flat
    (survivors, width) slab — one contiguous read per survivor straight
    into a reused staging ring (no chunk transpose, no per-batch host
    allocation) decoded by ONE fused survivors->missing matrix in ONE
    device dispatch, depth-N inflight like `_encode_rows`: up to
    `pipeline_depth` batches decode on-device while
    the next batch's slab reads run; drains are FIFO so rebuilt files
    receive bytes in order. The ten survivor reads of a batch run at once
    on the shard lanes (positional reads of the local files) and the
    calling thread waits for all ten before it dispatches; a drained
    batch's rebuilt rows are written and CRC'd on the lanes, each shard in
    batch order, beside the next batch's reads, and joined at the next
    drain or the end. Output is byte-identical to
    `rebuild_ec_files_serial` (zero-padding the tail slab is exact: GF
    matmul maps zero columns to zero columns, and the pad is trimmed
    before writing). Rebuilt shards' CRC32s are folded in as the bytes
    stream out and checked against the .eci-recorded values when present;
    a mid-stream failure (or CRC mismatch) drains inflight device work
    and unlinks the partial rebuilt files instead of leaking them.

    Returns the rebuilt shard ids."""
    enc = encoder or encoder_for_base(base_file_name)
    present, missing, shard_size = _check_rebuild_geometry(base_file_name, enc)
    if not missing:
        return []
    with ExitStack() as stack, trace_mod.ensure("rebuild.run", klass="maint"):
        sources = {
            s: stack.enter_context(LocalSlabSource(shard_file_name(base_file_name, s)))
            for s in present
        }
        return rebuild_ec_files_from_sources(
            base_file_name,
            sources,
            shard_size,
            encoder=enc,
            missing=missing,
            buffer_size=buffer_size,
            max_batch_bytes=max_batch_bytes,
            pipeline_depth=pipeline_depth,
        )


def _verify_rebuilt_crcs(base_file_name: str, crcs: dict) -> None:
    """Integrity gate on the rebuild output: when the volume's .eci recorded
    per-shard CRC32s at encode time, a rebuilt shard whose streaming CRC
    disagrees means a silently-corrupt survivor (or a decode bug) produced
    garbage — fail the rebuild rather than ship a wrong shard."""
    info = read_ec_info(base_file_name)
    recorded = (info or {}).get("shard_crc32")
    want_len = geometry_from_info(info).total_shards
    if not isinstance(recorded, list) or len(recorded) != want_len:
        return
    bad = {s: (c, recorded[s]) for s, c in crcs.items() if c != recorded[s]}
    if bad:
        raise IOError(
            f"rebuilt shard CRC mismatch vs .eci record: "
            f"{{shard: (got, want)}} = {bad} — corrupt survivor?"
        )


def rebuild_ec_files_serial(
    base_file_name: str,
    encoder: Optional[Encoder] = None,
    buffer_size: int = 4 * 1024 * 1024,
) -> list[int]:
    """The pre-pipeline serial rebuild: one blocking reconstruct per chunk.
    Kept as the correctness oracle (bench golden path + byte-identity
    tests) and the shape the AVX2-baseline comparison is defined against."""
    enc = encoder or encoder_for_base(base_file_name)
    present, missing, shard_size = _check_rebuild_geometry(base_file_name, enc)
    if not missing:
        return []
    with ExitStack() as stack:
        ins = {
            s: stack.enter_context(open(shard_file_name(base_file_name, s), "rb"))
            for s in present
        }
        outs = {
            s: stack.enter_context(open(shard_file_name(base_file_name, s), "wb"))
            for s in missing
        }
        for off in range(0, shard_size, buffer_size):
            n = min(buffer_size, shard_size - off)
            shards: list[Optional[np.ndarray]] = [None] * enc.total_shards
            for s in present:
                shards[s] = read_padded(ins[s], off, n)
            rec = enc.reconstruct(shards, wanted=missing)
            for s in missing:
                outs[s].write(np.ascontiguousarray(rec[s]))  # buffer-protocol write
    return missing


def write_dat_file(
    base_file_name: str,
    dat_file_size: Optional[int] = None,
    large_block_size: int = ERASURE_CODING_LARGE_BLOCK_SIZE,
    small_block_size: int = ERASURE_CODING_SMALL_BLOCK_SIZE,
) -> None:
    """Data shards -> <base>.dat (WriteDatFile / ec.decode semantics).

    Recorded .eci geometry (block sizes AND shard counts) overrides the
    arguments — decoding with the wrong layout would interleave garbage
    silently."""
    info = read_ec_info(base_file_name)
    if info is not None:
        large_block_size = info["large_block_size"]
        small_block_size = info["small_block_size"]
        if dat_file_size is None:
            dat_file_size = info["dat_size"]
    if dat_file_size is None:
        raise ValueError("dat_file_size required when no .eci sidecar exists")
    data_shards = geometry_from_info(info).data_shards
    n_large, _ = stripe_layout(
        dat_file_size, large_block_size, small_block_size, data_shards
    )

    # stage under a dot-tmp name: serving paths discover <base>.dat by
    # existence, so a crash mid-decode must never leave a torn .dat there
    tmp_dat = base_file_name + ".dat.tmp"
    with ExitStack() as stack:
        # no-op after the publishing replace; reaps the stage on any failure
        stack.callback(
            lambda: os.path.exists(tmp_dat) and os.remove(tmp_dat)
        )
        ins = [
            stack.enter_context(open(shard_file_name(base_file_name, s), "rb"))
            for s in range(data_shards)
        ]
        out = stack.enter_context(open(tmp_dat, "wb"))
        written = 0
        # large rows
        for row in range(n_large):
            for d in range(data_shards):
                ins[d].seek(row * large_block_size)
                out.write(ins[d].read(large_block_size))
                written += large_block_size
        # small rows
        small_start = n_large * large_block_size
        row = 0
        while written < dat_file_size:
            row_progress = 0
            for d in range(data_shards):
                if written >= dat_file_size:
                    break
                ins[d].seek(small_start + row * small_block_size)
                chunk = ins[d].read(small_block_size)
                take = min(len(chunk), dat_file_size - written)
                out.write(chunk[:take])
                written += take
                row_progress += take
            if row_progress == 0:
                raise IOError(
                    f"shards exhausted at {written} bytes but dat_file_size says "
                    f"{dat_file_size} — truncated shards or stale size"
                )
            row += 1
        out.flush()
        os.fsync(out.fileno())
        os.replace(tmp_dat, base_file_name + ".dat")


def write_idx_file_from_ec_index(base_file_name: str) -> None:
    """<base>.ecx + <base>.ecj -> <base>.idx (WriteIdxFileFromEcIndex):
    copy sorted entries, then append a tombstone per journaled deletion.
    Entries already tombstoned in .ecx (by compact_ecj) are normalized to
    the same (key, 0, -1) shape a journal replay would have appended."""
    with open(base_file_name + ".ecx", "rb") as f:
        ecx = f.read()
    entries = list(idx_mod.walk_index_buffer(ecx))
    deleted = read_ecj(base_file_name)
    tmp_idx = base_file_name + ".idx.tmp"
    with open(tmp_idx, "wb") as out:
        for key, off, size in entries:
            if types.is_deleted(size):
                out.write(types.pack_index_entry(key, 0, types.TOMBSTONE_FILE_SIZE))
            else:
                out.write(types.pack_index_entry(key, off, size))
        for key in deleted:
            out.write(types.pack_index_entry(key, 0, types.TOMBSTONE_FILE_SIZE))
        out.flush()
        os.fsync(out.fileno())
    os.replace(tmp_idx, base_file_name + ".idx")


# -- .ecj deletion journal ---------------------------------------------------


def append_ecj(base_file_name: str, needle_id: int) -> None:
    """Journal one EC deletion, fsync'd: an acked EC delete must survive a
    power cut (the .ecj is the ONLY record of it until compact_ecj folds
    the journal — same flush+fsync discipline kernel_sweep's --out uses).
    A crash mid-append can still leave a torn tail record; read_ecj
    ignores it, so the worst a torn append costs is the un-acked delete."""
    with open(base_file_name + ".ecj", "ab") as f:
        f.write(needle_id.to_bytes(types.NEEDLE_ID_SIZE, "big"))
        f.flush()
        os.fsync(f.fileno())


def read_ecj(base_file_name: str) -> list[int]:
    path = base_file_name + ".ecj"
    if not os.path.exists(path):
        return []
    with open(path, "rb") as f:
        buf = f.read()
    # // drops a torn tail record (crash mid-append): every COMPLETE entry
    # replays, the partial one is noise, never a mis-parsed needle id
    n = len(buf) // types.NEEDLE_ID_SIZE
    return [
        int.from_bytes(buf[i * 8 : i * 8 + 8], "big") for i in range(n)
    ]


def compact_ecj(base_file_name: str) -> int:
    """Fold the deletion journal into the index (the reference compacts the
    .ecj on mount so a delete-heavy EC volume's journal doesn't grow without
    bound [ref: weed/storage/erasure_coding ecj replay/compact; SURVEY §5]):
    tombstone every journaled id in .ecx, then drop .ecj.

    Crash-safe ordering: write .ecx.cpt -> fsync -> rename over .ecx ->
    unlink .ecj. A crash before the rename leaves both files untouched; a
    crash after it leaves a stale .ecj whose replay only re-tombstones
    already-dead entries — idempotent either way. Returns the number of
    journal entries folded."""
    deleted = set(read_ecj(base_file_name))
    if not deleted:
        return 0
    ecx = base_file_name + ".ecx"
    with open(ecx, "rb") as f:
        buf = f.read()
    tmp = ecx + ".cpt"
    with open(tmp, "wb") as out:
        for key, off, size in idx_mod.walk_index_buffer(buf):
            if key in deleted and not types.is_deleted(size):
                size = types.TOMBSTONE_FILE_SIZE
            out.write(types.pack_index_entry(key, off, size))
        out.flush()
        os.fsync(out.fileno())
    os.replace(tmp, ecx)
    try:
        os.remove(base_file_name + ".ecj")
    except FileNotFoundError:
        pass
    return len(deleted)
