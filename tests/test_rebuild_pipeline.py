"""Pipelined batched rebuild tests (the repair-path mirror of the encode
pipeline): `rebuild_ec_files` must stay byte-identical to the serial golden
path across geometries, every loss-pattern count (data/parity/mixed), and
non-multiple tail chunks — while issuing ONE device dispatch per batch.
`Encoder.reconstruct_batch`/`reconstruct_lazy` must match the per-call
`reconstruct` oracle, and `EcVolume.read_intervals`' batched degraded
recovery must match per-interval recovery. The last section drives the one
pipelined loop (`stripe._run_rebuild`) through every entry point that plans it."""

import os
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu import stats
from seaweedfs_tpu.ec import stripe
from seaweedfs_tpu.ec.constants import TOTAL_SHARDS_COUNT
from seaweedfs_tpu.obs import trace
from seaweedfs_tpu.ops.rs_codec import Encoder

ENC = Encoder(10, 4, backend="numpy")

# 1-4 missing shards: data-only, parity-only, and mixed patterns
LOSS_PATTERNS = [
    [2],
    [12],
    [0, 9],
    [11, 13],
    [3, 12],
    [0, 1, 2],
    [1, 10, 13],
    [0, 1, 2, 3],
    [10, 11, 12, 13],
    [0, 5, 11, 13],
]


def _make_volume(tmp_path, size, large=16384, small=4096, seed=1):
    base = os.path.join(str(tmp_path), "v")
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    stripe.write_ec_files(
        base, large_block_size=large, small_block_size=small, encoder=ENC
    )
    golden = {}
    for s in range(TOTAL_SHARDS_COUNT):
        with open(stripe.shard_file_name(base, s), "rb") as f:
            golden[s] = f.read()
    return base, golden


def _check_rebuild(base, golden, lost, enc, **kw):
    for s in lost:
        os.unlink(stripe.shard_file_name(base, s))
    rebuilt = stripe.rebuild_ec_files(base, encoder=enc, **kw)
    assert rebuilt == sorted(lost)
    for s in range(TOTAL_SHARDS_COUNT):
        with open(stripe.shard_file_name(base, s), "rb") as f:
            assert f.read() == golden[s], f"shard {s} differs after losing {lost}"


@pytest.mark.parametrize("lost", LOSS_PATTERNS)
def test_batched_rebuild_matches_serial_golden(tmp_path, lost):
    """Every loss-pattern count, against shards produced (and re-derivable)
    by the serial path — the pre-change byte-identity contract."""
    base, golden = _make_volume(tmp_path, size=655_360)
    _check_rebuild(base, golden, lost, ENC, buffer_size=8192, max_batch_bytes=10 * 3 * 8192)
    # and the serial oracle itself reproduces the same bytes
    for s in lost:
        os.unlink(stripe.shard_file_name(base, s))
    assert stripe.rebuild_ec_files_serial(base, encoder=ENC, buffer_size=8192) == sorted(lost)
    for s in lost:
        with open(stripe.shard_file_name(base, s), "rb") as f:
            assert f.read() == golden[s]


@pytest.mark.parametrize(
    "size",
    [
        1,  # tiny: single zero-padded small row
        123_457,  # prime-ish: small-row tail, shard not a buffer multiple
        163_840 * 10 + 7,  # just past one large row
    ],
)
def test_batched_rebuild_tail_geometries(tmp_path, size):
    """Non-multiple tails: the zero-padded tail chunk must trim back to the
    exact shard length (large/small two-tier geometry included)."""
    base, golden = _make_volume(tmp_path, size=size)
    _check_rebuild(
        base, golden, [0, 5, 11, 13], ENC, buffer_size=8192, max_batch_bytes=10 * 4 * 8192
    )


@pytest.mark.parametrize("backend", ["jax"])
def test_batched_rebuild_device_backend_matches(tmp_path, backend):
    base, golden = _make_volume(tmp_path, size=200_000)
    enc = Encoder(10, 4, backend=backend)
    _check_rebuild(base, golden, [1, 6, 12], enc, buffer_size=8192)


def test_rebuild_one_dispatch_per_batch(tmp_path):
    """The acceptance criterion: dispatches scale with batches (ceil of
    chunks / batch-cap), never with chunks — now as flat (survivors, width)
    slabs, one wide matmul per batch."""
    base, golden = _make_volume(tmp_path, size=655_360)  # shard = 65536 B
    calls = []
    orig = Encoder.reconstruct_lazy

    class Counting(Encoder):
        def reconstruct_lazy(self, stack, survivors, wanted, **kw):
            calls.append(stack.shape)
            return orig(self, stack, survivors, wanted, **kw)

    enc = Counting(10, 4, backend="numpy")
    # 8 chunks of 8 KiB per shard; cap = 3 chunks/batch -> 3 dispatches
    _check_rebuild(
        base, golden, [0, 13], enc, buffer_size=8192, max_batch_bytes=3 * 10 * 8192
    )
    assert len(calls) == 3, f"want 3 batch dispatches for 8 chunks, got {calls}"
    assert [c for c in calls] == [(10, 3 * 8192), (10, 3 * 8192), (10, 2 * 8192)]


def test_rebuild_too_few_survivors_raises(tmp_path):
    base, _ = _make_volume(tmp_path, size=65_536)
    for s in range(5):
        os.unlink(stripe.shard_file_name(base, s))
    with pytest.raises(ValueError, match="cannot rebuild"):
        stripe.rebuild_ec_files(base, encoder=ENC)


def test_rebuild_truncated_survivor_raises(tmp_path):
    base, _ = _make_volume(tmp_path, size=65_536)
    os.unlink(stripe.shard_file_name(base, 3))
    p = stripe.shard_file_name(base, 7)
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    with pytest.raises(IOError, match="disagree"):
        stripe.rebuild_ec_files(base, encoder=ENC)


# -- codec-level batched reconstruct -----------------------------------------


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("lost", [[0], [13], [0, 5, 11, 13]])
def test_reconstruct_batch_matches_oracle(backend, lost):
    rng = np.random.default_rng(3)
    full = ENC.encode([rng.integers(0, 256, 777, dtype=np.uint8) for _ in range(10)])
    survivors = [i for i in range(14) if i not in lost][:10]
    stack = np.stack([[full[s] for s in survivors] for _ in range(4)])
    enc = Encoder(10, 4, backend=backend)
    out = enc.reconstruct_batch(stack, survivors, lost)
    assert out.shape == (4, len(lost), 777)
    for b in range(4):
        for k, w in enumerate(lost):
            np.testing.assert_array_equal(out[b, k], full[w], err_msg=f"shard {w}")
    # the lazy form materializes to the same bytes
    np.testing.assert_array_equal(
        np.asarray(enc.reconstruct_lazy(stack, survivors, lost)), out
    )
    # bucketed form (pads to the serving buckets on device backends)
    np.testing.assert_array_equal(
        enc.reconstruct_batch(stack, survivors, lost, bucketed=True), out
    )


def test_reconstruct_batch_validates():
    stack = np.zeros((2, 10, 16), dtype=np.uint8)
    with pytest.raises(ValueError, match="distinct"):
        ENC.reconstruct_batch(stack, [0] * 10, [13])
    with pytest.raises(ValueError, match="at least one"):
        ENC.reconstruct_batch(stack, list(range(10)), [])
    with pytest.raises(ValueError, match="out of range"):
        ENC.reconstruct_batch(stack, list(range(10)), [14])
    with pytest.raises(ValueError, match="want"):
        ENC.reconstruct_batch(np.zeros((10, 16), np.uint8), list(range(10)), [13])


# -- EcVolume batched degraded-interval recovery ------------------------------


def test_read_intervals_batched_recovery_matches_per_interval(tmp_path):
    """A degraded volume's read_intervals (batched) must return exactly the
    bytes the per-interval recover ladder returns, and fuse the recovery of
    same-shard intervals into ONE reconstruct_batch call."""
    from seaweedfs_tpu.ec.ec_volume import EcVolume
    from seaweedfs_tpu.storage import idx as idx_mod
    from seaweedfs_tpu.storage import types

    large, small = 1024, 64
    rng = np.random.default_rng(17)
    base = str(tmp_path / "vol")
    records = {}
    offset = types.NEEDLE_PADDING_SIZE
    blobs = [b"\x03" + bytes(7)]
    for nid in range(1, 40):
        # big enough that many records span a full small row (10 x 64 B),
        # so one needle's intervals revisit the same (possibly missing)
        # shard — the case the batched recovery fuses
        body = int(rng.integers(100, 1800))
        total = types.actual_size(body, version=3)
        rec = rng.integers(0, 256, size=total, dtype=np.uint8).tobytes()
        records[nid] = (offset, body, rec)
        blobs.append(rec)
        offset += total
    with open(base + ".dat", "wb") as f:
        f.write(b"".join(blobs))
    idx_mod.write_entries(
        [(nid, types.offset_to_bytes(off), sz) for nid, (off, sz, _) in records.items()],
        base + ".idx",
    )
    stripe.write_ec_files(
        base, large_block_size=large, small_block_size=small, buffer_size=64, encoder=ENC
    )
    stripe.write_sorted_file_from_idx(base)
    for s in (0, 4, 11):
        os.remove(stripe.shard_file_name(base, s))

    batch_calls = []
    orig_batch = Encoder.reconstruct_batch

    class Counting(Encoder):
        def reconstruct_batch(self, stack, survivors, wanted, bucketed=False):
            batch_calls.append(stack.shape[0])
            return orig_batch(self, stack, survivors, wanted, bucketed)

    enc = Counting(10, 4, backend="numpy")
    with EcVolume(
        base, encoder=enc, large_block_size=large, small_block_size=small,
        warm_on_mount=False,
    ) as ev:
        multi = 0
        for nid, (off, sz, rec) in records.items():
            _, _, intervals = ev.locate_needle(nid)
            got = ev.read_intervals(intervals)
            assert got[: len(rec)] == rec, f"needle {nid}"
            # oracle: the per-interval single-recover ladder
            per = b"".join(
                ev._read_shard_interval(
                    *iv.to_shard_id_and_offset(large, small), iv.size
                ).tobytes()
                for iv in intervals
            )
            assert got == per, f"needle {nid}: batched != per-interval"
            on_missing = [
                iv.to_shard_id_and_offset(large, small)[0]
                for iv in intervals
                if iv.to_shard_id_and_offset(large, small)[0] in (0, 4, 11)
            ]
            if len(on_missing) > len(set(on_missing)):
                multi += 1  # >=2 intervals miss the SAME shard
        assert multi > 0, "fixture must exercise multi-interval degraded reads"
    assert any(b > 1 for b in batch_calls), (
        f"no multi-interval recovery was batched: {batch_calls}"
    )


# -- the one pipelined loop through each of its planners -----------------------
#
# `stripe._run_rebuild` as the local rebuild, survivor sources with two the
# lanes may not read, holder projections, a batch of one signature and a batch
# of mixed signatures and geometries plan it. Each must leave the bytes
# `rebuild_ec_files_serial` leaves, with shard lanes and inline, the last batch
# shorter than a buffer; each must record the whole set of stage spans under its
# run span; and a failure at a read, at a dispatch, at a lane's write and at the
# CRC check must come out as itself, with no partial file and no lane task left.

LARGE, SMALL = 16384, 4096
BUFFER = 8192
#: one buffer a batch, for 20+4 too; a 250,000-byte .dat of 10+4 gives shards
#: of 28,672 bytes: three whole batches and a tail of half a buffer
TUNING = dict(buffer_size=BUFFER, max_batch_bytes=10 * BUFFER)
E10 = ENC
E12 = Encoder(12, 3, backend="numpy", matrix_kind="cauchy")
E20 = Encoder(20, 4, backend="numpy", matrix_kind="cauchy")
ENTRY_POINTS = ["local", "sources", "projections", "batch_one_signature", "batch_mixed"]
#: of eight cores: seven, or one per source and output where those are fewer
LANES = {"projections": 2 + 4}


class _Boom(Exception):
    pass


class _Plain(stripe.SlabSource):
    """A survivor that says nothing of lanes: read on the pipeline's own thread."""

    def __init__(self, path):
        self._inner = stripe.LocalSlabSource(path)

    def read_into(self, offset, out):
        self._inner.read_into(offset, out)

    def close(self):
        self._inner.close()


class _LanesSeen(stripe._ShardLanes):
    made: list = []

    def __init__(self, shards):
        super().__init__(shards)
        self.made.append(self)


def _volume(tmp_path, vid, size, enc, missing):
    base = os.path.join(str(tmp_path), str(vid))
    rng = np.random.default_rng(vid)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    with open(base + ".idx", "wb"):
        pass
    stripe.write_ec_files(base, large_block_size=LARGE, small_block_size=SMALL, encoder=enc)
    stripe.write_sorted_file_from_idx(base)
    os.unlink(base + ".dat")
    for s in missing:
        os.unlink(stripe.shard_file_name(base, s))
    return base, enc, missing


def _survivors(base, enc, missing):
    return [s for s in range(enc.total_shards) if s not in missing]


def _scenario(entry, tmp_path, longer=1):
    """-> (volumes [(base, encoder, missing)], run): `run()` rebuilds them all
    through `entry` and returns the batch's result (None for one volume).
    `longer` = 2 makes every volume twice as long."""
    if entry == "batch_one_signature":
        volumes = [
            _volume(tmp_path, v, longer * n, E10, [3, 12]) for v, n in ((1, 250_000), (2, 90_001))
        ]
    elif entry == "batch_mixed":
        volumes = [
            _volume(tmp_path, 1, longer * 250_000, E10, [12, 13]),
            _volume(tmp_path, 2, longer * 90_001, E10, [3]),
            _volume(tmp_path, 3, longer * 120_003, E12, [0, 12]),
            _volume(tmp_path, 4, longer * 77_777, E20, [20, 23]),
        ]
    else:
        volumes = [_volume(tmp_path, 1, longer * 250_000, E10, [0, 3, 11, 13])]
    base, enc, missing = volumes[0]
    size = os.path.getsize(stripe.shard_file_name(base, _survivors(*volumes[0])[0]))
    assert size % BUFFER == BUFFER // 2  # the tail batch is not a whole buffer

    def local_sources(b, e, m, plain=()):
        return {
            s: (_Plain if s in plain else stripe.LocalSlabSource)(stripe.shard_file_name(b, s))
            for s in _survivors(b, e, m)
        }

    def run():
        if entry == "local":
            stripe.rebuild_ec_files(base, encoder=enc, **TUNING)
            return None
        if entry == "sources":
            sources = local_sources(base, enc, missing, plain=(2, 7))
            try:
                stripe.rebuild_ec_files_from_sources(
                    base, sources, size, encoder=enc, missing=missing, **TUNING
                )
            finally:
                for src in sources.values():
                    src.close()
            return None
        if entry == "projections":
            chosen = _survivors(base, enc, missing)[: enc.data_shards]
            coeffs = enc.repair_projection_plan(chosen, missing)
            groups = [
                stripe.LocalProjectionSource(
                    [stripe.shard_file_name(base, s) for s in part],
                    np.stack([coeffs[s] for s in part], axis=1),
                    enc,
                )
                for part in (chosen[:4], chosen[4:])
            ]
            try:
                stripe.rebuild_ec_files_from_projections(
                    base, groups, size, missing, encoder=enc, **TUNING
                )
            finally:
                for g in groups:
                    g.close()
            return None
        jobs = [
            {
                "base": b,
                "sources": local_sources(b, e, m),
                "shard_size": os.path.getsize(stripe.shard_file_name(b, _survivors(b, e, m)[0])),
                "missing": m,
                "encoder": e,
            }
            for b, e, m in volumes
        ]
        try:
            return stripe.rebuild_ec_files_batch(jobs, **TUNING)
        finally:
            for job in jobs:
                for src in job["sources"].values():
                    src.close()

    return volumes, run


def _rebuilt_bytes(volumes):
    out = {}
    for base, _, missing in volumes:
        for s in missing:
            with open(stripe.shard_file_name(base, s), "rb") as f:
                out[base, s] = f.read()
    return out


@pytest.mark.parametrize("cores", [8, 1], ids=["lanes", "inline"])
@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_every_entry_point_leaves_the_serial_rebuilds_bytes(tmp_path, monkeypatch, entry, cores):
    monkeypatch.setattr(os, "cpu_count", lambda: cores)
    monkeypatch.setattr(stripe, "_ShardLanes", _LanesSeen)
    volumes, run = _scenario(entry, tmp_path)
    _LanesSeen.made = []  # the encodes of the set-up made theirs
    res = run()
    if res is not None:
        assert not res["errors"], res["errors"]
        assert res["dispatch_groups"] == 1
        assert res["signature_groups"] == (1 if entry == "batch_one_signature" else len(volumes))
        assert res["rebuilt"] == {b: m for b, _, m in volumes}
    (lanes,) = _LanesSeen.made
    assert lanes.n == (LANES.get(entry, 7) if cores == 8 else 0)
    got = _rebuilt_bytes(volumes)
    for base, enc, missing in volumes:
        for s in missing:
            os.unlink(stripe.shard_file_name(base, s))
        assert stripe.rebuild_ec_files_serial(base, encoder=enc, buffer_size=BUFFER) == missing
    assert got == _rebuilt_bytes(volumes)


@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_every_entry_point_records_the_stage_spans_under_one_run(tmp_path, monkeypatch, entry):
    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    volumes, run = _scenario(entry, tmp_path)
    trace.RING.clear()
    with trace.ensure("rebuild.run", klass="maint"):
        run()
    (t,) = trace.RING.snapshot(kind="rebuild.run")
    root = t["root"]
    spans = [s for s in trace.iter_spans(t) if s is not root]
    count = {n: sum(1 for s in spans if s["name"] == n) for n in {s["name"] for s in spans}}
    batches = root["attrs"]["batches"]
    outputs = sum(len(m) for _, _, m in volumes)
    assert batches >= 4 and root["attrs"]["lanes"] == LANES.get(entry, 7)
    assert root["attrs"]["bytes"] == sum(len(b) for b in _rebuilt_bytes(volumes).values())
    for name in ("stage", "dispatch", "drain", "sync"):
        assert count[f"rebuild.{name}"] == batches, (name, count)
    assert count["rebuild.wait"] == 2 * batches + 1  # a stage's reads, a drain's join, the end
    assert count["rebuild.verify"] == len(volumes)
    assert count["rebuild.read"] >= 2 * batches
    assert count["rebuild.write"] == count["rebuild.crc"] >= outputs
    assert set(count) <= set(trace.SPAN_NAMES)

    def bytes_of(name):
        return sum(s["attrs"]["bytes"] for s in spans if s["name"] == name)

    assert bytes_of("rebuild.write") == bytes_of("rebuild.crc") == root["attrs"]["bytes"]
    assert bytes_of("rebuild.sync") == root["attrs"]["bytes"]


class _Fails:
    """Stands in for a callable: passes the first `ok` calls through, then raises."""

    def __init__(self, real, ok):
        self._real, self._left = real, ok

    def __call__(self, *a, **kw):
        self._left -= 1
        if self._left < 0:
            raise _Boom("injected")
        return self._real(*a, **kw)


class _FailingWrites:
    def __init__(self, f):
        self._f, self.write = f, _Fails(f.write, 1)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._f.close()


@pytest.mark.parametrize("cores", [8, 1], ids=["lanes", "inline"])
@pytest.mark.parametrize("where", ["read", "dispatch", "write", "verify"])
@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_a_failure_comes_out_as_itself_and_leaves_nothing(tmp_path, monkeypatch, entry, where, cores):
    monkeypatch.setattr(os, "cpu_count", lambda: cores)
    monkeypatch.setattr(stripe, "_ShardLanes", _LanesSeen)
    volumes, run = _scenario(entry, tmp_path)
    _LanesSeen.made = []  # the encodes of the set-up made theirs
    if where == "read":  # every survivor read after the first batch's: every group fails
        for cls in (stripe.LocalSlabSource, stripe.LocalProjectionSource):
            fails = _Fails(cls.read_into, 3 if entry == "projections" else 12)
            monkeypatch.setattr(cls, "read_into", lambda self, off, out, _f=fails: _f(self, off, out))
    elif where == "dispatch":  # the second of its kind
        for name in ("reconstruct_lazy", "reconstruct_block", "project_lazy"):
            fails = _Fails(getattr(Encoder, name), 1)
            monkeypatch.setattr(Encoder, name, lambda self, *a, _f=fails, **kw: _f(self, *a, **kw))
    elif where == "write":  # the first output file's second batch
        victim = stripe.shard_file_name(volumes[0][0], volumes[0][2][0])
        real_open = open

        def failing_open(path, mode="r", *a, **kw):
            f = real_open(path, mode, *a, **kw)
            return _FailingWrites(f) if (path == victim and "w" in mode) else f

        monkeypatch.setattr(stripe, "open", failing_open, raising=False)
    else:
        monkeypatch.setattr(stripe, "_verify_rebuilt_crcs", _Fails(None, 0))
    if entry.startswith("batch"):
        res = run()
        assert res["rebuilt"] == {}
        assert set(res["errors"]) == {b for b, _, _ in volumes}
        assert all(e.startswith("_Boom: injected") for e in res["errors"].values()), res
    else:
        with pytest.raises(_Boom, match="injected"):
            run()
    for base, enc, missing in volumes:
        for s in range(enc.total_shards):
            assert os.path.exists(stripe.shard_file_name(base, s)) == (s not in missing), (base, s)
    (lanes,) = _LanesSeen.made
    assert lanes._open == 0 and not lanes._queues


# -- staging runs ahead of the drain, into a ring the process keeps -------------
#
# Every entry point again: a batch's lane reads start before the drain ahead of
# it returns and its calling-thread reads after; a shorter cohort through the
# buffers a longer one left is byte-exact; a failed run gives its ring back
# only when no lane and no dispatch can touch it, and the next run through
# those buffers is byte-exact.

LAZY = ("reconstruct_lazy", "reconstruct_block", "project_lazy")


def _wrap_lazy(monkeypatch, wrap):
    """Every decode dispatch's handle, of every encoder, through `wrap`."""
    for name in LAZY:
        real = getattr(Encoder, name)
        monkeypatch.setattr(
            Encoder, name, lambda self, *a, _real=real, **kw: wrap(np.asarray(_real(self, *a, **kw)))
        )


def _poison_pool():
    for buf in stripe._pool_free:
        buf[: 1 << 20] = 0xA5  # more than any slot here, and no run of a volume's bytes


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_a_batchs_lane_reads_start_before_the_drain_ahead_of_it_returns(tmp_path, monkeypatch, entry, depth):
    """The device held at the first drain (batch 0's, made while batch `depth`
    is staged): every lane read of batches 0..depth has started by then and
    none further ahead; the calling thread stands in the drain, so its own
    reads of batch `depth` (sources that do not say `lane_reads`, projection
    groups) have not. Released, the run leaves the serial rebuild's bytes."""
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    volumes, run = _scenario(entry, tmp_path)
    syncing, release = threading.Event(), threading.Event()

    class Held:
        def __init__(self, value):
            self._value = value

        def __array__(self, *a, **kw):
            syncing.set()
            assert release.wait(20), "the test never released the device"
            return self._value

    _wrap_lazy(monkeypatch, Held)
    seen = {"lane": 0, "own": 0}
    for cls in (stripe.LocalSlabSource, stripe.LocalProjectionSource):

        def read_into(self, off, out, _real=cls.read_into):
            seen["lane" if threading.current_thread().name.startswith("ec-lane") else "own"] += 1
            _real(self, off, out)

        monkeypatch.setattr(cls, "read_into", read_into)
    plans = []
    real_run = stripe._run_rebuild
    monkeypatch.setattr(
        stripe, "_run_rebuild", lambda plan, _d, ahead: plans.append(plan) or real_run(plan, depth, ahead)
    )
    results = []
    worker = threading.Thread(target=lambda: results.append(run()))
    worker.start()
    try:
        assert syncing.wait(20), "no drain began"
        (plan,) = plans
        assert len(plan.batches) > depth + 1

        def fills(upto, lane):
            return sum(
                1 for b in plan.batches[:upto] for seg in b.segs for f in seg.fills
                if bool(f[0].lane_reads) == lane
            )

        assert fills(depth + 1, True) > fills(depth, True) or entry == "projections"
        deadline = time.monotonic() + 10
        while seen["lane"] < fills(depth + 1, True) and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.2)
        assert seen == {"lane": fills(depth + 1, True), "own": fills(depth, False)}
    finally:
        release.set()
        worker.join(30)
    assert results and (results[0] is None or not results[0]["errors"])
    got = _rebuilt_bytes(volumes)
    for base, enc, missing in volumes:
        for s in missing:
            os.unlink(stripe.shard_file_name(base, s))
        assert stripe.rebuild_ec_files_serial(base, encoder=enc, buffer_size=BUFFER) == missing
    assert got == _rebuilt_bytes(volumes)


@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_a_shorter_cohort_through_a_kept_ring_leaves_the_serial_rebuilds_bytes(tmp_path, monkeypatch, entry):
    """A cohort twice as long first, then the scenario's through the buffers it
    left (overwritten with 0xA5 in between, so a stale column would show): the
    second run allocates nothing and its bytes are those of a run with a fresh
    ring and of the serial oracle."""
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    (tmp_path / "longer").mkdir()
    (tmp_path / "kept").mkdir()
    _, run_longer = _scenario(entry, tmp_path / "longer", longer=2)
    volumes, run = _scenario(entry, tmp_path / "kept")
    monkeypatch.setattr(stripe, "_pool_free", [])
    counts = {o: stats.StagingRingLeases.labels(o) for o in ("allocated", "reused")}
    before = {o: c.value for o, c in counts.items()}
    run_longer()
    _poison_pool()
    run()
    assert {o: c.value - before[o] for o, c in counts.items()} == {"allocated": 1, "reused": 1}
    kept = _rebuilt_bytes(volumes)
    for attempt in ("fresh", "serial"):
        for base, enc, missing in volumes:
            for s in missing:
                os.unlink(stripe.shard_file_name(base, s))
            if attempt == "serial":
                assert stripe.rebuild_ec_files_serial(base, encoder=enc, buffer_size=BUFFER) == missing
        if attempt == "fresh":
            monkeypatch.setattr(stripe, "_pool_free", [])
            run()
        assert _rebuilt_bytes(volumes) == kept, attempt


@pytest.mark.parametrize("where", ["read", "dispatch"])
@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_a_failed_run_gives_its_ring_back_when_nothing_can_touch_it(tmp_path, monkeypatch, entry, where):
    """A survivor read that raises (its groups fail, the run ends by itself)
    and a dispatch that raises (the run fails): the ring is given back once,
    with no lane task open and every dispatched handle synced or discarded;
    the next run through those buffers is the serial rebuild's bytes."""
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    volumes, run = _scenario(entry, tmp_path)
    monkeypatch.setattr(stripe, "_pool_free", [])
    monkeypatch.setattr(stripe, "_ShardLanes", _LanesSeen)
    _LanesSeen.made = []
    pending = set()

    class Tracked:
        def __init__(self, value):
            self._value = value
            pending.add(self)

        def __array__(self, *a, **kw):
            pending.discard(self)
            return self._value

    given_back = []
    real_give_back = stripe._StagingRing.give_back

    def give_back(ring):
        lanes = _LanesSeen.made[-1]
        given_back.append((lanes._open, len(lanes._queues), len(pending)))
        real_give_back(ring)

    monkeypatch.setattr(stripe._StagingRing, "give_back", give_back)
    with monkeypatch.context() as failing:
        _wrap_lazy(failing, Tracked)
        if where == "read":  # every survivor read after the first batch's: every group fails
            for cls in (stripe.LocalSlabSource, stripe.LocalProjectionSource):
                fails = _Fails(cls.read_into, 3 if entry == "projections" else 12)
                failing.setattr(cls, "read_into", lambda self, off, out, _f=fails: _f(self, off, out))
        else:  # the third of its kind, with two inflight
            for name in LAZY:
                fails = _Fails(getattr(Encoder, name), 2)
                failing.setattr(Encoder, name, lambda self, *a, _f=fails, **kw: _f(self, *a, **kw))
        if entry.startswith("batch"):
            assert set(run()["errors"]) == {b for b, _, _ in volumes}
        else:
            with pytest.raises(_Boom, match="injected"):
                run()
    assert given_back == [(0, 0, 0)]
    kept = {id(b) for b in stripe._pool_free}
    assert len(kept) == stripe.DEFAULT_PIPELINE_DEPTH + 1
    _poison_pool()
    res = run()
    assert res is None or not res["errors"]
    assert given_back == [(0, 0, 0)] * 2 and {id(b) for b in stripe._pool_free} == kept
    got = _rebuilt_bytes(volumes)
    for base, enc, missing in volumes:
        for s in missing:
            os.unlink(stripe.shard_file_name(base, s))
        assert stripe.rebuild_ec_files_serial(base, encoder=enc, buffer_size=BUFFER) == missing
    assert got == _rebuilt_bytes(volumes)
