"""Depth-N streaming pipeline tests (r6): tile-boundary and odd-size
byte-exactness against the numpy golden, depth-1 vs depth-N byte-identity,
fused per-shard CRC recording/verification, exception-safety (a mid-stream
failure must drain inflight device work and unlink partial shard files),
decode-matrix cache boundedness, and the kernel_sweep --smoke CI gate."""

import io
import json
import os
import subprocess
import sys
import threading
import time
import zlib

import numpy as np
import pytest

from seaweedfs_tpu import stats
from seaweedfs_tpu.ec import stripe
from seaweedfs_tpu.ec.constants import TOTAL_SHARDS_COUNT
from seaweedfs_tpu.ops import gf8
from seaweedfs_tpu.ops.rs_codec import (
    Encoder,
    clear_decode_matrix_cache,
    decode_matrix_cache_info,
)
from seaweedfs_tpu.ops.rs_pallas import DEFAULT_TILE

ENC = Encoder(10, 4, backend="numpy")

# sizes straddling DEFAULT_TILE multiples, plus degenerate tails
TILE_EDGE_SIZES = [
    1,
    127,
    DEFAULT_TILE - 1,
    DEFAULT_TILE,
    DEFAULT_TILE + 1,
    2 * DEFAULT_TILE + 17,
]


# -- kernel-level: odd sizes must match the numpy golden byte-for-byte --------


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("n", TILE_EDGE_SIZES)
def test_encode_batch_tile_edges_match_golden(backend, n):
    rng = np.random.default_rng(n)
    data = rng.integers(0, 256, size=(2, 10, n), dtype=np.uint8)
    enc = Encoder(10, 4, backend=backend, pallas_interpret=True)
    got = enc.encode_batch(data)
    pm = gf8.parity_matrix(10, 4)
    for b in range(2):
        want = gf8.gf_mat_mul(pm, data[b])
        np.testing.assert_array_equal(got[b, :10], data[b])
        np.testing.assert_array_equal(got[b, 10:], want, err_msg=f"n={n}")


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("n", [1, DEFAULT_TILE - 1, DEFAULT_TILE + 1])
def test_reconstruct_batch_tile_edges_match_golden(backend, n):
    rng = np.random.default_rng(n + 1)
    data = rng.integers(0, 256, size=(10, n), dtype=np.uint8)
    full = ENC.encode(list(data))
    lost = [0, 5, 11, 13]
    survivors = [i for i in range(14) if i not in lost][:10]
    stack = np.stack([full[s] for s in survivors])[None]
    enc = Encoder(10, 4, backend=backend, pallas_interpret=True)
    out = enc.reconstruct_batch(stack, survivors, lost)
    for k, w in enumerate(lost):
        np.testing.assert_array_equal(out[0, k], full[w], err_msg=f"n={n} shard {w}")


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_encode_empty_width(backend):
    enc = Encoder(10, 4, backend=backend, pallas_interpret=True)
    out = enc.encode_batch(np.zeros((1, 10, 0), dtype=np.uint8))
    assert out.shape == (1, 14, 0)


# -- file-level: depth-1 vs depth-N byte-identity -----------------------------


def _write_dat(tmp_path, size, seed=1):
    base = os.path.join(str(tmp_path), "v")
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    return base


@pytest.mark.parametrize("size", [1, 123_457, 655_360])
def test_encode_depths_byte_identical(tmp_path, size):
    base = _write_dat(tmp_path, size)
    shards_by_depth = {}
    for depth in (1, 3):
        stripe.write_ec_files(
            base, large_block_size=16384, small_block_size=4096,
            buffer_size=4096, encoder=ENC, max_batch_bytes=10 * 3 * 4096,
            pipeline_depth=depth,
        )
        shards_by_depth[depth] = [
            open(stripe.shard_file_name(base, s), "rb").read()
            for s in range(TOTAL_SHARDS_COUNT)
        ]
    assert shards_by_depth[1] == shards_by_depth[3]


@pytest.mark.parametrize("depth", [1, 3])
def test_rebuild_depths_match_serial_oracle(tmp_path, depth):
    base = _write_dat(tmp_path, 200_000)
    stripe.write_ec_files(
        base, large_block_size=16384, small_block_size=4096, encoder=ENC
    )
    golden = {
        s: open(stripe.shard_file_name(base, s), "rb").read()
        for s in range(TOTAL_SHARDS_COUNT)
    }
    lost = [0, 5, 11, 13]
    for s in lost:
        os.unlink(stripe.shard_file_name(base, s))
    rebuilt = stripe.rebuild_ec_files(
        base, encoder=ENC, buffer_size=8192,
        max_batch_bytes=10 * 2 * 8192, pipeline_depth=depth,
    )
    assert rebuilt == lost
    for s in range(TOTAL_SHARDS_COUNT):
        with open(stripe.shard_file_name(base, s), "rb") as f:
            assert f.read() == golden[s], f"depth={depth} shard {s}"


def test_empty_dat_roundtrip(tmp_path):
    base = _write_dat(tmp_path, 0)
    stripe.write_ec_files(
        base, large_block_size=16384, small_block_size=4096, encoder=ENC
    )
    for s in range(TOTAL_SHARDS_COUNT):
        assert os.path.getsize(stripe.shard_file_name(base, s)) == 0
    os.unlink(stripe.shard_file_name(base, 2))
    assert stripe.rebuild_ec_files(base, encoder=ENC) == [2]
    assert os.path.getsize(stripe.shard_file_name(base, 2)) == 0


# -- fused CRC recording + verification ---------------------------------------


def test_eci_records_streaming_crcs(tmp_path):
    base = _write_dat(tmp_path, 100_000)
    stripe.write_ec_files(
        base, large_block_size=16384, small_block_size=4096, encoder=ENC
    )
    info = stripe.read_ec_info(base)
    crcs = info["shard_crc32"]
    assert len(crcs) == TOTAL_SHARDS_COUNT
    for s in range(TOTAL_SHARDS_COUNT):
        with open(stripe.shard_file_name(base, s), "rb") as f:
            assert crcs[s] == zlib.crc32(f.read()), f"shard {s}"


def test_ec_volume_verify_local_shards(tmp_path):
    from seaweedfs_tpu.ec.ec_volume import EcVolume
    from seaweedfs_tpu.storage import idx as idx_mod
    from seaweedfs_tpu.storage import types

    base = _write_dat(tmp_path, 50_000)
    idx_mod.write_entries([(1, types.offset_to_bytes(0), 100)], base + ".idx")
    stripe.write_ec_files(
        base, large_block_size=16384, small_block_size=4096, encoder=ENC
    )
    stripe.write_sorted_file_from_idx(base)
    # flip one byte in one shard without changing its length
    p = stripe.shard_file_name(base, 7)
    with open(p, "r+b") as f:
        f.seek(10)
        b = f.read(1)
        f.seek(10)
        f.write(bytes([b[0] ^ 0xFF]))
    with EcVolume(
        base, encoder=ENC, large_block_size=16384, small_block_size=4096,
        warm_on_mount=False,
    ) as ev:
        report = ev.verify_local_shards()
    assert report is not None
    assert report[7] is False
    assert all(ok for s, ok in report.items() if s != 7)


def test_rebuild_crc_gate_catches_corrupt_survivor(tmp_path):
    """A silently-corrupt survivor (same length, flipped bytes) produces a
    wrong rebuild; the streaming CRC check against the .eci record must
    fail the rebuild AND unlink the partial outputs."""
    base = _write_dat(tmp_path, 100_000)
    stripe.write_ec_files(
        base, large_block_size=16384, small_block_size=4096, encoder=ENC
    )
    os.unlink(stripe.shard_file_name(base, 13))
    p = stripe.shard_file_name(base, 3)  # survivor used by the decode
    with open(p, "r+b") as f:
        f.seek(100)
        f.write(b"\xde\xad\xbe\xef")
    with pytest.raises(IOError, match="CRC mismatch"):
        stripe.rebuild_ec_files(base, encoder=ENC)
    assert not os.path.exists(stripe.shard_file_name(base, 13))


# -- exception safety ---------------------------------------------------------


class _Boom(RuntimeError):
    pass


class _FailingEncoder(Encoder):
    """Raises on the Nth device dispatch — models a mid-stream read/decode
    failure with batches still inflight."""

    def __init__(self, *a, fail_at=2, **kw):
        super().__init__(*a, **kw)
        self.calls = 0
        self.fail_at = fail_at

    def _maybe_boom(self):
        self.calls += 1
        if self.calls >= self.fail_at:
            raise _Boom("mid-stream failure")

    def encode_parity_lazy(self, data, donate=False):
        self._maybe_boom()
        return super().encode_parity_lazy(data, donate=donate)

    def reconstruct_lazy(self, stack, survivors, wanted, donate=False):
        self._maybe_boom()
        return super().reconstruct_lazy(stack, survivors, wanted, donate=donate)


def test_encode_failure_unlinks_partial_shards(tmp_path):
    base = _write_dat(tmp_path, 655_360)
    enc = _FailingEncoder(10, 4, backend="numpy", fail_at=2)
    with pytest.raises(_Boom):
        stripe.write_ec_files(
            base, large_block_size=16384, small_block_size=4096,
            buffer_size=4096, encoder=enc, max_batch_bytes=10 * 2 * 4096,
        )
    for s in range(TOTAL_SHARDS_COUNT):
        assert not os.path.exists(stripe.shard_file_name(base, s)), f"shard {s} leaked"
    assert not os.path.exists(base + ".eci")


def test_rebuild_failure_unlinks_partials_keeps_survivors(tmp_path):
    base = _write_dat(tmp_path, 655_360)
    stripe.write_ec_files(
        base, large_block_size=16384, small_block_size=4096, encoder=ENC
    )
    lost = [0, 13]
    for s in lost:
        os.unlink(stripe.shard_file_name(base, s))
    enc = _FailingEncoder(10, 4, backend="numpy", fail_at=2)
    with pytest.raises(_Boom):
        stripe.rebuild_ec_files(
            base, encoder=enc, buffer_size=8192, max_batch_bytes=10 * 2 * 8192
        )
    for s in lost:
        assert not os.path.exists(stripe.shard_file_name(base, s)), f"partial {s} leaked"
    for s in range(TOTAL_SHARDS_COUNT):
        if s not in lost:
            assert os.path.exists(stripe.shard_file_name(base, s)), f"survivor {s} gone"


# -- decode-matrix cache boundedness (satellite: LRU cap) ---------------------


def test_decode_matrix_cache_is_bounded():
    import itertools

    clear_decode_matrix_cache()
    try:
        # churn MORE distinct loss patterns than the cap (flapping peers /
        # rolling repairs on a long-lived volume server): the memo must
        # evict, never grow for the life of the process
        info = decode_matrix_cache_info()
        n_patterns = 0
        for survivors in itertools.combinations(range(1, 14), 10):
            for wanted in (w for w in range(14) if w not in survivors):
                ENC.reconstruction_matrix(survivors, (wanted,))
                n_patterns += 1
            if n_patterns > info.maxsize + 50:
                break
        assert n_patterns > info.maxsize, "fixture must overflow the cap"
        info = decode_matrix_cache_info()
        assert info.currsize <= info.maxsize
        assert info.maxsize >= 16
    finally:
        clear_decode_matrix_cache()


def test_warm_decode_matrices_stays_bounded():
    clear_decode_matrix_cache()
    try:
        built = ENC.warm_decode_matrices()
        assert built == 14
        info = decode_matrix_cache_info()
        assert info.currsize <= info.maxsize
    finally:
        clear_decode_matrix_cache()


# -- kernel_sweep --smoke CI gate ---------------------------------------------


def test_kernel_sweep_smoke_gate():
    """Kernel refactors must not silently break the sweep: the --smoke mode
    runs every encode+rebuild variant byte-exactness gate on tiny shapes
    under JAX_PLATFORMS=cpu (interpret mode) and exits nonzero on any
    failure. EVERY staged kernel variant (rs_pallas.VARIANTS: int8, bf16,
    u8, mplane, dma) must appear in the gated set — a variant missing from
    the sweep would reach its first device window uncompiled."""
    from seaweedfs_tpu.ops import rs_pallas

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "kernel_sweep.py"), "--smoke"],
        cwd=root,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout.decode(errors="replace")[-2000:]
    summary = None
    seen = set()
    for line in proc.stdout.decode(errors="replace").splitlines():
        line = line.strip()
        if line.startswith("{"):
            rec = json.loads(line)
            if "smoke_ok" in rec:
                summary = rec
            elif rec.get("variant"):
                seen.add(rec["variant"])
    assert summary and summary["smoke_ok"], summary
    assert summary["variants"] >= 14
    for mxu in rs_pallas.VARIANTS:
        tag = "pallas-auto" if mxu == "int8" else f"pallas-{mxu}-auto"
        assert tag in seen, f"variant {mxu} missing from the smoke gate: {sorted(seen)}"
    assert any(v.startswith("rebuild-") for v in seen)


# -- shard lanes: the per-shard host work of a batch on the host's cores --------

LOST = [0, 3, 11, 13]
SMALL = dict(large_block_size=16384, small_block_size=4096, buffer_size=4096)


def _shard_bytes(base):
    return [open(stripe.shard_file_name(base, s), "rb").read() for s in range(TOTAL_SHARDS_COUNT)]


def _run_attr(kind, attr):
    from seaweedfs_tpu.obs import trace

    (t,) = trace.RING.snapshot(kind=kind)
    return t["root"]["attrs"][attr]


def _encode_then_rebuild(monkeypatch, d, size, cores, depth, max_batch_bytes):
    """write_ec_files, then lose LOST and rebuild_ec_files, on a host that
    says it has `cores`: -> (lanes of the encode, of the rebuild, the shard
    files after the encode, after the rebuild, the .eci's CRCs)."""
    from seaweedfs_tpu.obs import trace

    monkeypatch.setattr(os, "cpu_count", lambda: cores)
    d.mkdir()
    base = _write_dat(d, size)
    trace.RING.clear()
    stripe.write_ec_files(base, encoder=ENC, max_batch_bytes=max_batch_bytes, pipeline_depth=depth, **SMALL)
    encoded = _shard_bytes(base)
    for s in LOST:
        os.unlink(stripe.shard_file_name(base, s))
    rebuilt = stripe.rebuild_ec_files(
        base, encoder=ENC, buffer_size=8192, max_batch_bytes=10 * 2 * 8192, pipeline_depth=depth
    )
    assert rebuilt == LOST
    return (
        _run_attr("encode.run", "lanes"),
        _run_attr("rebuild.run", "lanes"),
        encoded,
        _shard_bytes(base),
        stripe.read_ec_info(base)["shard_crc32"],
    )


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize(
    "shape",
    [
        # two large rows and two small ones; a batch of three 4 KiB segments
        # cuts across the four-segment large rows, and leaves a tail batch
        ("tiers", 2 * 163_840 + 50_000, 10 * 3 * 4096),
        # small rows only, seven of them in batches of four: a tail batch of three
        ("tail", 6 * 40_960 + 123, 10 * 4 * 4096),
        ("empty", 0, 10 * 4 * 4096),
    ],
    ids=lambda shape: shape[0],
)
def test_lanes_write_the_inline_orders_bytes(tmp_path, monkeypatch, shape, depth):
    """Eight cores (seven lanes) against one core (inline, the order before
    the lanes): all 14 shard files, the .eci's CRCs and the four rebuilt
    shards are the same bytes, at every pipeline depth."""
    _, size, max_batch_bytes = shape
    lanes = _encode_then_rebuild(monkeypatch, tmp_path / "lanes", size, 8, depth, max_batch_bytes)
    inline = _encode_then_rebuild(monkeypatch, tmp_path / "inline", size, 1, depth, max_batch_bytes)
    assert lanes[:2] == (7, 7) and inline[:2] == (0, 0)
    assert lanes[2] == inline[2] and lanes[4] == inline[4]
    assert lanes[4] == [zlib.crc32(b) for b in lanes[2]]
    assert lanes[3] == inline[3] == lanes[2]


class _LanesSeen(stripe._ShardLanes):
    """Every lanes object the pipelines make, kept for the test to look at."""

    made: list = []

    def __init__(self, shards):
        super().__init__(shards)
        self.made.append(self)


class _FailingWrites:
    """A shard file whose `write` raises from its `fail_at`-th call on."""

    def __init__(self, f, fail_at):
        self._f, self._left = f, fail_at

    def write(self, b):
        self._left -= 1
        if self._left <= 0:
            raise _Boom("disk full")
        return self._f.write(b)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._f.close()


class _StrictSource(stripe.LocalSlabSource):
    """A local survivor that refuses to zero-fill: a slab past its file's
    end is an error (a survivor truncated after the geometry check)."""

    def read_into(self, offset, out):
        if offset + out.size > os.path.getsize(self._f.name):
            raise _Boom(f"short survivor {self._f.name}")
        super().read_into(offset, out)


@pytest.mark.parametrize("case", ["encode_write_fails", "rebuild_write_fails", "truncated_survivor"])
def test_lane_failure_surfaces_and_leaves_nothing(tmp_path, monkeypatch, case):
    """An exception raised on a lane thread (a shard file's write at batch
    2, a survivor that turns out short) reaches the caller as itself; no
    partial .ecNN and no .eci stay, survivors do, and when the pipeline
    returns no task of the run is queued or running."""
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    monkeypatch.setattr(stripe, "_ShardLanes", _LanesSeen)
    _LanesSeen.made = []
    base = _write_dat(tmp_path, 655_360)
    victim = stripe.shard_file_name(base, 12 if case == "encode_write_fails" else 13)
    real_open = open

    def failing_open(path, mode="r", *a, **kw):
        f = real_open(path, mode, *a, **kw)
        return _FailingWrites(f, 2) if (path == victim and "w" in mode) else f

    small_batches = dict(max_batch_bytes=10 * 2 * 4096, **SMALL)
    if case == "encode_write_fails":
        monkeypatch.setattr(stripe, "open", failing_open, raising=False)
        with pytest.raises(_Boom, match="disk full"):
            stripe.write_ec_files(base, encoder=ENC, **small_batches)
        gone = range(TOTAL_SHARDS_COUNT)
        assert not os.path.exists(base + ".eci")
    else:
        stripe.write_ec_files(base, encoder=ENC, **small_batches)
        gone = [0, 13]
        for s in gone:
            os.unlink(stripe.shard_file_name(base, s))
        _LanesSeen.made = []
        if case == "rebuild_write_fails":
            monkeypatch.setattr(stripe, "open", failing_open, raising=False)
            with pytest.raises(_Boom, match="disk full"):
                stripe.rebuild_ec_files(base, encoder=ENC, buffer_size=8192, max_batch_bytes=10 * 2 * 8192)
        else:
            survivors = [s for s in range(TOTAL_SHARDS_COUNT) if s not in gone]
            size = os.path.getsize(stripe.shard_file_name(base, 1))
            sources = {s: _StrictSource(stripe.shard_file_name(base, s)) for s in survivors}
            os.truncate(stripe.shard_file_name(base, 5), size - 10_000)
            try:
                with pytest.raises(_Boom, match="short survivor .*ec05"):
                    stripe.rebuild_ec_files_from_sources(
                        base, sources, size, encoder=ENC, missing=gone,
                        buffer_size=8192, max_batch_bytes=10 * 2 * 8192,
                    )
            finally:
                for src in sources.values():
                    src.close()
        for s in range(TOTAL_SHARDS_COUNT):
            if s not in gone:
                assert os.path.exists(stripe.shard_file_name(base, s)), f"survivor {s} gone"
    for s in gone:
        assert not os.path.exists(stripe.shard_file_name(base, s)), f"partial {s} leaked"
    assert _LanesSeen.made and all(lanes.n == 7 for lanes in _LanesSeen.made)
    assert all(lanes._open == 0 and not lanes._queues for lanes in _LanesSeen.made)


def test_two_volumes_encoded_at_once_share_the_lanes(tmp_path, monkeypatch):
    """Two write_ec_files of different volumes from two threads, on the one
    set of lane threads: both finish, each with the files it has alone."""
    monkeypatch.setattr(os, "cpu_count", lambda: 4)  # three lanes for 28 shards
    bases, alone = [], []
    for i, size in enumerate((700_001, 523_456)):
        d = tmp_path / f"v{i}"
        d.mkdir()
        bases.append(_write_dat(d, size, seed=10 + i))
        stripe.write_ec_files(bases[i], encoder=ENC, max_batch_bytes=10 * 3 * 4096, **SMALL)
        alone.append((_shard_bytes(bases[i]), stripe.read_ec_info(bases[i])["shard_crc32"]))
        for s in range(TOTAL_SHARDS_COUNT):
            os.unlink(stripe.shard_file_name(bases[i], s))
    errors = []

    def encode(base):
        try:
            stripe.write_ec_files(base, encoder=ENC, max_batch_bytes=10 * 3 * 4096, **SMALL)
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=encode, args=(b,)) for b in bases]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors and not any(t.is_alive() for t in threads)
    for base, (shards, crcs) in zip(bases, alone):
        assert _shard_bytes(base) == shards
        assert stripe.read_ec_info(base)["shard_crc32"] == crcs


class _Shim:
    """seek/readinto over a real file, as convert._VirtualDat has them: no
    OS file to the pipeline, and it says on which threads it was read."""

    def __init__(self, path):
        self._f = open(path, "rb")
        self.readers = set()

    def seek(self, pos):
        self._f.seek(pos)

    def readinto(self, mv):
        self.readers.add(threading.current_thread().name)
        return self._f.readinto(mv)


def test_sources_that_are_no_file_are_read_on_the_calling_thread(tmp_path, monkeypatch):
    """A seek/readinto shim in _encode_rows and a SlabSource that does not
    say `lane_reads` in the rebuild: every read on the calling thread, the
    writes and CRCs on the lanes all the same, the bytes those of files."""
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    me = threading.current_thread().name
    base = _write_dat(tmp_path, 6 * 40_960)  # six small rows, no large one
    stripe.write_ec_files(
        base, encoder=ENC, max_batch_bytes=10 * 4 * 4096,
        large_block_size=65536, small_block_size=4096, buffer_size=4096,
    )
    golden, crcs_golden = _shard_bytes(base), stripe.read_ec_info(base)["shard_crc32"]

    writers = set()

    class Out:
        def __init__(self):
            self.got = bytearray()

        def write(self, b):
            writers.add(threading.current_thread().name)
            self.got += bytes(b)

    shim, outs, crcs = _Shim(base + ".dat"), [Out() for _ in range(TOTAL_SHARDS_COUNT)], [0] * TOTAL_SHARDS_COUNT
    assert stripe._fd_of(shim) is None
    n = stripe._encode_rows(shim, ENC, outs, 0, 4096, 6, 4096, 10 * 4 * 4096, 2, crcs)
    shim._f.close()
    assert n == 2 and shim.readers == {me}
    assert [bytes(o.got) for o in outs] == golden and crcs == crcs_golden
    assert writers and all(w.startswith("ec-lane") for w in writers)

    readers = {}

    class Plain(stripe.SlabSource):  # says nothing of lanes: the base class's no
        def __init__(self, sid):
            self._sid, self._inner = sid, stripe.LocalSlabSource(stripe.shard_file_name(base, sid))

        def read_into(self, offset, out):
            readers.setdefault(self._sid, set()).add(threading.current_thread().name)
            self._inner.read_into(offset, out)

        def close(self):
            self._inner.close()

    class Told(stripe.LocalSlabSource):
        def read_into(self, offset, out):
            readers.setdefault("told", set()).add(threading.current_thread().name)
            super().read_into(offset, out)

    for s in LOST:
        os.unlink(stripe.shard_file_name(base, s))
    survivors = [s for s in range(TOTAL_SHARDS_COUNT) if s not in LOST]
    sources = {s: Plain(s) for s in survivors[:5]}
    sources.update({s: Told(stripe.shard_file_name(base, s)) for s in survivors[5:]})
    try:
        stripe.rebuild_ec_files_from_sources(
            base, sources, len(golden[0]), encoder=ENC, missing=LOST,
            buffer_size=8192, max_batch_bytes=10 * 2 * 8192,
        )
    finally:
        for src in sources.values():
            src.close()
    assert _shard_bytes(base) == golden
    assert all(readers[s] == {me} for s in survivors[:5])
    assert all(r.startswith("ec-lane") for r in readers["told"])


def test_one_core_host_runs_every_task_inline(tmp_path, monkeypatch):
    """os.cpu_count() == 1: lanes=0 on both run spans, and every read, write
    and CRC fold runs on the calling thread."""
    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    seen = set()
    real_pread, real_crc = stripe.pread_padded_into, zlib.crc32

    def pread(fd, offset, out):
        seen.add(threading.current_thread().name)
        real_pread(fd, offset, out)

    def crc32(*a):
        seen.add(threading.current_thread().name)
        return real_crc(*a)

    monkeypatch.setattr(stripe, "pread_padded_into", pread)
    monkeypatch.setattr(stripe.zlib, "crc32", crc32)
    got = _encode_then_rebuild(monkeypatch, tmp_path / "v", 300_000, 1, 2, 10 * 3 * 4096)
    assert got[0] == 0 and got[1] == 0 and got[3] == got[2]
    assert seen == {threading.current_thread().name}


# -- the bulk pipelines seen from inside: one stage catalog, every batch --------


#: what the calling thread records; reads, writes and CRC folds are the lanes'
CALLING = ("stage", "dispatch", "drain", "sync", "wait", "verify")


def _calling_self_ms(sp):
    """A calling-thread span's duration less its children on that thread
    (its children on lane threads run beside it, not inside it)."""
    return sp["dur_ms"] - sum(
        c["dur_ms"] for c in sp.get("spans", ()) if c["name"].rsplit(".", 1)[-1] in CALLING
    )


@pytest.mark.parametrize("pipeline", ["encode", "rebuild"])
def test_bulk_stage_spans_account_for_a_run(tmp_path, monkeypatch, pipeline):
    """Through the real write_ec_files / rebuild_ec_files, on the lanes: the
    run's span tree holds every stage once per batch (a read per source
    shard, a write and a CRC per shard written), `bytes` over the writes is
    what the files hold and over the reads what was staged, a lane's spans
    hang under the stage or drain that queued them, the calling thread's own
    spans account for the run's wall, the run says how many lanes it had,
    and the shards are those of a run with WEEDTPU_TRACE=off."""
    from seaweedfs_tpu.obs import trace

    # a run's wall must be its stages', not this box's spiky fsync of the .eci
    monkeypatch.setattr(os, "fsync", lambda fd: None)
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    lost = [0, 3, 11, 13]
    sizes = dict(large_block_size=1 << 20, small_block_size=1 << 16)
    batch = 10 * 4 * (1 << 16)  # four 64 KiB segments wide

    def run(sub, mode):
        monkeypatch.setenv("WEEDTPU_TRACE", mode)
        trace.RING.clear()
        d = tmp_path / sub
        d.mkdir()
        base = _write_dat(d, 6_000_000)
        stripe.write_ec_files(base, buffer_size=1 << 16, encoder=ENC, max_batch_bytes=batch, **sizes)
        encode = trace.RING.snapshot(kind="encode.run")
        for s in lost:
            os.unlink(stripe.shard_file_name(base, s))
        trace.RING.clear()
        assert stripe.rebuild_ec_files(base, encoder=ENC, buffer_size=1 << 16, max_batch_bytes=batch) == lost
        rebuild = trace.RING.snapshot(kind="rebuild.run")
        shards = [open(stripe.shard_file_name(base, s), "rb").read() for s in range(TOTAL_SHARDS_COUNT)]
        return {"encode": encode, "rebuild": rebuild}, shards

    traced, shards = run("on", "on")
    untraced, shards_off = run("off", "off")
    assert shards == shards_off and untraced == {"encode": [], "rebuild": []}

    (t,) = traced[pipeline]
    root = t["root"]
    spans = [s for s in trace.iter_spans(t) if s is not root]
    count = {n: sum(1 for s in spans if s["name"] == n) for n in {s["name"] for s in spans}}
    shard_size = len(shards[0])
    batches = -(-shard_size // (4 * (1 << 16)))
    assert batches == 3 and root["attrs"]["batches"] == batches
    assert root["attrs"]["lanes"] == 7
    per_shard = TOTAL_SHARDS_COUNT if pipeline == "encode" else len(lost)
    want = {f"{pipeline}.{s}": batches for s in ("stage", "dispatch", "drain", "sync")}
    want.update({f"{pipeline}.write": per_shard * batches, f"{pipeline}.crc": per_shard * batches})
    want[f"{pipeline}.read"] = 10 * batches  # one per data shard / survivor and batch
    want[f"{pipeline}.wait"] = 2 * batches + 1  # a stage's reads, a drain's join, the run's end
    if pipeline == "rebuild":
        want["rebuild.verify"] = 1
    else:
        want["encode.finish"] = 1  # the volume's files closed, its .eci written
    assert count == want
    assert set(want) <= set(trace.SPAN_NAMES)

    def bytes_of(name):
        return sum(s["attrs"]["bytes"] for s in spans if s["name"] == name)

    assert bytes_of(f"{pipeline}.write") == bytes_of(f"{pipeline}.crc") == per_shard * shard_size
    if pipeline == "encode":
        assert root["attrs"]["bytes"] == 6_000_000
        assert bytes_of("encode.read") == 10 * shard_size  # the .dat and the last row's zero fill
    else:
        assert root["attrs"]["bytes"] == len(lost) * shard_size
        assert bytes_of("rebuild.read") == 10 * shard_size  # ten survivor slabs (a whole number of buffers here)

    def names(sp):
        return sorted(c["name"].rsplit(".", 1)[-1] for c in sp["spans"])

    # drain means the same in both: the sync first, the join of what the lanes
    # still hold, then the written shards' writes and CRCs, queued from here
    written = per_shard - 10 if pipeline == "encode" else len(lost)
    for drain in (s for s in spans if s["name"] == f"{pipeline}.drain"):
        assert [c["name"] for c in drain["spans"]][:2] == [f"{pipeline}.sync", f"{pipeline}.wait"]
        assert names(drain) == sorted(["sync", "wait"] + ["write", "crc"] * written)
    # a stage holds its ten reads and the wait for them, and between the two,
    # from the batch that finds `depth` inflight on, the drain it runs ahead
    # of; an encode's also its data shards' writes and CRCs, which run on
    # beside the dispatch
    data = ["write", "crc"] * 10 if pipeline == "encode" else []
    stages = [s for s in spans if s["name"] == f"{pipeline}.stage"]
    stages.sort(key=lambda s: s["t_ms"])
    for i, stage in enumerate(stages):
        ahead_of = ["drain"] if i >= stripe.DEFAULT_PIPELINE_DEPTH else []
        assert names(stage) == sorted(["read"] * 10 + ["wait"] + ahead_of + data)
        own = [c for c in stage["spans"] if c["name"].rsplit(".", 1)[-1] in ("drain", "wait")]
        assert [c["name"].rsplit(".", 1)[-1] for c in own] == ahead_of + ["wait"]  # the join comes last
    calling = sum(_calling_self_ms(s) for s in spans if s["name"].rsplit(".", 1)[-1] in CALLING)
    assert calling >= 0.9 * root["dur_ms"], (calling, root["dur_ms"])


# -- staging runs ahead of the drain, into a ring the process keeps -------------


#: small rows only: one `_encode_rows` call, so one lease, an encode
ROWS = dict(large_block_size=65536, small_block_size=4096, buffer_size=4096)


class _Held:
    """A dispatch's lazy handle whose sync (np.asarray of it) says that it has
    begun and then blocks until the test lets every handle go."""

    def __init__(self, value, gate):
        self._value, self._gate = value, gate

    def __array__(self, *a, **kw):
        self._gate.syncing.set()
        assert self._gate.release.wait(20), "the test never released the device"
        return self._value


class _Gate:
    def __init__(self):
        self.syncing, self.release = threading.Event(), threading.Event()


class _HeldEncoder(Encoder):
    def __init__(self, *a, gate, **kw):
        super().__init__(*a, **kw)
        self._gate = gate

    def encode_parity_lazy(self, data, donate=False):
        return _Held(np.asarray(super().encode_parity_lazy(data, donate=donate)), self._gate)

    def reconstruct_lazy(self, stack, survivors, wanted, donate=False):
        return _Held(np.asarray(super().reconstruct_lazy(stack, survivors, wanted, donate=donate)), self._gate)


def _wait_until(cond, seconds):
    deadline = time.monotonic() + seconds
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    return cond()


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("source", ["file", "shim"])
def test_encode_reads_run_ahead_of_the_drain_where_a_lane_reads_them(tmp_path, monkeypatch, source, depth):
    """Eight one-row batches, the device held: the first drain is batch 0's,
    made while batch `depth` is staged. A real file's ten slab reads of that
    batch are on the lanes before the drain returns; a source that is no file
    is read on the calling thread, which stands in the drain, so after it. The
    bytes are the golden run's either way."""
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    rows, block = 8, 4096
    base = _write_dat(tmp_path, rows * 10 * block)
    stripe.write_ec_files(
        base, encoder=ENC, max_batch_bytes=10 * block,
        large_block_size=65536, small_block_size=block, buffer_size=block,
    )
    golden = _shard_bytes(base)
    gate = _Gate()
    enc = _HeldEncoder(10, 4, backend="numpy", gate=gate)
    batches_read = []  # the batch of every slab read, as it starts
    real_pread = stripe.pread_padded_into

    def pread(fd, offset, out):
        batches_read.append(offset // (10 * block))
        real_pread(fd, offset, out)

    class Shim(_Shim):
        def seek(self, pos):
            batches_read.append(pos // (10 * block))
            super().seek(pos)

    monkeypatch.setattr(stripe, "pread_padded_into", pread)
    f = open(base + ".dat", "rb") if source == "file" else Shim(base + ".dat")
    outs = [io.BytesIO() for _ in range(TOTAL_SHARDS_COUNT)]
    done = []
    worker = threading.Thread(
        target=lambda: done.append(stripe._encode_rows(f, enc, outs, 0, block, rows, block, 10 * block, depth))
    )
    worker.start()
    try:
        assert gate.syncing.wait(20), "no drain began"
        if source == "file":
            assert _wait_until(lambda: batches_read.count(depth) == 10, 10), sorted(set(batches_read))
        else:
            time.sleep(0.3)
            assert depth not in batches_read
        assert max(batches_read) == depth - (source == "shim")  # and nothing further ahead
    finally:
        gate.release.set()
        worker.join(30)
    (f if source == "file" else f._f).close()
    assert done == [rows] and [o.getvalue() for o in outs] == golden
    assert sorted(batches_read) == sorted(list(range(rows)) * 10)


ENCODE_SHAPES = [
    # (what, longer volume, the volume, batch budget): two large rows and small
    # ones, batches of three 4 KiB segments that cut across the large rows and
    # leave a tail batch; small rows only with a tail batch of three
    ("tiers", 5 * 163_840 + 70_001, 2 * 163_840 + 50_000, 10 * 3 * 4096),
    ("tail", 17 * 40_960 + 5, 6 * 40_960 + 123, 10 * 4 * 4096),
    ("one-batch", 2 * 40_960, 4096 + 1, 10 * 4 * 4096),
]


def _poison_pool():
    """Every buffer the pool holds, filled with a byte no volume here is made of
    in runs (the first MiB: more than any slot of these tests)."""
    for buf in stripe._pool_free:
        buf[: 1 << 20] = 0xA5


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("shape", ENCODE_SHAPES, ids=lambda s: s[0])
def test_encode_through_a_kept_ring_after_a_longer_volume_is_byte_exact(tmp_path, monkeypatch, shape, depth):
    """A longer volume first, a shorter one second, through the same pooled
    buffers (whose last tenant's bytes are then overwritten with 0xA5): the
    second run's 14 files, CRCs and rebuilt shards are those of a run with a
    fresh ring and of the inline order on one core."""
    _, longer, size, budget = shape
    monkeypatch.setattr(stripe, "_pool_free", [])
    reused = stats.StagingRingLeases.labels("reused")
    first = _encode_then_rebuild(monkeypatch, tmp_path / "longer", longer, 8, depth, budget)
    assert first[3] == first[2]
    _poison_pool()
    before = reused.value
    kept = _encode_then_rebuild(monkeypatch, tmp_path / "kept", size, 8, depth, budget)
    assert reused.value - before >= 2  # the encode's tiers and the rebuild, none allocated
    monkeypatch.setattr(stripe, "_pool_free", [])
    fresh = _encode_then_rebuild(monkeypatch, tmp_path / "fresh", size, 8, depth, budget)
    monkeypatch.setattr(stripe, "_pool_free", [])
    inline = _encode_then_rebuild(monkeypatch, tmp_path / "inline", size, 1, depth, budget)
    assert kept[2:] == fresh[2:] == inline[2:]
    assert kept[3] == kept[2] and kept[4] == [zlib.crc32(b) for b in kept[2]]


def test_the_pool_keeps_no_more_than_its_bound_and_serves_both_geometries():
    """The bound is the constant PERF.md states. A server that has run one encode
    and one rebuild at the deployment's geometries holds the encode's three
    slots and nothing else, and the rebuild's ring is views of the same three
    buffers; geometry churn never leaves more than the bound behind, and what
    is kept is the largest buffers."""
    assert stripe.STAGING_POOL_MAX_BYTES == 3 * 64 * 1024 * 1024 == 201_326_592
    saved, stripe._pool_free = stripe._pool_free, []
    try:
        encode = stripe._ring_for(3, (10, 6_553_600))
        flat = {id(b) for b in encode._flat}
        encode.give_back()
        rebuild = stripe._ring_for(3, (10, 4_194_304))
        assert {id(b) for b in rebuild._flat} == flat and not stripe._pool_free
        assert rebuild.take().shape == (10, 4_194_304)
        rebuild.give_back()
        assert sum(b.size for b in stripe._pool_free) == 196_608_000 <= stripe.STAGING_POOL_MAX_BYTES
        # the other order: the rebuild's smaller buffers make room for the encode's
        stripe._pool_free = []
        stripe._ring_for(3, (10, 4_194_304)).give_back()
        stripe._ring_for(3, (10, 6_553_600)).give_back()
        assert [b.size for b in stripe._pool_free] == [65_536_000] * 3  # the 41.9 MB ones went
        for slots, shape in [(4, (10, 6_553_600)), (2, (14, 5_000_000)), (3, (2, 4 * 4_194_304)),
                             (3, (10, 4096)), (1, (1, stripe.STAGING_POOL_MAX_BYTES + 1)), (5, (20, 1 << 20))]:
            ring = stripe._ring_for(slots, shape)
            assert ring.take().shape == shape
            ring.give_back()
            assert sum(b.size for b in stripe._pool_free) <= stripe.STAGING_POOL_MAX_BYTES
            assert len({id(b) for b in stripe._pool_free}) == len(stripe._pool_free)
        with pytest.raises(TypeError):
            ring.take()  # given back: no slot to hand out
    finally:
        stripe._pool_free = saved


@pytest.mark.parametrize("pipeline", ["encode", "rebuild"])
def test_the_lease_counter_reads_allocated_once_and_reused_after(tmp_path, monkeypatch, pipeline):
    """From an empty pool: the first run allocates, every later one reuses, on
    the counter `/metrics` exposes and as `ring=` on the run's span."""
    from seaweedfs_tpu.obs import trace

    monkeypatch.setattr(stripe, "_pool_free", [])
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    seen = []
    for i in range(3):
        d = tmp_path / str(i)
        d.mkdir()
        base = _write_dat(d, 6 * 40_960 + 123)
        if pipeline == "rebuild":  # its set-up's encode is not what is counted
            stripe.write_ec_files(base, encoder=ENC, max_batch_bytes=10 * 4 * 4096, **ROWS)
            for s in LOST:
                os.unlink(stripe.shard_file_name(base, s))
            if i == 0:
                monkeypatch.setattr(stripe, "_pool_free", [])
        counts = {o: stats.StagingRingLeases.labels(o).value for o in ("allocated", "reused")}
        trace.RING.clear()
        if pipeline == "encode":
            stripe.write_ec_files(base, encoder=ENC, max_batch_bytes=10 * 4 * 4096, **ROWS)
        else:
            stripe.rebuild_ec_files(base, encoder=ENC, buffer_size=8192, max_batch_bytes=10 * 2 * 8192)
        seen.append((
            _run_attr(f"{pipeline}.run", "ring"),
            *(stats.StagingRingLeases.labels(o).value - counts[o] for o in ("allocated", "reused")),
        ))
    assert seen == [("allocated", 1, 0), ("reused", 0, 1), ("reused", 0, 1)]
    text = stats.REGISTRY.expose()
    assert 'weedtpu_staging_ring_leases_total{outcome="reused"}' in text
    assert 'weedtpu_staging_ring_leases_total{outcome="allocated"}' in text


class _Tracked:
    """A lazy handle that is in `pending` until it is synced."""

    pending: set = set()

    def __init__(self, value):
        self._value = value
        self.pending.add(self)

    def __array__(self, *a, **kw):
        self.pending.discard(self)
        return self._value


@pytest.mark.parametrize("where", ["read", "dispatch", "write"])
def test_a_failed_encode_gives_its_ring_back_after_its_inflight_work_is_gone(tmp_path, monkeypatch, where):
    """A read, a dispatch or a lane's write that raises at the third batch of
    depth 2: the ring goes back once, after the lanes are aborted and every
    dispatched handle is synced or discarded; the next encode runs through
    those buffers and is byte-exact."""
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    monkeypatch.setattr(stripe, "_pool_free", [])
    monkeypatch.setattr(stripe, "_ShardLanes", _LanesSeen)
    size, budget = 6 * 40_960 + 123, 10 * 4096  # seven one-segment batches
    d = tmp_path / "golden"
    d.mkdir()
    golden_base = _write_dat(d, size)
    stripe.write_ec_files(golden_base, encoder=ENC, max_batch_bytes=budget, **ROWS)
    golden = _shard_bytes(golden_base)
    _LanesSeen.made, _Tracked.pending = [], set()
    monkeypatch.setattr(stripe, "_pool_free", [])

    class Enc(Encoder):
        calls = 0

        def encode_parity_lazy(self, data, donate=False):
            Enc.calls += 1
            if where == "dispatch" and Enc.calls == 3:
                raise _Boom("dispatch")
            return _Tracked(np.asarray(super().encode_parity_lazy(data, donate=donate)))

    reads = []
    real_pread = stripe.pread_padded_into

    def pread(fd, offset, out):
        reads.append(offset)
        if where == "read" and len(reads) == 25:
            raise _Boom("read")
        real_pread(fd, offset, out)

    monkeypatch.setattr(stripe, "pread_padded_into", pread)
    if where == "write":
        real_open = open

        def failing_open(path, mode="r", *a, **kw):
            f = real_open(path, mode, *a, **kw)
            return _FailingWrites(f, 3) if (path.endswith(".ec05") and "w" in mode) else f

        monkeypatch.setattr(stripe, "open", failing_open, raising=False)
    given_back = []
    real_give_back = stripe._StagingRing.give_back

    def give_back(ring):
        lanes = _LanesSeen.made[-1]
        given_back.append((lanes._open, len(lanes._queues), len(_Tracked.pending)))
        real_give_back(ring)

    monkeypatch.setattr(stripe._StagingRing, "give_back", give_back)
    base = _write_dat(tmp_path, size)
    with pytest.raises(_Boom):
        stripe.write_ec_files(base, encoder=Enc(10, 4, backend="numpy"), max_batch_bytes=budget,
                              pipeline_depth=2, **ROWS)
    assert given_back == [(0, 0, 0)]
    kept = stripe._pool_free
    assert len(kept) == 3
    monkeypatch.undo()  # the failures; the pool stays the failed run's
    monkeypatch.setattr(stripe, "_pool_free", kept)
    before = stats.StagingRingLeases.labels("reused").value
    _poison_pool()
    stripe.write_ec_files(base, encoder=ENC, max_batch_bytes=budget, pipeline_depth=2, **ROWS)
    assert stats.StagingRingLeases.labels("reused").value == before + 1
    assert _shard_bytes(base) == golden


@pytest.mark.parametrize("pair", ["encode+encode", "encode+rebuild", "rebuild+rebuild"])
def test_two_runs_at_once_never_hold_the_same_buffer(tmp_path, monkeypatch, pair):
    """Two bulk runs in one process, each held on the device until both have
    their ring: no buffer is in both leases, although the pool had one whole
    ring to give; afterwards every buffer is in the pool once, within the
    bound, and both runs wrote the right bytes."""
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    monkeypatch.setattr(stripe, "_pool_free", [])
    size, budget = 6 * 40_960 + 123, 10 * 4 * 4096
    bases = []
    for i in range(2):
        d = tmp_path / str(i)
        d.mkdir()
        bases.append(_write_dat(d, size, seed=i + 1))
        stripe.write_ec_files(bases[i], encoder=ENC, max_batch_bytes=budget, **ROWS)
    golden = [_shard_bytes(b) for b in bases]
    assert len(stripe._pool_free) == 3  # one ring to give, and two runs to want it
    leases, both = [], threading.Barrier(2, timeout=20)
    real_ring_for = stripe._ring_for

    def ring_for(slots, shape):
        ring = real_ring_for(slots, shape)
        leases.append({id(b) for b in ring._flat})
        return ring

    class Meet(Encoder):
        """The first dispatch of a run waits until the other run has its ring too."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self._met = False

        def _meet(self):
            if not self._met:
                self._met = True
                both.wait()

        def encode_parity_lazy(self, data, donate=False):
            self._meet()
            return super().encode_parity_lazy(data, donate=donate)

        def reconstruct_lazy(self, stack, survivors, wanted, donate=False):
            self._meet()
            return super().reconstruct_lazy(stack, survivors, wanted, donate=donate)

    monkeypatch.setattr(stripe, "_ring_for", ring_for)
    errors = []

    def run(kind, base):
        try:
            enc = Meet(10, 4, backend="numpy")
            if kind == "encode":
                stripe.write_ec_files(base, encoder=enc, max_batch_bytes=budget, **ROWS)
            else:
                for s in LOST:
                    os.unlink(stripe.shard_file_name(base, s))
                stripe.rebuild_ec_files(base, encoder=enc, buffer_size=8192, max_batch_bytes=10 * 2 * 8192)
        except BaseException as e:  # noqa: BLE001 — for the test's thread to see
            errors.append(e)

    threads = [threading.Thread(target=run, args=(k, b)) for k, b in zip(pair.split("+"), bases)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors, errors
    assert len(leases) == 2 and not (leases[0] & leases[1])
    assert len({id(b) for b in stripe._pool_free}) == len(stripe._pool_free) >= 3
    assert sum(b.size for b in stripe._pool_free) <= stripe.STAGING_POOL_MAX_BYTES
    assert [_shard_bytes(b) for b in bases] == golden
