"""`ec.encode` without `-volumeId` (`sweep10p4`: the maintenance script's pass
over every volume that filled up): the selected volumes of one source server
ride ONE `VolumeEcShardsGenerateBatch`, whose pipeline packs their rows into
the same device batches, and each volume keeps the guarantees the per-volume
loop gave it. Small sizes, on the CPU, against the single-volume path
(`write_ec_files` alone) and the plain reference (striping as arithmetic on
the original `.dat`, numpy GF(2^8) of `benchmark/reference/gf8_ref.py`)."""

import io
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import test_ec_rebuild_cluster as cl
from seaweedfs_tpu import stats
from seaweedfs_tpu.cluster.client import MasterClient
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.ec import stripe
from seaweedfs_tpu.obs import trace
from seaweedfs_tpu.ops import rs_jax
from seaweedfs_tpu.ops.rs_codec import Encoder, new_encoder
from seaweedfs_tpu.shell import CommandEnv, ShellError, command_ec, run_script
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume

LARGE, SMALL = cl.LARGE, cl.SMALL
VIDS = list(range(1, 9))
#: needles a volume: unequal sizes, one to three small-block rows each
NEEDLES = {1: 24, 2: 9, 3: 30, 4: 3, 5: 17, 6: 28, 7: 12, 8: 21}
ENCODE_RPCS = ("VolumeEcShardsGenerateBatch", "VolumeEcShardsGenerate")
EXTS = [stripe.to_ext(s) for s in range(14)] + [".eci"]
FLAGS = f"-force -largeBlockSize {LARGE} -smallBlockSize {SMALL}"


def _calls(method):
    return stats.RpcServerSeconds.labels(method).total


def _read(path):
    with open(path, "rb") as f:
        return f.read()


def _reference_shards(dat: bytes) -> list[bytes]:
    """All 14 shards of a `.dat` of small-block rows, by the striping rule and
    the reference's parity (`cl._reference_shards`, for any number of rows)."""
    rows = -(-len(dat) // (10 * SMALL))
    cells = np.zeros(rows * 10 * SMALL, dtype=np.uint8)
    cells[:len(dat)] = np.frombuffer(dat, dtype=np.uint8)
    cells = cells.reshape(rows, 10, SMALL)
    pm = cl.gf8_ref.parity_matrix(10, 4)
    parity = [cl.gf8_ref.gf_mat_vec(pm, cells[r]) for r in range(rows)]
    return [cells[:, s, :].tobytes() for s in range(10)] + [
        b"".join(parity[r][p].tobytes() for r in range(rows)) for p in range(4)]


def _write_volume(directory, vid, n):
    """A sealed volume of `n` seeded needles. -> [(fid, payload)]"""
    rng = np.random.default_rng([40, vid])
    out = []
    with Volume(directory, vid) as v:
        for key in range(1, n + 1):
            payload = rng.bytes(int(rng.integers(500, 6000)))
            cookie = int(rng.integers(0, 1 << 32))
            v.write_needle(Needle(cookie=cookie, id=key, data=payload))
            out.append((f"{vid},{key:x}{cookie:08x}", payload))
    return out


# -- the pipeline: write_ec_files_batch against write_ec_files alone ---------------


def _dats(directory, sizes, seed=7):
    """Seeded `.dat` files of the given byte sizes. -> their bases"""
    rng = np.random.default_rng(seed)
    bases = []
    for i, size in enumerate(sizes):
        bases.append(os.path.join(directory, str(i + 1)))
        with open(bases[-1] + ".dat", "wb") as f:
            f.write(rng.bytes(size))
    return bases


def _alone(tmp_path, bases, **kw):
    """Every volume through `write_ec_files` on its own, in another directory.
    -> {base: {ext: bytes}}"""
    solo = tmp_path / "alone"
    solo.mkdir()
    out = {}
    for base in bases:
        twin = str(solo / os.path.basename(base))
        shutil.copy(base + ".dat", twin + ".dat")
        stripe.write_ec_files(twin, **kw)
        out[base] = {ext: _read(twin + ext) for ext in EXTS}
    return out


#: bytes a volume, in rows of 10 x 4096: tails of every width, a volume smaller
#: than a segment, one that ends on a row's edge
SIZES = [40960 * 3 + 17, 40960 * 7 - 4000, 900, 40960 * 2, 40960 * 5 + 1, 40960 * 9 + 333, 12345, 40960 * 4 - 1]


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("n", [1, 3, 8])
def test_a_batch_writes_each_volume_what_its_own_encode_writes(tmp_path, n, backend):
    """A batch of 1, 3 and 8 volumes of unequal sizes, slots of four segments
    so that batches straddle volumes: every volume's 14 shards and `.eci` are
    byte-identical to `write_ec_files` of that volume alone, the parity is the
    reference's, and the packed run takes fewer dispatches than the loop."""
    bases = _dats(str(tmp_path), SIZES[:n])
    kw = dict(large_block_size=1 << 30, small_block_size=SMALL, max_batch_bytes=10 * 4 * SMALL)
    want = _alone(tmp_path, bases, encoder=Encoder(10, 4, backend="numpy"), **kw)
    d0 = stats.EcDispatchTotal.labels(backend).value

    res = stripe.write_ec_files_batch(bases, encoder=Encoder(10, 4, backend=backend), **kw)

    assert res["errors"] == {}
    rows = [-(-size // (10 * SMALL)) for size in SIZES[:n]]
    assert res["batches"] == -(-sum(rows) // 4) == stats.EcDispatchTotal.labels(backend).value - d0
    assert res["batches"] <= sum(-(-r // 4) for r in rows)  # the loop: a tail batch a volume
    for base, size in zip(bases, SIZES[:n]):
        for ext in EXTS:
            assert _read(base + ext) == want[base][ext], f"{base}{ext} differs from the volume's own encode"
        reference = _reference_shards(_read(base + ".dat"))
        assert [_read(stripe.shard_file_name(base, s)) for s in range(14)] == reference
        assert stripe.read_ec_info(base)["dat_size"] == size


def test_write_ec_files_is_the_batch_of_one(tmp_path, monkeypatch):
    """`write_ec_files` runs nothing of its own: it is `write_ec_files_batch`
    over one base, and raises that volume's failure as it was."""
    (base,) = _dats(str(tmp_path), [40960 * 2 + 5])
    seen = []
    real = stripe.write_ec_files_batch

    def batch(bases, *a, **kw):
        seen.append(list(bases))
        return real(bases, *a, **kw)

    monkeypatch.setattr(stripe, "write_ec_files_batch", batch)
    stripe.write_ec_files(base, 1 << 30, SMALL, encoder=Encoder(10, 4, backend="numpy"))
    assert seen == [[base]] and stripe.read_ec_info(base)["dat_size"] == 40960 * 2 + 5
    with pytest.raises(FileNotFoundError):
        stripe.write_ec_files(str(tmp_path / "absent"), 1 << 30, SMALL, encoder=Encoder(10, 4, backend="numpy"))
    assert not os.path.exists(str(tmp_path / "absent") + stripe.to_ext(0))


def test_large_and_small_rows_of_many_volumes_keep_their_order(tmp_path):
    """Volumes over one large row beside volumes under it, a buffer between
    the two block sizes (so the large rows' run comes first and is cut
    coarser): every file is the volume's own encode's."""
    sizes = [10 * LARGE * 2 + 5000, 30000, 10 * LARGE + 1, 10 * LARGE * 3 + 70000]
    bases = _dats(str(tmp_path), sizes, seed=11)
    kw = dict(large_block_size=LARGE, small_block_size=SMALL, buffer_size=LARGE // 2,
              max_batch_bytes=10 * 3 * LARGE // 2, encoder=Encoder(10, 4, backend="numpy"))
    want = _alone(tmp_path, bases, **kw)
    assert stripe.write_ec_files_batch(bases, **kw)["errors"] == {}
    for base in bases:
        for ext in EXTS:
            assert _read(base + ext) == want[base][ext], f"{base}{ext}"


class _Breaks(Encoder):
    """Fails at its `at`-th dispatch."""

    def __init__(self, at):
        super().__init__(10, 4, backend="numpy")
        self.at, self.calls = at, 0

    def encode_parity_lazy(self, data, donate=False):
        self.calls += 1
        if self.calls == self.at:
            raise RuntimeError("the device went away")
        return super().encode_parity_lazy(data, donate=donate)


def test_a_volume_that_cannot_be_opened_is_left_out_and_a_broken_run_fails_the_unfinished(tmp_path, monkeypatch):
    """A missing `.dat` is that volume's error alone. A failure in the middle
    of the run fails every volume that was not finished: none of its shard
    files and no `.eci` of it is left; a volume finished before the failure
    (on a host without lanes its finish runs with its last write) is whole
    and stays."""
    bases = _dats(str(tmp_path), SIZES[:3])
    absent = str(tmp_path / "absent")
    kw = dict(large_block_size=1 << 30, small_block_size=SMALL, max_batch_bytes=10 * 4 * SMALL)
    res = stripe.write_ec_files_batch([bases[0], absent, bases[1]], encoder=Encoder(10, 4, backend="numpy"), **kw)
    assert list(res["errors"]) == [absent] and isinstance(res["errors"][absent], FileNotFoundError)
    assert all(os.path.exists(b + ".eci") for b in bases[:2]) and not os.path.exists(absent + ".ec00")

    def clean():
        for b in bases:
            for ext in EXTS:
                if os.path.exists(b + ext):
                    os.unlink(b + ext)

    clean()
    res = stripe.write_ec_files_batch(bases, encoder=_Breaks(2), **kw)
    assert sorted(res["errors"]) == sorted(bases)
    assert all(isinstance(e, RuntimeError) for e in res["errors"].values())
    assert not [b + ext for b in bases for ext in EXTS if os.path.exists(b + ext)]
    # rows 4, 7, 1 in slots of four: the third dispatch comes after the first batch,
    # which is all of volume 1, has drained and (inline) been written and finished
    clean()
    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    want = _alone(tmp_path, bases[:1], encoder=Encoder(10, 4, backend="numpy"), **kw)
    res = stripe.write_ec_files_batch(bases, encoder=_Breaks(3), pipeline_depth=1, **kw)
    assert sorted(res["errors"]) == sorted(bases[1:])
    assert {ext: _read(bases[0] + ext) for ext in EXTS} == want[bases[0]]
    assert not [b + ext for b in bases[1:] for ext in EXTS if os.path.exists(b + ext)]


def test_forty_small_volumes_are_each_finished_once_under_thread_pressure(tmp_path, monkeypatch):
    """More lane threads than cores and a switch interval of microseconds:
    several volumes end inside one batch, their 14 last writes race on the
    lanes, and each volume is finished exactly once, after all its bytes."""
    sizes = [int(n) for n in np.random.default_rng(3).integers(1, 3 * 40960, 40)]
    bases = _dats(str(tmp_path), sizes, seed=5)
    kw = dict(large_block_size=1 << 30, small_block_size=SMALL, max_batch_bytes=10 * 4 * SMALL,
              encoder=Encoder(10, 4, backend="numpy"))
    want = _alone(tmp_path, bases, **kw)
    finished = []
    real = stripe.write_ec_info

    def info(base, *a, **k):
        assert all(os.path.getsize(stripe.shard_file_name(base, s)) == len(want[base][stripe.to_ext(s)])
                   for s in range(14)), f"{base} finished before its last bytes"
        finished.append(base)
        return real(base, *a, **k)

    monkeypatch.setattr(stripe, "write_ec_info", info)
    monkeypatch.setattr(os, "cpu_count", lambda: 33)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        res = stripe.write_ec_files_batch(bases, **kw)
    finally:
        sys.setswitchinterval(interval)
    assert res["errors"] == {} and sorted(finished) == sorted(bases)
    for base in bases:
        assert {ext: _read(base + ext) for ext in EXTS} == want[base]


def test_a_sweep_compiles_two_programs_whatever_its_volumes_tails(tmp_path):
    """On the jax backend a packed sweep of eight volumes with eight different
    tails compiles at most two programs (the slot's width, the command's one
    narrower last batch); the per-volume loop compiles one a distinct tail."""
    bases = _dats(str(tmp_path), SIZES)
    kw = dict(large_block_size=1 << 30, small_block_size=SMALL, max_batch_bytes=10 * 4 * SMALL,
              encoder=Encoder(10, 4, backend="jax"))
    rs_jax.gf_apply.clear_cache()
    compiled0 = stats.CodecProgramsCompiled.value
    assert stripe.write_ec_files_batch(bases, **kw)["errors"] == {}
    assert stats.CodecProgramsCompiled.value - compiled0 <= 2
    packed = {ext: _read(bases[2] + ext) for ext in EXTS}
    rs_jax.gf_apply.clear_cache()
    compiled0 = stats.CodecProgramsCompiled.value
    for base in bases:
        stripe.write_ec_files(base, **kw)
    tails = {-(-size // (10 * SMALL)) % 4 for size in SIZES}
    assert stats.CodecProgramsCompiled.value - compiled0 == len((tails | {4}) - {0}) == 4
    assert {ext: _read(bases[2] + ext) for ext in EXTS} == packed


# -- the command: ec.encode through the shell, a master and a server --------------------


class Sweep:
    """master + one server that holds `vids` as sealed volumes, and a twin of
    each volume's files encoded alone (`generate_ec_files`) to compare with."""

    def __init__(self, tmp_path, backend, vids=VIDS, heartbeat_interval=0.2):
        self.vids = list(vids)
        self.master = MasterServer(port=0, reap_interval=3600)
        self.master.start()
        self.dir = str(tmp_path / "srv")
        self.twin = str(tmp_path / "twin")
        os.makedirs(self.dir)
        os.makedirs(self.twin)
        self.needles = {vid: _write_volume(self.dir, vid, NEEDLES[vid]) for vid in self.vids}
        self.dats = {}
        for vid in self.vids:
            for ext in (".dat", ".idx"):
                shutil.copy(self.base(vid) + ext, os.path.join(self.twin, str(vid)) + ext)
            self.dats[vid] = _read(self.base(vid) + ".dat")
            stripe.generate_ec_files(os.path.join(self.twin, str(vid)), large_block_size=LARGE,
                                     small_block_size=SMALL, encoder=Encoder(10, 4, backend="numpy"))
        self.server = VolumeServer([self.dir], self.master.address, heartbeat_interval=heartbeat_interval,
                                   max_volume_count=40, encoder=new_encoder(backend=backend))
        self.server.start()
        self.client = MasterClient(self.master.address)
        self.env = CommandEnv(self.master.address)
        cl._wait_for(lambda: len(self.master.topology.nodes) == 1, msg="the server joined")

    def base(self, vid):
        return os.path.join(self.dir, str(vid))

    def shell(self, script):
        """-> (what the script wrote, the ShellError that ended it or None)."""
        out = io.StringIO()
        try:
            run_script(self.env, script, out)
        except ShellError as e:
            return out.getvalue(), e
        return out.getvalue(), None

    def listed(self, vid):
        return {s: {n.url for n in nodes}
                for s, nodes in self.master.topology.lookup_ec_shards(vid).items() if nodes}

    def encoded_as_alone(self, vid):
        for ext in EXTS + [".ecx"]:
            assert _read(self.base(vid) + ext) == _read(os.path.join(self.twin, str(vid)) + ext), (vid, ext)
        assert [_read(stripe.shard_file_name(self.base(vid), s)) for s in range(14)] == \
            _reference_shards(self.dats[vid]), f"volume {vid} differs from the reference"
        assert not os.path.exists(self.base(vid) + ".dat")
        assert self.listed(vid) == {s: {self.server.url} for s in range(14)}
        for fid, payload in self.needles[vid]:
            assert self.client.read(fid) == payload

    def close(self):
        self.env.close()
        self.client.close()
        self.server.stop()
        self.master.stop()


@pytest.fixture
def make_sweep(tmp_path, monkeypatch):
    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    monkeypatch.setenv("WEEDTPU_TRACE_SAMPLE", "1.0")
    monkeypatch.chdir(tmp_path)  # the sweep's default checkpoint lands here
    made = []

    def make(backend, vids=VIDS, **kw):
        made.append(Sweep(tmp_path, backend, vids, **kw))
        return made[-1]

    yield make
    for c in made:
        c.close()


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_a_sweep_of_eight_volumes_is_one_batch(make_sweep, backend):
    """One `ec.encode` without `-volumeId`: exactly one
    `VolumeEcShardsGenerateBatch` and no `VolumeEcShardsGenerate` reached the
    server; every volume's shards, `.ecx` and `.eci` are its own encode's and
    the reference's; all 112 shards are listed, no `.dat` is left, every
    needle reads back; the counters and the spans say what ran."""
    c = make_sweep(backend)
    before = {m: _calls(m) for m in ENCODE_RPCS + ("VolumeEcShardsMount", "VolumeDelete", "VolumeMarkReadonly")}
    runs0 = stats.EcEncodeRuns.labels(backend).value
    batch0 = stats.EcEncodeBatchVolumes.value
    trace.RING.clear()

    out, err = c.shell(f"lock; ec.encode {FLAGS}; unlock")

    assert err is None, out
    assert {m: _calls(m) - n for m, n in before.items()} == {
        "VolumeEcShardsGenerateBatch": 1, "VolumeEcShardsGenerate": 0, "VolumeEcShardsMount": 8,
        "VolumeDelete": 8, "VolumeMarkReadonly": 8}
    assert f"ec.encode batch on {c.server.url}: 8 volumes in 1 batches\n" in out
    for vid in VIDS:
        assert f"ec.encode volume {vid}: spread {c.server.url}=" in out
        c.encoded_as_alone(vid)
    assert stats.EcEncodeRuns.labels(backend).value - runs0 == 8
    assert stats.EcEncodeBatchVolumes.value - batch0 == 8
    assert not os.path.exists(".ec_encode.checkpoint")  # a finished sweep clears it
    (run,) = [s for t in trace.RING.snapshot(kind="rpc.server", limit=100000)
              if t["root"]["attrs"].get("method") == "VolumeEcShardsGenerateBatch"
              for s in trace.iter_spans(t) if s["name"] == "encode.run"]
    assert run["attrs"]["batch"] == 8 and run["attrs"]["batches"] == 1
    assert run["attrs"]["volumes"] == "1,2,3,4,5,6,7,8" and run["attrs"]["ring"] in ("reused", "allocated")
    assert run["attrs"]["bytes"] == sum(len(d) for d in c.dats.values())
    # the command's span, under the script's root (`shell -c` is ONE trace)
    (root,) = [s for t in trace.RING.snapshot(kind="shell.script", limit=1000) for s in trace.iter_spans(t)
               if s["name"] == "shell.command" and s["attrs"].get("command") == "ec.encode"]
    # VolumeList twice (the size limit, the nodes), eight freezes, the one
    # batch, and a mount and a delete a volume
    assert root["attrs"]["rpcs"] == 2 + 8 + 1 + 8 + 8


def test_a_lone_volume_is_a_batch_of_one(make_sweep):
    """`-volumeId`: the same route, a batch of one, no single-volume RPC."""
    c = make_sweep("numpy", vids=[3, 5])
    before = {m: _calls(m) for m in ENCODE_RPCS}
    out, err = c.shell(f"lock; ec.encode -volumeId 5 {FLAGS}; unlock")
    assert err is None, out
    assert {m: _calls(m) - n for m, n in before.items()} == {
        "VolumeEcShardsGenerateBatch": 1, "VolumeEcShardsGenerate": 0}
    assert f"ec.encode batch on {c.server.url}: 1 volumes in 1 batches\n" in out
    c.encoded_as_alone(5)
    assert c.server.store.get_volume(3) is not None and not c.server.store.get_volume(3).read_only
    assert not os.path.exists(".ec_encode.checkpoint")  # a lone volume checkpoints nothing


def test_inline_keeps_the_single_volume_rpc(make_sweep):
    """`-inline` finalizes each volume's own encode-on-write state: the plan
    sees the flag and each volume goes the old way (here the warm fallback)."""
    c = make_sweep("numpy", vids=[1, 2])
    before = {m: _calls(m) for m in ENCODE_RPCS}
    out, err = c.shell(f"lock; ec.encode -inline {FLAGS}; unlock")
    assert err is None, out
    assert {m: _calls(m) - n for m, n in before.items()} == {
        "VolumeEcShardsGenerateBatch": 0, "VolumeEcShardsGenerate": 2}
    assert "ec.encode batch on" not in out and "(warm encode)" in out
    for vid in (1, 2):
        c.encoded_as_alone(vid)


def test_every_volume_is_frozen_before_it_is_read_and_deleted_after_its_own_mount(make_sweep, monkeypatch):
    """The per-volume rule, in whatever order the volumes' cut-overs run side
    by side: when the pipeline starts every volume of the batch is read-only;
    a volume's `VolumeDelete` goes out only when its own 14 shards, `.ecx` and
    `.eci` are on disk beside its `.dat` and its EC volume is mounted; and at
    least two cut-overs overlapped (`overlapped=` on the command's span). The
    server's own loop never beats here: that the master lists every shard when
    the command returns is the doing of the heartbeats the RPCs waited for."""
    c = make_sweep("numpy", vids=[1, 2, 3], heartbeat_interval=3600)
    frozen = []
    real_batch = stripe.write_ec_files_batch

    def batch(bases, *a, **kw):
        frozen.extend(c.server.store.get_volume(vid).read_only for vid in (1, 2, 3))
        return real_batch(bases, *a, **kw)

    monkeypatch.setattr(stripe, "write_ec_files_batch", batch)
    seen = []
    real_call = c.env.vs_call

    def vs_call(addr, method, req, **kw):
        if method == "VolumeDelete":
            vid = int(req["volume_id"])
            seen.append((vid, all(os.path.exists(c.base(vid) + ext) for ext in EXTS + [".ecx", ".dat"]),
                         c.server.store.get_ec_volume(vid) is not None))
        return real_call(addr, method, req, **kw)

    monkeypatch.setattr(c.env, "vs_call", vs_call)
    trace.RING.clear()
    out, err = c.shell(f"lock; ec.encode {FLAGS}; unlock")
    assert err is None, out
    assert frozen == [True, True, True]
    assert sorted(seen) == [(1, True, True), (2, True, True), (3, True, True)]
    (root,) = [s for t in trace.RING.snapshot(kind="shell.script", limit=1000) for s in trace.iter_spans(t)
               if s["name"] == "shell.command" and s["attrs"].get("command") == "ec.encode"]
    assert 1 <= root["attrs"]["overlapped"] <= 2
    assert 1 <= root["attrs"]["ckpt_writes"] <= 3
    # every line whole: a line a volume and the batch's, whatever the order
    assert sorted(ln for ln in out.splitlines() if ln.startswith("ec.encode")) == sorted(
        [f"ec.encode batch on {c.server.url}: 3 volumes in 1 batches"]
        + [f"ec.encode volume {v}: spread {c.server.url}={','.join(map(str, range(14)))}" for v in (1, 2, 3)])
    for vid in (1, 2, 3):
        c.encoded_as_alone(vid)


@pytest.mark.parametrize("where", ["generate", "cutover"])
def test_one_volume_of_three_fails_and_the_other_two_complete(make_sweep, monkeypatch, where):
    """Volume 2 fails, on the server inside the batch (its `.ecx`) or in its
    own cut-over: it is writable again and still a normal volume, the command
    says `NOT encoded` and ends in an error naming it, volumes 1 and 3 are
    encoded and cut over, the checkpoint holds exactly those two, and the
    rerun encodes volume 2 alone."""
    c = make_sweep("numpy", vids=[1, 2, 3])
    failing = [True]
    if where == "generate":
        real = stripe.write_sorted_file_from_idx

        def ecx(base, *a):
            if failing[0] and base == c.base(2):
                raise OSError("no space left on device")
            return real(base, *a)

        monkeypatch.setattr(stripe, "write_sorted_file_from_idx", ecx)
    else:
        real = command_ec._spread_cutover

        def cutover(env, nodes, locations, vid, *a):
            if failing[0] and vid == 2:
                raise ShellError("the spread of volume 2 failed")
            return real(env, nodes, locations, vid, *a)

        monkeypatch.setattr(command_ec, "_spread_cutover", cutover)

    out, err = c.shell(f"lock; ec.encode {FLAGS}; unlock")

    assert err is not None and "volumes [2] were not encoded" in str(err), out
    want = "no space left on device" if where == "generate" else "the spread of volume 2 failed"
    (line,) = [ln for ln in out.splitlines() if ln.startswith("ec.encode volume 2:")]
    assert line.startswith("ec.encode volume 2: NOT encoded: ") and want in line
    for vid in (1, 3):
        c.encoded_as_alone(vid)
    v2 = c.server.store.get_volume(2)
    assert v2 is not None and not v2.read_only and c.server.store.get_ec_volume(2) is None
    assert _read(c.base(2) + ".dat") == c.dats[2]
    for fid, payload in c.needles[2]:
        assert c.client.read(fid) == payload
    with open(".ec_encode.checkpoint") as f:
        assert json.load(f)["done"] == [1, 3]

    failing[0] = False
    cl._wait_for(lambda: all(len(c.listed(v)) == 14 for v in (1, 3)), msg="the master lists volumes 1 and 3")
    before = _calls("VolumeEcShardsGenerateBatch")
    out, err = c.shell(f"lock; ec.encode {FLAGS}; unlock")
    assert err is None, out
    assert "resuming, 2 volume(s) already done" in out and _calls("VolumeEcShardsGenerateBatch") - before == 1
    assert f"ec.encode batch on {c.server.url}: 1 volumes in 1 batches\n" in out
    c.encoded_as_alone(2)
    assert not os.path.exists(".ec_encode.checkpoint")


def test_a_sweep_is_cut_into_batches_by_volumes_and_bytes():
    """The plan's batches: source server by source server, at most
    ENCODE_BATCH_MAX_VOLUMES volumes and ENCODE_BATCH_MAX_BYTES of `.dat` a
    batch; a volume over the bytes is a batch of one."""
    gib = 1 << 30

    def plan(vid, url, size):
        return {"vid": vid, "collection": "", "locations": [{"url": url}], "size": size}

    plans = [plan(v, "a:1" if v % 2 else "b:1", gib) for v in range(1, 41)]
    got = [[p["vid"] for p in b] for b in command_ec._encode_batches(plans)]
    assert got == [list(range(1, 33, 2)), list(range(33, 41, 2)), list(range(2, 34, 2)), list(range(34, 41, 2))]
    plans = [plan(1, "a:1", 30 * gib), plan(2, "a:1", 9 * gib), plan(3, "a:1", 7 * gib), plan(4, "a:1", gib)]
    assert [[p["vid"] for p in b] for b in command_ec._encode_batches(plans)] == [[1], [2, 3], [4]]


@pytest.mark.parametrize("fault,sound", [("", True), ("flip_shard_byte", False), ("flip_first_encode", False),
                                         ("broken_apply", False)])
def test_the_benchmark_cell_rehearses_to_its_end_and_leaves_no_process(tmp_path, fault, sound):
    """`run.py --workload sweep10p4.encode-8x128m --rehearse`: every phase on
    the CPU with 8 MiB volumes, never a result. Sound, all checks pass and the
    facts say one batch RPC a command and no compile in the window; with a
    control's fault or the device's apply broken, the checks do not pass."""
    work = tmp_path / "tmp"
    work.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(work))
    cmd = [sys.executable, os.path.join(cl.ROOT, "benchmark", "run.py"), "--workload",
           "sweep10p4.encode-8x128m", "--seed", str(2**31 + 80 + len(fault)), "--seconds", "2", "--trace", "0", "--rehearse"]
    p = subprocess.run(cmd + (["--fault", fault] if fault else []),
                       cwd=cl.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 1, p.stdout[-3000:] + p.stderr[-3000:]
    assert '"correct": true' not in p.stdout
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["rehearse"] is True
    assert result["checks_ok"] is sound, p.stdout[-4000:]
    assert result["attempted"] >= 1 and result["failed"] == 0
    timed = result["timed"]
    assert timed["ops"] == result["attempted"] and timed["volumes"] == 8
    assert timed["programs_compiled_in_window"] == 0 and timed["batches"] >= 1
    assert timed["rpcs_per_command"]["VolumeEcShardsGenerateBatch"] == 1
    assert timed["rpcs_per_command"]["VolumeEcShardsGenerate"] == 0
    assert result["metrics"]["encode_MBps"]["value"] > 0
    assert (all(c["value"] == 0 for c in result["checks"].values())) is sound
    left = subprocess.run(["pgrep", "-f", str(work)], capture_output=True, text=True).stdout.split()
    assert not left, f"processes left behind: {left}"
    shutil.rmtree(work, ignore_errors=True)
