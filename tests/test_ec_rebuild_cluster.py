"""`ec.rebuild` over a cluster, as clusters run EC: two volumes encoded and
spread by the shell over four servers in four racks, one server lost, the
flagless command. Small sizes, on the CPU, compared with a plain reference
(striping as arithmetic on the original `.dat`, numpy GF(2^8) of
`benchmark/reference/gf8_ref.py`, which imports nothing of the program).

Also: ONE definition of where a rebuild lands. The shell (`pick_rebuilder`) and
the master's scheduler both choose through `placement.pick_rebuild_target`,
whose first key is "runs a device codec", learnt from each server's heartbeat.
"""

import importlib.util
import io
import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu import stats
from seaweedfs_tpu.cluster.client import MasterClient
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.ec import placement, stripe
from seaweedfs_tpu.ec.fleet import RepairScheduler
from seaweedfs_tpu.obs import trace
from seaweedfs_tpu.ops.rs_codec import new_encoder
from seaweedfs_tpu.pb import Heartbeat
from seaweedfs_tpu.shell import CommandEnv, ShellError, command_ec, run_script
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "gf8_ref", os.path.join(ROOT, "benchmark", "reference", "gf8_ref.py"))
gf8_ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(gf8_ref)

LARGE, SMALL = 16384, 4096
DATA, PARITY = 10, 4
VIDS = (1, 2)
JAX_BRIEF = {"backend": "jax", "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def _wait_for(cond, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timeout waiting for {msg}")


def _write_volume(directory, vid, seed):
    """A sealed volume of seeded needles, under 10 x LARGE so that every row
    is a small-block row. -> [(fid, payload)]"""
    rng = np.random.default_rng([seed, vid])
    out = []
    with Volume(directory, vid) as v:
        for key in range(1, 25):
            payload = rng.bytes(int(rng.integers(500, 6000)))
            cookie = int(rng.integers(0, 1 << 32))
            v.write_needle(Needle(cookie=cookie, id=key, data=payload))
            out.append((f"{vid},{key:x}{cookie:08x}", payload))
    return out


def _reference_shards(dat: bytes) -> list[bytes]:
    """All 14 shards of a `.dat` of small-block rows, by the striping rule and
    the reference's parity."""
    assert len(dat) < DATA * LARGE
    rows = -(-len(dat) // (DATA * SMALL))
    cells = np.zeros(rows * DATA * SMALL, dtype=np.uint8)
    cells[:len(dat)] = np.frombuffer(dat, dtype=np.uint8)
    cells = cells.reshape(rows, DATA, SMALL)
    pm = gf8_ref.parity_matrix(DATA, PARITY)
    shards = [cells[:, s, :].tobytes() for s in range(DATA)]
    parity = [gf8_ref.gf_mat_vec(pm, cells[r]) for r in range(rows)]
    shards += [b"".join(parity[r][p].tobytes() for r in range(rows)) for p in range(PARITY)]
    return shards


class Cluster:
    """master + four volume servers, each a rack of its own; server 0 starts
    with both volumes. `reports[i]` is what server i's heartbeat says of its
    codec: "jax" (a real jax encoder, on the CPU here), "host" (numpy) or
    "nothing" (a server that predates the report)."""

    def __init__(self, tmp_path, reports, vids=VIDS):
        self.vids = vids
        self.master = MasterServer(port=0, reap_interval=3600)
        self.master.start()
        self.dirs = [str(tmp_path / f"srv{i}") for i in range(4)]
        for d in self.dirs:
            os.makedirs(d)
        self.needles = {vid: _write_volume(self.dirs[0], vid, seed=28) for vid in vids}
        self.reference = {}
        for vid in vids:
            with open(os.path.join(self.dirs[0], f"{vid}.dat"), "rb") as f:
                self.reference[vid] = _reference_shards(f.read())
        self.servers = [self._server(i, r) for i, r in enumerate(reports)]
        self.client = MasterClient(self.master.address)
        self.env = CommandEnv(self.master.address)
        _wait_for(lambda: len(self.master.topology.nodes) == 4, msg="four servers joined")

    def _server(self, i, report):
        vs = VolumeServer(
            [self.dirs[i]], self.master.address, heartbeat_interval=0.2, rack=f"r{i}",
            max_volume_count=20,
            encoder=new_encoder(backend="jax" if report == "jax" else "numpy"),
        )
        if report == "nothing":
            vs._ec_backend_wire = dict
        vs.start()
        return vs

    def shell(self, script):
        out = io.StringIO()
        run_script(self.env, script, out)
        return out.getvalue()

    def encode_and_spread(self):
        self.shell("lock; " + "; ".join(
            f"ec.encode -volumeId {v} -force -largeBlockSize {LARGE} -smallBlockSize {SMALL}"
            for v in self.vids) + "; unlock")

    def held(self, vid):
        """url -> shard ids, as the master lists them."""
        out = {}
        for sid, nodes in self.master.topology.lookup_ec_shards(vid).items():
            for n in nodes:
                out.setdefault(n.url, set()).add(sid)
        return out

    def lose(self, i):
        victim = self.servers[i]
        victim.stop()
        _wait_for(lambda: victim.url not in self.master.topology.nodes
                  and all(victim.url not in self.held(v) for v in self.vids), msg="the master dropped the lost server")

    def close(self):
        self.env.close()
        self.client.close()
        for vs in self.servers:
            try:
                vs.stop()
            except Exception:  # noqa: BLE001 — the lost one is stopped already
                pass
        self.master.stop()


@pytest.fixture
def make_cluster(tmp_path, monkeypatch):
    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    monkeypatch.setenv("WEEDTPU_TRACE_SAMPLE", "1.0")
    made = []

    def make(reports, vids=VIDS):
        c = Cluster(tmp_path, reports, vids)
        made.append(c)
        return c

    yield make
    for c in made:
        c.close()


def _rebuilders(output):
    """volume -> the url `ec.rebuild`'s output names as its rebuilder."""
    return {int(v): url for v, url in
            re.findall(r"^ec\.rebuild volume (\d+): rebuilt \[[0-9, ]*\] on (\S+)", output, re.M)}


@pytest.mark.parametrize("lost", [0, 1, 2, 3])
def test_one_server_lost_is_rebuilt_on_the_device_server(make_cluster, lost):
    """Each server in turn is lost. The server that reports a device codec is
    the rebuilder for both volumes, whatever it holds; every lost shard comes
    back byte-identical to the reference's; every needle reads back; the gather
    moved exactly the files the rebuilder lacked and left none behind."""
    device = 1 if lost != 1 else 2
    reports = ["host"] * 4
    reports[device] = "jax"
    c = make_cluster(reports)
    c.encode_and_spread()
    spread = {vid: c.held(vid) for vid in VIDS}
    for vid in VIDS:
        assert sorted(len(s) for s in spread[vid].values()) == [3, 3, 4, 4]
        assert sorted(s for ss in spread[vid].values() for s in ss) == list(range(14))
    urls = [vs.url for vs in c.servers]
    assert all(sum(len(spread[vid][u]) for vid in VIDS) == 7 for u in urls)
    # the shards as ec.encode wrote them are the reference's
    for vid in VIDS:
        for i, u in enumerate(urls):
            for s in spread[vid][u]:
                with open(stripe.shard_file_name(os.path.join(c.dirs[i], str(vid)), s), "rb") as f:
                    assert f.read() == c.reference[vid][s], (vid, s)

    c.lose(lost)
    rebuilder = c.servers[device]
    base = {vid: os.path.join(c.dirs[device], str(vid)) for vid in VIDS}
    lacked = {vid: set(range(14)) - spread[vid][urls[lost]] - spread[vid][rebuilder.url] for vid in VIDS}
    expect_bytes = sum(len(c.reference[vid][s]) for vid in VIDS for s in lacked[vid])
    pulled0 = stats.EcCopyBytes.labels("pulled").value
    served0 = stats.EcCopyBytes.labels("served").value
    secs0 = stats.RpcServerSeconds.labels("VolumeEcShardsCopy").sum
    runs0 = stats.EcRebuildRuns.labels("jax").value
    host_runs0 = stats.EcRebuildRuns.labels("numpy").value
    trace.RING.clear()

    out = c.shell("lock; ec.rebuild; unlock")

    assert _rebuilders(out) == {vid: rebuilder.url for vid in VIDS}, out
    assert stats.EcRebuildRuns.labels("jax").value - runs0 == len(VIDS)
    assert stats.EcRebuildRuns.labels("numpy").value == host_runs0
    assert stats.EcCopyBytes.labels("pulled").value - pulled0 == expect_bytes
    assert stats.EcCopyBytes.labels("served").value - served0 == expect_bytes
    assert stats.RpcServerSeconds.labels("VolumeEcShardsCopy").sum > secs0
    for vid in VIDS:
        lost_ids = spread[vid][urls[lost]]
        for s in lost_ids:
            with open(stripe.shard_file_name(base[vid], s), "rb") as f:
                assert f.read() == c.reference[vid][s], f"volume {vid} shard {s} differs from the reference"
        # no survivor copy left behind, nothing half-copied
        assert set(stripe.find_local_shards(base[vid])) == spread[vid][rebuilder.url] | lost_ids
        assert sorted(c.master.topology.lookup_ec_shards(vid)) == list(range(14))
    assert not [n for n in os.listdir(c.dirs[device]) if n.endswith(".cpy")]
    for vid in VIDS:
        for fid, payload in c.needles[vid]:
            assert c.client.read(fid) == payload
    # every new span is recorded, under the RPC it belongs to
    by_method = {}
    for t in trace.RING.snapshot(kind="rpc.server", limit=100000):
        names = {s["name"] for s in trace.iter_spans(t)}
        by_method.setdefault(t["root"]["attrs"].get("method"), set()).update(names)
    assert {"ec.copy", "ec.copy.file", "ec.copy.fsync"} <= by_method["VolumeEcShardsCopy"]
    assert "ec.copy.serve" in by_method["VolumeEcShardFileCopy"]
    assert {"ec.copy", "ec.copy.file", "ec.copy.fsync", "ec.copy.serve"} <= set(trace.SPAN_NAMES)


@pytest.mark.parametrize("reports", ["host", "nothing"])
def test_without_a_device_report_the_choice_is_the_old_one(make_cluster, reports):
    """No server reports a device codec (host codecs, or servers that report
    nothing at all): the rebuilder is a survivor holding the most shards of the
    stripe, as before, and the scheduler's view of the same topology gives the
    same node."""
    c = make_cluster([reports] * 4)
    c.encode_and_spread()
    spread = {vid: c.held(vid) for vid in VIDS}
    c.lose(3)
    sched = RepairScheduler(c.master, max_inflight=1, batch=4, scan_interval=60.0, settle=0.0)
    nodes, registry, domains, _, _ = sched._topology_view()
    assert all(not n["ec_backend"] or n["ec_backend"]["backend"] == "numpy" for n in nodes)
    out = c.shell("lock; ec.rebuild; unlock")
    chosen = _rebuilders(out)
    lost_url = c.servers[3].url
    for vid in VIDS:
        survivors = {u: len(s) for u, s in spread[vid].items() if u != lost_url}
        assert survivors[chosen[vid]] == max(survivors.values())
        missing = sorted(spread[vid][lost_url])
        target = placement.pick_rebuild_target(nodes, registry[vid], domains, missing, PARITY)
        assert target["url"] == chosen[vid]
        for s in missing:
            base = os.path.join(c.dirs[[vs.url for vs in c.servers].index(chosen[vid])], str(vid))
            with open(stripe.shard_file_name(base, s), "rb") as f:
                assert f.read() == c.reference[vid][s]


# -- the two-volume pipeline of the volumes that need copies ----------------------


class Recorded:
    """`env.vs_call`, recording [start, end, method, volume, address] of every
    call the shell makes; `before(method, volume)` runs first (it may sleep or
    raise), `watch()` at each call's start and end."""

    def __init__(self, env, before=None, watch=None):
        self.calls, self._send, self._before, self._watch = [], env.vs_call, before, watch
        self._lock = threading.Lock()

    def __call__(self, addr, method, req, timeout=300):
        row = [time.monotonic(), None, method, int(req.get("volume_id", 0)), addr]
        with self._lock:
            self.calls.append(row)
        try:
            if self._watch:
                self._watch()
            if self._before:
                self._before(method, row[3])
            return self._send(addr, method, req, timeout=timeout)
        finally:
            row[1] = time.monotonic()
            if self._watch:
                self._watch()

    def of(self, method, vid=None):
        """-> [(start, end, volume)] of the calls of `method`, by start."""
        return sorted((a, b, v) for a, b, m, v, _ in self.calls if m == method and vid in (None, v))


def _slow_rebuilds(monkeypatch, seconds=0.15):
    """Every server-side whole-volume rebuild takes `seconds` longer, so that
    what the shell sends beside it arrives while it runs."""
    real = stripe.rebuild_ec_files

    def slow(*a, **kw):
        time.sleep(seconds)
        return real(*a, **kw)

    monkeypatch.setattr(stripe, "rebuild_ec_files", slow)


def _shell_root(command="ec.rebuild"):
    """The command's own span: under the script's root (`shell -c` is ONE trace)."""
    (root,) = [s for t in trace.RING.snapshot(kind="shell.script", limit=1000) for s in trace.iter_spans(t)
               if s["name"] == "shell.command" and s["attrs"].get("command") == command]
    return root


def _nothing_temporary(c, rebuilder_dir, own):
    """No `.cpy` on any server; on the rebuilder, of each volume, its own
    shards and what was rebuilt there, never a survivor's copy."""
    assert not [n for d in c.dirs for n in os.listdir(d) if n.endswith(".cpy")]
    for vid, (mine, lost) in own.items():
        assert set(stripe.find_local_shards(os.path.join(rebuilder_dir, str(vid)))) - lost == mine, vid


def _lost_server_setup(c, device=1, lost=3):
    """Encode, spread, lose server `lost`. -> (the rebuilder's directory,
    {volume: (the rebuilder's own shards, the lost ones)})."""
    c.encode_and_spread()
    spread = {vid: c.held(vid) for vid in c.vids}
    lost_url, device_url = c.servers[lost].url, c.servers[device].url
    c.lose(lost)
    return c.dirs[device], {vid: (spread[vid][device_url], spread[vid][lost_url]) for vid in c.vids}


def test_two_volumes_the_second_is_gathered_beside_the_first_rebuild(make_cluster, monkeypatch):
    """Both volumes need copies: volume 2's `VolumeEcShardsCopy` calls start
    before volume 1's `VolumeEcShardsRebuild` ends, and after volume 1's own
    copies have all landed; the shell and the rebuilder both say so."""
    c = make_cluster(["host", "jax", "host", "host"])
    rebuilder_dir, own = _lost_server_setup(c)
    _slow_rebuilds(monkeypatch)
    rec = Recorded(c.env)
    monkeypatch.setattr(c.env, "vs_call", rec)
    beside0 = stats.EcCopyBesideRebuild.value
    trace.RING.clear()

    out = c.shell("lock; ec.rebuild; unlock")

    assert _rebuilders(out) == {vid: c.servers[1].url for vid in VIDS}, out
    assert out.endswith("ec.rebuild: 2 volumes with copies, 1 gathered beside a rebuild\ncluster unlocked\n"), out
    assert _shell_root()["attrs"]["overlapped"] == 1
    (r1_start, r1_end, _), (r2_start, _, _) = rec.of("VolumeEcShardsRebuild")
    assert [v for _, _, v in rec.of("VolumeEcShardsRebuild")] == [1, 2]
    copies1, copies2 = rec.of("VolumeEcShardsCopy", 1), rec.of("VolumeEcShardsCopy", 2)
    assert len(copies1) >= 2 and len(copies2) >= 2
    assert max(end for _, end, _ in copies1) <= min(start for start, _, _ in copies2)  # one gather at a time
    assert max(end for _, end, _ in copies1) <= r1_start
    assert max(start for start, _, _ in copies2) < r1_end, "volume 2 was gathered after volume 1's rebuild"
    assert max(end for _, end, _ in copies2) <= r2_start
    assert stats.EcCopyBesideRebuild.value - beside0 == len(copies2)
    assert "\nweedtpu_ec_copy_beside_rebuild_total " in stats.REGISTRY.expose()
    for vid, (_, lost) in own.items():
        for s in lost:
            with open(stripe.shard_file_name(os.path.join(rebuilder_dir, str(vid)), s), "rb") as f:
                assert f.read() == c.reference[vid][s], f"volume {vid} shard {s} differs from the reference"
        assert sorted(c.master.topology.lookup_ec_shards(vid)) == list(range(14))
    _nothing_temporary(c, rebuilder_dir, own)


def test_four_volumes_never_more_than_two_volumes_copies_on_the_rebuilder(make_cluster, monkeypatch):
    """Four volumes that need copies: whenever the shell sends or ends an RPC,
    at most two volumes have temporary copies (or a `.cpy`) on the rebuilder,
    and at some moment two have; the rebuilds run in plan order, one at a
    time, each after all its volume's copies; a volume's gather starts only
    once the volume two before it has been dropped."""
    vids = (1, 2, 3, 4)
    c = make_cluster(["host", "jax", "host", "host"], vids)
    rebuilder_dir, own = _lost_server_setup(c)
    _slow_rebuilds(monkeypatch, 0.1)
    seen = []

    def watch():
        names = os.listdir(rebuilder_dir)
        holding = set()
        for vid, (mine, lost) in own.items():
            there = {int(n[-2:]) for n in names if re.fullmatch(rf"{vid}\.ec\d\d", n)}
            if there - mine - lost or any(n.startswith(f"{vid}.") and n.endswith(".cpy") for n in names):
                holding.add(vid)
        seen.append(holding)

    rec = Recorded(c.env, watch=watch)
    monkeypatch.setattr(c.env, "vs_call", rec)
    trace.RING.clear()

    out = c.shell("lock; ec.rebuild; unlock")

    assert _rebuilders(out) == {vid: c.servers[1].url for vid in vids}, out
    assert out.endswith("ec.rebuild: 4 volumes with copies, 3 gathered beside a rebuild\ncluster unlocked\n"), out
    assert _shell_root()["attrs"]["overlapped"] == 3
    assert max(len(h) for h in seen) == 2, sorted(map(sorted, seen))
    assert all(max(h) - min(h) <= 1 for h in seen if h)  # and only ever neighbours of the plan
    rebuilds = rec.of("VolumeEcShardsRebuild")
    assert [v for _, _, v in rebuilds] == list(vids)
    assert all(a[1] <= b[0] for a, b in zip(rebuilds, rebuilds[1:])), "two rebuilds at once"
    drops = {v: (a, b) for a, b, v in rec.of("VolumeEcShardsDelete")}
    for start, end, vid in rebuilds:
        copies = rec.of("VolumeEcShardsCopy", vid)
        assert max(e for _, e, _ in copies) <= start and end <= drops[vid][0]
        if vid + 1 in own:  # the next volume's gather starts beside this rebuild, never earlier
            first = min(s for s, _, _ in rec.of("VolumeEcShardsCopy", vid + 1))
            assert max(e for _, e, _ in copies) <= first < end
        if vid - 2 in own:
            assert drops[vid - 2][1] <= min(s for s, _, _ in copies)
    for vid, (_, lost) in own.items():
        for s in lost:
            with open(stripe.shard_file_name(os.path.join(rebuilder_dir, str(vid)), s), "rb") as f:
                assert f.read() == c.reference[vid][s]
        assert sorted(c.master.topology.lookup_ec_shards(vid)) == list(range(14))
    _nothing_temporary(c, rebuilder_dir, own)


@pytest.mark.parametrize("case", ["rebuild_fails_with_the_next_gather_in_flight", "a_pull_fails_half_way"])
def test_a_failure_in_the_pipeline_leaves_nothing_temporary(make_cluster, monkeypatch, case):
    """Volume 1's rebuild fails while volume 2's gather is in flight: the
    gather is awaited and what it landed is dropped before the error, which
    names volume 1, goes up; volume 2 is as it was. Or one pull of volume 2's
    gather fails after its first file landed: volume 1 completes and is
    dropped, then the error names volume 2, whose landed copies are gone."""
    c = make_cluster(["host", "jax", "host", "host"])
    rebuilder_dir, own = _lost_server_setup(c)
    _slow_rebuilds(monkeypatch)
    if case == "rebuild_fails_with_the_next_gather_in_flight":
        def before(method, vid):
            if method == "VolumeEcShardsCopy" and vid == 2:
                time.sleep(0.3)  # still on its way when the rebuild has failed
            if method == "VolumeEcShardsRebuild" and vid == 1:
                time.sleep(0.05)
                raise RuntimeError("the rebuilder refused")
        failed, whole = 1, 2
    else:
        before = None
        pulls = []
        real = VolumeServer._pull_ec_file

        def pull(client, vid, collection, base, ext):
            if vid == 2:
                pulls.append(ext)
                if len(pulls) == 2:
                    raise OSError("the stream broke")
            return real(client, vid, collection, base, ext)

        monkeypatch.setattr(VolumeServer, "_pull_ec_file", staticmethod(pull))
        failed, whole = 2, 1
    rec = Recorded(c.env, before=before)
    monkeypatch.setattr(c.env, "vs_call", rec)
    trace.RING.clear()
    out = io.StringIO()

    with pytest.raises(ShellError, match=rf"volume {failed}\b") as err:
        run_script(c.env, "lock; ec.rebuild", out)
    c.shell("unlock")

    out = out.getvalue()
    assert f"volume {whole}" not in str(err.value)
    assert out.endswith("ec.rebuild: 2 volumes with copies, 1 gathered beside a rebuild\n"), out
    assert _shell_root()["attrs"]["overlapped"] == 1
    # every copy asked for was dropped after it had ended, landed or not
    for vid in VIDS:
        copies, (drop,) = rec.of("VolumeEcShardsCopy", vid), rec.of("VolumeEcShardsDelete", vid)
        assert max(e for _, e, _ in copies) <= drop[0]
    _nothing_temporary(c, rebuilder_dir, own)
    assert not set(stripe.find_local_shards(os.path.join(rebuilder_dir, str(failed)))) & own[failed][1]
    assert _rebuilders(out) == ({} if failed == 1 else {1: c.servers[1].url})
    if whole == 1:  # rebuilt, byte for byte, and listed
        for s in own[1][1]:
            with open(stripe.shard_file_name(os.path.join(rebuilder_dir, "1"), s), "rb") as f:
                assert f.read() == c.reference[1][s]
        assert sorted(c.master.topology.lookup_ec_shards(1)) == list(range(14))
    else:  # as it was: its survivors listed where they were, the rebuilder serving its own
        assert sorted(c.master.topology.lookup_ec_shards(2)) == sorted(set(range(14)) - own[2][1])
        assert c.held(2)[c.servers[1].url] == own[2][0]
    for vid in VIDS:  # what is lost still decodes from what is there
        for fid, payload in c.needles[vid][:6]:
            assert c.client.read(fid) == payload
    # the command can be given again, and then succeeds
    monkeypatch.undo()
    assert _rebuilders(c.shell("lock; ec.rebuild; unlock")) == (
        {1: c.servers[1].url, 2: c.servers[1].url} if failed == 1 else {2: c.servers[1].url})
    assert all(sorted(c.master.topology.lookup_ec_shards(v)) == list(range(14)) for v in VIDS)


@pytest.mark.parametrize("case", ["a_lone_volume_with_copies", "all_local"])
def test_the_routes_the_pipeline_leaves_alone(make_cluster, monkeypatch, case):
    """A lone volume that needs copies is a pipeline of one: the RPCs of
    before in the order of before (its copies, the single-volume rebuild, the
    drop). Volumes whose survivors are all on their rebuilder still share ONE
    `VolumeEcShardsRebuildBatch`, with nothing copied and nothing beside."""
    c = make_cluster(["host", "jax", "host", "host"])
    if case == "all_local":
        for i in (1, 2, 3):  # only server 0 is there when the volumes are encoded
            c.lose(i)
        c.encode_and_spread()
        assert all(c.held(v) == {c.servers[0].url: set(range(14))} for v in VIDS)
        victim, lost = c.servers[0], {1: [0, 3, 11], 2: [5]}
    else:
        c.encode_and_spread()
        victim = c.servers[3]
        lost = {1: sorted(c.held(1)[victim.url])}
    for vid, shards in lost.items():
        c.env.vs_call(victim.grpc_address, "VolumeEcShardsDelete",
                      {"volume_id": vid, "collection": "", "shard_ids": shards})
    _wait_for(lambda: all(victim.url not in c.held(v).get(s, ()) and s not in c.master.topology.lookup_ec_shards(v)
                          for v, ss in lost.items() for s in ss), msg="the master dropped the lost shards")
    rec = Recorded(c.env)
    monkeypatch.setattr(c.env, "vs_call", rec)
    beside0 = stats.EcCopyBesideRebuild.value
    trace.RING.clear()

    out = c.shell("lock; ec.rebuild; unlock")

    sent = [m for _, _, m, _, _ in sorted(rec.calls) if m != "VolumeStatus"]
    if case == "all_local":
        assert sent == ["VolumeEcShardsRebuildBatch"], sent
        assert f"ec.rebuild batch on {victim.url}: 2 volumes in 2 signature groups\n" in out
        assert out.endswith("ec.rebuild: 0 volumes with copies, 0 gathered beside a rebuild\ncluster unlocked\n"), out
        rebuilder = 0
    else:
        copies = sent.count("VolumeEcShardsCopy")
        assert copies >= 2 and sent == ["VolumeEcShardsCopy"] * copies + ["VolumeEcShardsRebuild", "VolumeEcShardsDelete"]
        (rebuild,), (drop,) = rec.of("VolumeEcShardsRebuild"), rec.of("VolumeEcShardsDelete")
        assert max(e for _, e, _ in rec.of("VolumeEcShardsCopy")) <= rebuild[0] and rebuild[1] <= drop[0]
        assert out.endswith("ec.rebuild: 1 volumes with copies, 0 gathered beside a rebuild\ncluster unlocked\n"), out
        rebuilder = [vs.url for vs in c.servers].index(_rebuilders(out)[1])  # wherever the rule puts it
    assert _shell_root()["attrs"]["overlapped"] == 0
    assert stats.EcCopyBesideRebuild.value == beside0
    assert _rebuilders(out) == {vid: c.servers[rebuilder].url for vid in lost}, out
    for vid, shards in lost.items():
        for s in shards:
            with open(stripe.shard_file_name(os.path.join(c.dirs[rebuilder], str(vid)), s), "rb") as f:
                assert f.read() == c.reference[vid][s]
        assert sorted(c.master.topology.lookup_ec_shards(vid)) == list(range(14))
    assert not [n for d in c.dirs for n in os.listdir(d) if n.endswith(".cpy")]


def test_the_rebuilds_in_flight_are_counted_under_many_callers(make_cluster):
    """What `VolumeEcShardsCopy` reads to say it ran beside a rebuild: sixteen
    threads of failing rebuilds (a volume nobody has) leave every one begun
    and none in flight."""
    c = make_cluster(["host"] * 4)
    vs = c.servers[0]
    begun0 = vs._ec_rebuilds[0]
    failures = []

    def hammer():
        for _ in range(25):
            try:
                vs._rpc_ec_rebuild({"volume_id": 999}, None)
            except Exception:  # noqa: BLE001 — no such volume: the count must still come down
                failures.append(1)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=hammer) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(failures) == 16 * 25
    assert vs._ec_rebuilds == (begun0 + 16 * 25, 0)


# -- the rule itself ------------------------------------------------------------


def _nodes(racks=("r0", "r1", "r2")):
    return [{"url": f"n{i}:80", "data_center": "dc", "rack": r, "ec_load": 7} for i, r in enumerate(racks)]


def _stripe(nodes, counts):
    """holders of one stripe: node i holds counts[i] shards; the rest are lost."""
    holders, sid = {}, 0
    for n, k in zip(nodes, counts):
        for _ in range(k):
            holders[sid] = [n["url"]]
            sid += 1
    return holders, list(range(sid, 14))


# One server of four is lost, so no surviving rack has headroom for the rebuilt
# shards: every survivor is a candidate, as in the deployment.
PICK_CASES = {
    # name: (shards held per node, which node reports what, expected node[, racks])
    "device_beats_more_shards": ((4, 4, 3), {2: JAX_BRIEF}, 2),
    "device_with_no_shard_of_the_stripe": ((4, 4, 3, 0), {3: JAX_BRIEF}, 3, ("r0", "r1", "r2", "r0")),
    "two_devices_most_shards_wins": ((3, 4, 4), {0: JAX_BRIEF, 2: JAX_BRIEF}, 2),
    "host_codec_is_no_device": ((3, 4, 4), {0: {"backend": "xorsched"}}, 1),
    "device_backend_without_a_device": ((3, 4, 4), {0: {"backend": "jax"}}, 1),
    "mesh_counts_as_a_device_codec": ((4, 3, 4), {1: {"backend": "mesh", "device": {"platform": "tpu"}}}, 1),
    "nobody_reports": ((3, 4, 4), {}, 1),
}


@pytest.mark.parametrize("case", sorted(PICK_CASES))
def test_pick_rebuild_target_prefers_a_device_codec(case):
    counts, briefs, want, *racks = PICK_CASES[case]
    nodes = _nodes(*racks)
    for i, b in briefs.items():
        nodes[i]["ec_backend"] = b
    holders, missing = _stripe(nodes, counts)
    domains = {n["url"]: placement.domain_of(n) for n in nodes}
    got = placement.pick_rebuild_target(nodes, holders, domains, missing, PARITY)
    assert got["url"] == nodes[want]["url"]
    # a cluster that reports nothing, or only host codecs, ranks as the rule
    # did before: most shards of the stripe, then url
    bare = [{k: v for k, v in n.items() if k != "ec_backend"} for n in nodes]
    host = [dict(n, ec_backend={"backend": "numpy"}) for n in bare]
    before = min(bare, key=lambda n: (-sum(n["url"] in h for h in holders.values()), n["url"]))
    assert (placement.pick_rebuild_target(bare, holders, domains, missing, PARITY)["url"]
            == placement.pick_rebuild_target(host, holders, domains, missing, PARITY)["url"]
            == before["url"])


# What a tool child must not have loaded when its commands run: the codec and
# its numpy, the offline tools that import them, and the servers and stores of
# the shell families its command line never names.
NOT_IN_THE_CHILD = (
    "numpy", "jax", "sqlite3", "seaweedfs_tpu.ec.stripe", "seaweedfs_tpu.ops.rs_codec",
    "seaweedfs_tpu.command.local", "seaweedfs_tpu.filer", "seaweedfs_tpu.s3api", "seaweedfs_tpu.mq",
    # the hand-over of a script's trace is gRPC on the open channel for this: no HTTP client in the child
    "http.client", "urllib.request",
)
# the package's own modules in a `lock; ec.rebuild; unlock` child: the trace it
# hands over (PR 42) brought none, the door of the checkpoint's marks (PR 43) one
# (the installation's own add to them: 242 modules in all on the chip's host)
PACKAGE_MODULES_IN_THE_EC_CHILD = {
    "seaweedfs_tpu", "seaweedfs_tpu.cluster", "seaweedfs_tpu.cluster.client", "seaweedfs_tpu.command",
    "seaweedfs_tpu.command.servers", "seaweedfs_tpu.ec", "seaweedfs_tpu.ec.constants", "seaweedfs_tpu.ec.placement",
    "seaweedfs_tpu.ec.shard_bits", "seaweedfs_tpu.obs", "seaweedfs_tpu.obs.trace", "seaweedfs_tpu.pb",
    "seaweedfs_tpu.pb.wire", "seaweedfs_tpu.rpc", "seaweedfs_tpu.security", "seaweedfs_tpu.security.guard",
    "seaweedfs_tpu.security.jwt", "seaweedfs_tpu.security.tls", "seaweedfs_tpu.shell",
    "seaweedfs_tpu.shell.command_cluster", "seaweedfs_tpu.shell.command_ec", "seaweedfs_tpu.stats",
    "seaweedfs_tpu.utils", "seaweedfs_tpu.utils.config", "seaweedfs_tpu.utils.door", "seaweedfs_tpu.utils.glog",
}
# `python -m seaweedfs_tpu <argv>` is `__main__.main(argv)`. The master is real
# and has no volume server: `ec.encode` / `ec.decode` of a volume nobody holds
# get past their first RPCs and fail there, which is far enough.
_CHILD = """
import sys
from seaweedfs_tpu.__main__ import main
try:
    rc = main(sys.argv[1:])
except Exception as e:
    rc = f"{type(e).__name__}: {e}"
print("MODULES", *sorted(sys.modules))
print("RC", rc)
"""
CHILD_CASES = {
    # case: (argv after `shell -master <address>`, or the child's whole code; shell families loaded; RC)
    "import-command_ec-and-placement": (
        "import sys; from seaweedfs_tpu.shell import command_ec; from seaweedfs_tpu.ec import placement; "
        "print('MODULES', *sorted(sys.modules)); print('RC 0')", {"command_ec"}, "0"),
    "lock-unlock": (["-c", "lock; unlock"], {"command_cluster"}, "0"),
    "ec.rebuild": (["-c", "lock; ec.rebuild; unlock"], {"command_cluster", "command_ec"}, "0"),
    "ec.encode": (["-c", "lock; ec.encode -volumeId 1; unlock"], {"command_cluster", "command_ec"},
                  "ShellError: volume 1 not found"),
    "ec.decode": (["-c", "lock; ec.decode -volumeId 1; unlock"], {"command_cluster", "command_ec"},
                  "ShellError: ec volume 1 not found"),
    "volume.list": (["-c", "volume.list"], {"command_volume"}, "0"),
    "repl-exit": ([], set(), "0"),
}


@pytest.mark.parametrize("case", sorted(CHILD_CASES))
def test_a_tool_child_imports_what_its_command_line_names(case):
    """The child the benchmark and operators time enters through `__main__`:
    neither the CLI's table nor the shell's may load numpy, the GF tables
    (`ec.placement` repeats the codec's tuple for that), `command/local.py`, or
    a family of commands the line does not name. Names, not milliseconds."""
    from seaweedfs_tpu.ops import rs_codec

    assert placement.DEVICE_BACKENDS == rs_codec.DEVICE_BACKENDS
    argv, families, rc = CHILD_CASES[case]
    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    try:
        cmd = ([sys.executable, "-c", argv] if isinstance(argv, str)
               else [sys.executable, "-c", _CHILD, "shell", "-master", master.address, *argv])
        done = subprocess.run(cmd, cwd=ROOT, timeout=120, capture_output=True, text=True, input="exit\n")
    finally:
        master.stop()
    assert done.returncode == 0, done.stderr
    modules, said = done.stdout.split("MODULES ", 1)[1].split("\nRC ")
    assert said.startswith(rc), (said, done.stderr)
    loaded = set(modules.split())
    assert "seaweedfs_tpu" in loaded
    assert not [m for m in loaded for bad in NOT_IN_THE_CHILD if m == bad or m.startswith(bad + ".")]
    assert {m.rsplit(".", 1)[1] for m in loaded if m.startswith("seaweedfs_tpu.shell.command_")} == families
    if case == "ec.rebuild":
        ours = {m for m in loaded if m.split(".")[0] == "seaweedfs_tpu" and m != "seaweedfs_tpu.__main__"}
        # `ec.placement` loads when a volume is planned: this master has none
        assert ours | {"seaweedfs_tpu.ec.placement"} == PACKAGE_MODULES_IN_THE_EC_CHILD


def test_the_domain_cap_still_comes_before_the_device():
    """A device codec ranks nodes; it does not make an illegal placement legal:
    where some rack has headroom for the rebuilt shards, the target is there."""
    nodes = _nodes([f"r{i}" for i in range(8)])
    nodes[0]["ec_backend"] = JAX_BRIEF
    domains = {n["url"]: placement.domain_of(n) for n in nodes}
    holders = {s: [nodes[s % 4]["url"]] for s in range(12)}  # racks r0-r3 hold 3 each
    got = placement.pick_rebuild_target(nodes, holders, domains, [12, 13], PARITY)
    assert got["url"] in {n["url"] for n in nodes[4:]}
    nodes[5]["ec_backend"] = JAX_BRIEF
    assert placement.pick_rebuild_target(nodes, holders, domains, [12, 13], PARITY)["url"] == nodes[5]["url"]


@pytest.mark.parametrize("device", [None, 0, 2])
def test_shell_and_scheduler_choose_the_same_target(device):
    """The same topology, seen through VolumeList by the shell and through the
    topology by the scheduler, gives one target: both learn the codec from the
    heartbeat."""
    from seaweedfs_tpu.ec.shard_bits import EcVolumeInfo, ShardBits

    m = MasterServer(port=0, reap_interval=3600, http_port=None)
    try:
        spread = {0: [0, 4, 8, 12], 1: [1, 5, 9, 13], 2: [2, 6, 10]}  # 3, 7, 11 were on a lost server
        for i, sids in spread.items():
            info = EcVolumeInfo(volume_id=9, shard_bits=ShardBits.from_ids(sids), shard_size=1000,
                                data_shards=DATA, total_shards=DATA + PARITY).to_dict()
            m.topology.process_heartbeat(Heartbeat(
                ip="127.0.0.1", port=8000 + i, grpc_port=18000 + i, rack=f"r{i}", data_center="dc",
                max_volume_count=30, ec_shards=[info],
                ec_backend=dict(JAX_BRIEF) if i == device else {"backend": "native"}))
        sched = RepairScheduler(m, max_inflight=1, batch=4, scan_interval=60.0, settle=0.0)
        nodes, registry, domains, _, _ = sched._topology_view()
        missing = [3, 7, 11]
        theirs = placement.pick_rebuild_target(nodes, registry[9], domains, missing, PARITY)
        shell_nodes = [dict(nd, data_center=dc, rack=rack)
                       for dc, racks in m.topology.to_dict()["data_centers"].items()
                       for rack, nds in racks.items() for nd in nds]
        ours = command_ec.pick_rebuilder(shell_nodes, command_ec._shard_holders(shell_nodes, 9), missing, PARITY)
        assert ours["url"] == theirs["url"]
        if device is not None:
            assert ours["url"] == f"127.0.0.1:{8000 + device}"
        else:
            assert ours["url"] == "127.0.0.1:8000"  # most shards, then url: as before
    finally:
        m._server.stop()


def test_the_heartbeat_carries_the_backend_on_both_wires():
    from seaweedfs_tpu.pb import MASTER_SERVICE, wire

    hb = Heartbeat(ip="127.0.0.1", port=1, grpc_port=2, ec_backend={
        "backend": "jax", "source": "platform", "requested": "auto",
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}})
    assert Heartbeat.from_dict(hb.to_dict()).ec_backend == hb.ec_backend
    assert "ec_backend" not in Heartbeat(ip="127.0.0.1", port=1, grpc_port=2).to_dict()
    ser, de = wire.codec().request_serdes(MASTER_SERVICE, "Heartbeat")
    over_the_wire = Heartbeat.from_dict(de(ser(hb.to_dict()))).ec_backend
    assert {k: v for k, v in over_the_wire.items() if v} == hb.ec_backend
    assert placement.runs_device_codec({"ec_backend": over_the_wire})
    bare = Heartbeat.from_dict(de(ser(Heartbeat(ip="h", port=1, grpc_port=2).to_dict())))
    assert not placement.runs_device_codec({"ec_backend": bare.ec_backend})


@pytest.mark.parametrize("fault,sound", [("", True), ("flip_shard_byte", False), ("broken_apply", False)])
def test_the_benchmark_cell_rehearses_to_its_end_and_leaves_no_process(tmp_path, fault, sound):
    """`run.py --workload spread10p4.rebuild-serverlost --rehearse`: the whole
    cycle on the CPU with 8 MiB volumes, never a result. Sound, all checks
    pass; with the control's fault (a rebuilt shard altered on disk) or the
    timed path broken underneath (the device's apply), they do not. No peer
    outlives the run either way."""
    work = tmp_path / "tmp"
    work.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(work))
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
           "spread10p4.rebuild-serverlost", "--seed", str(2**31 + 29), "--seconds", "1", "--trace", "0",
           "--rehearse"]
    p = subprocess.run(cmd + (["--fault", fault] if fault else []),
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 1, p.stdout[-3000:] + p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["rehearse"] is True
    assert result["checks_ok"] is sound, p.stdout[-4000:]
    assert result["attempted"] >= 1
    # every command begun is in the rate, unless it failed (and then it is in `failed`)
    assert result["timed"]["ops"] == result["attempted"] - result["failed"]
    if sound:
        assert result["failed"] == 0 and result["timed"]["ops"] == result["attempted"]
        assert result["metrics"]["rebuild_MBps"]["value"] > 0
    # every peer is gone: no process still names this run's directories
    left = subprocess.run(["pgrep", "-f", str(work)], capture_output=True, text=True).stdout.split()
    assert not left, f"processes left behind: {left}"
    shutil.rmtree(work, ignore_errors=True)


# -- one command, one trace, kept (PR 42) -----------------------------------------


@pytest.fixture(scope="module")
def one_script(tmp_path_factory):
    """`lock; ec.rebuild; unlock` as a `-c` child runs it (the start marks of
    a process that IS the script), against four servers in this process, one
    lost, two volumes with copies. -> what the command left behind."""
    import types
    import urllib.request

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("WEEDTPU_TRACE", "on")
        mp.setenv("WEEDTPU_TRACE_SAMPLE", "1.0")
        mp.setenv("WEEDTPU_TRACE_RING", "100000")
        c = Cluster(tmp_path_factory.mktemp("one_script"), ["host", "jax", "host", "host"])
        try:
            _lost_server_setup(c)
            _slow_rebuilds(mp)
            trace.RING.clear()
            phases0 = stats.ShellCommandSeconds.labels("ec.rebuild", "rpc").total
            out = io.StringIO()
            now = time.monotonic()
            run_script(c.env, "lock; ec.rebuild; unlock", out,
                       started=(now - 0.30, now - 0.20, now - 0.12, now - 0.02))
            snap = trace.RING.snapshot(limit=100000)
            (script,) = [t for t in snap if t["kind"] == "shell.script"]
            joined = io.StringIO()
            run_script(c.env, f"ec.trace -traceId {script['trace_id']}", joined)
            http = f"127.0.0.1:{c.master.http_port}"
            with urllib.request.urlopen(f"http://{http}/debug/traces?kind=shell.script&limit=1000", timeout=10) as r:
                served = json.loads(r.read().decode())
            with urllib.request.urlopen(f"http://{http}/metrics", timeout=10) as r:
                metrics = r.read().decode()
            yield types.SimpleNamespace(
                c=c, out=out.getvalue(), snap=snap, script=script, joined=joined.getvalue(),
                served=served, metrics=metrics, phases0=phases0)
        finally:
            c.close()


def _commands(script):
    return [s for s in script["root"]["spans"] if s["name"] == "shell.command"]


def _descendants(span, name):
    return [s for s in trace.iter_spans({"root": span}) if s["name"] == name and s is not span]


def test_a_script_is_one_trace_under_one_id(one_script):
    """`lock`, `ec.rebuild` and `unlock` of one `shell -c` are ONE trace: one
    `shell.script` root, no `shell.command` root, and every RPC any server
    recorded while it ran carries its id."""
    kinds = [t["kind"] for t in one_script.snap]
    assert kinds.count("shell.script") == 1 and "shell.command" not in kinds
    assert {t["trace_id"] for t in one_script.snap} == {one_script.script["trace_id"]}
    methods = {t["root"]["attrs"]["method"] for t in one_script.snap if t["kind"] == "rpc.server"}
    assert {"LeaseAdminToken", "VolumeList", "VolumeEcShardsCopy", "VolumeEcShardFileCopy",
            "VolumeEcShardsRebuild", "VolumeEcShardsDelete", "ReleaseAdminToken"} <= methods
    assert "VolumeStatus" not in methods  # the geometry came with the one VolumeList
    assert "ReportTrace" not in methods  # the hand-over is outside the trace it carries


def test_the_masters_ring_holds_the_scripts_tree(one_script):
    """`shell.script` > `shell.start` + three `shell.command`, from the birth
    of the process; the plan is a span of its own; what the spans leave
    uncovered of the script's wall is under 5%."""
    script, root = one_script.script, one_script.script["root"]
    assert script["class"] == "shell" and root["attrs"] == {"script": "lock; ec.rebuild; unlock"}
    assert script["birth_unix_ns"] == script["unix_ns"] and abs(script["unix_ns"] / 1e9 - script["start"]) < 0.01
    assert [s["name"] for s in root["spans"]] == ["shell.start"] + ["shell.command"] * 3
    start = root["spans"][0]
    assert start["t_ms"] == 0 and 280 <= start["dur_ms"] <= 400
    assert (start["attrs"]["interp_ms"], start["attrs"]["import_ms"], start["attrs"]["connect_ms"]) == (
        pytest.approx(100, abs=0.01), pytest.approx(80, abs=0.01), pytest.approx(100, abs=0.01))
    assert start["attrs"]["modules"] == _commands(script)[0]["attrs"]["modules"]
    assert [s["attrs"]["command"] for s in _commands(script)] == ["lock", "ec.rebuild", "unlock"]
    rebuild = _commands(script)[1]
    (plan,) = [s for s in rebuild["spans"] if s["name"] == "shell.plan"]
    # ONE VolumeList (nodes, collections and geometry from it), before the first copy
    assert plan["attrs"] == {"volumes": 2, "rpcs": 1} and len(_descendants(plan, "rpc.client")) == 1
    first_copy = min(s["t_ms"] for s in _descendants(rebuild, "rpc.client")
                     if s["attrs"]["method"] == "VolumeEcShardsCopy")
    assert plan["t_ms"] + plan["dur_ms"] <= first_copy
    covered = sum(s["dur_ms"] for s in root["spans"])
    assert covered >= 0.95 * script["duration_s"] * 1e3


def test_a_command_has_as_many_rpc_client_spans_as_its_rpcs_says(one_script):
    for cmd, want in zip(_commands(one_script.script), (1, 9, 1)):
        clients = _descendants(cmd, "rpc.client")
        assert len(clients) == cmd["attrs"]["rpcs"] == want, cmd["attrs"]
        assert all(set(s["attrs"]) >= {"method", "target"} for s in clients)


def test_the_gather_workers_calls_say_their_volume_and_run_beside_the_rebuild(one_script):
    """The pool threads' copies attach to the command that queued them, say
    `thread=` and `volume=`; volume 2's begin with volume 1's rebuild and end
    before volume 2's own; the command's own thread made every other call."""
    rebuild = _commands(one_script.script)[1]
    clients = [s for s in rebuild["spans"] if s["name"] == "rpc.client"]  # direct children: the queuing span
    by = lambda method, vid: [s for s in clients if s["attrs"]["method"] == method  # noqa: E731
                              and s["attrs"].get("volume") == vid]
    copies1, copies2 = by("VolumeEcShardsCopy", 1), by("VolumeEcShardsCopy", 2)
    assert len(copies1) == 2 and len(copies2) == 2
    assert all("thread" in s["attrs"] for s in copies1 + copies2)
    assert not [s for s in _descendants(rebuild, "rpc.client")
                if "thread" in s["attrs"] and s["attrs"]["method"] != "VolumeEcShardsCopy"]
    (r1,), (r2,) = by("VolumeEcShardsRebuild", 1), by("VolumeEcShardsRebuild", 2)
    assert max(s["t_ms"] + s["dur_ms"] for s in copies1) <= r1["t_ms"]
    assert all(r1["t_ms"] - 5 <= s["t_ms"] < r1["t_ms"] + r1["dur_ms"] for s in copies2)
    assert max(s["t_ms"] + s["dur_ms"] for s in copies2) <= r2["t_ms"]
    assert rebuild["attrs"]["overlapped"] == 1


def test_every_rpc_server_root_lies_inside_its_rpc_client_by_the_wall_clock(one_script):
    """A root says `unix_ns`, the wall clock at its start; the script's spans
    are offsets from its own. Mapped so, each server's half of an RPC lies
    inside the shell's half of the same method, on every server."""
    script = one_script.script
    clients = {}
    for s in _descendants(script["root"], "rpc.client"):
        t0 = script["unix_ns"] + s["t_ms"] * 1e6
        clients.setdefault(s["attrs"]["method"], []).append((t0, t0 + s["dur_ms"] * 1e6))
    served = [t for t in one_script.snap if t["kind"] == "rpc.server"
              and t["root"]["attrs"]["method"] in clients]  # (a peer's VolumeEcShardFileCopy has no shell half)
    assert len(served) == 11  # one VolumeList and no VolumeStatus: three fewer than before PR 47
    for t in served:
        t0, t1 = t["unix_ns"], t["unix_ns"] + t["duration_s"] * 1e9
        sticks_out = min(max(a - t0, t1 - b, 0) for a, b in clients[t["root"]["attrs"]["method"]])
        assert sticks_out < 1e6, (t["root"]["attrs"]["method"], sticks_out)  # nanoseconds: under 1 ms


def test_ec_trace_prints_the_scripts_tree_with_each_servers_half_in_place(one_script):
    """`ec.trace -traceId`: the shell's tree once, and under each `rpc.client`
    the `rpc.server` tree of the same id, each printed exactly once."""
    text, tid = one_script.joined, one_script.script["trace_id"]
    lines = text.splitlines()
    assert sum(line.startswith(f"trace={tid} shell.script class=shell") for line in lines) == 1
    rebuilder = one_script.c.servers[1].grpc_address
    at = [i for i, line in enumerate(lines) if "rpc.client method=VolumeEcShardsRebuild " in line]
    assert len(at) == 2
    for i in at:
        assert lines[i + 1].strip("| ").startswith(f"@ {rebuilder} rpc.server ")
        assert " rebuild.run volume=" in lines[i + 2] and lines[i + 2].startswith(lines[i + 1].split("@")[0] + "|  +-")
    assert text.count(" rebuild.run volume=") == 2 and text.count(" ec.copy source=") == 4
    assert text.count("@ ") == 11  # every RPC of the script that a server recorded, in its place
    # what had no caller among the script's spans comes after, under its server's name
    tail = text[text.rindex("@ "):]
    assert tail.count("rpc.server class=rpc method=VolumeEcShardFileCopy") == text.count("method=VolumeEcShardFileCopy") > 0


def test_the_master_serves_the_script_and_counts_its_phases(one_script):
    (served,) = [t for t in one_script.served["traces"] if t["trace_id"] == one_script.script["trace_id"]]
    assert served == one_script.script
    rebuild = _commands(one_script.script)[1]
    rows = {(c, p): s for c, p, s in trace.script_phases(one_script.script)}
    assert set(rows) == {("lock", "start")} | {(c, p) for c in ("lock", "ec.rebuild", "unlock")
                                               for p in ("plan", "rpc", "other")}
    assert rows[("lock", "start")] == pytest.approx(one_script.script["root"]["spans"][0]["dur_ms"] / 1e3)
    assert sum(rows[("ec.rebuild", p)] for p in ("plan", "rpc", "other")) == pytest.approx(rebuild["dur_ms"] / 1e3)
    # rpc: what the command's own thread waited for: not the worker's copies beside the rebuild
    own = [s for s in rebuild["spans"] if s["name"] == "rpc.client" and "thread" not in s["attrs"]]
    assert rows[("ec.rebuild", "rpc")] == pytest.approx(sum(s["dur_ms"] for s in own) / 1e3)
    assert stats.ShellCommandSeconds.labels("ec.rebuild", "rpc").total == one_script.phases0 + 1
    for phase in ("plan", "rpc", "other"):
        assert f'weedtpu_shell_command_seconds_count{{command="ec.rebuild",phase="{phase}"}} ' in one_script.metrics
    assert 'weedtpu_shell_command_seconds_count{command="lock",phase="start"} ' in one_script.metrics


def test_with_tracing_off_nothing_is_recorded_or_handed_over_and_the_shards_are_the_same(tmp_path, monkeypatch):
    """`WEEDTPU_TRACE=off`: no root, no span, no `ReportTrace` call; the same
    commands write the same bytes (the reference's, as with tracing on)."""
    monkeypatch.setenv("WEEDTPU_TRACE", "off")
    c = Cluster(tmp_path, ["host", "jax", "host", "host"])
    try:
        rebuilder_dir, own = _lost_server_setup(c)
        trace.RING.clear()
        reports0 = stats.RpcServerSeconds.labels("ReportTrace").total
        out = c.shell("lock; ec.rebuild; unlock")
        assert out.endswith("ec.rebuild: 2 volumes with copies, 1 gathered beside a rebuild\ncluster unlocked\n")
        assert stats.RpcServerSeconds.labels("ReportTrace").total == reports0
        assert trace.RING.snapshot() == [] and trace.RING.stats()["offered"] == 0
        for vid, (mine, lost) in own.items():
            for s in mine | lost:
                with open(stripe.shard_file_name(os.path.join(rebuilder_dir, str(vid)), s), "rb") as f:
                    assert f.read() == c.reference[vid][s], f"volume {vid} shard {s} differs from the reference"
        _nothing_temporary(c, rebuilder_dir, own)
    finally:
        c.close()
