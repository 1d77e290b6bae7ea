"""`ec.rebuild` of MANY volumes that each lost one shard (`many10p4`: a server
or a disk of a wide cluster dies), through the operator's flagless command:
the volumes of one rebuilder ride ONE `VolumeEcShardsRebuildBatch`, whose
packed batches are one device program each on the jax backend. Small sizes, on
the CPU, against the plain reference (striping as arithmetic on the original
`.dat`, numpy GF(2^8) of `benchmark/reference/gf8_ref.py`)."""

import hashlib
import io
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import test_ec_rebuild_cluster as cl
from seaweedfs_tpu import rpc, stats
from seaweedfs_tpu.analysis import fsrec
from seaweedfs_tpu.cluster.client import MasterClient
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.ec import stripe
from seaweedfs_tpu.obs import trace
from seaweedfs_tpu.ops import rs_jax
from seaweedfs_tpu.ops.rs_codec import Encoder, new_encoder
from seaweedfs_tpu.pb import VOLUME_SERVICE
from seaweedfs_tpu.shell import CommandEnv, ShellError, run_script

ROOT = cl.ROOT
LARGE, SMALL = cl.LARGE, cl.SMALL
with open(os.path.join(ROOT, "benchmark", "configs", "many10p4.json")) as _f:
    CONFIG = json.load(_f)
#: volume -> the shard id it loses: the configuration's own
LOST = {int(v): int(s) for v, s in CONFIG["lost_shard_of_volume"].items()}
VIDS = sorted(LOST)
REBUILD_RPCS = ("VolumeEcShardsRebuildBatch", "VolumeEcShardsRebuild")


def _sha(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _calls(method):
    return stats.RpcServerSeconds.labels(method).total


class Many:
    """master + the server that holds all 14 shards of eight volumes, and,
    where asked, a peer that joins after the encode."""

    def __init__(self, tmp_path, backend):
        self.tmp = tmp_path
        self.master = MasterServer(port=0, reap_interval=3600)
        self.master.start()
        self.dir = str(tmp_path / "srv")
        os.makedirs(self.dir)
        self.needles = {vid: cl._write_volume(self.dir, vid, seed=36) for vid in VIDS}
        self.reference = {}
        for vid in VIDS:
            with open(os.path.join(self.dir, f"{vid}.dat"), "rb") as f:
                self.reference[vid] = cl._reference_shards(f.read())
        self.server = self._server(self.dir, "r0", backend)
        self.peer = None
        self.client = MasterClient(self.master.address)
        self.env = CommandEnv(self.master.address)
        cl._wait_for(lambda: len(self.master.topology.nodes) == 1, msg="the server joined")
        self.shell("lock; " + "; ".join(
            f"ec.encode -volumeId {v} -force -largeBlockSize {LARGE} -smallBlockSize {SMALL}"
            for v in VIDS) + "; unlock")

    def _server(self, directory, rack, backend):
        vs = VolumeServer([directory], self.master.address, heartbeat_interval=0.2, rack=rack,
                          max_volume_count=40, encoder=new_encoder(backend=backend))
        vs.start()
        return vs

    def add_peer(self):
        d = str(self.tmp / "peer")
        os.makedirs(d)
        self.peer = self._server(d, "r0", "numpy")  # one rack: the rebuilder is who holds most
        cl._wait_for(lambda: len(self.master.topology.nodes) == 2, msg="the peer joined")
        return d

    def shell(self, script):
        """-> (what the script wrote, the ShellError that ended it or None)."""
        out = io.StringIO()
        try:
            run_script(self.env, script, out)
        except ShellError as e:
            return out.getvalue(), e
        return out.getvalue(), None

    def base(self, vid):
        return os.path.join(self.dir, str(vid))

    def path(self, vid, shard):
        return stripe.shard_file_name(self.base(vid), shard)

    def call(self, server, method, req):
        with rpc.RpcClient(server.grpc_address) as c:
            return c.call(VOLUME_SERVICE, method, req, timeout=60)

    def listed(self, vid):
        return {s: {n.url for n in nodes}
                for s, nodes in self.master.topology.lookup_ec_shards(vid).items() if nodes}

    def lose(self, shards_of):
        """Delete {volume: [shard ids]} on the server; wait for the master."""
        for vid, shards in shards_of.items():
            self.call(self.server, "VolumeEcShardsDelete",
                      {"volume_id": vid, "collection": "", "shard_ids": list(shards)})
        cl._wait_for(lambda: all(self.server.url not in self.listed(vid).get(s, ())
                                 for vid, shards in shards_of.items() for s in shards),
                     msg="the master dropped the lost shards")

    def move_to_peer(self, vid, shards):
        """`ec.balance` by hand: the peer copies `shards` of `vid`, mounts
        them, and the server drops its own."""
        self.call(self.peer, "VolumeEcShardsCopy",
                  {"volume_id": vid, "collection": "", "shard_ids": list(shards),
                   "source_data_node": self.server.grpc_address, "copy_ecx_file": True})
        self.call(self.peer, "VolumeEcShardsMount", {"volume_id": vid, "collection": "", "shard_ids": list(shards)})
        self.call(self.server, "VolumeEcShardsDelete", {"volume_id": vid, "collection": "", "shard_ids": list(shards)})
        cl._wait_for(lambda: all(self.listed(vid).get(s) == {self.peer.url} for s in shards),
                     msg="the master lists the moved shards on the peer alone")

    def close(self):
        self.env.close()
        self.client.close()
        for vs in (self.server, self.peer):
            if vs is not None:
                vs.stop()
        self.master.stop()


@pytest.fixture
def make_many(tmp_path, monkeypatch):
    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    monkeypatch.setenv("WEEDTPU_TRACE_SAMPLE", "1.0")
    made = []

    def make(backend):
        made.append(Many(tmp_path, backend))
        return made[-1]

    yield make
    for c in made:
        c.close()


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_one_shard_of_each_of_eight_volumes_is_one_batch(make_many, backend):
    """The configuration's loss, one flagless `ec.rebuild`: every rebuilt shard
    is the reference's and the deleted file's, exactly one
    `VolumeEcShardsRebuildBatch` and no `VolumeEcShardsRebuild` reached the
    rebuilder, all 112 shards are listed, every needle reads back."""
    c = make_many(backend)
    for vid in VIDS:
        for s in range(14):
            with open(c.path(vid, s), "rb") as f:
                assert f.read() == c.reference[vid][s], (vid, s)
    deleted = {vid: _sha(c.path(vid, LOST[vid])) for vid in VIDS}
    c.lose({vid: [LOST[vid]] for vid in VIDS})
    assert not any(os.path.exists(c.path(vid, LOST[vid])) for vid in VIDS)
    before = {m: _calls(m) for m in REBUILD_RPCS + ("VolumeEcShardsMount",)}
    runs0 = stats.EcRebuildRuns.labels(backend).value
    batch0 = stats.EcRebuildBatchVolumes.value
    trace.RING.clear()

    out, err = c.shell("lock; ec.rebuild; unlock")

    assert err is None, out
    assert {m: _calls(m) - n for m, n in before.items()} == {
        "VolumeEcShardsRebuildBatch": 1, "VolumeEcShardsRebuild": 0, "VolumeEcShardsMount": 0}
    assert f"ec.rebuild batch on {c.server.url}: 8 volumes in 7 signature groups\n" in out
    for vid in VIDS:
        assert f"ec.rebuild volume {vid}: rebuilt [{LOST[vid]}] on {c.server.url}\n" in out
        with open(c.path(vid, LOST[vid]), "rb") as f:
            got = f.read()
        assert got == c.reference[vid][LOST[vid]], f"volume {vid} differs from the reference"
        assert hashlib.sha256(got).hexdigest() == deleted[vid]
        assert {s: urls for s, urls in c.listed(vid).items()} == {s: {c.server.url} for s in range(14)}
        for fid, payload in c.needles[vid]:
            assert c.client.read(fid) == payload
    assert sum(len(c.listed(vid)) for vid in VIDS) == 112
    assert stats.EcRebuildRuns.labels(backend).value - runs0 == 8
    assert stats.EcRebuildBatchVolumes.value - batch0 == 8
    # the spans say what ran: the batch's run span, and the shell's count of its RPCs
    run = _batch_run()
    assert run["attrs"]["batch"] == 8 and run["attrs"]["signature_groups"] == 7
    assert run["attrs"]["ring"] in ("reused", "allocated")
    # the command's span, under the script's root (`shell -c` is ONE trace):
    # ONE VolumeList (the geometry of all eight comes with it) and the one batch
    assert _rebuild_command()["attrs"]["rpcs"] == 1 + 0 + 1


def test_a_lone_volume_with_its_survivors_at_hand_is_a_batch_of_one(make_many):
    """One volume, four shards lost, every survivor on the rebuilder (the
    `warm10p4.rebuild-4lost` cell's loss): the same route, a batch of one, no
    single-volume RPC and no separate mount."""
    c = make_many("numpy")
    lost = [0, 3, 11, 13]
    c.lose({5: lost})
    before = {m: _calls(m) for m in REBUILD_RPCS + ("VolumeEcShardsMount",)}

    out, err = c.shell("lock; ec.rebuild; unlock")

    assert err is None, out
    assert {m: _calls(m) - n for m, n in before.items()} == {
        "VolumeEcShardsRebuildBatch": 1, "VolumeEcShardsRebuild": 0, "VolumeEcShardsMount": 0}
    assert f"ec.rebuild batch on {c.server.url}: 1 volumes in 1 signature groups\n" in out
    assert f"ec.rebuild volume 5: rebuilt {lost} on {c.server.url}\n" in out
    for s in lost:
        with open(c.path(5, s), "rb") as f:
            assert f.read() == c.reference[5][s]
    assert sorted(c.listed(5)) == list(range(14))


def test_mixed_placement_copies_a_lost_volume_and_a_soft_failure(make_many):
    """Volume 1 has three survivors on a peer: it is rebuilt on its own
    (copied over, the single-volume RPC, the copies deleted, none left), so
    that only one volume's copies are on the rebuilder at a time; volume 2 has
    nine survivors ("data LOST", the others rebuilt); volumes 3, 4 and 5 need
    no copy and share one batch, in which volume 3 fails softly (a survivor is
    corrupt: the CRC gate refuses its rebuilt shard, the error is printed, the
    others are mounted), and the command ends in an error that names it."""
    c = make_many("numpy")
    peer_dir = c.add_peer()
    c.move_to_peer(1, [4, 6, 13])
    c.lose({1: [0], 2: [1, 2, 3, 4, 5], 3: [11], 4: [13], 5: [5]})
    with open(c.path(3, 2), "r+b") as f:  # a survivor the decode reads
        f.seek(100)
        b = f.read(1)
        f.seek(100)
        f.write(bytes([b[0] ^ 0x5A]))
    pulled0 = stats.EcCopyBytes.labels("pulled").value
    before = {m: _calls(m) for m in REBUILD_RPCS}

    out, err = c.shell("lock; ec.rebuild; unlock")

    assert isinstance(err, ShellError) and "[3]" in str(err), (out, err)
    assert "ec.rebuild volume 2: only 9 shards survive, need 10 — data LOST\n" in out
    assert f"ec.rebuild volume 3: NOT rebuilt on {c.server.url}: " in out and "CRC mismatch" in out
    assert {m: _calls(m) - n for m, n in before.items()} == {
        "VolumeEcShardsRebuildBatch": 1, "VolumeEcShardsRebuild": 1}
    assert f"ec.rebuild batch on {c.server.url}: 3 volumes in 3 signature groups\n" in out
    moved = sum(len(c.reference[1][s]) for s in (4, 6, 13))
    assert stats.EcCopyBytes.labels("pulled").value - pulled0 == moved
    for vid, shard in ((1, 0), (4, 13), (5, 5)):
        assert f"ec.rebuild volume {vid}: rebuilt [{shard}] on {c.server.url}\n" in out
        with open(c.path(vid, shard), "rb") as f:
            assert f.read() == c.reference[vid][shard]
        assert c.server.url in c.listed(vid)[shard]
    # the copies are gone from the rebuilder, the peer keeps its own
    assert set(stripe.find_local_shards(c.base(1))) == set(range(14)) - {4, 6, 13}
    assert set(stripe.find_local_shards(os.path.join(peer_dir, "1"))) == {4, 6, 13}
    assert not [n for n in os.listdir(c.dir) if n.endswith(".cpy")]
    assert sorted(c.listed(1)) == list(range(14))
    assert not os.path.exists(c.path(3, 11)) and 11 not in c.listed(3)
    for fid, payload in c.needles[1] + c.needles[4]:
        assert c.client.read(fid) == payload


# -- the plan: all volumes at once (PR 47) ----------------------------------------


def _rebuild_command():
    """The `shell.command` span of the ring's one `ec.rebuild`."""
    (cmd,) = [s for t in trace.RING.snapshot(kind="shell.script", limit=1000) for s in trace.iter_spans(t)
              if s["name"] == "shell.command" and s["attrs"].get("command") == "ec.rebuild"]
    return cmd


def _clients(span):
    return [s["attrs"] for s in trace.iter_spans({"root": span}) if s["name"] == "rpc.client"]


def _batch_run():
    """The run span of the ring's one `VolumeEcShardsRebuildBatch` (a root of
    its own where the caller sent no trace id)."""
    (run,) = [s for t in trace.RING.snapshot(limit=100000) for s in trace.iter_spans(t)
              if s["name"] == "rebuild.run" and "batch" in s["attrs"]]
    return run


def test_the_plan_of_eight_volumes_is_one_volume_list_and_no_volume_status(make_many):
    """The master's answer names every volume's geometry: a flagless
    `ec.rebuild` of the eight sends ONE `VolumeList`, no `VolumeStatus`, and
    the one batch; the plan's span says so."""
    c = make_many("numpy")
    c.lose({vid: [LOST[vid]] for vid in VIDS})
    assert {v: (g["data_shards"], g["total_shards"]) for v, g in c.master.topology.ec_geometry.items()} == {
        vid: (10, 14) for vid in VIDS}
    before = {m: _calls(m) for m in ("VolumeStatus", "VolumeList")}
    trace.RING.clear()

    out, err = c.shell("lock; ec.rebuild; unlock")

    assert err is None, out
    assert {m: _calls(m) - n for m, n in before.items()} == {"VolumeStatus": 0, "VolumeList": 1}
    cmd = _rebuild_command()
    assert sorted(a["method"] for a in _clients(cmd)) == ["VolumeEcShardsRebuildBatch", "VolumeList"]
    (plan,) = [s for s in cmd["spans"] if s["name"] == "shell.plan"]
    assert plan["attrs"] == {"volumes": 8, "rpcs": 1}
    for vid in VIDS:
        with open(c.path(vid, LOST[vid]), "rb") as f:
            assert f.read() == c.reference[vid][LOST[vid]]


def test_a_volume_the_master_names_no_geometry_for_is_asked_of_its_witness(make_many, monkeypatch):
    """Volume 3's holder heartbeats no geometry (a server from before
    heartbeats carried one): the master's answer lacks it, that volume alone
    is asked of the holder of most of its shards, as before, and both lost
    shards come back."""
    c = make_many("numpy")
    infos = c.server.store.ec_volume_infos

    def as_an_old_server():
        out = infos()
        for info in out:
            if info.volume_id == 3:
                info.shard_size = info.data_shards = info.total_shards = 0
        return out

    monkeypatch.setattr(c.server.store, "ec_volume_infos", as_an_old_server)
    c.lose({3: [LOST[3]], 4: [LOST[4]]})
    cl._wait_for(lambda: 3 not in c.master.topology.ec_geometry, msg="the master forgot volume 3's geometry")
    assert "3" not in c.env.volume_list()["ec_geometry"] and "4" in c.env.volume_list()["ec_geometry"]
    status0 = _calls("VolumeStatus")
    trace.RING.clear()

    out, err = c.shell("lock; ec.rebuild; unlock")

    assert err is None, out
    assert _calls("VolumeStatus") - status0 == 1
    asked = [a for a in _clients(_rebuild_command()) if a["method"] == "VolumeStatus"]
    assert [(a["volume"], a["target"]) for a in asked] == [(3, c.server.grpc_address)]
    assert f"ec.rebuild batch on {c.server.url}: 2 volumes in 2 signature groups\n" in out
    for vid in (3, 4):
        with open(c.path(vid, LOST[vid]), "rb") as f:
            assert f.read() == c.reference[vid][LOST[vid]]


@pytest.mark.parametrize("family,k,total,lost", [("cauchy_12_3", 12, 15, 14), ("merge_20_4", 20, 24, 17)])
def test_a_converted_volume_is_planned_from_the_masters_geometry(make_many, family, k, total, lost):
    """`ec.convert`'s cut-over has heartbeated the new geometry before the
    command returns, so the master's copy is what `VolumeStatus` would say:
    a lost shard id the legacy 0..13 would never see is planned from it,
    with no `VolumeStatus`, and rebuilt byte-exact."""
    c = make_many("numpy")
    out, err = c.shell(f"lock; ec.convert -volumeId 6 -family {family}; unlock")
    assert err is None and f"rs_10_4 -> {family}" in out and "cut over" in out, out
    geo = c.master.topology.ec_geometry[6]  # no wait: the RPC's own heartbeat brought it
    assert (geo["data_shards"], geo["total_shards"]) == (k, total)
    assert sorted(c.listed(6)) == list(range(total))
    with open(c.path(6, lost), "rb") as f:
        golden = f.read()
    c.lose({6: [lost]})
    status0 = _calls("VolumeStatus")

    out, err = c.shell("lock; ec.rebuild; unlock")

    assert err is None, out
    assert f"ec.rebuild volume 6: rebuilt [{lost}] on {c.server.url}\n" in out
    assert _calls("VolumeStatus") == status0
    with open(c.path(6, lost), "rb") as f:
        assert f.read() == golden
    assert sorted(c.listed(6)) == list(range(total))
    for fid, payload in c.needles[6]:
        assert c.client.read(fid) == payload


def test_the_master_believes_the_holder_of_the_most_shards_about_a_geometry():
    """Holders may disagree for a while (stale old-geometry shards beside a
    converted volume): the claim of the node that holds the most shards
    stands, whoever heartbeated last; a holder that reports no geometry
    claims nothing; `VolumeList` carries what stands."""
    from seaweedfs_tpu.cluster.topology import Topology
    from seaweedfs_tpu.ec.shard_bits import EcVolumeInfo, ShardBits
    from seaweedfs_tpu.pb import Heartbeat

    def beat(port, *infos):
        return Heartbeat(ip="127.0.0.1", port=port, grpc_port=port + 1000, rack="r", data_center="dc",
                         max_volume_count=10, ec_shards=list(infos))

    def info(sids, k=0, total=0, size=0):
        return EcVolumeInfo(7, shard_bits=ShardBits.from_ids(sids), shard_size=size,
                            data_shards=k, total_shards=total).to_dict()

    old = {"data_shards": 10, "total_shards": 14, "shard_size": 1000}
    new = {"data_shards": 20, "total_shards": 24, "shard_size": 500}
    topo = Topology()
    topo.process_heartbeat(beat(8001, info(range(7), 10, 14, 1000)))
    topo.process_heartbeat(beat(8002, info(range(7, 14), 10, 14, 1000)))
    assert topo.ec_geometry == {7: old}
    topo.process_heartbeat(beat(8001, info(range(24), 20, 24, 500)))  # 8001 converted and cut over
    assert topo.ec_geometry == {7: new}
    topo.process_heartbeat(beat(8002, info(range(7, 14), 10, 14, 1000)))  # the stale holder beats later
    assert topo.ec_geometry == {7: new} and topo.to_dict()["ec_geometry"] == {"7": new}
    topo.process_heartbeat(beat(8002))  # its stale shards were deleted
    assert topo.ec_geometry == {7: new}
    topo.process_heartbeat(beat(8002, info([3], 10, 14, 1000)))  # one stale shard comes back
    topo.process_heartbeat(beat(8001, info(range(24))))  # the big holder reports no geometry: no claim
    assert topo.ec_geometry == {7: old}
    topo.unregister_node("127.0.0.1:8002")
    assert topo.ec_geometry == {} and topo.to_dict()["ec_geometry"] == {}
    topo.unregister_node("127.0.0.1:8001")
    assert topo.ec_geometry == {} and 7 not in topo.ec_locations


def _batch_rpc(c, vids):
    from seaweedfs_tpu.ec import placement

    return c.call(c.server, "VolumeEcShardsRebuildBatch", placement.rebuild_batch_request((v, "") for v in vids))


def test_the_plans_of_a_batch_run_side_by_side(make_many, monkeypatch):
    """The first two `LookupEcVolume` of a batch of eight are answered only
    once BOTH are in flight (a barrier with a time limit, no clock): plans
    that ran one after the other would break it and fail their volumes.
    Every volume still asks the master afresh, and the spans say what ran:
    a `rebuild.plan` a volume under the run, `planned=` and `plan_ms=`."""
    import itertools
    import threading

    c = make_many("numpy")
    c.lose({vid: [LOST[vid]] for vid in VIDS})
    query, together, arrivals = c.server._master_query, threading.Barrier(2), itertools.count()
    asked = []

    def held_back(method, req, *a, **kw):
        if method == "LookupEcVolume":
            asked.append(req["volume_id"])
            if next(arrivals) < 2:
                together.wait(timeout=30)  # BrokenBarrierError where the second never comes
        return query(method, req, *a, **kw)

    monkeypatch.setattr(c.server, "_master_query", held_back)
    trace.RING.clear()

    resp = _batch_rpc(c, VIDS)

    assert [(r["volume_id"], r["rebuilt_shard_ids"], r["error"]) for r in resp["results"]] == [
        (vid, [LOST[vid]], "") for vid in VIDS]
    assert not together.broken, "the first lookup waited alone: the plans ran one after the other"
    assert sorted(asked) == VIDS  # a fresh holder map a volume: none skipped, none from the cache
    run = _batch_run()
    assert run["attrs"]["planned"] == 8 and run["attrs"]["batch"] == 8 and run["attrs"]["plan_ms"] > 0
    plans = [s for s in run["spans"] if s["name"] == "rebuild.plan"]
    assert sorted(s["attrs"]["volume"] for s in plans) == VIDS and all(s["attrs"]["local"] is True for s in plans)
    assert all([g["name"] for g in s.get("spans", ())] == ["ec.lookup"] for s in plans)
    first_stage = min(s["t_ms"] for s in run["spans"] if s["name"] == "rebuild.stage")
    assert max(s["t_ms"] + s["dur_ms"] for s in plans) <= first_stage  # every plan before the first byte
    for vid in VIDS:
        with open(c.path(vid, LOST[vid]), "rb") as f:
            assert f.read() == c.reference[vid][LOST[vid]]


def test_a_mixed_batch_keeps_request_order_and_its_results(make_many):
    """Job order is request order whatever order the plans finish in: the
    scheduler's 2-missing volume first, same-signature volumes side by side,
    a healthy volume rebuilt as nothing, an unknown one a soft error of its
    own; `results` by volume id, as before."""
    c = make_many("numpy")
    c.lose({5: [5, 9], 2: [3], 7: [3], 8: [12]})

    resp = _batch_rpc(c, [5, 8, 1, 7, 99, 2])

    assert resp["block_order"] == [5, 8, 7, 2] and resp["signature_groups"] == 3
    assert resp["volumes_fused"] == 4 and resp["dispatch_groups"] == 1 and resp["wire_bytes"] == 0
    results = {r["volume_id"]: r for r in resp["results"]}
    assert [r["volume_id"] for r in resp["results"]] == [1, 2, 5, 7, 8, 99]
    assert {v: r["rebuilt_shard_ids"] for v, r in results.items()} == {
        1: [], 2: [3], 5: [5, 9], 7: [3], 8: [12], 99: []}
    assert all(not results[v]["error"] for v in (1, 2, 5, 7, 8))
    assert "ec volume 99 not found" in results[99]["error"]
    for vid, shards in ((5, [5, 9]), (2, [3]), (7, [3]), (8, [12])):
        for s in shards:
            with open(c.path(vid, s), "rb") as f:
                assert f.read() == c.reference[vid][s]
            assert c.listed(vid)[s] == {c.server.url}


def _open_shard_files(c, vid):
    """Descriptors of this process onto volume `vid`'s shard files."""
    prefix = c.base(vid) + ".ec"
    held = []
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if target.startswith(prefix) and target[len(prefix):].isdigit():
            held.append(target)
    return sorted(held)


def test_a_plan_that_raises_is_its_volumes_soft_error_and_closes_what_it_opened(make_many, monkeypatch):
    """Volume 4's plan raises after its ten local survivors were opened: the
    error is volume 4's alone, the descriptors it opened are closed (the
    process holds what it held before), the other seven are rebuilt."""
    c = make_many("numpy")
    c.lose({vid: [LOST[vid]] for vid in VIDS})
    held0 = _open_shard_files(c, 4)
    remote, seen = c.server._remote_slab_sources, []

    def breaks_for_four(vid, shard_ids, executor):
        if vid == 4:
            seen.append(len(_open_shard_files(c, 4)) - len(held0))
            raise OSError("no route to volume 4's holders")
        return remote(vid, shard_ids, executor)

    monkeypatch.setattr(c.server, "_remote_slab_sources", breaks_for_four)

    resp = _batch_rpc(c, VIDS)

    assert seen == [10]  # the plan had its ten survivors open when it failed
    assert _open_shard_files(c, 4) == held0
    results = {r["volume_id"]: r for r in resp["results"]}
    assert results[4]["error"] == "OSError: no route to volume 4's holders" and not results[4]["rebuilt_shard_ids"]
    assert 4 not in resp["block_order"] and resp["volumes_fused"] == 7
    for vid in VIDS:
        if vid != 4:
            assert results[vid] == {"volume_id": vid, "rebuilt_shard_ids": [LOST[vid]], "error": "", "wire_bytes": 0}
            with open(c.path(vid, LOST[vid]), "rb") as f:
                assert f.read() == c.reference[vid][LOST[vid]]
    assert not os.path.exists(c.path(4, LOST[4]))


@pytest.mark.parametrize("vids,pooled", [([5], 0), ([5, 6, 7], 3)])
def test_a_batch_of_one_is_planned_on_the_calling_thread(make_many, monkeypatch, vids, pooled):
    """A batch of one hands its executor nothing (the statements of before,
    on the RPC's thread, `planned=1`); a batch of several hands it a plan a
    volume."""
    from concurrent import futures

    c = make_many("numpy")
    c.lose({vid: [LOST[vid]] for vid in vids})
    submit, handed = futures.ThreadPoolExecutor.submit, []

    def counted(self, fn, *args, **kw):
        if self._thread_name_prefix == "ec-rebuild-batch":
            handed.append(fn)
        return submit(self, fn, *args, **kw)

    monkeypatch.setattr(futures.ThreadPoolExecutor, "submit", counted)
    trace.RING.clear()

    resp = _batch_rpc(c, vids)

    assert len(handed) == pooled
    assert [(r["volume_id"], r["rebuilt_shard_ids"], r["error"]) for r in resp["results"]] == [
        (vid, [LOST[vid]], "") for vid in vids]
    run = _batch_run()
    assert run["attrs"]["planned"] == len(vids) and run["attrs"]["plan_ms"] > 0
    assert len([s for s in run["spans"] if s["name"] == "rebuild.plan"]) == len(vids)


# -- the packed plan against the loop ---------------------------------------------

BUF = 4 * Encoder.BLOCK_TILE  # a slot of four tiles


def _shard_sets(directory, rows, enc):
    """Eight volumes of `rows` small-block rows each, encoded, each without the
    configuration's shard: -> {base: (golden shard bytes, lost id)}."""
    out = {}
    for vid in VIDS:
        base = os.path.join(directory, str(vid))
        rng = np.random.default_rng([36, vid])
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, rows * 10 * SMALL - 77 * vid, dtype=np.uint8).tobytes())
        with open(base + ".idx", "wb"):
            pass
        stripe.write_ec_files(base, large_block_size=1 << 30, small_block_size=SMALL, encoder=enc)
        stripe.write_sorted_file_from_idx(base)
        with open(stripe.shard_file_name(base, LOST[vid]), "rb") as f:
            out[base] = (f.read(), LOST[vid])
        os.unlink(stripe.shard_file_name(base, LOST[vid]))
    return out


def _batch(bases, enc):
    jobs = [{"base": b, "shard_size": os.path.getsize(stripe.shard_file_name(b, 1)), "missing": None,
             "sources": {s: stripe.LocalSlabSource(stripe.shard_file_name(b, s))
                         for s in stripe.find_local_shards(b, 14)}} for b in bases]
    try:
        return stripe.rebuild_ec_files_batch(jobs, encoder=enc, buffer_size=BUF, max_batch_bytes=10 * BUF)
    finally:
        for job in jobs:
            for src in job["sources"].values():
                src.close()


# rows of 4096 bytes a shard, each shard longer than a slot so that some batches
# hold one signature: 91 puts every seam off the 65,536-column grid, 80 on it
@pytest.mark.parametrize("rows", [91, 80])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_the_packed_plan_gives_the_loops_bytes(tmp_path, rows, backend):
    """`rebuild_ec_files_batch` over the eight jobs writes files byte-identical
    to eight `rebuild_ec_files` calls. On the jax backend the plan compiles
    the number of programs PERF.md states, 2 (the flat program and the tiled
    one, both over the whole slot), whether seams fall on the tile grid or
    off it; the loop compiles 1."""
    enc = Encoder(10, 4, backend=backend)
    goldens = _shard_sets(str(tmp_path), rows, Encoder(10, 4, backend="numpy"))
    if backend == "jax":
        rs_jax.gf_apply.clear_cache()
        rs_jax.gf_apply_tiled.clear_cache()
    compiled0 = stats.CodecProgramsCompiled.value
    res = _batch(list(goldens), enc)
    assert not res["errors"] and res["signature_groups"] == 7 and res["volumes_fused"] == 8
    # group-major: volume 7 shares volume 2's signature and runs beside it
    assert [os.path.basename(b) for b in res["block_order"]] == ["1", "2", "7", "3", "4", "5", "6", "8"]
    if backend == "jax":
        assert stats.CodecProgramsCompiled.value - compiled0 == 2
        assert rs_jax.gf_apply._cache_size() == 1 and rs_jax.gf_apply_tiled._cache_size() == 1
    for base, (golden, lost) in goldens.items():
        path = stripe.shard_file_name(base, lost)
        with open(path, "rb") as f:
            assert f.read() == golden, f"{base}: the packed plan's shard {lost} differs"
        os.unlink(path)
        assert stripe.rebuild_ec_files(base, encoder=enc, buffer_size=BUF, max_batch_bytes=10 * BUF) == [lost]
        with open(path, "rb") as f:
            assert f.read() == golden, f"{base}: the loop's shard {lost} differs"
    if backend == "jax":  # the loop ran the flat program the plan had compiled, and no other
        assert stats.CodecProgramsCompiled.value - compiled0 == 2


def test_a_packed_batch_is_one_program_whatever_its_blocks(monkeypatch):
    """`reconstruct_block` on the jax backend: blocks on the tile grid are one
    `apply_matrix` call over the whole batch with a matrix a tile (one
    dispatch counted, one program for two different layouts); a block off the
    grid falls back to an apply a block. Both are byte-exact. And the tiled
    program answers through `rs_jax.apply_matrix`, where the benchmark's
    `--fault broken_apply` alters the device's answer: the control reaches it."""
    from seaweedfs_tpu.ops import gf8

    enc = Encoder(10, 4, backend="jax")
    tile = Encoder.BLOCK_TILE
    assert enc.block_tile(4 * tile) == tile and enc.block_tile(3 * 4096) == 4096
    assert Encoder(10, 4, backend="numpy").block_tile(4 * tile) == 1
    staging = np.random.default_rng(5).integers(0, 256, (10, 4 * tile), dtype=np.uint8)

    def block(missing, c0, w):
        return {"survivors": [s for s in range(14) if s not in missing][:10], "wanted": missing,
                "col_start": c0, "width": w}

    def check(blocks):
        out = np.asarray(enc.reconstruct_block(staging, blocks))
        for b in blocks:
            m = enc.reconstruction_matrix(b["survivors"], b["wanted"])
            cols = slice(b["col_start"], b["col_start"] + b["width"])
            assert (out[: len(b["wanted"]), cols] == gf8.gf_mat_vec(m, staging[:, cols])).all()

    rs_jax.gf_apply_tiled.clear_cache()
    d0 = stats.EcDispatchTotal.labels("jax").value
    check([block([0], 0, tile + 5), block([3, 11], 2 * tile, tile), block([13], 3 * tile, 9)])
    check([block([5, 9], 0, 3 * tile), block([12], 3 * tile, tile)])
    assert stats.EcDispatchTotal.labels("jax").value - d0 == 2
    assert rs_jax.gf_apply_tiled._cache_size() == 1
    check([block([0], 0, tile + 5), block([3], tile + 5, 100)])  # off the grid
    assert stats.EcDispatchTotal.labels("jax").value - d0 == 4
    assert rs_jax.gf_apply_tiled._cache_size() == 1

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "harness"))
    try:
        import chip_server
    finally:
        sys.path.pop(0)
    monkeypatch.setattr(rs_jax, "apply_matrix", rs_jax.apply_matrix)  # put back at the end
    chip_server._break_apply_matrix()
    with pytest.raises(AssertionError):
        check([block([5, 9], 0, 3 * tile), block([12], 3 * tile, tile)])


def test_the_batch_writes_a_shard_as_the_single_rebuild_does(tmp_path):
    """What reaches a rebuilt shard's file, in order (create, writes by offset
    and bytes, and any fsync or rename), is the same from the batch path as
    from `rebuild_ec_files`: whatever durability the one has, the other has.
    Both check the rebuilt CRC32 against `.eci` (a corrupt survivor fails both)."""
    enc = Encoder(10, 4, backend="numpy")
    goldens = _shard_sets(str(tmp_path), 91, enc)

    def record(work):
        rec = fsrec.install(str(tmp_path))
        try:
            work()
        finally:
            got = rec.trace()
            fsrec.uninstall()
        return got

    def ops_of(got, base, lost):
        name = os.path.basename(stripe.shard_file_name(base, lost))
        return [(o.kind, o.offset, o.data, o.dst) for o in got.ops if o.path == name]

    batch = record(lambda: _batch(list(goldens), enc))
    in_batch = {base: ops_of(batch, base, lost) for base, (_, lost) in goldens.items()}
    for base, (golden, lost) in goldens.items():
        os.unlink(stripe.shard_file_name(base, lost))
        single = record(lambda b=base: stripe.rebuild_ec_files(
            b, encoder=enc, buffer_size=BUF, max_batch_bytes=10 * BUF))
        alone = ops_of(single, base, lost)
        assert alone[0][0] == "create" and b"".join(o[2] for o in alone if o[0] == "write") == golden
        # the same kinds of operation, in the same order (a write may be cut elsewhere)
        def kinds(ops):
            return [k for i, (k, *_) in enumerate(ops) if i == 0 or ops[i - 1][0] != k]
        assert kinds(in_batch[base]) == kinds(alone)
        assert b"".join(o[2] for o in in_batch[base] if o[0] == "write") == golden
    # the CRC gate holds in both: a corrupt survivor fails the volume
    base, (_, lost) = next(iter(goldens.items()))
    os.unlink(stripe.shard_file_name(base, lost))
    with open(stripe.shard_file_name(base, 1 if lost != 1 else 2), "r+b") as f:
        f.write(b"\xff\x00\xff")
    with pytest.raises(IOError, match="CRC mismatch"):
        stripe.rebuild_ec_files(base, encoder=enc, buffer_size=BUF, max_batch_bytes=10 * BUF)
    res = _batch(list(goldens)[:2], enc)
    assert "CRC mismatch" in res["errors"][base] and not os.path.exists(stripe.shard_file_name(base, lost))


def test_one_request_builder_for_the_scheduler_and_the_shell():
    from seaweedfs_tpu.ec import placement

    assert placement.rebuild_batch_request([(3, "c"), (1, None)]) == {
        "volumes": [{"volume_id": 3, "collection": "c"}, {"volume_id": 1, "collection": ""}]}
    import inspect

    from seaweedfs_tpu.ec import fleet
    from seaweedfs_tpu.shell import command_ec
    for mod in (fleet, command_ec):
        assert "placement.rebuild_batch_request(" in inspect.getsource(mod)
        assert '{"volumes":' not in inspect.getsource(mod)


@pytest.mark.parametrize("fault,sound", [("", True), ("flip_shard_byte", False), ("broken_apply", False)])
def test_the_benchmark_cell_rehearses_to_its_end_and_leaves_no_process(tmp_path, fault, sound):
    """`run.py --workload many10p4.rebuild-1lost-each --rehearse`: every phase
    on the CPU with 8 MiB volumes, never a result. Sound, all checks pass and
    the facts say one batch RPC a command and no compile in the window; with
    the control's fault or the device's apply broken, the checks do not pass."""
    work = tmp_path / "tmp"
    work.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(work))
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
           "many10p4.rebuild-1lost-each", "--seed", str(2**31 + 36), "--seconds", "1", "--trace", "0",
           "--rehearse"]
    p = subprocess.run(cmd + (["--fault", fault] if fault else []),
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 1, p.stdout[-3000:] + p.stderr[-3000:]
    assert '"correct": true' not in p.stdout
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["rehearse"] is True
    assert result["checks_ok"] is sound, p.stdout[-4000:]
    assert result["attempted"] >= 1
    timed = result["timed"]
    assert timed["ops"] == result["attempted"] - result["failed"]
    assert timed["volumes"] == 8 and timed["programs_compiled_in_window"] == 0
    if fault != "broken_apply":  # there every rebuilt CRC32 is refused and the command fails
        assert timed["signature_groups"] == 7
        assert timed["rpcs_per_command"]["VolumeEcShardsRebuildBatch"] == 1
        assert timed["rpcs_per_command"]["VolumeEcShardsRebuild"] == 0
    if sound:
        assert result["failed"] == 0 and result["metrics"]["rebuild_MBps"]["value"] > 0
        assert all(c["value"] == 0 for c in result["checks"].values())
    left = subprocess.run(["pgrep", "-f", str(work)], capture_output=True, text=True).stdout.split()
    assert not left, f"processes left behind: {left}"
    shutil.rmtree(work, ignore_errors=True)
