"""Shell (weed/shell analog) end-to-end tests against a real in-process
cluster — the §3.1/§3.3 call stacks driven the way an operator drives
them: lock, ec.encode, degraded read, ec.rebuild, ec.balance,
volume.fix.replication (SURVEY.md §4 test strategy)."""

import io
import os
import subprocess
import sys
import types

import pytest

from seaweedfs_tpu.cluster.client import MasterClient
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.ec.shard_bits import ShardBits
from seaweedfs_tpu import shell
from seaweedfs_tpu.shell import CommandEnv, ShellError, repl, run_command, run_script

LARGE, SMALL = 4096, 512


@pytest.fixture
def cluster(tmp_path):
    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    servers = []
    for i in range(4):
        d = tmp_path / f"srv{i}"
        d.mkdir()
        vs = VolumeServer(
            [str(d)],
            master.address,
            heartbeat_interval=0.3,
            rack=f"rack{i % 2}",
            max_volume_count=50,
        )
        vs.start()
        servers.append(vs)
    client = MasterClient(master.address)
    env = CommandEnv(master.address)
    yield master, servers, client, env
    env.close()
    client.close()
    for vs in servers:
        vs.stop()
    master.stop()


def run(env, line):
    out = io.StringIO()
    run_command(env, line, out)
    return out.getvalue()


def _upload_some(client, n=20, size=700):
    import os as _os

    fids = []
    for i in range(n):
        res = client.submit(_os.urandom(size))
        fids.append((res.fid, client.read(res.fid)))
    return fids


def _ec_shard_spread(env, vid):
    """url -> shard ids for vid, from the master's view."""
    out = {}
    for n in env.topology_nodes():
        for e in n.get("ec_shards", []):
            if int(e["volume_id"]) == vid:
                out[n["url"]] = ShardBits(e["shard_bits"]).shard_ids()
    return out


def test_lock_required_and_contention(cluster):
    master, servers, client, env = cluster
    with pytest.raises(ShellError, match="lock the cluster"):
        run(env, "volume.delete -volumeId 1")
    assert "locked" in run(env, "lock")
    env2 = CommandEnv(master.address, client_name="intruder")
    try:
        with pytest.raises(Exception, match="held by"):
            env2.lock()
    finally:
        env2.close()
    assert "unlocked" in run(env, "unlock")
    env2 = CommandEnv(master.address, client_name="second")
    try:
        env2.lock()  # free now
        env2.unlock()
    finally:
        env2.close()


def test_help_and_volume_list(cluster):
    master, servers, client, env = cluster
    _upload_some(client, n=3)
    out = run(env, "help")
    assert "ec.encode" in out and "volume.list" in out
    out = run(env, "volume.list")
    assert "DataCenter" in out and "volume 1" in out
    out = run(env, "collection.list")
    assert "collection: ''" in out
    out = run(env, "cluster.check")
    assert "4 nodes" in out and "unreachable" not in out.replace("0 unreachable", "")


# -- the name table: a command line imports the family of the name it gives ------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_name_table_is_what_the_families_register():
    """`run_command` imports the one module `COMMAND_MODULE` names for a
    command: the table must be what the six families register, name for name
    and module for module, or a command is lost to operators."""
    registered = shell.commands()
    assert len(registered) == 54
    assert shell.COMMAND_MODULE == {
        name: cmd.do.__module__.rsplit(".", 1)[1] for name, cmd in registered.items()}
    assert all(shell.find_command(name) is cmd for name, cmd in registered.items())


def test_help_is_whole_and_an_unknown_name_fails_as_it_did():
    """`help`, `?` and `help <name>` answer from all six families, as when
    every command line imported them; so does the search for a name the table
    lacks, which finds a command some module registered without it."""
    registered = shell.commands()
    listed = run(None, "help").splitlines()
    assert [line.split()[0] for line in listed] == sorted(registered) and len(listed) == 54
    assert all(line == f"  {name:<28} {registered[name].help.splitlines()[0]}"
               for line, name in zip(listed, sorted(registered)))
    assert run(None, "?") == run(None, "help") == run(None, "help nosuch.name")
    assert run(None, "help ec.encode") == f"ec.encode\n\t{registered['ec.encode'].help}\n"
    assert "-volumeId" in run(None, "help ec.encode")
    assert run(None, "") == run(None, "# a comment") == ""
    for line in ("fs.nope -x", "ec.rebuil", "EC.REBUILD"):
        with pytest.raises(ShellError) as e:
            run(None, line)
        assert str(e.value) == f"unknown command {line.split()[0]!r} (try `help`)"
    late = shell.register(shell.ShellCommand("late.arrival", "not in the table", lambda a, e, w: w.write("ran\n")))
    try:
        assert "late.arrival" not in shell.COMMAND_MODULE and shell.find_command("late.arrival") is late
        assert run(None, "late.arrival") == "ran\n"
    finally:
        del shell._REGISTRY["late.arrival"]


_FAMILY_PROBE = """
import sys
from seaweedfs_tpu import shell
names = sys.argv[2:]
got = [shell.find_command(name) for name in names]
assert all(c is not None and c.name == n and c.do.__module__ == "seaweedfs_tpu.shell." + sys.argv[1]
           for n, c in zip(names, got)), got
print(*sorted(m.rsplit(".", 1)[1] for m in sys.modules if m.startswith("seaweedfs_tpu.shell.command_")))
"""


@pytest.mark.parametrize("family", sorted(shell._FAMILIES))
def test_a_family_registers_its_commands_with_no_other_family_loaded(family):
    """In a new interpreter that resolves only this family's names, this
    family alone is imported and every name is there: no family leans on a
    registration or an import that a neighbour used to make for it."""
    done = subprocess.run([sys.executable, "-c", _FAMILY_PROBE, family, *shell._FAMILIES[family]],
                          cwd=ROOT, timeout=120, capture_output=True, text=True)
    assert done.returncode == 0, done.stderr
    assert done.stdout.split() == [family]


def test_the_repl_resolves_each_line_as_a_script_does_and_survives_an_unknown_name():
    env = types.SimpleNamespace(master_address="nowhere:0")
    out = io.StringIO()
    repl(env, io.StringIO("help ec.rebuild\nnosuch.command -x\n\n?\nexit\nhelp\n"), out)
    said = out.getvalue()
    assert said.startswith("seaweedfs_tpu shell — connected to nowhere:0\n> ec.rebuild\n\t")
    assert "> error: unknown command 'nosuch.command' (try `help`)\n> > " in said
    assert said.count("  volume.list ") == 1 and said.endswith("> ")  # `?` listed; nothing ran after `exit`


def test_ec_encode_read_rebuild_balance(cluster):
    master, servers, client, env = cluster
    fids = _upload_some(client, n=25)
    vid = int(fids[0][0].split(",", 1)[0])
    run(env, "lock")

    out = run(
        env,
        f"ec.encode -volumeId {vid} -largeBlockSize {LARGE} -smallBlockSize {SMALL}",
    )
    assert f"ec.encode volume {vid}" in out
    spread = _ec_shard_spread(env, vid)
    assert sorted(s for sids in spread.values() for s in sids) == list(range(14))
    assert len(spread) == 4  # spread across all nodes
    # original volume is gone from the topology
    assert not any(
        int(v["id"]) == vid
        for n in env.topology_nodes()
        for v in n.get("volumes", [])
    ), "original volume must be deleted after cut-over"

    # every blob still readable through the EC path (incl. remote intervals)
    for fid, payload in fids:
        assert client.read(fid) == payload, f"fid {fid} corrupted after ec.encode"

    # lose one node's shards entirely -> rebuild restores 14/14
    victim_url, victim_sids = sorted(spread.items())[0]
    victim = next(s for s in servers if s.url == victim_url)
    host = victim_url.rsplit(":", 1)[0]
    env.vs_call(
        f"{host}:{victim.grpc_port}",
        "VolumeEcShardsDelete",
        {"volume_id": vid, "shard_ids": victim_sids},
    )
    assert sorted(
        s for sids in _ec_shard_spread(env, vid).values() for s in sids
    ) != list(range(14))
    out = run(env, "ec.rebuild")
    assert "rebuilt" in out
    spread2 = _ec_shard_spread(env, vid)
    assert sorted(s for sids in spread2.values() for s in sids) == list(range(14))
    for fid, payload in fids:
        assert client.read(fid) == payload, f"fid {fid} corrupted after ec.rebuild"

    # balance: counts within 1 of each other afterwards
    run(env, "ec.balance")
    counts = [len(s) for s in _ec_shard_spread(env, vid).values()]
    assert max(counts) - min(counts) <= 1 or len(counts) == 4

    # decode back to a normal volume; data still readable
    out = run(env, f"ec.decode -volumeId {vid}")
    assert "restored as normal volume" in out
    assert _ec_shard_spread(env, vid) == {}
    for fid, payload in fids:
        assert client.read(fid) == payload, f"fid {fid} corrupted after ec.decode"


def _make_second_volume(cluster):
    """Two live volumes in the default collection: fill vid 1, mark it
    readonly is not enough (ec.encode skips nothing by state) — instead
    grow by marking 1 readonly so the next upload allocates vid 2."""
    master, servers, client, env = cluster
    fids_a = _upload_some(client, n=6)
    vid_a = int(fids_a[0][0].split(",", 1)[0])
    owner = next(s for s in servers if s.store.get_volume(vid_a) is not None)
    owner.store.get_volume(vid_a).read_only = True
    # master must notice via heartbeat before assign picks a fresh volume
    import time as _time

    deadline = _time.monotonic() + 5
    vid_b = vid_a
    fids_b = []
    while _time.monotonic() < deadline and vid_b == vid_a:
        try:
            res = client.submit(b"second-volume-seed")
        except Exception:  # master hasn't seen the readonly mark yet (422)
            _time.sleep(0.1)
            continue
        fids_b.append((res.fid, b"second-volume-seed"))
        vid_b = int(res.fid.split(",", 1)[0])
        _time.sleep(0.1)
    assert vid_b != vid_a, "second volume never grew"
    owner.store.get_volume(vid_a).read_only = False
    return fids_a + fids_b, vid_a, vid_b


def test_ec_encode_batch_resume_after_interrupt(cluster, tmp_path, monkeypatch):
    """SURVEY §5: a batch ec.encode killed mid-run resumes — the rerun
    skips checkpointed volumes instead of re-encoding them."""
    import seaweedfs_tpu.shell.command_ec as cec

    master, servers, client, env = cluster
    fids, vid_a, vid_b = _make_second_volume(cluster)
    ckpt = str(tmp_path / "enc.ckpt")
    run(env, "lock")

    # simulated kill: the cut-over of the SECOND volume dies at its start —
    # after the first volume's completed and was checkpointed (a volume is
    # done when ITS cut-over is, whether or not a batch generated both)
    real = cec._spread_cutover
    calls = {"n": 0}

    def dying(env_, nodes, locations, vid, coll, w, *a):
        calls["n"] += 1
        if calls["n"] >= 2:
            raise KeyboardInterrupt("simulated operator kill")
        return real(env_, nodes, locations, vid, coll, w, *a)

    monkeypatch.setattr(cec, "_spread_cutover", dying)
    with pytest.raises(KeyboardInterrupt):
        run(env, f"ec.encode -collection '' -force -checkpoint {ckpt} "
                 f"-largeBlockSize {LARGE} -smallBlockSize {SMALL}")
    import json as _json

    with open(ckpt) as f:
        saved = _json.load(f)
    assert saved["done"] == [vid_a], "first volume must be checkpointed"

    # rerun (no kill): the checkpointed volume is skipped even though the
    # master's topology may still show it (stale heartbeat window)
    monkeypatch.setattr(cec, "_spread_cutover", real)
    out = run(env, f"ec.encode -collection '' -force -checkpoint {ckpt} "
                   f"-largeBlockSize {LARGE} -smallBlockSize {SMALL}")
    if f"volume {vid_a}" in out:
        assert f"ec.encode volume {vid_a}: skip (checkpointed)" in out
    assert f"ec.encode volume {vid_b}" in out
    import os as _os

    assert not _os.path.exists(ckpt), "completed batch must clear checkpoint"
    # every blob from both volumes still readable
    for fid, payload in fids:
        assert client.read(fid) == payload, fid


def test_rebuild_shard_copies_run_concurrently(cluster, monkeypatch):
    """command_ec_rebuild.go's prepareDataToRecover analog: survivor shard
    pulls overlap in time — rebuild wall time is the slowest source, not
    the sum of copies."""
    import threading
    import time as _t

    master, servers, client, env = cluster
    fids = _upload_some(client, n=10)
    vid = int(fids[0][0].split(",", 1)[0])
    run(env, "lock")
    run(env, f"ec.encode -volumeId {vid} -largeBlockSize {LARGE} -smallBlockSize {SMALL}")

    spread = _ec_shard_spread(env, vid)
    victim_url, victim_sids = sorted(spread.items())[0]
    victim = next(s for s in servers if s.url == victim_url)
    host = victim_url.rsplit(":", 1)[0]
    env.vs_call(
        f"{host}:{victim.grpc_port}",
        "VolumeEcShardsDelete",
        {"volume_id": vid, "shard_ids": victim_sids},
    )

    orig = env.vs_call
    lock = threading.Lock()
    state = {"cur": 0, "max": 0, "copies": 0}

    def tracked(addr, method, req, timeout=300):
        if method != "VolumeEcShardsCopy":
            return orig(addr, method, req, timeout=timeout)
        with lock:
            state["cur"] += 1
            state["copies"] += 1
            state["max"] = max(state["max"], state["cur"])
        _t.sleep(0.25)  # hold the slot so overlap is observable
        try:
            return orig(addr, method, req, timeout=timeout)
        finally:
            with lock:
                state["cur"] -= 1

    monkeypatch.setattr(env, "vs_call", tracked)
    out = run(env, "ec.rebuild")
    assert "rebuilt" in out
    assert state["copies"] >= 2, "expected pulls from >=2 survivor sources"
    assert state["max"] >= 2, "shard copies ran strictly serially"
    for fid, payload in fids:
        assert client.read(fid) == payload


def test_volume_vacuum_and_mark(cluster):
    master, servers, client, env = cluster
    fids = _upload_some(client, n=10)
    vid = int(fids[0][0].split(",", 1)[0])
    for fid, _ in fids[:6]:
        client.delete(fid)
    run(env, "lock")
    out = run(env, f"volume.vacuum -volumeId {vid}")
    assert "->" in out
    for fid, payload in fids[6:]:
        assert client.read(fid) == payload
    out = run(env, f"volume.mark -volumeId {vid} -readonly")
    assert "readonly" in out
    out = run(env, f"volume.mark -volumeId {vid} -writable")
    assert "writable" in out


def test_fix_replication(cluster):
    master, servers, client, env = cluster
    res = client.submit(b"replicated payload", replication="001")
    vid = int(res.fid.split(",", 1)[0])
    # wait for heartbeats to register both replicas
    holders = [
        n for n in env.topology_nodes()
        if any(int(v["id"]) == vid for v in n.get("volumes", []))
    ]
    assert len(holders) == 2
    # drop one replica behind the master's back
    victim = holders[0]
    host = victim["url"].rsplit(":", 1)[0]
    env.vs_call(f"{host}:{victim['grpc_port']}", "VolumeDelete", {"volume_id": vid})
    out = run(env, "volume.fix.replication -noFix")
    assert f"volume {vid}: 1/2 replicas" in out
    run(env, "lock")
    out = run(env, "volume.fix.replication")
    assert "fixed 1" in out
    holders = [
        n for n in env.topology_nodes()
        if any(int(v["id"]) == vid for v in n.get("volumes", []))
    ]
    assert len(holders) == 2
    assert client.read(res.fid) == b"replicated payload"


def test_lock_lost_after_lease_steal(cluster):
    """If the master re-leases the lock to someone else (our lease expired),
    the next renewal must drop the token so mutating commands abort."""
    import time as _time

    master, servers, client, env = cluster
    env.lock()
    assert env.is_locked
    with master._admin_lock_mu:
        master._admin_locks["admin"] = (999, _time.monotonic() + 30, "thief")
    assert env._renew_once() is False
    assert not env.is_locked
    with pytest.raises(ShellError, match="lock the cluster"):
        run(env, "volume.delete -volumeId 1")


def test_ec_lifecycle_with_collection(cluster):
    """Collection must ride the heartbeat into the EC registry so rebuild
    resolves the right shard paths without a flag."""
    master, servers, client, env = cluster
    import os as _os

    fids = []
    for i in range(8):
        res = client.submit(_os.urandom(600), collection="foo")
        fids.append((res.fid, client.read(res.fid)))
    vid = int(fids[0][0].split(",", 1)[0])
    run(env, "lock")
    out = run(
        env,
        f"ec.encode -volumeId {vid} -largeBlockSize {LARGE} -smallBlockSize {SMALL}",
    )
    assert f"ec.encode volume {vid}" in out
    # master's registry knows the collection
    assert env.volume_list().get("ec_collections", {}).get(str(vid)) == "foo"
    # lose shards, rebuild WITHOUT passing -collection
    spread = _ec_shard_spread(env, vid)
    victim_url, victim_sids = sorted(spread.items())[0]
    victim = next(s for s in servers if s.url == victim_url)
    host = victim_url.rsplit(":", 1)[0]
    env.vs_call(
        f"{host}:{victim.grpc_port}",
        "VolumeEcShardsDelete",
        {"volume_id": vid, "collection": "foo", "shard_ids": victim_sids},
    )
    out = run(env, "ec.rebuild")
    assert "rebuilt" in out
    assert sorted(
        s for sids in _ec_shard_spread(env, vid).values() for s in sids
    ) == list(range(14))
    for fid, payload in fids:
        assert client.read(fid) == payload


def test_run_script_multiple_commands(cluster):
    master, servers, client, env = cluster
    out = io.StringIO()
    run_script(env, "lock; volume.list; unlock", out)
    s = out.getvalue()
    assert "locked" in s and "DataCenter" in s and "unlocked" in s


def test_volume_balance_moves_volumes(cluster):
    """command_volume_balance.go analog: an uneven cluster converges to
    counts within 1, moved volumes stay fully readable."""
    master, servers, client, env = cluster
    fids = _upload_some(client, n=30, size=900)
    # force growth of several volumes so there's something to move
    for _ in range(6):
        client.assign()  # each assign may grow a volume
    import time as _t

    _t.sleep(0.8)  # heartbeats settle
    counts_before = {
        n["url"]: len(n.get("volumes", [])) for n in env.topology_nodes()
    }
    run(env, "lock")
    out = run(env, "volume.balance")
    assert "volume.balance:" in out
    _t.sleep(0.8)  # heartbeats propagate the moves
    counts = {n["url"]: len(n.get("volumes", [])) for n in env.topology_nodes()}
    assert max(counts.values()) - min(counts.values()) <= 1, (counts_before, counts)
    for fid, payload in fids:
        assert client.read(fid) == payload, f"{fid} unreadable after balance"


def test_volume_move_to_named_node(cluster):
    master, servers, client, env = cluster
    fids = _upload_some(client, n=8, size=800)
    vid = int(fids[0][0].split(",", 1)[0])
    run(env, "lock")
    src = next(s for s in servers if s.store.get_volume(vid) is not None)
    dst = next(
        s for s in servers
        if s.store.get_volume(vid) is None and s.url != src.url
    )
    out = run(env, f"volume.move -volumeId {vid} -target {dst.url}")
    assert f"-> {dst.url}" in out
    assert dst.store.get_volume(vid) is not None
    assert src.store.get_volume(vid) is None
    for fid, payload in fids:
        assert client.read(fid) == payload, f"{fid} unreadable after move"
    # moved volume accepts writes again (thawed on the destination)
    import os as _os

    res = client.submit(_os.urandom(500))
    assert client.read(res.fid)
    # moving again to the same node is a no-op
    out = run(env, f"volume.move -volumeId {vid} -target {dst.url}")
    assert "already on" in out
    # unknown target is refused
    with pytest.raises(ShellError, match="unknown node"):
        run(env, f"volume.move -volumeId {vid} -target 127.0.0.1:1")


def test_cluster_ps_and_raft_ps(cluster):
    master, servers, client, env = cluster
    out = run(env, "cluster.raft.ps")
    assert "raft disabled" in out and master.address in out
    out = run(env, "cluster.ps")
    assert out.count("volume server") == 4
    assert f"master * {master.address}" in out


def test_collection_delete(cluster):
    master, servers, client, env = cluster
    res = client.submit(b"c" * 300, collection="trash")
    keep = client.submit(b"k" * 300)
    run(env, "lock")
    out = run(env, "collection.delete -collection trash")
    assert "would delete" in out  # dry run without -force
    assert client.read(res.fid) == b"c" * 300  # still there
    out = run(env, "collection.delete -collection trash -force")
    assert "removed" in out
    import time as _t

    _t.sleep(0.5)
    for n in env.topology_nodes():
        assert not any(
            v.get("collection") == "trash" for v in n.get("volumes", [])
        )
    assert client.read(keep.fid) == b"k" * 300  # other collections untouched
    with pytest.raises(Exception):
        client.read(res.fid)


def test_volume_delete_empty(cluster):
    master, servers, client, env = cluster
    res = client.submit(b"e" * 200)
    vid = int(res.fid.split(",", 1)[0])
    import time as _t

    _t.sleep(0.6)  # heartbeat carries the new file_count
    run(env, "lock")
    out = run(env, "volume.deleteEmpty -force")
    # sibling volumes grown alongside ours may legitimately be empty; the
    # volume with a live needle must survive
    assert f"removed {vid} from" not in out
    assert any(
        int(v["id"]) == vid
        for n in env.topology_nodes()
        for v in n.get("volumes", [])
    )
    client.delete(res.fid)
    _t.sleep(0.6)  # heartbeat carries the new delete_count
    out = run(env, "volume.deleteEmpty")
    assert f"volume {vid} is empty" in out  # dry run reports
    out = run(env, "volume.deleteEmpty -force")
    assert f"removed {vid} from" in out
    _t.sleep(0.5)
    assert all(
        int(v["id"]) != vid
        for n in env.topology_nodes()
        for v in n.get("volumes", [])
    )


def test_volume_configure_replication(cluster):
    master, servers, client, env = cluster
    res = client.submit(b"r" * 100)
    vid = int(res.fid.split(",", 1)[0])
    run(env, "lock")
    out = run(env, f"volume.configure.replication -volumeId {vid} -replication 001")
    assert "replication -> 001" in out
    # persisted in the superblock: visible on the live volume object
    holder = next(s for s in servers if s.store.get_volume(vid) is not None)
    assert str(holder.store.get_volume(vid).super_block.replica_placement) == "001"
    import time as _t

    _t.sleep(0.6)
    v = next(
        v
        for n in env.topology_nodes()
        for v in n.get("volumes", [])
        if int(v["id"]) == vid
    )
    assert v.get("replica_placement") == "001"
    with pytest.raises(ShellError, match="no matching volumes"):
        run(env, "volume.configure.replication -volumeId 9999 -replication 010")


def test_volume_check_disk_detects_and_fixes(cluster):
    import base64

    master, servers, client, env = cluster
    res = client.submit(b"sync me" * 50, replication="001")
    vid = int(res.fid.split(",", 1)[0])
    import time as _t

    _t.sleep(0.6)
    holders = [s for s in servers if s.store.get_volume(vid) is not None]
    assert len(holders) == 2  # 001 => two same-DC copies
    # diverge: write one needle directly to a single replica (bypasses the
    # HTTP fan-out), as if the other replica missed a write while down
    lone = f"{vid},deadbeef01020304"
    from seaweedfs_tpu import rpc
    from seaweedfs_tpu.pb import VOLUME_SERVICE

    with rpc.RpcClient(holders[0].grpc_address) as c:
        c.call(
            VOLUME_SERVICE,
            "WriteNeedle",
            {"fid": lone, "data": base64.b64encode(b"lone needle").decode()},
        )
    run(env, "lock")
    out = run(env, f"volume.check.disk -volumeId {vid}")
    assert "missing 1 needles" in out and "0 needles synced" in out
    out = run(env, f"volume.check.disk -volumeId {vid} -fix")
    assert "1 needles synced" in out
    # both replicas now serve the needle with identical bytes
    for h in holders:
        n = h.store.read_needle(vid, 0xDEADBEEF)
        assert n.data == b"lone needle"
        assert n.cookie == 0x01020304
    out = run(env, f"volume.check.disk -volumeId {vid}")
    assert "0 divergent" in out


def test_volume_server_evacuate_and_leave(cluster):
    master, servers, client, env = cluster
    fids = _upload_some(client, n=20, size=600)
    vid = int(fids[0][0].split(",", 1)[0])
    run(env, "lock")
    run(env, f"ec.encode -volumeId {vid} -force")  # give the node EC shards too
    import time as _t

    _t.sleep(0.8)
    victim = next(
        n
        for n in env.topology_nodes()
        if n.get("volumes") or n.get("ec_shards")
    )
    out = run(env, f"volumeServer.evacuate -node {victim['url']} -noApply")
    assert "dry" in out
    out = run(env, f"volumeServer.evacuate -node {victim['url']}")
    assert "volumeServer.evacuate:" in out
    _t.sleep(0.8)
    after = next(n for n in env.topology_nodes() if n["url"] == victim["url"])
    assert not after.get("volumes") and not after.get("ec_shards"), after
    for fid, payload in fids:
        assert client.read(fid) == payload, f"{fid} unreadable after evacuate"
    # leave: the emptied node departs the topology and stops heartbeating
    out = run(env, f"volumeServer.leave -node {victim['url']}")
    assert "left the cluster" in out
    _t.sleep(0.8)
    assert all(n["url"] != victim["url"] for n in env.topology_nodes())


def test_volume_check_disk_propagates_deletes(cluster):
    """A replica that missed a DELETE must get the tombstone propagated —
    never the deleted needle resurrected from the lagging replica."""
    master, servers, client, env = cluster
    res = client.submit(b"doomed" * 30, replication="001")
    vid = int(res.fid.split(",", 1)[0])
    import time as _t

    _t.sleep(0.6)
    holders = [s for s in servers if s.store.get_volume(vid) is not None]
    assert len(holders) == 2
    # delete on ONE replica only (as if the other was down for the delete)
    from seaweedfs_tpu import rpc
    from seaweedfs_tpu.pb import VOLUME_SERVICE

    with rpc.RpcClient(holders[0].grpc_address) as c:
        c.call(VOLUME_SERVICE, "DeleteNeedle", {"fid": res.fid})
    nid = int(res.fid.split(",", 1)[1][:-8], 16)
    assert holders[1].store.get_volume(vid).nm.get(nid) is not None
    run(env, "lock")
    out = run(env, f"volume.check.disk -volumeId {vid}")
    assert "outlived its delete" in out
    out = run(env, f"volume.check.disk -volumeId {vid} -fix")
    assert "1 needles synced" in out
    # the delete propagated: gone from BOTH replicas, not resurrected
    for h in holders:
        assert h.store.get_volume(vid).nm.get(nid) is None
    out = run(env, f"volume.check.disk -volumeId {vid}")
    assert "0 divergent" in out


def test_volume_check_disk_rewrite_after_delete_wins(cluster):
    """A needle re-written AFTER its delete must not be destroyed by the
    tombstone rule: the rewrite postdates the delete, so check.disk copies
    the new write to the replica that missed it."""
    import base64

    master, servers, client, env = cluster
    res = client.submit(b"first life" * 20, replication="001")
    vid = int(res.fid.split(",", 1)[0])
    nid = int(res.fid.split(",", 1)[1][:-8], 16)
    import time as _t

    _t.sleep(0.6)
    holders = [s for s in servers if s.store.get_volume(vid) is not None]
    assert len(holders) == 2
    # delete everywhere (normal fan-out)...
    client.delete(res.fid)
    # ...then re-write the same needle on ONE replica only (replica B was
    # down for the re-write)
    from seaweedfs_tpu import rpc
    from seaweedfs_tpu.pb import VOLUME_SERVICE

    with rpc.RpcClient(holders[0].grpc_address) as c:
        c.call(
            VOLUME_SERVICE,
            "WriteNeedle",
            {"fid": res.fid, "data": base64.b64encode(b"second life").decode()},
        )
    run(env, "lock")
    out = run(env, f"volume.check.disk -volumeId {vid} -fix")
    assert "1 needles synced" in out and "outlived" not in out
    # the rewrite won: live with the new bytes on BOTH replicas
    for h in holders:
        n = h.store.read_needle(vid, nid)
        assert n.data == b"second life"


def test_volume_grow(cluster):
    master, servers, client, env = cluster
    run(env, "lock")
    before = sum(len(n.get("volumes", [])) for n in env.topology_nodes())
    out = run(env, "volume.grow -count 3 -collection grown")
    assert "3 volumes created" in out
    import time as _t

    _t.sleep(0.8)
    grown = [
        v
        for n in env.topology_nodes()
        for v in n.get("volumes", [])
        if v.get("collection") == "grown"
    ]
    assert len(grown) == 3
    after = sum(len(n.get("volumes", [])) for n in env.topology_nodes())
    assert after >= before + 3
    # grown volumes are immediately writable
    res = client.submit(b"to a pre-grown volume", collection="grown")
    assert client.read(res.fid) == b"to a pre-grown volume"


def test_volume_unmount_and_mount(cluster):
    """volume.unmount fences a volume (files kept, dropped from topology);
    volume.mount brings it back with data intact."""
    master, servers, client, env = cluster
    res = client.submit(b"fence me" * 10)
    vid = int(res.fid.split(",", 1)[0])
    holder = next(s for s in servers if s.store.get_volume(vid) is not None)
    run(env, "lock")
    out = run(env, f"volume.unmount -volumeId {vid} -node {holder.url}")
    assert "volume.unmount" in out
    assert holder.store.get_volume(vid) is None  # not serving
    import os as _os
    import time as _t

    _t.sleep(0.5)
    assert all(  # gone from the topology
        int(v["id"]) != vid
        for n in env.topology_nodes()
        for v in n.get("volumes", [])
    )
    # files still on disk
    dat = [
        p
        for loc in holder.store.locations
        for p in _os.listdir(loc.directory)
        if p.endswith(".dat")
    ]
    assert dat
    out = run(env, f"volume.mount -volumeId {vid} -node {holder.url}")
    assert "volume.mount" in out
    assert client.read(res.fid) == b"fence me" * 10


def test_ec_encode_quiet_for_filter(cluster):
    """-quietFor skips volumes with recent writes (the reference's encode
    safety filter: a volume still taking writes must not be EC-frozen)."""
    master, servers, client, env = cluster
    _upload_some(client, n=4)
    import time as _t

    _t.sleep(0.6)  # heartbeat carries last_modified
    run(env, "lock")
    out = run(env, "ec.encode -quietFor 3600 -force")
    assert "no matching volumes" in out  # everything was just written
    out = run(env, "ec.encode -force")  # filter disabled: encodes
    assert "ec.encode volume" in out


def test_ec_balance_improves_rack_spread(cluster):
    """Integration: ec.balance's move path (copy/mount/delete RPCs) spreads
    a rack-concentrated volume back across racks; the candidate ORDERING
    itself is pinned by test_pick_balance_move_prefers_rack_spread."""
    master, servers, client, env = cluster
    fids = _upload_some(client, n=12)
    vid = int(fids[0][0].split(",", 1)[0])
    run(env, "lock")
    run(env, f"ec.encode -volumeId {vid} -force")
    # concentrate everything onto rack0's two nodes (racks are i%2)
    rack0 = [s for i, s in enumerate(servers) if i % 2 == 0]
    rack1 = [s for i, s in enumerate(servers) if i % 2 == 1]
    import time as _t

    _t.sleep(0.8)
    spread = _ec_shard_spread(env, vid)
    for s in rack1:
        sids = spread.get(s.url, [])
        if not sids:
            continue
        env.vs_call(
            rack0[0].grpc_address, "VolumeEcShardsCopy",
            {"volume_id": vid, "collection": "", "shard_ids": sids,
             "source_data_node": s.grpc_address, "copy_ecx_file": False},
        )
        env.vs_call(
            rack0[0].grpc_address, "VolumeEcShardsMount",
            {"volume_id": vid, "collection": "", "shard_ids": sids},
        )
        env.vs_call(
            s.grpc_address, "VolumeEcShardsDelete",
            {"volume_id": vid, "collection": "", "shard_ids": sids},
        )
    _t.sleep(0.8)
    spread = _ec_shard_spread(env, vid)
    rack1_before = sum(len(spread.get(s.url, [])) for s in rack1)
    assert rack1_before == 0  # fully concentrated in rack0
    run(env, "ec.balance")
    _t.sleep(0.8)
    spread = _ec_shard_spread(env, vid)
    rack1_after = sum(len(spread.get(s.url, [])) for s in rack1)
    assert rack1_after >= 5, spread  # balance pushed shards back across racks
    for fid, payload in fids:
        assert client.read(fid) == payload


def test_pick_balance_move_prefers_rack_spread():
    """Unit-pin the rack-preference ordering: with two candidate volumes,
    the one concentrated in the heavy node's rack moves first."""
    from seaweedfs_tpu.shell.command_ec import pick_balance_move

    by_url = {
        "a:1": {"rack": "r0"},
        "b:1": {"rack": "r0"},
        "c:1": {"rack": "r1"},
    }
    # vid 7: all shards in rack r0 (concentrated); vid 9: already spread
    placement = {
        "a:1": {7: {0, 1, 2}, 9: {0, 1}},
        "b:1": {7: {3, 4}},
        "c:1": {9: {2, 3}},
    }
    picked = pick_balance_move(placement, by_url, "a:1", "c:1", {}, "")
    assert picked is not None and picked[0] == 7  # spread gain wins
    # collection filter excludes vid 7 -> vid 9 is the only candidate
    picked = pick_balance_move(
        placement, by_url, "a:1", "c:1", {7: "x", 9: "y"}, "y"
    )
    assert picked is not None and picked[0] == 9
    # nothing movable -> None
    assert pick_balance_move({"a:1": {}, "c:1": {}}, by_url, "a:1", "c:1", {}, "") is None


def test_orphans_after_cutoff_chunks_and_classifies(monkeypatch):
    """fsck's orphan dating: the VolumeNeedleTs RPC is chunked (an
    unchunked JSON request can blow gRPC's 4 MB cap), a post-cutoff copy
    on ANY replica spares the needle, and ids NO reachable holder could
    date come back as 'undatable' (holder unreachable) — distinct from
    'dated after the cutoff'."""
    from seaweedfs_tpu.shell import command_volume as cv

    monkeypatch.setattr(cv, "_NEEDLE_TS_CHUNK", 3)
    cutoff = 1000
    nids = list(range(1, 11))  # 10 ids -> 4 chunks per holder

    calls = []

    class Env:
        def vs_call(self, addr, method, req, timeout=300):
            assert method == "VolumeNeedleTs"
            chunk = req["needle_ids"]
            assert len(chunk) <= 3
            calls.append((addr, tuple(chunk)))
            if addr.startswith("down"):
                raise ConnectionError("holder down")
            if 7 in chunk and addr.startswith("flaky"):
                raise ConnectionError("mid-volume failure")
            # holder 'a' dates needles 2 and 7 after the cutoff
            return {"ts": {str(n): 2000 if n in (2, 7) else 10 for n in chunk}}

    holders = [
        {"url": "a:80", "grpc_port": 1},
        {"url": "down:80", "grpc_port": 1},
    ]
    fresh, undatable = cv._orphans_after_cutoff(Env(), holders, 5, nids, cutoff)
    assert fresh == {2, 7}
    assert undatable == set()
    # chunking: 4 chunks on the live holder; the down holder fast-fails
    # after its first chunk (no RPC-timeout-per-chunk against a dead box)
    assert calls == [
        ("a:1", (1, 2, 3)),
        ("a:1", (4, 5, 6)),
        ("a:1", (7, 8, 9)),
        ("a:1", (10,)),
        ("down:1", (1, 2, 3)),
    ]

    # every holder down: nothing datable, nothing falsely 'in flight'
    fresh, undatable = cv._orphans_after_cutoff(
        Env(), [{"url": "down:80", "grpc_port": 1}], 5, nids, cutoff
    )
    assert fresh == set() and undatable == set(nids)

    # a mid-volume failure on the only holder: that chunk AND the holder's
    # remaining chunks are undatable (fast-fail), earlier chunks keep their
    # dates
    calls.clear()
    fresh, undatable = cv._orphans_after_cutoff(
        Env(), [{"url": "flaky:80", "grpc_port": 1}], 5, nids, cutoff
    )
    assert fresh == {2} and 7 not in fresh
    assert undatable == {7, 8, 9, 10}  # failed chunk + fast-failed remainder


# -- the script's trace, handed to the master when the script ends (PR 42) -----


@pytest.fixture
def traced(monkeypatch):
    from seaweedfs_tpu.obs import trace

    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    monkeypatch.setenv("WEEDTPU_TRACE_SAMPLE", "1.0")
    trace.RING.clear()
    yield trace
    trace.RING.clear()


def _report_calls():
    from seaweedfs_tpu import stats

    return stats.RpcServerSeconds.labels("ReportTrace").total


@pytest.mark.parametrize("master_is", ["there", "down", "late"])
def test_the_hand_over_never_changes_what_a_script_prints_or_raises(traced, monkeypatch, master_is):
    """The script's trace goes to the master in ONE call when the script
    ends, a failed script's too. A master that is down at that moment, or
    answers after the 0.2 s the child gives it, costs the trace its place in
    the master's ring (this process's ring then has it) and nothing else."""
    import time

    import grpc

    if master_is == "late":
        real = MasterServer._rpc_report_trace
        monkeypatch.setattr(MasterServer, "_rpc_report_trace",
                            lambda self, req, ctx: (time.sleep(1.5), real(self, req, ctx))[1])
    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    env = CommandEnv(master.address)
    try:
        if master_is == "down":
            def down(method, req, timeout):
                assert method == "ReportTrace" and timeout == shell._HAND_OVER_TIMEOUT == 0.2
                raise grpc.RpcError("the master went away")

            monkeypatch.setattr(env.client, "call_current", down)
        calls0 = _report_calls()
        out = io.StringIO()
        t0 = time.monotonic()
        run_script(env, "lock; unlock", out)
        took = time.monotonic() - t0
        assert out.getvalue() == "cluster locked\ncluster unlocked\n"
        assert took < 1.2  # the script and 0.2 s at most: never the late master's 1.5 s
        (script,) = traced.RING.snapshot(kind="shell.script")
        assert [s["attrs"]["command"] for s in script["root"]["spans"]] == ["lock", "unlock"]
        if master_is == "there":
            assert _report_calls() == calls0 + 1 and script["birth_unix_ns"] == script["unix_ns"]
        else:
            assert "birth_unix_ns" not in script  # this process's own ring took it, as it is
        # a script that fails hands its trace over too, and raises what it raised
        traced.RING.clear()
        out = io.StringIO()
        with pytest.raises(ShellError, match="lock the cluster first"):
            run_script(env, "ec.rebuild; lock", out)
        (failed,) = traced.RING.snapshot(kind="shell.script")
        assert failed["error"].startswith("ShellError: lock the cluster first") and not env.is_locked
        assert failed["root"]["spans"][0]["error"] == "ShellError" and out.getvalue() == ""
        if master_is == "late":
            time.sleep(1.6)  # let the late answers land before the ring is cleared
    finally:
        env.close()
        master.stop()


@pytest.mark.parametrize("master_is", ["there", "late"])
def test_a_child_exits_zero_with_the_same_output_whatever_the_master_does_with_its_trace(
        traced, monkeypatch, master_is):
    """The real `-c` child, `python -m seaweedfs_tpu shell`: exit code, stdout
    and stderr are the same against a master that takes the trace and one
    that sits on it; the one that takes it holds the child's whole life, from
    its birth, after the child has gone."""
    import time

    if master_is == "late":
        real = MasterServer._rpc_report_trace
        monkeypatch.setattr(MasterServer, "_rpc_report_trace",
                            lambda self, req, ctx: (time.sleep(1.5), real(self, req, ctx))[1])
    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    try:
        t0 = time.time()
        done = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu", "shell", "-master", master.address, "-c", "lock; unlock"],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=120,
            capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
        wall = time.time() - t0
        assert (done.returncode, done.stdout, done.stderr) == (0, "cluster locked\ncluster unlocked\n", "")
        scripts = traced.RING.snapshot(kind="shell.script")
        if master_is == "late":
            assert scripts == []  # the child did not wait for it
            time.sleep(1.6)
            return
        (script,) = scripts
        start, lock, unlock = script["root"]["spans"]
        assert start["name"] == "shell.start" and start["t_ms"] == 0.0
        assert set(start["attrs"]) == {"interp_ms", "import_ms", "connect_ms", "modules"}
        parts = sum(start["attrs"][k] for k in ("interp_ms", "import_ms", "connect_ms"))
        assert 0 < parts <= start["dur_ms"] + 0.01 and 50 <= start["attrs"]["modules"] <= lock["attrs"]["modules"]
        # born after this test spawned it (the kernel's tick is 10 ms), gone before it returned
        assert t0 - 0.02 <= script["unix_ns"] / 1e9 <= t0 + wall
        assert script["duration_s"] <= wall + 0.02
        covered = sum(s["dur_ms"] for s in script["root"]["spans"])
        assert covered >= 0.95 * script["duration_s"] * 1e3
        # every RPC of the child carried the script's id: lock and unlock are one trace
        ids = {t["trace_id"] for t in traced.RING.snapshot(kind="rpc.server")
               if t["root"]["attrs"]["method"] in ("LeaseAdminToken", "ReleaseAdminToken")}
        assert ids == {script["trace_id"]}
    finally:
        master.stop()


def test_the_lock_renewer_carries_the_running_commands_trace(traced, monkeypatch):
    """A renewal that falls inside a command is one of its `rpcs=` and an
    `rpc.client` span under it, `thread=` saying it was not the command's own
    thread; the master records it under the script's id."""
    import time

    monkeypatch.setattr(shell, "_RENEW_INTERVAL", 0.05)
    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    env = CommandEnv(master.address)
    slow = shell.register(shell.ShellCommand("slow.command", "sleeps", lambda a, e, w: time.sleep(0.3)))
    try:
        run_script(env, "lock; slow.command; unlock", io.StringIO())
    finally:
        del shell._REGISTRY[slow.name]
        env.close()
        master.stop()
    (script,) = traced.RING.snapshot(kind="shell.script")
    command = script["root"]["spans"][1]
    renewals = [s for s in command["spans"] if s["name"] == "rpc.client"]
    assert command["attrs"]["command"] == "slow.command" and command["attrs"]["rpcs"] == len(renewals) >= 2
    assert all(s["attrs"]["method"] == "LeaseAdminToken" and "thread" in s["attrs"] for s in renewals)
    leases = [t for t in traced.RING.snapshot(kind="rpc.server", limit=1000)
              if t["root"]["attrs"]["method"] == "LeaseAdminToken"]
    assert len(leases) >= 3 and {t["trace_id"] for t in leases} == {script["trace_id"]}
