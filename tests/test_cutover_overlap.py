"""What lets a sweep cut its volumes over side by side (PR 43): the door
(`utils/door.py`: one at a time, those who wait share the next), the
checkpoint's marks through it, the volume server's heartbeats through it, and
an EC mount that builds its volume outside the store's lock. On the CPU, each
test under a time limit of its own (`_threads`)."""

import json
import os
import sys
import threading
import time

import pytest

import test_ec_rebuild_cluster as cl
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.ec import ec_volume, stripe
from seaweedfs_tpu.obs import trace
from seaweedfs_tpu.ops.rs_codec import Encoder
from seaweedfs_tpu.shell import command_ec, grpc_addr
from seaweedfs_tpu.storage import store as store_mod
from seaweedfs_tpu.utils.door import Door


class _Threads:
    """Threads of a test under ONE time limit: `join()` fails the test where
    one is still running at the limit, and raises what one raised."""

    def __init__(self, limit):
        self.deadline = time.monotonic() + limit
        self.threads, self.errors = [], []

    def start(self, fn, *args):
        def run():
            try:
                fn(*args)
            except BaseException as e:  # noqa: BLE001 — the test's failure, raised in join()
                self.errors.append(e)

        self.threads.append(threading.Thread(target=run, daemon=True))
        self.threads[-1].start()
        return self.threads[-1]

    def join(self):
        for t in self.threads:
            t.join(max(0.0, self.deadline - time.monotonic()))
            assert not t.is_alive(), "time limit: a thread is still running"
        if self.errors:
            raise self.errors[0]


# -- the door ------------------------------------------------------------------------


def test_callers_that_wait_together_share_the_next_run_and_runs_never_overlap():
    """The first caller runs alone; three that arrive while it runs share ONE
    next run, which begins after the first has ended and holds exactly their
    items; each returns how many the run served."""
    runs, inside, release = [], threading.Event(), threading.Event()
    running = [0]

    def run(items):
        running[0] += 1
        assert running[0] == 1, "two runs at once"
        runs.append(sorted(items))
        if len(runs) == 1:
            inside.set()
            assert release.wait(10)
        running[0] -= 1

    door = Door(run)
    served = {}
    ts = _Threads(20)
    ts.start(lambda: served.__setitem__(0, door.through(0)))
    assert inside.wait(10)
    for i in (1, 2, 3):
        ts.start(lambda i=i: served.__setitem__(i, door.through(i)))
    cl._wait_for(lambda: len(door._next.items) == 3, timeout=10, msg="the three joined the next run")
    assert runs == [[0]] and served == {}  # nobody returns before a run that held it has ended
    release.set()
    ts.join()
    assert runs == [[0], [1, 2, 3]] and served == {0: 1, 1: 3, 2: 3, 3: 3}
    assert door.through(4) == 1 and runs[-1] == [4]  # a lone caller runs at once


def test_a_failed_run_fails_every_caller_of_it_and_a_closed_door_lets_waiters_go():
    inside, release = threading.Event(), threading.Event()

    def run(items):
        if 0 in items:
            inside.set()
            assert release.wait(10)
        if 1 in items:
            raise OSError("no space left on device")

    door = Door(run)
    got = {}

    def through(i):
        try:
            got[i] = door.through(i)
        except OSError as e:
            got[i] = str(e)

    ts = _Threads(20)
    ts.start(through, 0)
    assert inside.wait(10)
    ts.start(through, 1)
    ts.start(through, 2)
    cl._wait_for(lambda: len(door._next.items) == 2, timeout=10, msg="two joined the next run")
    release.set()
    ts.join()
    assert got == {0: 1, 1: "no space left on device", 2: "no space left on device"}
    assert door.through(3) == 1  # the next run is a new one

    inside.clear()
    release.clear()
    ts = _Threads(20)
    ts.start(through, 0)
    assert inside.wait(10)
    ts.start(through, 5)
    cl._wait_for(lambda: len(door._next.items) == 1, timeout=10, msg="one waits")
    door.close()
    cl._wait_for(lambda: got.get(5) == 0, timeout=10, msg="the waiter left without a run")
    release.set()
    ts.join()
    assert door.through(6) == 0


def test_the_door_under_thread_pressure_runs_every_item_once_after_its_call():
    """More threads than cores and a switch interval of microseconds: runs
    never overlap, every item is in exactly one run, and that run began after
    the item's caller had called."""
    began, in_run, state = {}, {}, {"running": 0, "runs": 0}

    def run(items):
        state["running"] += 1
        assert state["running"] == 1, "two runs at once"
        state["runs"] += 1
        for item in items:
            assert item in began and item not in in_run, f"{item} ran before its call or twice"
            in_run[item] = state["runs"]
        time.sleep(0.0005)
        state["running"] -= 1

    door = Door(run)

    def caller(t):
        for n in range(40):
            began[(t, n)] = True
            assert door.through((t, n)) >= 1
            assert (t, n) in in_run, "returned before the run that holds it ended"

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = _Threads(60)
        for t in range(32):
            ts.start(caller, t)
        ts.join()
    finally:
        sys.setswitchinterval(interval)
    assert len(in_run) == 32 * 40 and state["runs"] < 32 * 40  # some were shared


# -- the checkpoint -------------------------------------------------------------------


def test_eight_volumes_marked_from_eight_threads_share_writes_and_none_is_marked_early(tmp_path, monkeypatch):
    """Eight `mark(vid)` at once with a slow `os.fsync`: no file ever holds a
    vid whose mark had not been called, every file that is renamed was
    fsynced first, a mark returns only after the rename of a file that holds
    its vid, and the eight take between one and eight writes (here fewer than
    eight: those that wait for the first write share the second)."""
    path = str(tmp_path / "ckpt")
    ckpt = command_ec.EncodeCheckpoint(path, {"collection": "", "force": True})
    called, renamed, synced = set(), [], []
    real_fsync, real_replace = os.fsync, os.replace

    def fsync(fd):
        time.sleep(0.05)
        real_fsync(fd)
        synced.append(os.readlink(f"/proc/self/fd/{fd}"))

    def replace(src, dst):
        assert synced and synced[-1] == src, "renamed before its fsync"
        with open(src) as f:
            holds = set(json.load(f)["done"])
        assert holds <= called, f"{holds - called} in a file before their mark was called"
        real_replace(src, dst)
        renamed.append(holds)

    monkeypatch.setattr(os, "fsync", fsync)
    monkeypatch.setattr(os, "replace", replace)
    start = threading.Barrier(8)

    def mark(vid):
        start.wait(10)
        called.add(vid)
        ckpt.mark(vid)
        assert any(vid in holds for holds in renamed), f"mark({vid}) returned before its file was renamed"
        with open(path) as f:
            assert vid in json.load(f)["done"]

    ts = _Threads(30)
    for vid in range(1, 9):
        ts.start(mark, vid)
    ts.join()
    assert 1 <= ckpt.writes == len(renamed) < 8
    assert renamed[-1] == set(range(1, 9)) == ckpt.done
    assert all(a <= b for a, b in zip(renamed, renamed[1:]))  # a file never loses a volume
    assert os.listdir(tmp_path) == ["ckpt"]  # no temporary file left
    assert command_ec.EncodeCheckpoint(path, {"collection": "", "force": True}).load_done() == set(range(1, 9))


# -- the heartbeat ---------------------------------------------------------------------


@pytest.fixture
def one_server(tmp_path, monkeypatch):
    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    monkeypatch.setenv("WEEDTPU_TRACE_SAMPLE", "1.0")
    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    # the loop never beats inside the test: every heartbeat is a caller's
    server = VolumeServer([str(tmp_path)], master.address, heartbeat_interval=3600,
                          encoder=Encoder(10, 4, backend="numpy"))
    server.start()
    yield master, server
    server.stop()
    master.stop()


def test_heartbeats_reach_the_master_in_the_order_they_were_composed(one_server):
    """Three callers at once, the fan-out of the first held back: the master
    processes the heartbeats in composition order, the two late callers share
    ONE heartbeat composed after both had called, and every caller returns
    only after a heartbeat composed after its call was answered."""
    master, server = one_server
    composed, processed, answered = [], [], []
    first_out, release = threading.Event(), threading.Event()
    real_make, real_fanout = server._make_heartbeat, server._masters_fanout
    real_process = master.topology.process_heartbeat

    def make():
        hb = real_make()
        composed.append(1000 + len(composed))
        hb.max_volume_count = composed[-1]  # the number rides to the master
        return hb

    def fanout(method, req, timeout):
        if method == "Heartbeat" and req["max_volume_count"] == 1000:
            first_out.set()
            assert release.wait(10)
        n = real_fanout(method, req, timeout)
        if method == "Heartbeat":
            answered.append(req["max_volume_count"])
        return n

    def process(hb):
        processed.append(hb.max_volume_count)
        return real_process(hb)

    server._make_heartbeat, server._masters_fanout = make, fanout
    master.topology.process_heartbeat = process
    waiters = {}

    def call(i):
        before = len(composed)
        with trace.start("test.caller") as root:
            server.heartbeat_once()
        assert any(n >= 1000 + before for n in answered), f"caller {i} returned before a heartbeat composed after its call"
        (hb,) = [s for s in trace.iter_spans({"root": root.to_dict()}) if s["name"] == "vs.heartbeat"]
        waiters[i] = hb["attrs"]["waiters"]

    ts = _Threads(30)
    ts.start(call, 0)
    assert first_out.wait(10)
    ts.start(call, 1)
    ts.start(call, 2)
    cl._wait_for(lambda: len(server._hb_door._next.items) == 2, timeout=10, msg="both late callers wait")
    assert composed == [1000] and answered == []  # the next is not composed while one is on its way
    release.set()
    ts.join()
    assert composed == processed == answered == [1000, 1001]
    assert waiters == {0: 1, 1: 2, 2: 2}


def test_a_server_that_leaves_lets_its_waiting_callers_go(one_server):
    """`_stop` still ends it: callers that wait for the next heartbeat return
    when the server leaves, and no heartbeat re-registers it afterwards."""
    master, server = one_server
    first_out, release = threading.Event(), threading.Event()
    real_fanout = server._masters_fanout
    sent = []

    def fanout(method, req, timeout):
        if method == "Heartbeat":
            sent.append(method)
            first_out.set()
            assert release.wait(10)
        return real_fanout(method, req, timeout)

    server._masters_fanout = fanout
    ts = _Threads(30)
    ts.start(server.heartbeat_once)
    assert first_out.wait(10)
    late = ts.start(server.heartbeat_once)
    cl._wait_for(lambda: len(server._hb_door._next.items) == 1, timeout=10, msg="the late caller waits")
    server._leave_cluster()
    late.join(10)
    assert not late.is_alive()
    release.set()
    ts.join()
    server.heartbeat_once()
    assert sent == ["Heartbeat"]


# -- the mount ---------------------------------------------------------------------------


def _ec_volumes(tmp_path, vids):
    for vid in vids:
        cl._write_volume(str(tmp_path), vid, 43)
        stripe.generate_ec_files(str(tmp_path / str(vid)), large_block_size=cl.LARGE, small_block_size=cl.SMALL,
                                 encoder=Encoder(10, 4, backend="numpy"))
    return store_mod.Store([str(tmp_path)], encoder=Encoder(10, 4, backend="numpy"))


def test_two_mounts_of_one_volume_leave_one_open_and_another_volume_does_not_wait(tmp_path, monkeypatch):
    """`Store.mount_ec_volume` builds the `EcVolume` outside the store's lock
    and lets one mount of a vid through at a time: with the constructor of
    volume 1 held back, a second mount of volume 1 waits for the first and
    volume 2 mounts at once; released, the two mounts of volume 1 leave
    exactly one open `EcVolume` in the map, the other closed, and no file
    descriptor leaks."""
    store = _ec_volumes(tmp_path, (1, 2))
    fds = len(os.listdir("/proc/self/fd"))
    gate, built = threading.Event(), []
    arrived = threading.Semaphore(0)

    class Slowed(store_mod.EcVolume):
        def __init__(self, base, **kw):
            if os.path.basename(base) == "1":
                arrived.release()
                assert gate.wait(20)
            super().__init__(base, warm_on_mount=False, **kw)
            built.append(self)

    monkeypatch.setattr(store_mod, "EcVolume", Slowed)
    ts = _Threads(30)
    try:
        ts.start(store.mount_ec_volume, 1, str(tmp_path / "1"))
        ts.start(store.mount_ec_volume, 1, str(tmp_path / "1"))
        assert arrived.acquire(timeout=10)
        other = ts.start(store.mount_ec_volume, 2, str(tmp_path / "2"))
        other.join(10)
        assert not other.is_alive(), "the mount of volume 2 waited for volume 1's constructor"
        assert store.get_ec_volume(2) is not None and store.get_ec_volume(1) is None
        assert not arrived.acquire(timeout=0.2), "two mounts of volume 1 were built at once"
    finally:
        gate.set()
    ts.join()
    ones = [ev for ev in built if os.path.basename(ev.base) == "1"]
    assert len(ones) == 2
    assert [ev for ev in ones if ev.shard_ids] == [store.get_ec_volume(1)]  # the other is closed
    assert len(store.get_ec_volume(1).shard_ids) == 14
    assert len(os.listdir("/proc/self/fd")) == fds + 28
    store.close()
    assert len(os.listdir("/proc/self/fd")) == fds


def _fat_journal(base, needle_id):
    """A `.ecj` over the mount's compaction threshold: one deletion, journaled
    again and again."""
    with open(base + ".ecj", "ab") as f:
        f.write(needle_id.to_bytes(8, "big") * (ec_volume.ECJ_COMPACT_THRESHOLD // 8))


def test_a_delete_acknowledged_while_a_remount_folds_the_journal_is_still_deleted(tmp_path, monkeypatch):
    """A remount whose constructor folds the `.ecj` into the `.ecx` unlinks the
    journal: a delete that the mount it replaces journaled between the fold's
    read and its unlink would go with it. So that remount takes the old mount
    out of serving first, and a closed mount journals nothing: a delete sent
    at the worst moment, through the store or through a reference to the old
    mount, is refused (and so not acknowledged) or survives the next mount."""
    store = _ec_volumes(tmp_path, (1,))
    base = str(tmp_path / "1")
    old = store.mount_ec_volume(1, base)
    assert store.delete_needle(1, 3)
    _fat_journal(base, 3)
    acked, refused = [], []

    def delete(how, needle_id):
        try:
            if how(needle_id):
                acked.append(needle_id)
        except KeyError as e:
            refused.append(type(e))

    real_read = stripe.read_ecj
    folding = []

    def read_ecj(path):
        out = real_read(path)
        if folding and folding.pop():  # compact_ecj has read the journal and not yet unlinked it
            ts = _Threads(10)
            ts.start(delete, old.delete_needle, 5)
            ts.start(delete, lambda n: store.delete_needle(1, n), 7)
            ts.join()
        return out

    real_compact = stripe.compact_ecj

    def compact_ecj(path):
        folding.append(True)
        return real_compact(path)

    monkeypatch.setattr(stripe, "read_ecj", read_ecj)
    monkeypatch.setattr(stripe, "compact_ecj", compact_ecj)
    new = store.mount_ec_volume(1, base)
    assert folding == [], "the journal was not folded: the test tested nothing"
    assert not old.shard_ids and new is store.get_ec_volume(1)
    store.mount_ec_volume(1, base)
    for needle_id in [3] + acked:  # what was acknowledged is deleted for good
        with pytest.raises(ec_volume.NeedleDeleted):
            store.get_ec_volume(1).find_needle_from_ecx(needle_id)
    assert sorted(refused, key=str) == sorted([ec_volume.EcVolumeClosed, KeyError], key=str) and acked == []
    # sent again, they are taken by the mount that serves, and the next mount knows all three
    assert store.delete_needle(1, 5) and store.delete_needle(1, 7)
    store.mount_ec_volume(1, base)
    for needle_id in (3, 5, 7):
        with pytest.raises(ec_volume.NeedleDeleted):
            store.get_ec_volume(1).find_needle_from_ecx(needle_id)
        assert not store.delete_needle(1, needle_id)
    store.get_ec_volume(1).find_needle_from_ecx(4)
    store.close()


def test_a_remount_beside_a_serving_mount_leaves_the_journal_and_takes_over_its_deletes(tmp_path, monkeypatch):
    """The remount that folds nothing is built while the old mount serves: a
    delete the old mount takes while the new one is being built is on disk (the
    journal is left alone, even where it has grown over the threshold
    meanwhile) and known to the new mount from the swap on; and two mounts of
    one vid at once, both due to fold, fold one after the other."""
    store = _ec_volumes(tmp_path, (1,))
    base = str(tmp_path / "1")
    old = store.mount_ec_volume(1, base)
    real_init = store_mod.EcVolume.__init__

    def init(self, path, **kw):
        real_init(self, path, **kw)
        if old.shard_ids:  # built, not yet swapped in: the old mount still serves
            assert store.get_ec_volume(1) is old and store.delete_needle(1, 5)
            _fat_journal(base, 5)

    monkeypatch.setattr(store_mod.EcVolume, "__init__", init)
    new = store.mount_ec_volume(1, base)
    assert not old.shard_ids and 5 in stripe.read_ecj(base)
    with pytest.raises(ec_volume.NeedleDeleted):
        new.find_needle_from_ecx(5)
    ts = _Threads(30)
    for _ in range(2):
        ts.start(store.mount_ec_volume, 1, base)
    ts.join()
    assert not os.path.exists(base + ".ecj") and not os.path.exists(base + ".ecx.cpt")
    with pytest.raises(ec_volume.NeedleDeleted):
        store.get_ec_volume(1).find_needle_from_ecx(5)
    store.close()


# -- the copies of several volumes against one source server ---------------------------------


def test_cut_overs_side_by_side_keep_the_copies_against_their_source_at_the_pool_and_each_volumes_order(
        tmp_path, monkeypatch):
    """A sweep of four volumes from ONE source server over four racks: every
    volume's spread pulls from three targets, twelve `VolumeEcShardsCopy` that
    the four cut-overs would send at once. Slowed, at most `_POOL` are ever in
    flight against the source (what one volume's spread alone may put there),
    copies of several volumes do overlap, and each volume keeps its order:
    a target's mount after its copy, the source's `VolumeEcShardsDelete` after
    every copy and mount on the targets, `VolumeDelete` last."""
    monkeypatch.chdir(tmp_path)  # the sweep's default checkpoint lands here
    c = cl.Cluster(tmp_path, ["host"] * 4, vids=(1, 2, 3, 4))
    try:
        mu = threading.Lock()
        log, flying, peak = [], set(), [0]
        real_call = c.env.vs_call
        source = grpc_addr(next(n for n in c.env.topology_nodes() if n["url"] == c.servers[0].url))

        def vs_call(addr, method, req, **kw):
            vid = int(req.get("volume_id", 0))
            copy = method == "VolumeEcShardsCopy"
            with mu:
                log.append((vid, method, addr, "sent"))
                if copy:
                    flying.add((vid, addr))
                    peak[0] = max(peak[0], len(flying))
            try:
                if copy:
                    assert req["source_data_node"] == source
                    time.sleep(0.3)
                return real_call(addr, method, req, **kw)
            finally:
                with mu:
                    flying.discard((vid, addr))
                    log.append((vid, method, addr, "answered"))

        monkeypatch.setattr(c.env, "vs_call", vs_call)
        out = c.shell(f"lock; ec.encode -force -largeBlockSize {cl.LARGE} -smallBlockSize {cl.SMALL}; unlock")
        assert "NOT encoded" not in out, out
        assert sum(1 for e in log if e[1] == "VolumeEcShardsCopy" and e[3] == "sent") == 12
        assert 3 < peak[0] <= command_ec._POOL, peak  # more than one volume's, never more than the pool
        for vid in c.vids:
            mine = [e for e in log if e[0] == vid]
            at = {e: i for i, e in enumerate(mine)}
            targets = {e[2] for e in mine if e[1] == "VolumeEcShardsCopy"}
            assert len(targets) == 3
            (moved,) = [i for e, i in at.items() if e[1] == "VolumeEcShardsDelete" and e[3] == "sent"]
            (dropped,) = [i for e, i in at.items() if e[1] == "VolumeDelete" and e[3] == "sent"]
            for addr in targets:
                assert at[(vid, "VolumeEcShardsCopy", addr, "answered")] \
                    < at[(vid, "VolumeEcShardsMount", addr, "sent")] \
                    < at[(vid, "VolumeEcShardsMount", addr, "answered")] < moved
            assert dropped == max(i for e, i in at.items() if e[3] == "sent")
            assert sorted(s for held in c.held(vid).values() for s in held) == list(range(14))
            assert len(c.held(vid)) == 4
            for fid, payload in c.needles[vid]:
                assert c.client.read(fid) == payload
    finally:
        c.close()
