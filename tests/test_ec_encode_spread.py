"""`ec.encode` without `-volumeId` on a cluster (`sweepspread10p4`: one source
server's full volumes, spread over four servers in four racks): the sweep
plans the spread of ALL its volumes before it freezes the first, each volume's
allocation made with the shards already given to the volumes before it counted
into every server's load, through the one planner (`placement.plan_spread`),
so the sweep leaves the servers level where the parent gave every volume the
same allocation (16/16/12/12 for four volumes). Per volume nothing changed: no
rack holds more than 4 of its 14 shards, and its bytes are its own encode's.
Small sizes, on the CPU, against the single-volume encode and the plain
reference (`benchmark/reference/gf8_ref.py`)."""

import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

import test_ec_encode_sweep as sw
import test_ec_rebuild_cluster as cl
from seaweedfs_tpu.ec import placement, stripe
from seaweedfs_tpu.obs import trace
from seaweedfs_tpu.ops.rs_codec import Encoder
from seaweedfs_tpu.shell import ShellError, command_ec

VIDS = (1, 2, 3, 4)
FLAGS = f"-force -largeBlockSize {cl.LARGE} -smallBlockSize {cl.SMALL}"


def _read(path):
    with open(path, "rb") as f:
        return f.read()


class Spread(cl.Cluster):
    """`cl.Cluster` (four host-codec servers, a rack each, every volume on
    server 0) with a twin of each volume encoded alone to compare with."""

    def __init__(self, tmp_path, vids=VIDS):
        super().__init__(tmp_path, ["host"] * 4, vids=vids)
        self.twin = str(tmp_path / "twin")
        os.makedirs(self.twin)
        for vid in vids:
            for ext in (".dat", ".idx"):
                shutil.copy(os.path.join(self.dirs[0], str(vid)) + ext, os.path.join(self.twin, str(vid)) + ext)
            stripe.generate_ec_files(os.path.join(self.twin, str(vid)), large_block_size=cl.LARGE,
                                     small_block_size=cl.SMALL, encoder=Encoder(10, 4, backend="numpy"))
        self.urls = [vs.url for vs in self.servers]

    def shell(self, script):
        """-> (what the script wrote, the ShellError that ended it or None)."""
        try:
            return super().shell(script), None
        except ShellError as e:
            return "", e

    def totals(self, vids):
        """Shards of `vids` on each server as the master lists them, most first."""
        return sorted((sum(len(self.held(v).get(u, ())) for v in vids) for u in self.urls), reverse=True)

    def spread_as_alone(self, vid):
        """Every shard lies on exactly one server, no server (a rack each)
        holds more than 4, and each file, wherever it lies, is the volume's
        own encode's and the reference's; every needle reads back."""
        held = self.held(vid)
        assert sorted(s for ss in held.values() for s in ss) == list(range(14)), held
        assert max(len(ss) for ss in held.values()) <= 4, held
        for url, directory in zip(self.urls, self.dirs):
            on_disk = {s for s in range(14) if os.path.exists(stripe.shard_file_name(os.path.join(directory, str(vid)), s))}
            assert on_disk == held.get(url, set()), (vid, url, on_disk)
            for s in on_disk:
                got = _read(stripe.shard_file_name(os.path.join(directory, str(vid)), s))
                assert got == _read(stripe.shard_file_name(os.path.join(self.twin, str(vid)), s)), (vid, s)
                assert got == self.reference[vid][s], (vid, s)
        assert not os.path.exists(os.path.join(self.dirs[0], f"{vid}.dat"))
        for fid, payload in self.needles[vid]:
            assert self.client.read(fid) == payload

    def command_span(self):
        (root,) = [s for t in trace.RING.snapshot(kind="shell.script", limit=1000) for s in trace.iter_spans(t)
                   if s["name"] == "shell.command" and s["attrs"].get("command") == "ec.encode"]
        return root["attrs"]


@pytest.fixture
def make_spread(tmp_path, monkeypatch):
    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    monkeypatch.setenv("WEEDTPU_TRACE_SAMPLE", "1.0")
    monkeypatch.chdir(tmp_path)  # the sweep's default checkpoint lands here
    made = []

    def make(vids=VIDS):
        made.append(Spread(tmp_path, vids))
        trace.RING.clear()
        return made[-1]

    yield make
    for c in made:
        c.close()


def _parents_allocation(c):
    """What the parent's `_spread_cutover` computed for every volume alike: the
    planner over the topology as it is now, nothing of the sweep counted."""
    return command_ec._fmt_alloc(placement.plan_spread(
        c.env.topology_nodes(), 14, 4, load_of=command_ec._node_ec_load))


def test_a_sweep_of_four_volumes_leaves_four_servers_level(make_spread):
    """(a) 14/14/14/14, twelve copies, no server over 4 of any volume, every
    shard where the master lists it and byte-identical to the volume's own
    encode and to the reference; the command's span says both."""
    c = make_spread()
    first = _parents_allocation(c)

    out, err = c.shell(f"lock; ec.encode {FLAGS}; unlock")

    assert err is None and "NOT encoded" not in out, out
    assert c.totals(VIDS) == [14, 14, 14, 14]
    for vid in VIDS:
        c.spread_as_alone(vid)
    # the first volume's allocation is the lone volume's; the others' differ from it
    lines = {int(ln.split()[2].rstrip(":")): ln.split("spread ", 1)[1] for ln in out.splitlines()
             if ln.startswith("ec.encode volume")}
    assert lines[1] == first and any(lines[v] != first for v in (2, 3, 4)), lines
    attrs = c.command_span()
    assert attrs["copies"] == 12 and attrs["spread"] == "14/14/14/14"
    # `ec.trace` of the command shows both, and every copy on the pool thread that sent it
    (tid,) = [t["trace_id"] for t in trace.RING.snapshot(kind="shell.script", limit=10)]
    shown, err = c.shell(f"ec.trace -traceId {tid}")
    assert err is None and "copies=12 spread=14/14/14/14" in shown
    copies = [ln for ln in shown.splitlines() if "rpc.client method=VolumeEcShardsCopy" in ln]
    assert len(copies) == 12 and all("thread=" in ln for ln in copies), shown


def test_a_sweep_fills_the_emptier_servers_first(make_spread):
    """(b) servers that already hold uneven EC load (a lone encode's 4/4/3/3):
    the sweep's first volume gives its 4s to the servers that held 3, and the
    three volumes of the sweep leave all four level."""
    c = make_spread()
    out, err = c.shell(f"lock; ec.encode -volumeId 4 {FLAGS}; unlock")
    assert err is None, out
    before = {u: len(ss) for u, ss in c.held(4).items()}
    assert sorted(before.values()) == [3, 3, 4, 4]
    trace.RING.clear()

    out, err = c.shell(f"lock; ec.encode {FLAGS}; unlock")

    assert err is None and "NOT encoded" not in out, out
    assert {u: len(ss) for u, ss in c.held(1).items()} == {u: 7 - n for u, n in before.items()}
    assert c.totals(VIDS) == [14, 14, 14, 14] and c.totals((1, 2, 3)) == [11, 11, 10, 10]
    for vid in VIDS:
        c.spread_as_alone(vid)
    assert c.command_span()["spread"] == "11/11/10/10"


def test_a_volume_whose_cut_over_fails_leaves_the_others_as_planned(make_spread, monkeypatch):
    """(c) volume 2's cut-over fails: it is writable again and named, its
    planned shards go to nobody, and volumes 1, 3 and 4 lie exactly where the
    plan of the whole sweep put them (the four plans together are level)."""
    c = make_spread()
    real = command_ec._spread_cutover
    planned = {}

    def cutover(env, nodes, locations, vid, *a):
        planned[vid] = {u: set(ss) for u, ss in a[-1].items()}
        if vid == 2:
            raise ShellError("the spread of volume 2 failed")
        return real(env, nodes, locations, vid, *a)

    monkeypatch.setattr(command_ec, "_spread_cutover", cutover)

    out, err = c.shell(f"lock; ec.encode {FLAGS}; unlock")

    assert err is not None and "volumes [2] were not encoded" in str(err)
    assert sorted(sum(len(planned[v][u]) for v in VIDS) for u in c.urls) == [14, 14, 14, 14]
    for vid in (1, 3, 4):
        assert c.held(vid) == planned[vid]
        c.spread_as_alone(vid)
    v2 = c.servers[0].store.get_volume(2)
    assert v2 is not None and not v2.read_only and not c.held(2)
    want = sorted((sum(len(planned[v][u]) for v in (1, 3, 4)) for u in c.urls), reverse=True)
    assert c.totals(VIDS) == want and c.command_span()["spread"] == "/".join(map(str, want))
    assert c.command_span()["copies"] == 9


def test_a_lone_volume_gets_todays_allocation_letter_for_letter(make_spread):
    """(d) `-volumeId`: the allocation the parent's cut-over computed, and a
    second lone encode plans from the topology as the first left it."""
    c = make_spread(vids=(1, 2))
    for vid in (1, 2):
        want = _parents_allocation(c)
        trace.RING.clear()
        out, err = c.shell(f"lock; ec.encode -volumeId {vid} {FLAGS}; unlock")
        assert err is None, out
        assert f"ec.encode volume {vid}: spread {want}\n" in out
        c.spread_as_alone(vid)
        assert c.command_span()["copies"] == 3 and c.command_span()["spread"] == "4/4/3/3"
    assert c.totals((1, 2)) == [7, 7, 7, 7]


def test_a_sweep_on_one_server_keeps_everything_there(tmp_path, monkeypatch):
    """(d) a one-server cluster: every volume's 14 shards stay, no copy."""
    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    monkeypatch.setenv("WEEDTPU_TRACE_SAMPLE", "1.0")
    monkeypatch.chdir(tmp_path)
    c = sw.Sweep(tmp_path, "numpy", vids=[1, 2, 3])
    try:
        trace.RING.clear()
        out, err = c.shell(f"lock; ec.encode {sw.FLAGS}; unlock")
        assert err is None, out
        everything = ",".join(map(str, range(14)))
        for vid in (1, 2, 3):
            assert f"ec.encode volume {vid}: spread {c.server.url}={everything}\n" in out
            c.encoded_as_alone(vid)
        (root,) = [s for t in trace.RING.snapshot(kind="shell.script", limit=1000) for s in trace.iter_spans(t)
                   if s["name"] == "shell.command" and s["attrs"].get("command") == "ec.encode"]
        assert root["attrs"]["copies"] == 0 and root["attrs"]["spread"] == "42"
    finally:
        c.close()


def _nodes(racks, loads=None):
    """Node dicts as `topology_nodes` gives them: `racks[i]` is server i's
    rack, `loads[i]` the EC shards it already holds (of some other volume)."""
    loads = loads or [0] * len(racks)
    return [{"url": f"10.0.0.{i}:8080", "rack": r, "data_center": "dc",
             "ec_shards": [{"volume_id": 900 + i, "shard_bits": (1 << n) - 1}] if n else []}
            for i, (r, n) in enumerate(zip(racks, loads))]


@pytest.mark.parametrize("racks,loads,volumes", [
    (["a", "b", "c", "d"], None, 4),  # the cell's cluster
    (["a", "b", "c", "d"], None, 64),  # upstream's pass: 256/256/192/192 before, level now
    (["a", "b", "c", "d"], [9, 0, 4, 2], 7),
    (["a", "b", "c", "d", "e"], None, 5),
    (["a", "b", "c", "d", "e", "f", "g"], [0, 3, 0, 0, 5, 0, 1], 3),
    (["a", "a", "b", "b", "c", "c", "d", "d"], None, 6),  # two servers a rack: the cap is the rack's
])
def test_the_plans_of_a_sweep_are_level_and_each_keeps_the_cap(racks, loads, volumes):
    """The planner, volume after volume with what was given counted: every
    volume's allocation holds all 14 ids once and no rack over 4; after each
    volume the totals (what was there and what was given) are as level as one
    more allocation can make them: the gap between the fullest and the
    emptiest server never grows past one, and ends within one."""
    nodes = _nodes(racks, loads)
    rack_of = {n["url"]: n["rack"] for n in nodes}
    total = {n["url"]: command_ec._node_ec_load(n) for n in nodes}
    given = {}
    gap = max(total.values()) - min(total.values())
    for _ in range(volumes):
        alloc = command_ec.allocate_shards(nodes, given=given)
        assert sorted(s for ss in alloc.values() for s in ss) == list(range(14))
        per_rack = {}
        for url, ss in alloc.items():
            per_rack[rack_of[url]] = per_rack.get(rack_of[url], 0) + len(ss)
            given[url] = given.get(url, 0) + len(ss)
            total[url] += len(ss)
        assert max(per_rack.values()) <= 4, per_rack
        now = max(total.values()) - min(total.values())
        assert now <= max(gap, 1), (total, gap)
        gap = now
    assert gap <= 1, total
    # nothing given: the lone volume's allocation, the planner as the parent called it
    assert command_ec.allocate_shards(nodes) == placement.plan_spread(
        nodes, 14, 4, load_of=command_ec._node_ec_load)


# -- the benchmark's cell, rehearsed where the tier-1 command collects it -----------------


def _benchmarks_cases():
    """`benchmark/tests/test_checks_fail_sweepspread.py` under a name of its
    own (as `tests/test_benchmark_rate.py` takes the rate's cases): it imports
    `harness` from the benchmark's directory, which is on the path for as long
    as that takes."""
    bench = os.path.join(cl.ROOT, "benchmark")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            "benchmark_tests_test_checks_fail_sweepspread",
            os.path.join(bench, "tests", "test_checks_fail_sweepspread.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(bench)
    return module


_cases = _benchmarks_cases()
test_the_cell_is_in_the_manifest_with_its_metrics = _cases.test_the_cell_is_in_the_manifest_with_its_metrics


@pytest.mark.parametrize("fault,shows_in", _cases.CASES)
def test_the_benchmark_cell_rehearses_to_its_end_and_leaves_no_process(tmp_path, fault, shows_in):
    """`run.py --workload sweepspread10p4.encode-4x128m-4srv-x12 --rehearse`:
    every phase on the CPU with 8 MiB volumes and four servers, never a
    result. Sound, all checks pass, the sweep is level and the facts say one
    batch RPC and twelve copies a command; with a control's fault or the
    device's apply broken, the checks do not pass; no server outlives the run."""
    work = tmp_path / "tmp"
    work.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(work))
    result, out = _cases.rehearse(fault, 2**31 + 4500 + len(fault), env)
    _cases.see(result, out, fault, shows_in)
    assert '"correct": true' not in out
    assert result["timed"]["shards_per_server"] == [14, 14, 14, 14]
    left = subprocess.run(["pgrep", "-f", str(work)], capture_output=True, text=True).stdout.split()
    assert not left, f"processes left behind: {left}"
    shutil.rmtree(work, ignore_errors=True)
