"""weedtrace tests: the context-local span recorder and tail-biased
trace ring (seaweedfs_tpu/obs/trace.py), per-stage attribution math,
the /debug/traces surface, the `ec.trace`/`ec.status` shell commands —
and the acceptance e2e: one trace id round-tripping a full distributed
degraded read (client -> master -> volume server -> remote holders and
back) including the hedge, coalesce, and rebuild slab/trace branches."""

import io
import json
import logging
import os
import threading
import time
import urllib.request

import pytest

from seaweedfs_tpu.cluster.client import MasterClient
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.ec import stripe as stripe_mod
from seaweedfs_tpu.obs import trace
from seaweedfs_tpu.shell import CommandEnv, run_command
from seaweedfs_tpu.utils import glog

LARGE, SMALL = 4096, 512


# -- helpers ------------------------------------------------------------------


def _mk(dur, kind="http.read", klass="healthy", error=None, tid=None):
    """A completed trace with a pinned duration (the ring orders and
    evicts on `dur`, never on wall time — so tests can fabricate it)."""
    st = trace._TraceState(tid or trace.new_trace_id(), kind, klass)
    root = trace.Span(kind, None, st)
    root.dur = dur
    return trace._Completed(root, st, error)


@pytest.fixture
def on(monkeypatch):
    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    trace.RING.clear()
    yield
    trace.RING.clear()


# -- recording primitives -----------------------------------------------------


def test_disabled_tracing_is_total_noop(monkeypatch):
    monkeypatch.setenv("WEEDTPU_TRACE", "off")
    assert not trace.enabled()
    ctx = trace.start("http.read")
    assert ctx is trace._NULL  # shared singleton, no per-call allocation
    with ctx as root:
        assert root is None
        with trace.span("ec.recover", shard=1) as sp:
            assert sp is None  # no ambient trace -> span is a no-op
        assert trace.current_trace_id() is None
        trace.annotate(x=1)  # must not raise outside a trace
        trace.set_class("degraded")


def test_span_tree_records_nesting_attrs_and_errors(on):
    ring = trace.TraceRing(capacity=8, slowest_n=1, sample=1.0, seed=1)
    with trace.start("http.read", klass="degraded", ring=ring) as root:
        tid = root.trace.trace_id
        with trace.span("ec.recover", shard=3):
            with trace.span("ec.gather", shard=3) as g:
                g.annotate(have=9)
            with pytest.raises(ValueError):
                with trace.span("ec.decode", backend="numpy"):
                    raise ValueError("boom")
    [t] = ring.snapshot()
    assert t["trace_id"] == tid and t["class"] == "degraded"
    assert t["error"] is None  # the root exited clean: only the SPAN errored
    (recover,) = t["root"]["spans"]
    assert recover["name"] == "ec.recover" and recover["attrs"] == {"shard": 3}
    gather, decode = recover["spans"]
    assert gather["attrs"] == {"shard": 3, "have": 9}
    assert decode["error"] == "ValueError"
    assert t["duration_s"] >= recover["dur_ms"] / 1e3 >= 0


def test_root_error_always_retained(on):
    ring = trace.TraceRing(capacity=8, slowest_n=1, sample=0.0, seed=1)
    with pytest.raises(IOError):
        with trace.start("http.read", ring=ring):
            raise IOError("disk gone")
    snap = ring.snapshot()
    errs = [t for t in snap if t["error"]]
    assert len(errs) == 1 and "disk gone" in errs[0]["error"]


def test_continue_trace_only_roots_with_propagated_id(on):
    ring = trace.TraceRing(capacity=8, slowest_n=1, sample=1.0, seed=1)
    assert trace.continue_trace("rpc.server", None, ring=ring) is trace._NULL
    assert trace.continue_trace("rpc.server", "<script>", ring=ring) is trace._NULL
    with trace.continue_trace("rpc.server", "AbC123", ring=ring) as root:
        assert root.trace.trace_id == "abc123"  # sanitized lowercase
    assert ring.snapshot()[0]["trace_id"] == "abc123"


def test_valid_id_rejects_wire_junk():
    assert trace.valid_id("deadbeef01") == "deadbeef01"
    assert trace.valid_id("DEAD-BEEF") == "dead-beef"
    for bad in (None, 7, "", "-leading", "zz not hex start" * 8, "x" * 80,
                "inj\nected", "a b"):
        assert trace.valid_id(bad) is None, bad


def test_ensure_nests_under_ambient_else_roots(on):
    ring = trace.TraceRing(capacity=8, slowest_n=1, sample=1.0, seed=1)
    # no ambient trace: ensure() roots a fresh maintenance trace
    with trace.start("rebuild.run", klass="maint", ring=ring):
        pass
    assert ring.snapshot()[0]["kind"] == "rebuild.run"
    ring.clear()
    # ambient trace active: ensure() nests a span, no second root
    with trace.start("shell.command", klass="shell", ring=ring) as root:
        tid = root.trace.trace_id
        with trace.ensure("rebuild.run"):
            pass
    [t] = ring.snapshot()
    assert t["trace_id"] == tid
    assert [s["name"] for s in t["root"]["spans"]] == ["rebuild.run"]


def test_attach_bridges_worker_threads(on):
    ring = trace.TraceRing(capacity=8, slowest_n=1, sample=1.0, seed=1)
    with trace.start("http.read", ring=ring) as root:
        parent = trace.current()

        def worker():
            # a bare thread has no ambient span; attach adopts the parent
            assert trace.current() is None
            with trace.attach(parent), trace.span("ec.fetch", shard=2):
                assert trace.current_trace_id() == root.trace.trace_id

        t = threading.Thread(target=worker)
        t.start()
        t.join(10)
    [tr] = ring.snapshot()
    assert [s["name"] for s in tr["root"]["spans"]] == ["ec.fetch"]


# -- the ring: tail-biased retention ------------------------------------------


def test_ring_keeps_errors_and_slowest_drops_the_rest_at_sample_zero():
    ring = trace.TraceRing(capacity=16, slowest_n=2, sample=0.0, seed=7)
    # descending durations: the first two fill the slowest row, every
    # later (faster) trace must be dropped outright at sample=0
    for i in range(50):
        ring.offer(_mk(dur=0.001 * (50 - i), tid=f"aa{i:04x}"))
    ring.offer(_mk(dur=0.0005, error="IOError: x", tid="ee01"))
    snap = ring.snapshot()
    # 2 slowest + 1 error survived; the 48 fast healthy traces did not
    assert len(snap) == 3
    assert snap[0]["duration_s"] >= snap[1]["duration_s"]
    assert {t["trace_id"] for t in snap} == {"aa0000", "aa0001", "ee01"}
    st = ring.stats()
    assert st["offered"] == 51 and st["kept"] == 3
    assert st["sampled"] == 0 and st["errors"] == 1


def test_ring_slowest_is_per_kind_class_key():
    ring = trace.TraceRing(capacity=4, slowest_n=1, sample=0.0, seed=7)
    ring.offer(_mk(0.9, klass="healthy", tid="aa01"))
    ring.offer(_mk(0.1, klass="degraded", tid="aa02"))
    ring.offer(_mk(0.2, kind="http.write", klass="put", tid="aa03"))
    # each (kind, class) keeps its own slowest: the 0.1s degraded trace
    # survives even though a 0.9s healthy one exists
    assert {t["trace_id"] for t in ring.snapshot()} == {"aa01", "aa02", "aa03"}


def test_ring_sampled_fifo_is_bounded():
    ring = trace.TraceRing(capacity=10, slowest_n=1, sample=1.0, seed=7)
    for i in range(200):
        ring.offer(_mk(dur=0.001, tid=f"bb{i:04x}"))
    st = ring.stats()
    assert st["sampled"] == 10  # FIFO capped
    snap = ring.snapshot(limit=1000)
    assert len(snap) <= 10 + 1  # FIFO + at most one distinct slowest


def test_sampling_is_deterministic_under_seed():
    def kept_ids(seed):
        ring = trace.TraceRing(capacity=64, slowest_n=1, sample=0.5, seed=seed)
        for i in range(64):
            ring.offer(_mk(dur=0.001, tid=f"cc{i:04x}"))
        return [t["trace_id"] for t in ring.snapshot(limit=100)]

    assert kept_ids(42) == kept_ids(42)
    assert kept_ids(42) != kept_ids(43)  # 2^-64 flake odds, effectively zero


def test_snapshot_filters_and_debug_payload(monkeypatch):
    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    ring = trace.TraceRing(capacity=32, slowest_n=1, sample=1.0, seed=1)
    ring.offer(_mk(0.500, klass="degraded", tid="dd01"))
    ring.offer(_mk(0.010, klass="healthy", tid="dd02"))
    ring.offer(_mk(0.020, kind="http.write", klass="put", tid="dd03"))
    assert {t["trace_id"] for t in ring.snapshot(klass="degraded")} == {"dd01"}
    assert {t["trace_id"] for t in ring.snapshot(kind="http.write")} == {"dd03"}
    assert {t["trace_id"] for t in ring.snapshot(min_duration=0.1)} == {"dd01"}
    assert len(ring.snapshot(limit=2)) == 2
    # slowest-first ordering
    assert [t["trace_id"] for t in ring.snapshot()][0] == "dd01"
    payload = trace.debug_payload(
        "/debug/traces?class=degraded&min_ms=100&limit=5", ring=ring
    )
    assert payload["enabled"] is True
    assert [t["trace_id"] for t in payload["traces"]] == ["dd01"]
    # junk query values fall back to defaults instead of raising
    junk = trace.debug_payload("/debug/traces?min_ms=zap&limit=zap", ring=ring)
    assert len(junk["traces"]) == 3


# -- render + attribution -----------------------------------------------------


def _fake_trace():
    return {
        "trace_id": "4f1d0000", "kind": "http.read", "class": "degraded",
        "start": 0.0, "duration_s": 1.0, "error": None,
        "root": {
            "name": "http.read", "t_ms": 0.0, "dur_ms": 1000.0,
            "spans": [
                {
                    "name": "ec.recover", "t_ms": 50.0, "dur_ms": 900.0,
                    "attrs": {"shard": 3},
                    "spans": [
                        # parallel fan-out: child durations sum to 1.2s
                        # inside a 0.9s parent -> must be scaled, never
                        # attributed more wall time than passed
                        {"name": "ec.fetch", "t_ms": 51.0, "dur_ms": 600.0},
                        {"name": "ec.fetch", "t_ms": 51.0, "dur_ms": 600.0},
                    ],
                },
            ],
        },
    }


def test_render_trace_shows_tree_attrs_and_times():
    out = trace.render_trace(_fake_trace())
    lines = out.splitlines()
    assert lines[0] == "trace=4f1d0000 http.read class=degraded 1000.0ms"
    assert "ec.recover" in lines[1] and "shard=3" in lines[1]
    assert lines[2].startswith("|  +-") and "ec.fetch" in lines[2]
    err = dict(_fake_trace(), error="IOError: x")
    assert "ERROR=IOError: x" in trace.render_trace(err).splitlines()[0]


def test_attribute_stages_sums_exactly_to_e2e():
    stages = trace.attribute_stages(_fake_trace())
    assert abs(sum(stages.values()) - 1.0) < 1e-9
    # parallel fetches scaled to the recover span's 0.9s wall budget
    assert abs(stages["ec.fetch"] - 0.9) < 1e-9
    assert abs(stages["ec.recover"] - 0.0) < 1e-9  # no self-time left
    assert abs(stages["other"] - 0.1) < 1e-9  # root self-time
    # a trivial single-span trace: all self-time on the stage
    t = {
        "duration_s": 0.5,
        "root": {"name": "r", "dur_ms": 500.0, "spans": [
            {"name": "ec.decode", "t_ms": 0.0, "dur_ms": 200.0},
        ]},
    }
    s = trace.attribute_stages(dict(_fake_trace(), **t))
    assert abs(s["ec.decode"] - 0.2) < 1e-9 and abs(s["other"] - 0.3) < 1e-9


def test_attribution_aggregation_consistency(on):
    """assemble_trace_attribution: per-class stage totals must equal the
    summed end-to-end latencies (stage_coverage == 1.0) — the artifact's
    committed consistency gate."""
    from seaweedfs_tpu.ec import slo

    traces = [_fake_trace() for _ in range(10)]
    for i, t in enumerate(traces):
        t["trace_id"] = f"ab{i:02x}"
        t["duration_s"] = 0.1 * (i + 1)
    attrib = slo.assemble_trace_attribution(traces)
    cls = attrib["classes"]["degraded"]
    assert cls["count"] == 10
    assert abs(cls["stage_coverage"] - 1.0) < 1e-6
    assert abs(cls["e2e_total_s"] - sum(0.1 * (i + 1) for i in range(10))) < 1e-6
    assert len(attrib["slowest"]) == 5
    assert attrib["slowest"][0]["duration_s"] == pytest.approx(1.0)
    shares = sum(s["share"] for s in cls["stages"].values())
    assert abs(shares - 1.0) < 1e-3


# -- glog context -------------------------------------------------------------


def test_glog_lines_carry_the_active_trace_id(on):
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    logger = logging.getLogger("seaweedfs_tpu")
    h = _Capture(level=logging.INFO)
    logger.addHandler(h)
    try:
        ring = trace.TraceRing(capacity=8, slowest_n=1, sample=1.0, seed=1)
        glog.info("outside any trace")
        with trace.start("http.read", trace_id="feed0001", ring=ring):
            glog.info("inside span %s", glog.kv(vid=7))
    finally:
        logger.removeHandler(h)
    assert records[-2] == "outside any trace"
    assert records[-1] == "inside span vid=7 trace=feed0001"


def test_disabled_span_path_is_cheap(monkeypatch):
    """Overhead microbench (loose): with tracing off, 50k span call
    sites must cost well under a second total — the 'safe to leave the
    call sites in every hot loop' floor. The real 5% e2e gate lives in
    the weedload smoke (test_slo_harness)."""
    monkeypatch.setenv("WEEDTPU_TRACE", "off")
    t0 = time.monotonic()
    for _ in range(50_000):
        with trace.span("ec.decode"):
            pass
    assert time.monotonic() - t0 < 1.0


# -- live cluster e2e ---------------------------------------------------------


@pytest.fixture
def cluster(tmp_path, monkeypatch):
    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    monkeypatch.setenv("WEEDTPU_TRACE_SAMPLE", "1.0")
    # deterministic hedging: the bench RPC delay makes every remote
    # shard fetch run ~20 ms (a modeled RTT), and a 5 ms hedge delay
    # guarantees the backup launches while the primary is still pending
    # wherever a second holder exists
    monkeypatch.setenv("WEEDTPU_BENCH_RPC_DELAY_MS", "20")
    monkeypatch.setenv("WEEDTPU_HEDGE_DELAY_MS", "5")
    trace.RING.clear()
    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    servers = []
    for i in range(3):
        d = tmp_path / f"srv{i}"
        d.mkdir()
        vs = VolumeServer([str(d)], master.address, heartbeat_interval=0.3)
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
    trace.RING.clear()


def _shell(env, line):
    out = io.StringIO()
    run_command(env, line, out)
    return out.getvalue()


def _ec_spread_volume(client, env, n=16, size=3000):
    """Upload n blobs, EC-encode their volume spread across the cluster
    (the shell path operators use), return (vid, [(fid, payload)])."""
    fids = []
    for _ in range(n):
        import os as _os

        payload = _os.urandom(size)
        r = client.submit(payload)
        fids.append((r.fid, payload))
    vid = int(fids[0][0].split(",", 1)[0])
    _shell(env, "lock")
    _shell(
        env,
        f"ec.encode -volumeId {vid} -largeBlockSize {LARGE} "
        f"-smallBlockSize {SMALL}",
    )
    return vid, fids


def _holders_of(env, vid):
    """{shard_id: [node dict]} from the live topology."""
    out = {}
    for n in env.topology_nodes():
        for e in n.get("ec_shards", []):
            if int(e["volume_id"]) != vid:
                continue
            from seaweedfs_tpu.ec.shard_bits import ShardBits

            for s in ShardBits(e["shard_bits"]).shard_ids():
                out.setdefault(s, []).append(n)
    return out


def _grpc_of(node, servers):
    return next(s for s in servers if s.url == node["url"]).grpc_address


def _traced_get(url, fid, payload):
    tid = trace.new_trace_id()
    req = urllib.request.Request(
        f"http://{url}/{fid}", headers={trace.HTTP_HEADER: tid}
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        body = r.read()
        echo = r.headers.get(trace.HTTP_HEADER)
    assert body == payload, f"bytes differ for {fid}"
    assert echo == tid, "traced reply must echo the request's trace id"
    return tid


def test_trace_id_round_trips_distributed_degraded_read(cluster):
    """The acceptance e2e: ids minted at the client survive the full
    degraded read — serving VS (http.read root), its master lookup
    (rpc.server LookupEcVolume), remote holder fetches (rpc.server
    VolumeEcShardRead), the hedge branch, and the coalesce branch — and
    come back on the HTTP reply. In-process servers share one trace
    ring, so cross-process assertions reduce to: every leg's root landed
    in the ring under the SAME propagated id."""
    master, servers, client, env = cluster
    vid, fids = _ec_spread_volume(client, env)
    holders = _holders_of(env, vid)

    # drop two data shards cluster-wide -> needles there reconstruct
    lost = [2, 3]
    for s in lost:
        for node in holders[s]:
            env.vs_call(
                _grpc_of(node, servers), "VolumeEcShardsDelete",
                {"volume_id": vid, "shard_ids": [s]},
            )
    # give one surviving shard a SECOND holder so hedges have an
    # alternate to race (shell ec.encode places each shard once). The
    # duplicated shard must be REMOTE to the serving front, or its
    # fan-out never fetches it at all
    import shutil

    from seaweedfs_tpu.ec import stripe as stripe_mod

    front = servers[0]
    donor_shard, donor = next(
        (s, holders[s][0]) for s in sorted(holders)
        if s not in lost and holders[s][0]["url"] != front.url
    )
    donor = next(s for s in servers if s.url == donor["url"])
    recip = next(s for s in servers if s.url not in (front.url, donor.url))
    src = stripe_mod.shard_file_name(donor._base_path_for(vid), donor_shard)
    dst_base = recip._base_path_for(vid)
    shutil.copy(src, stripe_mod.shard_file_name(dst_base, donor_shard))
    for ext in (".ecx", ".eci"):
        shutil.copy(donor._base_path_for(vid) + ext, dst_base + ext)
    env.vs_call(recip.grpc_address, "VolumeEcShardsMount", {"volume_id": vid})

    # read everything through one serving VS with a fresh id per request
    tid_of = {fid: _traced_get(front.url, fid, payload) for fid, payload in fids}
    ids = set(tid_of.values())

    snap = trace.RING.snapshot(limit=100000)
    degraded = [
        t for t in snap
        if t["kind"] == "http.read" and t["class"] == "degraded"
        and t["trace_id"] in ids
    ]
    assert degraded, "no degraded read landed in the ring"
    names = {s["name"] for t in degraded for s in trace.iter_spans(t)}
    assert {"ec.recover", "ec.gather", "ec.fetch", "ec.decode"} <= names, names

    # the remote-holder leg: VolumeEcShardRead rpc.server roots under the
    # same ids the client minted
    fetch_legs = [
        t for t in snap
        if t["kind"] == "rpc.server" and t["trace_id"] in ids
        and t["root"].get("attrs", {}).get("method") == "VolumeEcShardRead"
    ]
    assert fetch_legs, "remote shard fetches did not continue the trace id"

    # the master leg: the serving VS's shard-location lookup carried the
    # id of whichever traced read was first to need it
    master_legs = [
        t for t in snap
        if t["kind"] == "rpc.server" and t["trace_id"] in ids
        and t["root"].get("attrs", {}).get("method") == "LookupEcVolume"
    ]
    assert master_legs, "master lookup did not continue the trace id"

    # the fids whose first read reconstructed (their id landed in the
    # ring classed degraded) — the needles the branch probes re-read
    degraded_ids = {t["trace_id"] for t in degraded}
    d_fids = [
        (fid, p) for fid, p in fids if tid_of[fid] in degraded_ids
    ]
    assert d_fids, "no fid classified degraded"

    # hedge branch: reads of a degraded needle re-issued until a backup
    # fetch span shows up under one of our ids (delay pinned to 1 ms, a
    # second holder exists -> fires almost every fan-out)
    hedge_seen = any("ec.hedge" in {s["name"] for s in trace.iter_spans(t)}
                     for t in degraded)
    tries = 0
    while not hedge_seen and tries < 40:
        tries += 1
        fid, p = d_fids[tries % len(d_fids)]
        tid = _traced_get(front.url, fid, p)
        for t in trace.RING.snapshot(limit=100000):
            if t["trace_id"] == tid and any(
                s["name"] == "ec.hedge" for s in trace.iter_spans(t)
            ):
                hedge_seen = True
                break
    assert hedge_seen, "hedge branch never recorded under a propagated id"

    # coalesce branch: concurrent readers of ONE degraded needle, each
    # with its own id — waiters must record ec.coalesce.wait under THEIR
    # id (ids never bleed across coalesced requests)
    deg_fid, deg_payload = d_fids[0]
    coalesce_tid = None
    for _ in range(10):
        tids, threads = [], []

        def rd():
            tids.append(_traced_get(front.url, deg_fid, deg_payload))

        for _ in range(12):
            threads.append(threading.Thread(target=rd))
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        for tr in trace.RING.snapshot(limit=100000):
            if tr["trace_id"] in tids and any(
                s["name"] == "ec.coalesce.wait"
                for s in trace.iter_spans(tr)
            ):
                coalesce_tid = tr["trace_id"]
                break
        if coalesce_tid:
            break
    assert coalesce_tid, "coalesce waiter never recorded under its own id"

    # -- the /debug/traces surface, live --------------------------------------
    def dbg(query):
        with urllib.request.urlopen(
            f"http://{front.url}/debug/traces?{query}", timeout=10
        ) as r:
            return json.loads(r.read().decode())

    p = dbg("class=degraded&limit=3")
    assert p["enabled"] and len(p["traces"]) <= 3
    assert all(t["class"] == "degraded" for t in p["traces"])
    durs = [t["duration_s"] for t in p["traces"]]
    assert durs == sorted(durs, reverse=True), "slowest-first ordering"
    assert dbg("min_ms=10000000")["traces"] == []
    assert {t["kind"] for t in dbg("kind=rpc.server&limit=5")["traces"]} <= {
        "rpc.server"
    }

    # -- operator surfaces: ec.trace + ec.status ------------------------------
    out = _shell(env, "ec.trace -klass degraded -limit 2")
    assert "trace=" in out and "ec.recover" in out
    one = _shell(env, f"ec.trace -traceId {coalesce_tid}")
    assert f"trace={coalesce_tid}" in one
    status = _shell(env, "ec.status")
    for n in env.topology_nodes():
        assert n["url"] in status
    assert "ec_volumes=" in status and "scrub=" in status
    assert "backend=" in status and "rebuild=" in status
    assert "cache=" in status and "inval=" in status


def test_trace_id_round_trips_shell_rebuild_trace_and_slab(cluster):
    """The rebuild branches: `ec.rebuild -remote` under the shell's
    trace root must land the rebuild RPC (and the rebuild.run pipeline
    under it) in the ring with the SHELL's id — in projection (trace)
    mode AND forced-slab mode."""
    master, servers, client, env = cluster
    vid, fids = _ec_spread_volume(client, env)
    holders = _holders_of(env, vid)

    for mode, lost_shard in (("on", 12), ("off", 13)):
        for node in holders[lost_shard]:
            env.vs_call(
                _grpc_of(node, servers), "VolumeEcShardsDelete",
                {"volume_id": vid, "shard_ids": [lost_shard]},
            )
        trace.RING.clear()
        out = _shell(env, f"ec.rebuild -remote -trace {mode}")
        assert "rebuilt" in out
        snap = trace.RING.snapshot(limit=100000)
        shells = [
            t for t in snap
            if t["kind"] == "shell.command"
            and t["root"].get("attrs", {}).get("command") == "ec.rebuild"
        ]
        assert len(shells) == 1, "shell must root exactly one trace"
        tid = shells[0]["trace_id"]
        legs = [
            t for t in snap
            if t["kind"] == "rpc.server" and t["trace_id"] == tid
            and t["root"].get("attrs", {}).get("method")
            == "VolumeEcShardsRebuild"
        ]
        assert legs, f"-trace {mode}: rebuild RPC did not continue the id"
        names = {s["name"] for t in legs for s in trace.iter_spans(t)}
        assert "rebuild.run" in names, (mode, names)
        assert "rebuild.drain" in names, (mode, names)
        # holder-side slab/projection streams continued the same id too
        holder_methods = {
            t["root"].get("attrs", {}).get("method")
            for t in snap
            if t["kind"] == "rpc.server" and t["trace_id"] == tid
        }
        assert holder_methods & {
            "VolumeEcShardSlabRead", "VolumeEcShardSlabProject"
        }, holder_methods

    for fid, payload in fids:
        assert client.read(fid) == payload


def test_ec_trace_renders_the_bulk_stages_of_one_shell_encode_and_rebuild(cluster):
    """One `ec.encode` through the shell: `ec.trace -traceId <its id>` shows
    the generate RPC's run span (volume ids, bytes, batches) and the per-batch
    stages under the shell's id; the local rebuild RPC's run span likewise."""
    master, servers, client, env = cluster
    trace.RING.clear()
    vid, _ = _ec_spread_volume(client, env)

    def leg(command, method, run):
        snap = trace.RING.snapshot(limit=100000)
        (shell,) = [t for t in snap if t["kind"] == "shell.command"
                    and t["root"].get("attrs", {}).get("command") == command]
        (rpc_leg,) = [t for t in snap if t["kind"] == "rpc.server" and t["trace_id"] == shell["trace_id"]
                      and t["root"].get("attrs", {}).get("method") == method]
        (run_span,) = [s for s in trace.iter_spans(rpc_leg) if s["name"] == run]
        return shell["trace_id"], run_span, {s["name"] for s in trace.iter_spans(rpc_leg)}

    tid, run, names = leg("ec.encode", "VolumeEcShardsGenerateBatch", "encode.run")
    assert run["attrs"]["volumes"] == str(vid) and run["attrs"]["batch"] == 1
    assert run["attrs"]["batches"] >= 1 and run["attrs"]["bytes"] > 0
    assert {f"encode.{s}" for s in ("stage", "read", "write", "crc", "dispatch", "drain", "sync")} <= names
    rendered = _shell(env, f"ec.trace -traceId {tid}")
    for want in (tid, f"encode.run volumes={vid}", "encode.read bytes=", "encode.sync bytes=", "encode.crc bytes="):
        assert want in rendered, (want, rendered)

    # the local rebuild RPC, on a second volume whose 14 shards stay on its server
    fid = client.submit(os.urandom(3000)).fid
    vid2 = int(fid.split(",", 1)[0])
    assert vid2 != vid
    holder = next(s for s in servers if s.store.get_volume(vid2) is not None)
    env.vs_call(holder.grpc_address, "VolumeEcShardsGenerate",
                {"volume_id": vid2, "large_block_size": LARGE, "small_block_size": SMALL})
    os.unlink(stripe_mod.shard_file_name(holder.store.get_volume(vid2).base_path, 0))
    trace.RING.clear()
    with trace.start("shell.command", klass="shell") as root:
        root.annotate(command="ec.rebuild")
        got = env.vs_call(holder.grpc_address, "VolumeEcShardsRebuild", {"volume_id": vid2})
    assert got["rebuilt_shard_ids"] == [0]
    _, run, names = leg("ec.rebuild", "VolumeEcShardsRebuild", "rebuild.run")
    assert run["attrs"]["volume"] == vid2 and run["attrs"]["batches"] >= 1 and run["attrs"]["bytes"] > 0
    assert {f"rebuild.{s}" for s in ("read", "write", "crc", "dispatch", "sync", "verify")} <= names


def test_shell_command_root_says_how_much_its_process_had_loaded(cluster):
    """`run_command` annotates its root with the command's name and
    `modules=len(sys.modules)` at the command's first line: for a `shell -c`
    child's first command that is what the child's start loaded, and
    `render_trace` (`ec.trace`, `/debug/traces`) prints it with the root. At
    the command's end it adds `rpcs`, the calls the command made through the
    env (`volume.list`: the master's VolumeList)."""
    import sys

    _master, _servers, _client, env = cluster
    trace.RING.clear()
    assert "volume" in _shell(env, "volume.list").lower()
    (root,) = [t for t in trace.RING.snapshot(limit=100000) if t["kind"] == "shell.command"]
    attrs = root["root"]["attrs"]
    assert set(attrs) == {"command", "modules", "rpcs"} and attrs["command"] == "volume.list"
    assert 50 <= attrs["modules"] <= len(sys.modules) and attrs["rpcs"] == 1
    assert f"command=volume.list modules={attrs['modules']} rpcs=1" in trace.render_trace(root)


# -- the profiler mirror (PR 25): the program's spans on another clock ---------


@pytest.fixture
def mirror(on):
    """A recording mirror: (event, name, attrs, thread id) per call."""
    calls = []

    class _Open:
        def __init__(self, name, attrs):
            self.name = name
            calls.append(("enter", name, dict(attrs or {}), threading.get_ident()))

        def __exit__(self, *exc):
            calls.append(("exit", self.name, None, threading.get_ident()))

    trace.set_mirror(_Open)
    try:
        yield calls
    finally:
        trace.set_mirror(None)


def test_mirror_gets_enter_exit_in_pairs_on_the_recording_thread(mirror):
    ring = trace.TraceRing(capacity=8, slowest_n=2, sample=1.0, seed=1)
    both_alive = threading.Barrier(2, timeout=30)  # or the second thread may reuse the first's id

    def rpc(method):
        both_alive.wait()
        with trace.continue_trace("rpc.server", "abc123", ring=ring, method=method):
            with trace.ensure("encode.run"):
                with trace.ensure("encode.run"):  # the pipeline under the RPC: the same span, no second event
                    with trace.span("encode.read", bytes=7):
                        pass
                with pytest.raises(ValueError):
                    with trace.span("encode.sync"):
                        raise ValueError("boom")
        both_alive.wait()

    workers = [threading.Thread(target=rpc, args=(m,)) for m in ("A", "B")]
    for w in workers:
        w.start()
    for w in workers:
        w.join(timeout=30)
        assert not w.is_alive()
    by_thread = {}
    for event, name, attrs, tid in mirror:
        by_thread.setdefault(tid, []).append((event, name, attrs))
    assert len(by_thread) == 2 and threading.get_ident() not in by_thread
    for events in by_thread.values():
        method = events[0][2]["method"]
        # a mirrored ROOT also says its id and the wall clock at its start:
        # one of them ties a profiler session's clock to every other process's
        unix_ns = events[0][2].pop("unix_ns")
        assert abs(unix_ns - time.time_ns()) < 60e9 and events[0][2].pop("trace_id") == "abc123"
        assert events == [
            ("enter", "rpc.server", {"method": method}), ("enter", "encode.run", {}),
            ("enter", "encode.read", {"bytes": 7}), ("exit", "encode.read", None),
            ("enter", "encode.sync", {}), ("exit", "encode.sync", None),
            ("exit", "encode.run", None), ("exit", "rpc.server", None),
        ]
    # what the mirror saw is what the ring holds
    assert sorted(t["root"]["attrs"]["method"] for t in ring.snapshot()) == ["A", "B"]


def test_mirror_is_not_called_without_a_recorded_span(mirror, monkeypatch):
    with trace.span("encode.read", bytes=1):  # no ambient trace: nothing is recorded
        pass
    assert trace.continue_trace("rpc.server", None, method="X") is trace._NULL
    monkeypatch.setenv("WEEDTPU_TRACE", "off")
    with trace.start("http.read"):
        with trace.ensure("encode.run"):
            with trace.span("encode.read", bytes=1):
                pass
    assert mirror == []


def test_spans_land_in_a_profiler_session_with_their_attributes(on, tmp_path):
    """What the chip-owning server installs at boot, under a real profiler
    session (CPU here): names and attributes survive into ProfileData, on
    the recording thread's line, nested."""
    import glob
    import types

    import jax

    from seaweedfs_tpu.command import servers

    def vs(backend):
        return types.SimpleNamespace(store=types.SimpleNamespace(encoder=types.SimpleNamespace(backend=backend)))

    try:
        servers._mirror_spans_to_profiler(vs("xorsched"))
        assert trace._mirror is None  # a CPU backend: no mirror, jax not imported for it
        servers._mirror_spans_to_profiler(vs("jax"))
        assert trace._mirror is not None
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with trace.continue_trace("rpc.server", "abc123", method="VolumeEcShardsGenerate"):
                with trace.ensure("encode.run"):
                    with trace.span("encode.read", bytes=4096):
                        time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
    finally:
        trace.set_mirror(None)
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.split("#")[0] in ("rpc.server", "encode.run", "encode.read"):
                    found[e.name.split("#")[0]] = (line.name, e.start_ns, e.start_ns + e.duration_ns,
                                                   e.name + repr(sorted((str(k), v) for k, v in e.stats)))
    rpc, run, read = found["rpc.server"], found["encode.run"], found["encode.read"]
    assert rpc[0] == run[0] == read[0]  # one thread, one line
    assert rpc[1] <= run[1] <= read[1] and read[2] <= run[2] <= rpc[2]
    assert read[2] - read[1] >= 2_000_000  # nanoseconds
    assert "VolumeEcShardsGenerate" in rpc[3] and "4096" in read[3]


def test_obs_trace_imports_no_jax():
    import subprocess
    import sys

    code = ("import sys; from seaweedfs_tpu.obs import trace; trace.set_mirror(None); "
            "assert 'jax' not in sys.modules and 'jaxlib' not in sys.modules, sorted(m for m in sys.modules if 'jax' in m)")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# -- one command, one trace, kept (PR 42): the pieces -------------------------


def test_a_root_can_begin_before_it_was_opened_and_take_finished_spans(on):
    """`start(t0=...)` back-dates a root on both clocks (a script's begins at
    its process's birth); `record` adds a span that is over already."""
    ring = trace.TraceRing(capacity=8, slowest_n=2, sample=1.0, seed=1)
    born = time.monotonic() - 0.5
    with trace.start("shell.script", klass="shell", ring=ring, t0=born, script="lock; unlock") as root:
        trace.record("shell.start", born, born + 0.4, interp_ms=100.0, modules=7)
        with trace.span("rpc.client", method="LeaseAdminToken"):
            pass
    (t,) = ring.snapshot()
    assert t["root"]["attrs"] == {"script": "lock; unlock"} and root.t0 == born
    assert 0.5 <= t["duration_s"] < 0.6 and abs(t["unix_ns"] / 1e9 - (time.time() - t["duration_s"])) < 0.05
    start, call = t["root"]["spans"]
    assert (start["name"], start["t_ms"], start["dur_ms"]) == ("shell.start", 0.0, pytest.approx(400.0))
    assert start["attrs"] == {"interp_ms": 100.0, "modules": 7}
    assert call["t_ms"] >= 500.0
    trace.record("shell.start", born, born + 0.1)  # no ambient trace: nothing, and no error


def test_mark_reaches_the_mirror_alone(mirror, monkeypatch):
    assert trace.mark("shell.trace", names="a;b", depth="0;1") is True
    assert [(e, n, a) for e, n, a, _ in mirror] == [
        ("enter", "shell.trace", {"names": "a;b", "depth": "0;1"}), ("exit", "shell.trace", None)]
    assert trace.RING.snapshot() == []
    del mirror[:]
    monkeypatch.setenv("WEEDTPU_TRACE", "off")
    assert trace.mark("shell.trace", names="a") is False and mirror == []
    trace.set_mirror(None)
    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    assert trace.mark("shell.trace", names="a") is False


def test_process_birth_is_read_from_the_kernel_and_falls_back():
    now = time.monotonic()
    born = trace.process_birth(now)
    assert born <= now and now - born < 24 * 3600  # this interpreter started before this line, today
    assert trace.process_birth(now) == pytest.approx(born, abs=0.02)  # the tick is 10 ms
    assert trace.process_birth(born - 5.0) == born - 5.0  # never later than the first line of __main__


def _tree(fan, depth, t=0.0):
    sp = {"name": "rpc.client", "t_ms": t, "dur_ms": 1.0}
    if depth:
        sp["spans"] = [_tree(fan, depth - 1, t + i) for i in range(fan)]
    return sp


def test_a_handed_over_tree_is_cut_to_2000_spans_the_deepest_first():
    root = _tree(5, 5)  # 1 + 5 + 25 + 125 + 625 + 3125
    count = lambda sp: sum(1 for _ in trace.iter_spans({"root": sp}))  # noqa: E731
    assert count(root) == 3906 and trace.cap_spans(_tree(5, 4)) == 0
    assert trace.cap_spans(root) == 1906 and count(root) == trace.REPORT_MAX_SPANS
    by_depth = {}
    for _, depth in trace._depths(root):
        by_depth[depth] = by_depth.get(depth, 0) + 1
    assert by_depth == {0: 1, 1: 5, 2: 25, 3: 125, 4: 625, 5: 1219}  # every level above the deepest is whole
    assert trace.cap_spans(root, 31) == 1969 and max(d for _, d in trace._depths(root)) == 2


SCRIPT_TRACE = {
    "trace_id": "ab12", "kind": "shell.script", "class": "shell", "start": 1000.0, "unix_ns": 1000 * 10**9,
    "birth_unix_ns": 1000 * 10**9, "duration_s": 1.0, "error": None,
    "root": {"name": "shell.script", "t_ms": 0.0, "dur_ms": 1000.0, "attrs": {"script": "lock; ec.rebuild"}, "spans": [
        {"name": "shell.start", "t_ms": 0.0, "dur_ms": 300.0,
         "attrs": {"interp_ms": 170.0, "import_ms": 90.0, "connect_ms": 40.0, "modules": 241}},
        {"name": "shell.command", "t_ms": 300.0, "dur_ms": 10.0, "attrs": {"command": "lock", "rpcs": 1}, "spans": [
            {"name": "rpc.client", "t_ms": 301.0, "dur_ms": 8.0, "attrs": {"method": "LeaseAdminToken", "target": "m:1"}}]},
        {"name": "shell.command", "t_ms": 310.0, "dur_ms": 690.0, "attrs": {"command": "ec.rebuild", "rpcs": 4}, "spans": [
            {"name": "shell.plan", "t_ms": 310.0, "dur_ms": 50.0, "attrs": {"volumes": 2, "rpcs": 1}, "spans": [
                {"name": "rpc.client", "t_ms": 311.0, "dur_ms": 40.0, "attrs": {"method": "VolumeList", "target": "m:1"}}]},
            {"name": "rpc.client", "t_ms": 400.0, "dur_ms": 300.0,
             "attrs": {"method": "VolumeEcShardsRebuild", "target": "v:2", "volume": 1}},
            {"name": "rpc.client", "t_ms": 400.0, "dur_ms": 500.0,
             "attrs": {"method": "VolumeEcShardsCopy", "target": "v:2", "volume": 2, "thread": "pool_0"}},
            {"name": "rpc.client", "t_ms": 650.0, "dur_ms": 100.0,
             "attrs": {"method": "VolumeEcShardsDelete", "target": "v:2", "volume": 1}}]},
    ]},
}


def test_a_script_trace_flat_for_the_profiler():
    flat = trace.flatten(SCRIPT_TRACE)
    assert flat["trace_id"] == "ab12" and flat["birth_unix_ns"] == 1000 * 10**9
    assert flat["names"].split(";") == ["shell.script", "shell.start", "shell.command", "rpc.client",
                                        "shell.command", "shell.plan", "rpc.client"] + ["rpc.client"] * 3
    assert flat["what"].split(";") == ["", "", "lock", "LeaseAdminToken", "ec.rebuild", "", "VolumeList",
                                       "VolumeEcShardsRebuild", "VolumeEcShardsCopy", "VolumeEcShardsDelete"]
    assert flat["depth"] == "0;1;1;2;1;2;3;2;2;2" and flat["thread"] == "0;0;0;0;0;0;0;0;1;0"
    assert flat["t_ns"].split(";")[6] == "311000000" and flat["dur_ns"].split(";")[1] == "300000000"
    assert flat["start_ms"] == "170.0;90.0;40.0"
    assert all(isinstance(v, (str, int)) for v in flat.values())  # what an annotation's attributes can carry


def test_a_script_trace_as_phases_of_its_commands():
    import copy

    odd = copy.deepcopy(SCRIPT_TRACE)
    odd["root"]["spans"][1]["attrs"]["command"] = 'x"} 1\nweedtpu_injected{a="'
    assert {c for c, _, _ in trace.script_phases(odd)} == {"other", "ec.rebuild"}  # a label is never wire input as it came
    rows = {(c, p): s for c, p, s in trace.script_phases(SCRIPT_TRACE)}
    assert rows == {
        ("lock", "start"): pytest.approx(0.300), ("lock", "plan"): 0.0,
        ("lock", "rpc"): pytest.approx(0.008), ("lock", "other"): pytest.approx(0.002),
        ("ec.rebuild", "plan"): pytest.approx(0.050),
        # the union of what the command's own thread waited for outside the plan:
        # 400-700 and 650-750; the worker's copy ran beside them
        ("ec.rebuild", "rpc"): pytest.approx(0.350), ("ec.rebuild", "other"): pytest.approx(0.290),
    }


def test_a_received_trace_is_kept_as_a_root_of_the_rings_own(on):
    import copy

    ring = trace.TraceRing(capacity=8, slowest_n=2, sample=1.0, seed=1)
    sent = copy.deepcopy(SCRIPT_TRACE)
    assert trace.offer_received(sent, ring) is True
    assert ring.snapshot(kind="shell.script", klass="shell", min_duration=0.5) == [SCRIPT_TRACE]
    assert ring.snapshot(kind="shell.script", min_duration=1.5) == []
    slow = dict(copy.deepcopy(SCRIPT_TRACE), duration_s=3.0, trace_id="AB13")
    trace.offer_received(slow, ring)
    assert [t["trace_id"] for t in ring.snapshot()] == ["ab13", "ab12"]  # slowest first, the id sanitized


@pytest.mark.parametrize("bad", [
    {}, {"trace_id": "ab12"}, dict(SCRIPT_TRACE, trace_id="<script>"), dict(SCRIPT_TRACE, kind="nosuch.kind"),
    dict(SCRIPT_TRACE, root="x"), dict(SCRIPT_TRACE, root={"name": "rpc.server", "t_ms": 0, "dur_ms": 1}),
    dict(SCRIPT_TRACE, duration_s="long"), dict(SCRIPT_TRACE, duration_s=-1.0), [SCRIPT_TRACE],
], ids=["empty", "id-alone", "bad-id", "unknown-kind", "root-no-tree", "root-of-another-kind",
        "duration-no-number", "duration-negative", "a-list"])
def test_a_malformed_received_trace_is_refused(on, bad):
    ring = trace.TraceRing(capacity=8, slowest_n=2, sample=1.0, seed=1)
    with pytest.raises(ValueError):
        trace.offer_received(bad, ring)
    assert ring.snapshot() == []


def test_report_trace_refuses_what_is_no_trace_and_keeps_what_is(on):
    import grpc

    from seaweedfs_tpu import rpc, stats
    from seaweedfs_tpu.pb import MASTER_SERVICE, wire

    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    try:
        with rpc.RpcClient(master.address) as c:
            for body in ({}, {"trace": "not json"}, {"trace": json.dumps({"trace_id": "ab12"})},
                         {"trace": json.dumps(dict(SCRIPT_TRACE, pad="x" * trace.REPORT_MAX_BYTES))}):
                with pytest.raises(grpc.RpcError) as e:
                    c.call(MASTER_SERVICE, "ReportTrace", body)
                assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT
            assert trace.RING.snapshot(kind="shell.script") == []
            before = stats.ShellCommandSeconds.labels("ec.rebuild", "plan").total
            assert c.call(MASTER_SERVICE, "ReportTrace", {"trace": json.dumps(SCRIPT_TRACE)}) == {"kept": True}
        assert trace.RING.snapshot(kind="shell.script") == [SCRIPT_TRACE]
        assert stats.ShellCommandSeconds.labels("ec.rebuild", "plan").total == before + 1
        assert stats.ShellCommandSeconds.labels("ec.rebuild", "plan").sum >= 0.05
    finally:
        master.stop()
    # and on the protobuf wire: a tree of any depth travels as one string
    ser, de = wire.codec().request_serdes(MASTER_SERVICE, "ReportTrace")
    assert json.loads(de(ser({"trace": json.dumps(SCRIPT_TRACE)}))["trace"]) == SCRIPT_TRACE


def test_the_first_few_of_a_kind_are_not_lost_when_slower_ones_come():
    """A root that leaves the slowest row goes through the sample gate like
    any other: before PR 42 it was dropped, so the first (fast) RPCs of a
    command vanished from the ring the moment its long ones ended."""
    ring = trace.TraceRing(capacity=8, slowest_n=2, sample=1.0, seed=1)
    for dur in (0.001, 0.002, 0.5, 0.7, 0.003):
        ring.offer(_mk(dur, kind="rpc.server", klass="rpc"))
    assert sorted(t["duration_s"] for t in ring.snapshot()) == [0.001, 0.002, 0.003, 0.5, 0.7]
    assert ring.stats()["kept"] == 5 and ring.stats()["sampled"] == 3
    none = trace.TraceRing(capacity=8, slowest_n=2, sample=0.0, seed=1)
    for dur in (0.001, 0.002, 0.5, 0.7, 0.003):
        none.offer(_mk(dur, kind="rpc.server", klass="rpc"))
    assert sorted(t["duration_s"] for t in none.snapshot()) == [0.5, 0.7]  # at sample 0: the slowest alone


def test_render_trace_puts_each_servers_half_under_the_call_that_waited_for_it():
    def served(method, at_ms, dur_s, inner):
        return {"trace_id": "ab12", "kind": "rpc.server", "class": "rpc", "start": 1000.0 + at_ms / 1e3,
                "unix_ns": 1000 * 10**9 + int(at_ms * 1e6), "duration_s": dur_s, "error": None,
                "root": {"name": "rpc.server", "t_ms": 0.0, "dur_ms": dur_s * 1e3, "attrs": {"method": method},
                         "spans": [{"name": inner, "t_ms": 0.1, "dur_ms": dur_s * 1e3 - 0.2}]}}

    rebuild, copy_ = served("VolumeEcShardsRebuild", 401.0, 0.298, "rebuild.run"), served(
        "VolumeEcShardsCopy", 400.5, 0.499, "ec.copy")
    stray = served("VolumeEcShardFileCopy", 420.0, 0.1, "ec.copy.serve")  # a peer's: the shell never called it
    other = dict(served("VolumeEcShardsRebuild", 402.0, 0.2, "rebuild.run"), trace_id="ffff")
    pairs = [("v:9", rebuild), ("v:2", rebuild), ("v:2", copy_), ("v:3", stray), ("v:2", other)]
    text = trace.render_trace(SCRIPT_TRACE, pairs)
    lines = text.splitlines()
    at = lines.index("|  +-    400.0ms     300.0ms rpc.client method=VolumeEcShardsRebuild target=v:2 volume=1")
    assert lines[at + 1] == "|  |  @ v:2 rpc.server 298.0ms"  # the target's own ring first
    assert lines[at + 2] == "|  |  |  +-      0.1ms     297.8ms rebuild.run"
    assert text.count("@ ") == 2 and "ec.copy.serve" not in text
    assert pairs == [("v:3", stray), ("v:2", other)]  # what found no caller is left for the caller to print
    assert trace.render_trace(SCRIPT_TRACE) == trace.render_trace(SCRIPT_TRACE, [])
