"""SLO harness tests: the HDR-style latency recorder and artifact schema
(seaweedfs_tpu/ec/slo.py), the weedload open-loop smoke (tiny in-process
cluster, schema + zero-loss gate, <=20 s), rebuild admission control,
the typed-degraded-error -> HTTP 503 mapping, and the bounded-retry
master lookup."""

import importlib.util
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from seaweedfs_tpu import rpc, stats
from seaweedfs_tpu.cluster.client import MasterClient
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.ec import slo, stripe
from seaweedfs_tpu.ec.constants import TOTAL_SHARDS_COUNT
from seaweedfs_tpu.ops.rs_codec import Encoder
from seaweedfs_tpu.pb import VOLUME_SERVICE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(ROOT, "scripts")
ENC = Encoder(10, 4, backend="numpy")
VID = 9


def _load_script(name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(SCRIPTS, name + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- recorder -----------------------------------------------------------------


def test_recorder_quantiles_track_numpy():
    """Bucketed quantiles must stay within the geometric bucket width
    (~5%) of exact numpy percentiles on a skewed distribution — the
    recorder's one job is not lying about the tail."""
    rng = np.random.default_rng(5)
    samples = np.exp(rng.normal(-4.0, 1.0, size=20_000))  # lognormal, ~18ms median
    rec = slo.LatencyRecorder()
    for s in samples:
        rec.observe("steady", "healthy", float(s))
    cell = rec.merged("healthy")
    for q in (0.50, 0.95, 0.99):
        exact = float(np.percentile(samples, q * 100))
        got = cell.quantile(q)
        assert exact * 0.9 <= got <= exact * 1.12, (
            f"p{int(q*100)}: recorder {got} vs exact {exact}"
        )
    assert cell.total == len(samples)


def test_recorder_phases_classes_and_errors():
    rec = slo.LatencyRecorder()
    rec.observe("steady", "healthy", 0.01)
    rec.observe("steady", "degraded", 0.05)
    rec.observe("chaos", "degraded", 0.2)
    rec.error("chaos", "degraded")
    phases = rec.phases()
    assert set(phases) == {"steady", "chaos"}
    assert phases["chaos"]["degraded"]["errors"] == 1
    assert phases["steady"]["healthy"]["count"] == 1
    merged = rec.merged("degraded")
    assert merged.total == 2 and merged.errors == 1


def test_recorder_round_trips_and_merges_across_processes():
    """to_dict/merge_dict is the multi-process generator contract: a
    worker ships its cells as JSON and the driver folds them in. The
    merge must be bucket-exact (same quantiles as observing locally)
    and refuse cells from a different bucket layout."""
    a, b = slo.LatencyRecorder(), slo.LatencyRecorder()
    local = slo.LatencyRecorder()
    rng = np.random.default_rng(11)
    for s in np.exp(rng.normal(-4.0, 1.0, size=2000)):
        a.observe("steady", "cached", float(s))
        local.observe("steady", "cached", float(s))
    for s in np.exp(rng.normal(-3.0, 1.0, size=500)):
        b.observe("chaos", "degraded", float(s))
        local.observe("chaos", "degraded", float(s))
    b.error("chaos", "degraded")
    local.error("chaos", "degraded")

    merged = slo.LatencyRecorder()
    # JSON round-trip exactly as the worker files do
    merged.merge_dict(json.loads(json.dumps(a.to_dict())))
    merged.merge_dict(json.loads(json.dumps(b.to_dict())))
    for klass in ("cached", "degraded"):
        want, got = local.merged(klass), merged.merged(klass)
        assert got.total == want.total and got.errors == want.errors
        for q in (0.5, 0.99):
            assert got.quantile(q) == want.quantile(q)
    # a cell serialized by a different code version (bucket layout
    # mismatch) must be rejected loudly, not merged wrong
    bad = a.to_dict()
    key = next(iter(bad))
    bad[key]["counts"] = bad[key]["counts"][:-1]
    with pytest.raises(ValueError, match="bucket count mismatch"):
        slo.LatencyRecorder().merge_dict(bad)


def test_slo_verdict_and_report_schema(tmp_path):
    rec = slo.LatencyRecorder()
    for _ in range(30):
        rec.observe("steady", "healthy", 0.01)
        rec.observe("steady", "degraded", 0.03)
    verdict = slo.slo_verdict(rec, factor=5.0)
    assert verdict["ok"] and verdict["enough_samples"]
    assert verdict["ratio"] is not None and verdict["ratio"] < 5.0
    # degraded blows the budget -> not ok
    for _ in range(5):
        rec.observe("steady", "degraded", 3.0)
    assert not slo.slo_verdict(rec, factor=5.0)["ok"]
    # empty healthy side must yield None ratio (strict JSON), not Infinity
    empty = slo.LatencyRecorder()
    empty.observe("steady", "degraded", 0.1)
    v = slo.slo_verdict(empty)
    assert v["ratio"] is None and not v["ok"]
    json.dumps(v, allow_nan=False)  # must not raise
    # a mostly-FAILING degraded class must not certify the SLO off the
    # few reads that succeeded: the error-rate bound fails it
    erry = slo.LatencyRecorder()
    for _ in range(30):
        erry.observe("steady", "healthy", 0.01)
        erry.observe("steady", "degraded", 0.02)
    for _ in range(60):
        erry.error("steady", "degraded")
    ve = slo.slo_verdict(erry, factor=5.0)
    assert ve["ratio"] is not None and ve["ratio"] < 5.0
    assert ve["degraded_error_rate"] > 0.5 and not ve["ok"]

    report = slo.assemble_report(rec, workload={"rps": 1})
    for key in slo.REPORT_SCHEMA_KEYS:
        assert key in report
    out = tmp_path / "SLO_t.json"
    slo.write_report(str(out), report)
    again = json.loads(out.read_text())
    assert again["slo"]["target"].startswith("degraded_p99 < ")
    with pytest.raises(ValueError, match="missing required key"):
        slo.write_report(str(out), {"when": "x"})


# -- weedload smoke (tier-1 CI gate) ------------------------------------------


def _weedload_smoke(tmp_path) -> tuple[dict, float]:
    """-> (the artifact `weedload --smoke` wrote, the seconds it took)."""
    weedload = _load_script("weedload")
    out = tmp_path / "SLO_smoke.json"
    t0 = time.monotonic()
    rc = weedload.main(["--smoke", "--out", str(out)])
    took = time.monotonic() - t0
    assert rc == 0, "weedload smoke lost bytes or crashed"
    return json.loads(out.read_text()), took


def test_weedload_smoke_schema_and_zero_loss(tmp_path):
    """The committed-artifact pipeline end to end on a tiny in-process
    cluster: weedload --smoke must write a schema-complete SLO artifact,
    observe all three traffic classes, and lose zero bytes. What it asserts
    of time is in `test_weedload_smoke_wall_clock_gates` (slow tier): on a
    host shared with five other test workers neither the run's wall nor a
    5% latency ratio says anything of the program."""
    report, _ = _weedload_smoke(tmp_path)
    for key in slo.REPORT_SCHEMA_KEYS:
        assert key in report, f"artifact missing {key}"
    assert report["lost"] == [] and report["ok"]
    assert report["workload"]["open_loop"] is True
    by_class = report["workload"]["objects_by_class"]
    assert by_class["healthy"] > 0 and by_class["degraded"] > 0
    # degraded traffic actually reconstructed server-side
    assert report["counters"]["weedtpu_degraded_read_seconds_count"] > 0
    merged_degraded = report["overall"]["degraded"]
    assert merged_degraded["count"] > 0 and merged_degraded["p99"] > 0
    # weedtrace rode along: per-stage tail attribution with stage sums
    # consistent with the observed end-to-end latencies (coverage is
    # exactly 1.0 by construction of attribute_stages), and the slowest
    # exemplar span trees retained
    attrib = report["trace_attribution"]
    for key in slo.TRACE_ATTRIB_SCHEMA_KEYS:
        assert key in attrib, f"trace attribution missing {key}"
    assert attrib["trace_count"] > 0
    for klass in ("healthy", "degraded"):
        cls = attrib["classes"][klass]
        assert cls["count"] > 0
        assert abs(cls["stage_coverage"] - 1.0) < 0.01, (klass, cls)
    assert len(attrib["slowest"]) >= 1
    assert all(t["root"].get("spans") is not None or t["kind"]
               for t in attrib["slowest"])
    # the tracing-overhead measurement ran and left its evidence; whether
    # it held its bounds is the slow tier's to say
    overhead = report["trace_overhead"]
    assert overhead["method"] == "interleaved-ABBA" and overhead["attempts"]
    for attempt in overhead["attempts"]:
        assert {"p99_ratio", "throughput_ratio", "mean_delta_us_per_read", "ok"} <= set(attempt)
    # hot-set serving: the decoded-interval cache must actually engage
    # under the zipf hot set (weedload itself exits 1 when hits == 0 —
    # these assertions pin the artifact evidence, not just the exit code)
    cache = report["cache"]
    assert cache["hits"] >= 1 and cache["hit_rate"] is not None
    assert cache["budget_mb"] > 0
    # the read-class header routed cache hits into their own class, so
    # `degraded` in this artifact means reads that actually decoded
    assert report["overall"]["cached"]["count"] > 0


@pytest.mark.slow
def test_weedload_smoke_wall_clock_gates(tmp_path):
    """The two statements about time, for a host with cores to spare: the
    smoke stays inside its budget, and the leave-tracing-ON design claim
    holds: trace-on healthy p99/throughput within 5% of trace-off on the
    same live cluster, or within the absolute per-read floor (loopback reads
    are so cheap that tracing's fixed few-dozen-µs cost can exceed 5%
    relatively while staying invisible against any real ms-scale read)."""
    report, took = _weedload_smoke(tmp_path)
    # 30 s: the original 20 s load budget plus the tracing-overhead
    # gate's interleaved A/B phases (up to 3 damping attempts)
    assert took < 30.0, f"smoke run must stay under the 30 s CI budget ({took:.1f}s)"
    overhead = report["trace_overhead"]
    assert overhead["ok"], f"tracing overhead gate failed: {overhead}"


def test_weedload_smoke_s3_front(tmp_path):
    """weedload --front s3: the same open-loop harness through the S3
    gateway (signed V4 requests -> s3api -> filer -> volume tier), with
    classes derived from the objects' chunk fids. The EC'd volume lives
    in the bucket's collection (`load_<vid>` on disk) — this smoke is
    what catches a harness that only handles the default collection."""
    weedload = _load_script("weedload")
    out = tmp_path / "SLO_smoke_s3.json"
    t0 = time.monotonic()
    rc = weedload.main(["--smoke", "--front", "s3", "--out", str(out)])
    took = time.monotonic() - t0
    assert rc == 0, "s3-front smoke lost bytes or crashed"
    assert took < 40.0, f"s3 smoke must stay inside the CI budget ({took:.1f}s)"
    report = json.loads(out.read_text())
    assert report["lost"] == [] and report["ok"]
    assert report["workload"]["front"] == "s3"
    by_class = report["workload"]["objects_by_class"]
    assert by_class["healthy"] > 0 and by_class["degraded"] > 0
    # degraded chunk reads reconstructed server-side through the gateway
    assert report["counters"]["weedtpu_degraded_read_seconds_count"] > 0
    assert report["overall"]["degraded"]["count"] > 0


# -- in-process cluster for server-side checks --------------------------------


def _build_ec_volume(dirpath: str, size: int = 400_000, seed: int = 3):
    base = os.path.join(dirpath, str(VID))
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    with open(base + ".idx", "wb"):
        pass
    stripe.write_ec_files(
        base, large_block_size=16384, small_block_size=4096, encoder=ENC
    )
    stripe.write_sorted_file_from_idx(base)
    golden = {}
    for s in range(TOTAL_SHARDS_COUNT):
        with open(stripe.shard_file_name(base, s), "rb") as f:
            golden[s] = f.read()
    os.unlink(base + ".dat")
    return base, golden


@pytest.fixture
def mini_cluster(tmp_path):
    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    d = tmp_path / "srv0"
    d.mkdir()
    vs = VolumeServer([str(d)], master.address, heartbeat_interval=0.3)
    vs.start()
    yield master, vs
    vs.stop()
    master.stop()


def test_rebuild_admission_gate_counts_waits(tmp_path, monkeypatch):
    """With WEEDTPU_REBUILD_MAX_INFLIGHT=1, two concurrent slab streams
    serialize: the second waits for the token (counted) and both still
    deliver byte-correct CRC-framed data."""
    monkeypatch.setenv("WEEDTPU_REBUILD_MAX_INFLIGHT", "1")
    monkeypatch.setenv("WEEDTPU_REBUILD_YIELD_MS", "50")
    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    d = tmp_path / "gated"
    d.mkdir()
    vs = VolumeServer([str(d)], master.address, heartbeat_interval=0.3)
    vs.start()
    try:
        base = vs._base_path_for(VID)
        _, golden = _build_ec_volume(str(d), size=3_000_000)
        with rpc.RpcClient(vs.grpc_address) as c:
            c.call(VOLUME_SERVICE, "VolumeEcShardsMount", {"volume_id": VID})
        waits0 = stats.RebuildAdmissionWaits.value
        results: dict[int, bytes] = {}

        def pull(i: int) -> None:
            with rpc.RpcClient(vs.grpc_address) as c:
                frames = c.stream(
                    VOLUME_SERVICE,
                    "VolumeEcShardSlabRead",
                    {
                        "volume_id": VID,
                        "shard_id": i,
                        "offset": 0,
                        "size": len(golden[i]),
                        # small chunks: each stream spans several frames, so
                        # the 50 ms inter-chunk yield keeps the token held
                        # long enough that the streams MUST overlap (a
                        # single-chunk stream can finish before the second
                        # thread is even scheduled — a coin-flip on 1 core)
                        "chunk_size": 64 * 1024,
                    },
                    timeout=60,
                )
                results[i] = b"".join(rpc.crc_unframe(f) for f in frames)

        threads = [threading.Thread(target=pull, args=(i,)) for i in (1, 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert results[1] == golden[1] and results[2] == golden[2]
        assert stats.RebuildAdmissionWaits.value - waits0 >= 1, (
            "second slab stream should have waited for the admission token"
        )
    finally:
        vs.stop()
        master.stop()


def test_degraded_read_maps_to_503_with_retry_after(mini_cluster):
    """A needle whose stripe lost too many shards must answer HTTP 503
    with a Retry-After hint and the typed error class — not a bare 500 —
    so load balancers/clients back off instead of hammering."""
    master, vs = mini_cluster
    client = MasterClient(master.address)
    try:
        fids = []
        for i in range(8):
            r = client.submit(os.urandom(12_000))
            fids.append(r.fid)
        vid = int(fids[0].split(",", 1)[0])
        with rpc.RpcClient(vs.grpc_address) as c:
            c.call(VOLUME_SERVICE, "VolumeMarkReadonly", {"volume_id": vid})
            c.call(
                VOLUME_SERVICE, "VolumeEcShardsGenerate",
                {
                    "volume_id": vid,
                    "large_block_size": 16384,
                    "small_block_size": 4096,
                },
                timeout=120,
            )
            c.call(VOLUME_SERVICE, "VolumeEcShardsMount", {"volume_id": vid})
            c.call(VOLUME_SERVICE, "VolumeDelete", {"volume_id": vid})
            # lose 5 of 14: any reconstructing read is unservable
            c.call(
                VOLUME_SERVICE, "VolumeEcShardsDelete",
                {"volume_id": vid, "shard_ids": [0, 1, 2, 3, 4]},
            )
        errs0 = stats.DegradedReadErrors.labels("EcNoViableHolders").value
        saw_503 = 0
        for fid in fids:
            try:
                with urllib.request.urlopen(
                    f"http://{vs.url}/{fid}", timeout=30
                ) as r:
                    r.read()
            except urllib.error.HTTPError as e:
                assert e.code == 503, f"expected 503, got {e.code}"
                assert e.headers.get("Retry-After") is not None
                body = json.loads(e.read().decode())
                assert body["class"] in (
                    "EcNoViableHolders", "EcDegradedReadTimeout"
                )
                assert "attempted" in body and "suspected" in body
                saw_503 += 1
        assert saw_503 > 0, "no needle hit the lost shards — fixture too small"
        assert stats.DegradedReadErrors.labels("EcNoViableHolders").value > errs0
    finally:
        client.close()


def test_lookup_retry_with_jitter_rides_out_transient_failures(
    mini_cluster, monkeypatch
):
    """The single-flight lookup leader retries transient master errors
    (WEEDTPU_LOOKUP_RETRIES) instead of failing every waiter on one
    hiccup; with retries disabled the old fail-fast behavior returns."""
    master, vs = mini_cluster
    master.topology.ec_locations[77] = {0: {"127.0.0.1:1"}}
    calls = {"n": 0}
    real_query = vs._master_query

    def flaky(method, req, timeout=5.0):
        if method == "LookupEcVolume":
            calls["n"] += 1
            if calls["n"] <= 2:
                raise RuntimeError("transient master hiccup")
        return real_query(method, req, timeout)

    monkeypatch.setattr(vs, "_master_query", flaky)
    monkeypatch.setenv("WEEDTPU_LOOKUP_RETRIES", "2")
    locs = vs._lookup_shard_locations(77)
    assert calls["n"] == 3, "leader should have retried twice then succeeded"
    # the answer reached the caller (holders not on THIS node are filtered
    # out of the map, so emptiness is fine — no exception is the point)
    assert isinstance(locs, dict)

    vs._invalidate_shard_locations(77)
    calls["n"] = 0

    def always_down(method, req, timeout=5.0):
        if method == "LookupEcVolume":
            calls["n"] += 1
            raise RuntimeError("master down")
        return real_query(method, req, timeout)

    monkeypatch.setattr(vs, "_master_query", always_down)
    monkeypatch.setenv("WEEDTPU_LOOKUP_RETRIES", "0")
    with pytest.raises(RuntimeError, match="master down"):
        vs._lookup_shard_locations(77)
    assert calls["n"] == 1, "retries=0 must fail fast (pre-knob behavior)"
