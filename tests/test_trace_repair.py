"""Trace-repair (repair-bandwidth-optimal rebuild) tests: the GF(2^8)
projection math byte-exact against the gf8 golden, XOR-combined holder
projections equal to the fused decode, the projection rebuild pipeline
byte-identical to `rebuild_ec_files_serial`, the end-to-end trace-mode
`ec.rebuild -remote` over real RPC servers (wire bytes strictly below the
full-slab baseline, counter accounting, capability-negotiation fallback,
mid-rebuild failure fallback, torn-stream CRC rejection), the
RemoteSlabSource multi-holder striping upgrade, and the tier-1
`ec_rebuild_trace` bench smoke."""

import base64
import os
import shutil
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu import rpc, stats
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.ec import stripe
from seaweedfs_tpu.ec.constants import DATA_SHARDS_COUNT, TOTAL_SHARDS_COUNT
from seaweedfs_tpu.ops import gf8
from seaweedfs_tpu.ops.rs_codec import Encoder
from seaweedfs_tpu.pb import VOLUME_SERVICE

ENC = Encoder(10, 4, backend="numpy")
LARGE, SMALL = 16384, 4096
VID = 17


# -- projection math ----------------------------------------------------------


@pytest.mark.parametrize(
    "rows,cols,width",
    [
        (1, 3, 1),        # minimal
        (2, 5, 127),      # odd width
        (4, 10, 8192),    # tile-edge-ish power of two
        (3, 13, 1000),    # non-power-of-two
        (1, 10, 4097),    # just past a tile edge
        (14, 14, 64),     # full-square
    ],
)
def test_gf_project_bits_byte_exact_vs_golden(rows, cols, width):
    """The GF(2)/GF(2^8) bit-plane lift of the projection must agree with
    the table-driven golden on every shape — tile-edge and odd sizes
    included — since it is the formulation device kernels run."""
    rng = np.random.default_rng(rows * 131 + cols)
    m = rng.integers(0, 256, (rows, cols), dtype=np.uint8)
    x = rng.integers(0, 256, (cols, width), dtype=np.uint8)
    want = gf8.gf_project(m, x)
    got = gf8.gf_project_bits(m, x)
    assert want.shape == (rows, width)
    assert np.array_equal(want, got)


def test_repair_projection_plan_matches_decode_matrix():
    survivors = [0, 1, 2, 4, 5, 6, 7, 8, 9, 10]
    wanted = [3, 12]
    plan = ENC.repair_projection_plan(survivors, wanted)
    m = ENC.reconstruction_matrix(survivors, wanted)
    assert sorted(plan) == sorted(survivors)
    for i, s in enumerate(survivors):
        assert np.array_equal(plan[s], m[:, i])


def test_project_validates_shapes():
    with pytest.raises(ValueError):
        ENC.project(np.zeros((2, 3), dtype=np.uint8), np.zeros((4, 8), dtype=np.uint8))
    with pytest.raises(ValueError):
        ENC.project(np.zeros(3, dtype=np.uint8), np.zeros((3, 8), dtype=np.uint8))
    with pytest.raises(ValueError):
        ENC.project_lazy(
            np.zeros((2, 3), dtype=np.uint8), np.zeros((4, 8), dtype=np.uint8)
        )


def test_xor_combined_group_projections_equal_fused_decode():
    """Splitting the survivor set across holder groups and XORing their
    projections must reproduce the fused decode exactly — the invariant
    that makes trace rebuilds byte-identical to slab rebuilds."""
    rng = np.random.default_rng(7)
    data = [rng.integers(0, 256, 2048, dtype=np.uint8) for _ in range(10)]
    shards = ENC.encode(data)
    missing = [0, 11, 13]
    survivors = [s for s in range(TOTAL_SHARDS_COUNT) if s not in missing][
        :DATA_SHARDS_COUNT
    ]
    plan = ENC.repair_projection_plan(survivors, missing)
    direct = ENC.reconstruct_batch(
        np.stack([shards[s] for s in survivors])[None], survivors, missing
    )[0]
    for split in ([4, 7], [1, 2, 3, 9], [10]):
        bounds = [0, *split, len(survivors)]
        acc = np.zeros((len(missing), 2048), dtype=np.uint8)
        for lo, hi in zip(bounds, bounds[1:]):
            group = survivors[lo:hi]
            if not group:
                continue
            coeffs = np.stack([plan[s] for s in group], axis=1)
            acc ^= ENC.project(coeffs, np.stack([shards[s] for s in group]))
        assert np.array_equal(acc, direct)
        for k, s in enumerate(missing):
            assert np.array_equal(acc[k], np.asarray(shards[s]))


# -- the projection rebuild pipeline (no servers) -----------------------------


def _build_shard_set(dirpath: str, size: int = 400_000, seed: int = 5):
    base = os.path.join(dirpath, str(VID))
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    with open(base + ".idx", "wb"):
        pass
    stripe.write_ec_files(
        base, large_block_size=LARGE, small_block_size=SMALL, encoder=ENC
    )
    stripe.write_sorted_file_from_idx(base)
    golden = {}
    for s in range(TOTAL_SHARDS_COUNT):
        with open(stripe.shard_file_name(base, s), "rb") as f:
            golden[s] = f.read()
    os.unlink(base + ".dat")
    return base, golden


def _fake_remote_group(base, holder, sids, plan, rows, shard_size, **kw):
    """A TraceSlabSource whose transport projects straight from the local
    files — the server-side math without a server."""
    coeffs = np.stack([plan[s] for s in sids], axis=1)
    files = {s: open(stripe.shard_file_name(base, s), "rb") for s in sids}

    def fetch(offset: int, size: int) -> bytes:
        actual = max(0, min(size, shard_size - offset))
        if actual == 0:
            return b""
        stack = np.empty((len(sids), actual), dtype=np.uint8)
        for i, s in enumerate(sids):
            stripe.read_padded_into(files[s], offset, stack[i])
        return ENC.project(coeffs, stack).tobytes()

    src = stripe.TraceSlabSource(holder, sids, rows, fetch, **kw)
    orig_close = src.close

    def close():
        orig_close()
        for f in files.values():
            f.close()

    src.close = close
    return src


def test_projection_rebuild_byte_identical_vs_serial_oracle(tmp_path):
    """Trace-combine pipeline output == rebuild_ec_files_serial on the same
    survivor set, across odd window geometry and a multi-shard loss."""
    work = tmp_path / "work"
    work.mkdir()
    base, golden = _build_shard_set(str(work))
    missing = [3, 12]
    for s in missing:
        os.unlink(stripe.shard_file_name(base, s))
    shard_size = len(golden[0])
    survivors = sorted(stripe.find_local_shards(base))[:DATA_SHARDS_COUNT]
    plan = ENC.repair_projection_plan(survivors, missing)

    # serial oracle on a copy (same survivor set: its present == ours)
    oracle = tmp_path / "oracle"
    oracle.mkdir()
    obase = os.path.join(str(oracle), str(VID))
    for s in survivors:
        shutil.copy(stripe.shard_file_name(base, s), stripe.shard_file_name(obase, s))
    for ext in (".ecx", ".eci"):
        if os.path.exists(base + ext):
            shutil.copy(base + ext, obase + ext)
    stripe.rebuild_ec_files_serial(obase, encoder=ENC)

    groups = [
        _fake_remote_group(
            base, "a", survivors[:4], plan, len(missing), shard_size,
            chunk_bytes=70_000,  # odd chunk: forces multi-chunk windows
        ),
        _fake_remote_group(base, "b", survivors[4:9], plan, len(missing), shard_size),
        stripe.LocalProjectionSource(
            [stripe.shard_file_name(base, s) for s in survivors[9:]],
            np.stack([plan[s] for s in survivors[9:]], axis=1),
            ENC,
        ),
    ]
    try:
        rebuilt = stripe.rebuild_ec_files_from_projections(
            base, groups, shard_size, missing, encoder=ENC,
            buffer_size=16384, max_batch_bytes=10 * 3 * 16384,
        )
    finally:
        for g in groups:
            g.close()
    assert rebuilt == missing
    for s in missing:
        with open(stripe.shard_file_name(base, s), "rb") as f:
            got = f.read()
        with open(stripe.shard_file_name(obase, s), "rb") as f:
            assert got == f.read(), f"shard {s} differs from serial oracle"
        assert got == golden[s]
    # wire accounting: remote groups moved rows x shard bytes each
    assert groups[0].bytes_fetched == len(missing) * shard_size
    assert groups[1].bytes_fetched == len(missing) * shard_size
    assert groups[2].bytes_fetched == 0  # local group never hits the wire


def test_projection_rebuild_failure_unlinks_partials(tmp_path):
    base, golden = _build_shard_set(str(tmp_path))
    missing = [2]
    os.unlink(stripe.shard_file_name(base, 2))
    shard_size = len(golden[0])
    survivors = sorted(stripe.find_local_shards(base))[:DATA_SHARDS_COUNT]
    plan = ENC.repair_projection_plan(survivors, missing)
    calls = {"n": 0}

    def dying_fetch(offset: int, size: int) -> bytes:
        calls["n"] += 1
        if calls["n"] > 2:
            raise IOError("holder died mid-rebuild")
        actual = max(0, min(size, shard_size - offset))
        stack = np.empty((len(survivors), actual), dtype=np.uint8)
        for i, s in enumerate(survivors):
            with open(stripe.shard_file_name(base, s), "rb") as f:
                stripe.read_padded_into(f, offset, stack[i])
        coeffs = np.stack([plan[s] for s in survivors], axis=1)
        return ENC.project(coeffs, stack).tobytes()

    src = stripe.TraceSlabSource("dying", survivors, 1, dying_fetch, chunk_bytes=65536)
    with pytest.raises(IOError):
        stripe.rebuild_ec_files_from_projections(
            base, [src], shard_size, missing, encoder=ENC,
            buffer_size=16384, max_batch_bytes=10 * 16384,
        )
    src.close()
    assert not os.path.exists(stripe.shard_file_name(base, 2)), (
        "failed trace rebuild must not leave a partial shard"
    )


def test_trace_source_rejects_non_row_multiple_stream():
    src = stripe.TraceSlabSource("x", [0, 1], 3, lambda off, n: b"\x00" * 7)
    out = np.zeros(3 * 64, dtype=np.uint8)
    with pytest.raises(IOError, match="not a multiple"):
        src.read_into(0, out)
    src.close()


# -- RemoteSlabSource multi-holder striping -----------------------------------


def test_striped_windows_spread_across_holders_and_fail_over():
    """With two live replica holders the striped fetches must hit BOTH
    (bandwidth aggregation), and killing one mid-window must drain the
    remaining stripes through the survivor with the failover recorded."""
    counts = {"a": 0, "b": 0}
    dead = set()
    blob = bytes(range(256)) * 1024  # 256 KiB

    def fetch(addr, offset, size):
        if addr in dead:
            raise IOError(f"{addr} down")
        counts[addr] += 1
        return blob[offset : offset + size]

    src = stripe.RemoteSlabSource(
        0, ["a", "b"], fetch, stripe_bytes=64 * 1024, fanout=4
    )
    out = np.zeros(256 * 1024, dtype=np.uint8)
    src.read_into(0, out)
    assert bytes(out) == blob
    assert counts["a"] > 0 and counts["b"] > 0, (
        f"striping pinned one holder: {counts}"
    )
    assert src.bytes_fetched == len(blob)
    # now kill one holder: the next window must complete via the other
    dead.add("a")
    before_b = counts["b"]
    src.read_into(0, out)
    assert bytes(out) == blob
    assert counts["b"] >= before_b + 4
    assert src.failovers == ["a"]
    assert src.bytes_fetched == 2 * len(blob)
    src.close()


def test_least_inflight_pick_prefers_idle_holder():
    src = stripe.RemoteSlabSource(0, ["a", "b"], lambda *a: b"", fanout=2)
    with src._lock:
        src._inflight["a"] = 3
    assert src._pick_holder(["a", "b"], 0) == "b"
    # rotation still breaks ties once loads equalize
    with src._lock:
        src._inflight["b"] = 4
        src._inflight["a"] = 4
    first = src._pick_holder(["a", "b"], 0)
    second = src._pick_holder(["a", "b"], src._stripe)
    assert {first, second} == {"a", "b"}
    src.close()


# -- end to end over real RPC servers -----------------------------------------


def _wait_for(cond, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timeout waiting for {msg}")


@pytest.fixture
def trace_cluster(tmp_path):
    """master + rebuild target + two peer holders, one data shard lost
    cluster-wide: peer A holds 0-6 minus the loss, peer B holds 7-13."""
    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    servers = []
    for i in range(3):
        d = tmp_path / f"srv{i}"
        d.mkdir()
        vs = VolumeServer([str(d)], master.address, heartbeat_interval=0.3)
        vs.start()
        servers.append(vs)
    target, peer_a, peer_b = servers
    stage = tmp_path / "stage"
    stage.mkdir()
    base_stage, golden = _build_shard_set(str(stage))
    os.unlink(stripe.shard_file_name(base_stage, 3))
    base_a = peer_a._base_path_for(VID)
    base_b = peer_b._base_path_for(VID)
    for s in (0, 1, 2, 4, 5, 6):
        os.replace(stripe.shard_file_name(base_stage, s), stripe.shard_file_name(base_a, s))
    for s in range(7, 14):
        os.replace(stripe.shard_file_name(base_stage, s), stripe.shard_file_name(base_b, s))
    for base_p in (base_a, base_b):
        for ext in (".ecx", ".eci"):
            shutil.copy(base_stage + ext, base_p + ext)
    for vs in (peer_a, peer_b):
        with rpc.RpcClient(vs.grpc_address) as c:
            c.call(VOLUME_SERVICE, "VolumeEcShardsMount", {"volume_id": VID})
    _wait_for(
        lambda: len(master.topology.lookup_ec_shards(VID)) == 13,
        msg="13 survivor shards registered",
    )
    yield master, servers, golden
    for vs in servers:
        vs.stop()
    master.stop()


def _rebuild(target, trace_mode, timeout=120):
    with rpc.RpcClient(target.grpc_address) as tc:
        return tc.call(
            VOLUME_SERVICE,
            "VolumeEcShardsRebuild",
            {"volume_id": VID, "remote": True, "trace_mode": trace_mode},
            timeout=timeout,
        )


def _scrub(target, shard=3):
    p = stripe.shard_file_name(target._base_path_for(VID), shard)
    if os.path.exists(p):
        os.unlink(p)


def test_trace_rebuild_end_to_end_wire_bytes_below_slab(trace_cluster):
    """The headline: trace mode rebuilds byte-identically while moving
    strictly fewer survivor bytes than the slab baseline — asserted from
    BOTH the per-rebuild response accounting and the
    weedtpu_ec_repair_network_bytes_total counter."""
    master, (target, peer_a, peer_b), golden = trace_cluster
    shard_size = len(golden[0])
    trace_counter = stats.EcRepairNetworkBytes.labels("trace")
    slab_counter = stats.EcRepairNetworkBytes.labels("slab")
    t0 = trace_counter.value
    resp = _rebuild(target, "on")
    assert resp["mode"] == "trace", resp
    assert resp["rebuilt_shard_ids"] == [3]
    assert resp["trace_fallback"] == ""
    assert len(resp["trace_groups"]) == 2, resp["trace_groups"]
    with open(stripe.shard_file_name(target._base_path_for(VID), 3), "rb") as f:
        assert f.read() == golden[3]
    # 2 holder groups x 1 missing shard x shard bytes on the wire
    assert resp["wire_bytes"] == 2 * shard_size
    assert trace_counter.value - t0 == resp["wire_bytes"]

    _scrub(target)
    s0 = slab_counter.value
    resp_slab = _rebuild(target, "off")
    assert resp_slab["mode"] == "slab"
    assert resp_slab["wire_bytes"] == DATA_SHARDS_COUNT * shard_size
    assert slab_counter.value - s0 == resp_slab["wire_bytes"]
    with open(stripe.shard_file_name(target._base_path_for(VID), 3), "rb") as f:
        assert f.read() == golden[3]
    # the acceptance ratio, measured: strictly below, and below 0.6
    assert resp["wire_bytes"] < resp_slab["wire_bytes"]
    assert resp["wire_bytes"] / resp_slab["wire_bytes"] <= 0.6


def test_trace_auto_uses_projections_when_all_holders_capable(trace_cluster):
    master, (target, *_peers), golden = trace_cluster
    resp = _rebuild(target, "auto")
    assert resp["mode"] == "trace"
    with open(stripe.shard_file_name(target._base_path_for(VID), 3), "rb") as f:
        assert f.read() == golden[3]


def test_capability_negotiation_falls_back_to_slabs(trace_cluster):
    """A peer that does not speak projections (mixed-version cluster,
    modeled by WEEDTPU_TRACE_REPAIR=off latched on that server) must push
    auto mode onto the full-slab path — rebuild still succeeds, fallback
    reason recorded."""
    master, (target, peer_a, peer_b), golden = trace_cluster
    peer_b._trace_repair = "off"  # stops advertising slab_projection
    resp = _rebuild(target, "auto")
    assert resp["mode"] == "slab", resp
    assert "projection-capable" in resp["trace_fallback"], resp
    with open(stripe.shard_file_name(target._base_path_for(VID), 3), "rb") as f:
        assert f.read() == golden[3]


def test_incapable_peer_refuses_projection_read(trace_cluster):
    """Defense in depth: even if a planner raced the capability probe, an
    `off` peer refuses the projection read itself — and the rebuild's
    runtime fallback still lands on slabs with zero lost bytes."""
    master, (target, peer_a, peer_b), golden = trace_cluster
    # off BEFORE the call: the request is on the wire when `stream` returns,
    # and a handler that read the mode first would serve it
    peer_b._trace_repair = "off"
    with rpc.RpcClient(peer_b.grpc_address) as c:
        frames = c.stream(
            VOLUME_SERVICE,
            "VolumeEcShardSlabRead",
            {
                "volume_id": VID,
                "offset": 0,
                "size": 4096,
                "projection": [
                    {"shard_id": 7, "coeffs": base64.b64encode(b"\x01").decode()}
                ],
                "projection_rows": 1,
            },
            timeout=30,
        )
        with pytest.raises(Exception, match="disabled|UNIMPLEMENTED"):
            list(frames)


def test_midrebuild_trace_failure_falls_back_to_slab(trace_cluster, monkeypatch):
    """A trace pipeline that dies mid-rebuild (holder kill, torn stream)
    must fall back to the slab path within the SAME rebuild call: shards
    still rebuilt, zero lost bytes, reason recorded."""
    master, (target, *_peers), golden = trace_cluster

    def boom(*a, **kw):
        raise IOError("holder killed mid-rebuild")

    monkeypatch.setattr(stripe, "rebuild_ec_files_from_projections", boom)
    resp = _rebuild(target, "on")
    assert resp["mode"] == "slab", resp
    assert "holder killed mid-rebuild" in resp["trace_fallback"]
    with open(stripe.shard_file_name(target._base_path_for(VID), 3), "rb") as f:
        assert f.read() == golden[3]


def test_torn_projection_stream_is_rejected_by_crc(trace_cluster):
    """A flipped bit in a projected chunk must be caught at the transport
    seam (crc_unframe), not decoded into a silently-wrong shard."""
    master, (target, peer_a, peer_b), golden = trace_cluster

    class TornClient:
        def stream(self, service, method, req, timeout=None):
            good = rpc.crc_frame(b"\x00" * 128)
            torn = bytearray(rpc.crc_frame(b"\x11" * 128))
            torn[10] ^= 0x40  # flip one payload bit, keep the CRC
            return iter([good, bytes(torn)])

    class Pool:
        def get(self, addr):
            return TornClient()

    fetch = target._projection_fetcher("x:1", VID, [], 1)
    target_pool, target._peer_pool = target._peer_pool, Pool()
    try:
        with pytest.raises(IOError, match="CRC mismatch"):
            fetch(0, 4096)
    finally:
        target._peer_pool = target_pool


def test_volume_status_advertises_projection_capability(trace_cluster):
    master, (target, peer_a, peer_b), golden = trace_cluster
    with rpc.RpcClient(peer_a.grpc_address) as c:
        st = c.call(VOLUME_SERVICE, "VolumeStatus", {"volume_id": VID})
    assert "slab_projection" in st.get("capabilities", []), st
    peer_a._trace_repair = "off"
    with rpc.RpcClient(peer_a.grpc_address) as c:
        st = c.call(VOLUME_SERVICE, "VolumeStatus", {"volume_id": VID})
    assert st.get("capabilities") == []


# -- tier-1 CI smoke: the bench harness on tiny shards ------------------------


def test_bench_rebuild_trace_smoke(tmp_path):
    """Fast CPU smoke of bench.py's ec_rebuild_trace harness (tiny shards,
    three in-process servers): both modes must rebuild byte-identically
    and the wire ratio — a deterministic byte count, not a timing — must
    meet the <= 0.6 acceptance gate."""
    import bench

    out = bench._measure_rebuild_trace(
        str(tmp_path),
        dat_bytes=1 << 20,
        large=65536,
        small=16384,
        buffer_size=16384,
        max_batch_bytes=10 * 2 * 16384,
        delay_ms=0,
    )
    assert out["ok"], out
    assert out["trace"]["match"] and out["slab"]["match"]
    assert out["trace"]["mode_reported"] == "trace"
    assert out["wire_ratio"] is not None and out["wire_ratio"] <= 0.6, out
    # with survivors on two holders the trace wire cost is exactly
    # 2 x repaired bytes vs 10 full slabs
    assert out["trace"]["wire_bytes"] == 2 * out["slab"]["wire_bytes"] // 10
