"""Mesh backend: pod-scale encode/rebuild reachable from ec.encode/
ec.rebuild — byte-identity vs the single-device oracle on tile-edge/odd/
multi-loss shapes (the r9 contract), the per-mesh-shape MULTICHIP
evidence rule for `auto` promotion, the WEEDTPU_MESH* knobs, stats, the
BENCH_MODE=mesh smoke, and the ingest persistent-staging-ring follow-up.
All on the 8 virtual CPU devices conftest forces — no TPU needed."""

import io
import json
import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import stripe
from seaweedfs_tpu.ops import rs_codec
from seaweedfs_tpu.ops.rs_codec import Encoder

pytestmark = []


def _golden():
    return Encoder(10, 4, backend="numpy")


def _encode_all(enc, data):
    return np.stack(enc.encode(list(data)))


# -- dispatch-level byte-identity --------------------------------------------


@pytest.mark.parametrize("shape", [(8, 1), (4, 2), (2, 4), (1, 8)])
def test_mesh_encode_matches_golden_odd_width(shape):
    """Odd widths force the internal zero-pad path; output must still be
    byte-identical to the numpy oracle."""
    enc = Encoder(10, 4, backend="mesh", mesh_shape=shape)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, size=(10, 1003), dtype=np.uint8)
    out = np.asarray(enc.encode_parity_lazy(data))
    want = np.asarray(_golden().encode_parity_lazy(data))
    assert np.array_equal(out, want)


@pytest.mark.parametrize("rebuild", ["ring", "alltoall"])
@pytest.mark.parametrize("lost", [(3,), (1, 5, 10, 13), (0, 1, 2, 3)])
def test_mesh_reconstruct_lazy_matches_golden(rebuild, lost):
    """The rebuild pipeline's flat (survivors, width) form through BOTH
    distributed formulations, single- and multi-loss, odd width."""
    enc = Encoder(10, 4, backend="mesh", mesh_shape=(4, 2), mesh_rebuild=rebuild)
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, size=(10, 777), dtype=np.uint8)
    shards = _encode_all(_golden(), data)
    surv = [i for i in range(14) if i not in lost][:10]
    got = np.asarray(enc.reconstruct_lazy(shards[surv], surv, list(lost), donate=True))
    assert np.array_equal(got, shards[list(lost)])


def test_mesh_batched_forms_match_golden():
    """3-D (B, C, N) encode/reconstruct forms (serving/batched paths)."""
    enc = Encoder(10, 4, backend="mesh", mesh_shape=(2, 4))
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, size=(3, 10, 257), dtype=np.uint8)
    assert np.array_equal(enc.encode_batch(data), _golden().encode_batch(data))
    shards = np.stack([_encode_all(_golden(), v) for v in data])
    lost = [2, 7, 11]
    surv = [i for i in range(14) if i not in lost][:10]
    got = enc.reconstruct_batch(shards[:, surv, :], surv, lost)
    assert np.array_equal(got, shards[:, lost, :])


def test_mesh_serving_reconstruct_and_verify():
    """The reedsolomon-parity API surface (reconstruct/verify/encode)
    through the mesh backend, including the bucketed serving path."""
    enc = Encoder(10, 4, backend="mesh", mesh_shape=(4, 2))
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, size=(10, 5000), dtype=np.uint8)
    shards = list(_encode_all(_golden(), data))
    assert enc.verify(shards)
    holed = list(shards)
    holed[0] = holed[12] = None
    rec = enc.reconstruct(holed)
    for s in range(14):
        assert np.array_equal(rec[s], shards[s]), s


# -- the way back: each shard into its place (PR 49) --------------------------


def _recon_matrix(lost=(0, 3, 11, 13)):
    surv = [i for i in range(14) if i not in lost][:10]
    return _golden().reconstruction_matrix(surv, list(lost))


_RESTORE_LAYOUTS = {
    # name -> shape of the host batch: flat (10, w), or batched (b, 10, n)
    "flat_aligned": (10, 4096),
    "flat_padded_tail": (10, 1003),
    # n = 257 is no multiple of a shard's width on any of the meshes below
    "batched": (3, 10, 257),
}


@pytest.mark.parametrize("layout", list(_RESTORE_LAYOUTS))
@pytest.mark.parametrize("form", ["ring", "alltoall", "cols"])
@pytest.mark.parametrize("shape", [(2, 2), (4, 2), (8, 1)])
def test_mesh_restore_puts_every_shard_in_its_place(shape, form, layout):
    """A result comes back byte-identical to the golden codec's in every form
    and layout, as ONE C-contiguous writable array of the handle's shape whose
    row slices are contiguous, with every shard copied once (`pieces`, `copied`,
    the byte counter) and jax's assembled host copy never taken."""
    from seaweedfs_tpu import stats
    from seaweedfs_tpu.obs import trace
    from seaweedfs_tpu.ops import gf8
    from seaweedfs_tpu.parallel.backend import MeshDispatch

    md = MeshDispatch(shape=shape, rebuild="ring" if form == "cols" else form)
    m = _golden().parity_matrix if form == "cols" else _recon_matrix()
    x = np.random.default_rng(49).integers(0, 256, size=_RESTORE_LAYOUTS[layout], dtype=np.uint8)
    want = np.stack([gf8.gf_mat_vec(m, v) for v in x]) if x.ndim == 3 else gf8.gf_mat_vec(m, x)
    handle = md.apply(m, x) if form == "cols" else md.reconstruct(m, x)
    before = {k: stats.EcMeshRestoreBytes.labels(k).value for k in ("result", "copied")}
    with trace.start("test.sync") as root:
        got = np.asarray(handle)
    assert got.shape == handle.shape == want.shape and got.dtype == np.uint8
    assert np.array_equal(got, want)
    assert got.flags.c_contiguous and got.flags.writeable
    row = got[1, 3:200] if got.ndim == 2 else got[2, 1, 3:200]
    assert row.flags.c_contiguous and np.array_equal(row, want[1, 3:200] if got.ndim == 2 else want[2, 1, 3:200])
    assert handle._dev._npy_value is None  # the global array was never read as a whole
    rose = {k: stats.EcMeshRestoreBytes.labels(k).value - v for k, v in before.items()}
    assert rose == {"result": got.size, "copied": got.size}
    (sp,) = [c for c in root.children if c.name == "mesh.restore"]
    n_dev = shape[0] * shape[1]
    assert sp.attrs["pieces"] == sp.attrs["devices"] == n_dev and sp.attrs["copied"] == got.size
    assert sp.attrs["variant"] == form and sp.attrs["mesh"] == md.shape_str()


def test_mesh_restore_leaves_whole_pad_shards_alone():
    """A tail so short that some devices hold nothing but pad: their shards
    are not copied (`pieces` counts the others), the bytes are the golden's."""
    from seaweedfs_tpu.obs import trace
    from seaweedfs_tpu.ops import gf8
    from seaweedfs_tpu.parallel.backend import MeshDispatch

    md = MeshDispatch(shape=(4, 2), rebuild="ring")
    m = _recon_matrix()
    x = np.random.default_rng(50).integers(0, 256, size=(10, 3), dtype=np.uint8)
    with trace.start("test.sync") as root:
        got = np.asarray(md.reconstruct(m, x))
    assert np.array_equal(got, gf8.gf_mat_vec(m, x))
    (sp,) = [c for c in root.children if c.name == "mesh.restore"]
    assert sp.attrs["pieces"] == 3 and sp.attrs["copied"] == got.size == 12


def test_mesh_restore_refuses_shards_that_do_not_cover_the_result():
    from seaweedfs_tpu.parallel.backend import MeshDispatch

    md = MeshDispatch(shape=(2, 2), rebuild="ring")
    handle = md.reconstruct(_recon_matrix(), np.zeros((10, 64), dtype=np.uint8))
    with pytest.raises(RuntimeError, match="its shards cover"):
        md._restore("ring", (4, 128), handle._dev)


_POOLED = (4, 262144)  # 1 MiB: the smallest result the pool keeps


def _address(a: np.ndarray) -> int:
    return a.__array_interface__["data"][0]


@pytest.mark.parametrize("held_by", ["the_array", "a_row_slice", "a_slice_of_a_slice"])
def test_result_pool_hands_a_buffer_out_again_only_after_its_last_holder_died(held_by):
    from seaweedfs_tpu.parallel.backend import _ResultPool

    pool = _ResultPool()
    out, kept = pool.take(_POOLED)
    assert not kept and out.shape == _POOLED and out.flags.c_contiguous and out.flags.writeable
    where = _address(out)
    holder = {"the_array": out, "a_row_slice": out[2, 10:5000], "a_slice_of_a_slice": out[1:3][1, 7:9]}[held_by]
    holder[...] = 7
    del out
    other, kept = pool.take(_POOLED)  # the first is still held: never the same memory
    assert not kept and _address(other) != where and pool.kept_bytes() == 0
    assert (holder == 7).all()
    del holder
    assert pool.kept_bytes() == _POOLED[0] * _POOLED[1]  # came home by itself
    again, kept = pool.take(_POOLED)
    assert kept and _address(again) == where and pool.kept_bytes() == 0


@pytest.mark.parametrize("case", ["bound", "smaller_result_fits", "small_results_bypass"])
def test_result_pool_bounds_and_sizes(case):
    from seaweedfs_tpu.parallel import backend as mb

    if case == "bound":
        pool = mb._ResultPool(max_bytes=5 << 20)
        outs = [pool.take((2, 1 << 20))[0] for _ in range(2)] + [pool.take((3, 1 << 20))[0]]
        del outs
        # 2 + 2 + 3 MiB came home, the smallest went first: at most the bound stays
        assert pool.kept_bytes() == 5 << 20
        taken = [pool.take(shape) for shape in ((3, 1 << 20), (2, 1 << 20), (2, 1 << 20))]
        assert [kept for _, kept in taken] == [True, True, False]
    elif case == "smaller_result_fits":
        pool = mb._ResultPool()
        where = _address(pool.take((4, 1 << 20))[0])
        out, kept = pool.take((4, 300000))  # a narrower tail batch takes the kept buffer's first bytes
        assert kept and _address(out) == where and out.shape == (4, 300000) and out.flags.c_contiguous
    else:
        pool = mb._ResultPool()
        out, kept = pool.take((4, 1000))
        assert not kept and out.base is None
        del out
        assert pool.kept_bytes() == 0
        assert mb.RESULT_POOL_MAX_BYTES >= 3 * 4 * 6553600  # three results of the widest slot


def test_mesh_restores_of_a_run_share_kept_results():
    """Through the dispatcher: a result that died serves the next restore of
    its size (`kept=` on the span says so), one that lives never does, and
    what the pool holds stays under its bound."""
    from seaweedfs_tpu.obs import trace
    from seaweedfs_tpu.ops import gf8
    from seaweedfs_tpu.parallel.backend import RESULT_POOL_MAX_BYTES, MeshDispatch

    md = MeshDispatch(shape=(2, 2), rebuild="ring")
    m = _recon_matrix()
    rng = np.random.default_rng(51)
    seen, held = [], []
    for i in range(5):
        x = rng.integers(0, 256, size=(10, _POOLED[1]), dtype=np.uint8)
        with trace.start("test.sync") as root:
            got = np.asarray(md.reconstruct(m, x))
        (sp,) = [c for c in root.children if c.name == "mesh.restore"]
        assert np.array_equal(got[:, :4096], gf8.gf_mat_vec(m, x[:, :4096]))
        assert np.array_equal(got[:, -4096:], gf8.gf_mat_vec(m, x[:, -4096:]))
        seen.append((sp.attrs["kept"], _address(got)))
        if i < 2:
            held.append(got[3, 5:50])  # a lane's view of batches 0 and 1 outlives them
        del got
    kept, where = zip(*seen)
    assert kept == (False, False, False, True, True)
    assert len(set(where[:3])) == 3 and where[3] == where[2] and where[4] == where[2]
    assert md._results.kept_bytes() <= RESULT_POOL_MAX_BYTES
    del held
    assert md._results.kept_bytes() == 3 * _POOLED[0] * _POOLED[1]


# -- file-pipeline byte-identity (the production path) ------------------------


def _write_dat(base, data):
    os.makedirs(os.path.dirname(base), exist_ok=True)
    with open(base + ".dat", "wb") as f:
        f.write(data)


def test_mesh_write_ec_files_byte_identical_tile_edge(tmp_path):
    """write_ec_files through the mesh streaming pipeline (aligned spans,
    zero-filled tail gap, donation, inline CRC) vs the warm oracle on a
    tile-edge/odd layout."""
    rng = np.random.default_rng(5)
    large, small, buf = 64 * 1024, 16 * 1024, 16 * 1024
    data = rng.integers(
        0, 256, 2 * large * 10 + 3 * small * 10 + 4321, dtype=np.uint8
    ).tobytes()
    base_o, base_m = str(tmp_path / "o" / "7"), str(tmp_path / "m" / "7")
    for b in (base_o, base_m):
        _write_dat(b, data)
    stripe.write_ec_files(base_o, large, small, buf, encoder=_golden(),
                          max_batch_bytes=1 << 20)
    enc = Encoder(10, 4, backend="mesh", mesh_shape=(4, 2))
    stripe.write_ec_files(base_m, large, small, buf, encoder=enc,
                          max_batch_bytes=1 << 20)
    for s in range(14):
        assert (
            open(stripe.shard_file_name(base_o, s), "rb").read()
            == open(stripe.shard_file_name(base_m, s), "rb").read()
        ), f"shard {s}"
    # identical geometry AND identical streamed CRCs in the sidecar
    assert open(base_o + ".eci", "rb").read() == open(base_m + ".eci", "rb").read()


@pytest.mark.parametrize("rebuild", ["ring", "alltoall"])
def test_mesh_rebuild_ec_files_byte_identical_to_serial(tmp_path, rebuild):
    """rebuild_ec_files with the mesh encoder (both variants) vs the
    serial oracle on the same survivor set, multi-loss, with the .eci CRC
    gate active (a byte drift would fail the rebuild, not just the
    comparison)."""
    rng = np.random.default_rng(6)
    large, small, buf = 64 * 1024, 16 * 1024, 16 * 1024
    data = rng.integers(0, 256, 3 * large * 10 + 987, dtype=np.uint8).tobytes()
    base = str(tmp_path / "v" / "7")
    _write_dat(base, data)
    stripe.write_ec_files(base, large, small, buf, encoder=_golden(),
                          max_batch_bytes=1 << 20)
    lost = (0, 5, 11, 13)
    expected = {
        s: open(stripe.shard_file_name(base, s), "rb").read() for s in lost
    }
    for s in lost:
        os.unlink(stripe.shard_file_name(base, s))
    enc = Encoder(10, 4, backend="mesh", mesh_shape=(2, 4), mesh_rebuild=rebuild)
    rebuilt = stripe.rebuild_ec_files(
        base, encoder=enc, buffer_size=48 * 1024, max_batch_bytes=1 << 20
    )
    assert sorted(rebuilt) == sorted(lost)
    for s in lost:
        assert open(stripe.shard_file_name(base, s), "rb").read() == expected[s]
    # serial oracle on the SAME survivor set agrees (transitivity check)
    for s in lost:
        os.unlink(stripe.shard_file_name(base, s))
    stripe.rebuild_ec_files_serial(base, encoder=_golden())
    for s in lost:
        assert open(stripe.shard_file_name(base, s), "rb").read() == expected[s]


# -- factory, knobs, audit -----------------------------------------------------


def test_new_encoder_mesh_explicit_and_audit():
    enc = rs_codec.new_encoder(backend="mesh")
    assert enc.backend == "mesh"
    sel = enc.selection
    assert sel.get("mesh_shape") and "x" in sel["mesh_shape"]
    assert sel.get("mesh_rebuild") in ("ring", "alltoall")
    assert sel.get("mesh_devices") >= 1
    assert "mesh" in sel.get("audit", "")


def test_mesh_shape_env_knob(monkeypatch):
    monkeypatch.setenv("WEEDTPU_MESH_SHAPE", "2x2")
    enc = Encoder(10, 4, backend="mesh")
    md = enc._mesh_dispatch()
    assert (md.dp, md.sp) == (2, 2)
    assert md.width_align == 4


def test_mesh_shape_env_knob_malformed(monkeypatch):
    monkeypatch.setenv("WEEDTPU_MESH_SHAPE", "banana")
    enc = Encoder(10, 4, backend="mesh")
    with pytest.raises(ValueError, match="DPxSP"):
        enc._mesh_dispatch()


def test_mesh_rebuild_variant_validation():
    enc = Encoder(10, 4, backend="mesh", mesh_shape=(2, 2), mesh_rebuild="bogus")
    with pytest.raises(ValueError, match="variant"):
        enc._mesh_dispatch()


def test_default_mesh_shape_rule():
    from seaweedfs_tpu.parallel import backend as mb

    assert mb.default_mesh_shape(8) == (4, 2)
    assert mb.default_mesh_shape(2) == (2, 1)
    assert mb.parse_mesh_shape("") is None
    assert mb.parse_mesh_shape("auto") is None
    assert mb.parse_mesh_shape("4x2") == (4, 2)
    with pytest.raises(ValueError):
        mb.parse_mesh_shape("0x4")


def test_mesh_stats_gauge_and_dispatch_counter():
    from seaweedfs_tpu import stats

    enc = Encoder(10, 4, backend="mesh", mesh_shape=(4, 2))
    before = stats.EcDispatchTotal.labels("mesh").value
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, size=(10, 64), dtype=np.uint8)
    np.asarray(enc.encode_parity_lazy(data))
    assert stats.EcMeshDevices.value == 8
    assert stats.EcDispatchTotal.labels("mesh").value == before + 1


# -- per-mesh-shape evidence rule ---------------------------------------------


def _fresh_when():
    import datetime

    return datetime.datetime.utcnow().strftime("%Y-%m-%dT%H:%MZ")


def _write_multichip(dirpath, meas, name="MULTICHIP_r91.json"):
    with open(os.path.join(dirpath, name), "w", encoding="utf-8") as f:
        json.dump(meas, f)


def _evidence(**kw):
    ev = {
        "when": _fresh_when(),
        "platform": "tpu (TPU v5 lite)",
        "round": 91,
        "single_device": {"encode_gbps": 31.0},
        "shapes": {
            "4x2": {
                "encode_gbps": 180.0,
                "rebuild_ring_gbps": 120.0,
                "rebuild_alltoall_gbps": 95.0,
                "match": True,
            },
            "16x2": {"encode_gbps": 500.0, "match": True},
        },
    }
    ev.update(kw)
    return ev


def test_mesh_evidence_promotes_on_fresh_onchip(tmp_path):
    _write_multichip(tmp_path, _evidence())
    ok, dec = rs_codec.pick_mesh_backend(8, art_dir=str(tmp_path))
    assert ok
    # 16x2 is faster but needs 32 devices — only achievable shapes count
    assert dec["mesh_shape"] == "4x2"
    assert dec["mesh_rebuild"] == "ring"  # ring beats alltoall in the evidence
    assert dec["evidence_round"] == 91
    assert "beats single-device" in dec["reason"]


def test_mesh_evidence_alltoall_wins_when_faster(tmp_path):
    ev = _evidence()
    ev["shapes"]["4x2"]["rebuild_alltoall_gbps"] = 200.0
    _write_multichip(tmp_path, ev)
    ok, dec = rs_codec.pick_mesh_backend(8, art_dir=str(tmp_path))
    assert ok and dec["mesh_rebuild"] == "alltoall"


def test_mesh_evidence_absent_keeps_backend(tmp_path):
    ok, dec = rs_codec.pick_mesh_backend(8, art_dir=str(tmp_path))
    assert not ok and "no committed mesh evidence" in dec["reason"]


def test_mesh_evidence_off_chip_never_promotes(tmp_path):
    _write_multichip(tmp_path, _evidence(platform="cpu (cpu)"))
    ok, dec = rs_codec.pick_mesh_backend(8, art_dir=str(tmp_path))
    assert not ok and "on-chip" in dec["reason"]


def test_mesh_evidence_stale_never_promotes(tmp_path):
    _write_multichip(tmp_path, _evidence(when="2020-01-01T00:00Z"))
    ok, dec = rs_codec.pick_mesh_backend(8, art_dir=str(tmp_path))
    assert not ok and "stale" in dec["reason"]


def test_mesh_evidence_unparseable_age_is_stale(tmp_path):
    _write_multichip(tmp_path, _evidence(when="yesterday-ish"))
    ok, dec = rs_codec.pick_mesh_backend(8, art_dir=str(tmp_path))
    assert not ok and "stale" in dec["reason"]


def test_mesh_evidence_losing_shape_keeps_backend(tmp_path):
    ev = _evidence()
    ev["shapes"]["4x2"]["encode_gbps"] = 12.0  # below single_device 31.0
    del ev["shapes"]["16x2"]
    _write_multichip(tmp_path, ev)
    ok, dec = rs_codec.pick_mesh_backend(8, art_dir=str(tmp_path))
    assert not ok and "beats the single-device" in dec["reason"]


def test_mesh_evidence_failed_byte_verify_disqualifies(tmp_path):
    ev = _evidence()
    ev["shapes"]["4x2"]["match"] = False
    del ev["shapes"]["16x2"]
    _write_multichip(tmp_path, ev)
    ok, _dec = rs_codec.pick_mesh_backend(8, art_dir=str(tmp_path))
    assert not ok


def test_mesh_evidence_no_shape_table_keeps_backend(tmp_path):
    _write_multichip(tmp_path, {"when": _fresh_when(), "platform": "tpu", "tail": "ok"})
    ok, dec = rs_codec.pick_mesh_backend(8, art_dir=str(tmp_path))
    assert not ok and "per-mesh-shape" in dec["reason"]


def test_mesh_evidence_newest_round_wins(tmp_path):
    _write_multichip(tmp_path, _evidence(), name="MULTICHIP_r90.json")
    ev2 = _evidence(platform="cpu (cpu)")
    _write_multichip(tmp_path, ev2, name="MULTICHIP_r91.json")
    ok, dec = rs_codec.pick_mesh_backend(8, art_dir=str(tmp_path))
    # the newest round is off-chip: it must NOT fall back to older rounds
    assert not ok and dec["evidence_file"] == "MULTICHIP_r91.json"


def test_cpu_platform_multichip_record_never_promotes(tmp_path):
    """A record of a CPU host-device run — what `BENCH_MODE=mesh` writes
    off the chip — must be refused by the evidence rule (platform gate), so
    `auto` on an 8-device host cannot flip to mesh without on-chip numbers."""
    _write_multichip(tmp_path, _evidence(platform="cpu (cpu)"), name="MULTICHIP_r06.json")
    ev = rs_codec.load_mesh_evidence(str(tmp_path))
    assert ev is not None and ev["_file"] == "MULTICHIP_r06.json"
    ok, dec = rs_codec.pick_mesh_backend(8, art_dir=str(tmp_path))
    assert not ok and "not an on-chip measurement" in dec["reason"]


def test_new_encoder_auto_promotes_to_mesh_on_evidence(tmp_path, monkeypatch):
    """End-to-end `auto` flow: a simulated TPU pod (device identity
    faked, the 8 virtual CPU devices kept for the actual mesh build)
    with committed fresh mesh evidence promotes to the mesh backend with
    the evidence's shape + rebuild variant in the audit."""
    from seaweedfs_tpu.utils import devices as devices_mod

    _write_multichip(tmp_path, _evidence())
    monkeypatch.setattr(devices_mod, "is_tpu_device", lambda d: True)
    monkeypatch.setattr(rs_codec, "_artifacts_dir", lambda: str(tmp_path / "none"))
    monkeypatch.setattr(rs_codec, "_multichip_dir", lambda: str(tmp_path))
    enc = rs_codec.new_encoder()
    assert enc.backend == "mesh"
    assert enc.mesh_shape == (4, 2) and enc.mesh_rebuild == "ring"
    sel = enc.selection
    assert sel["source"] == "mesh-evidence"
    assert sel["mesh_shape"] == "4x2" and sel["mesh_devices"] == 8
    assert "evidence=r91" in sel["audit"]
    # and the promoted encoder still encodes byte-identically
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, size=(10, 123), dtype=np.uint8)
    assert np.array_equal(
        np.asarray(enc.encode_parity_lazy(data)),
        np.asarray(_golden().encode_parity_lazy(data)),
    )


def test_new_encoder_auto_keeps_backend_without_mesh_evidence(tmp_path, monkeypatch):
    from seaweedfs_tpu.utils import devices as devices_mod

    monkeypatch.setattr(devices_mod, "is_tpu_device", lambda d: True)
    monkeypatch.setattr(rs_codec, "_artifacts_dir", lambda: str(tmp_path / "none"))
    monkeypatch.setattr(rs_codec, "_multichip_dir", lambda: str(tmp_path))
    enc = rs_codec.new_encoder()
    assert enc.backend == "jax"  # tpu default without kernel evidence
    assert "no committed mesh evidence" in enc.selection["mesh"]["reason"]


# -- shell audit command ------------------------------------------------------


def test_ec_backend_shell_command_reports_selection(tmp_path):
    """`ec.backend` reads each volume server's selection from its /status —
    the shell builds no encoder of its own."""
    from seaweedfs_tpu.cluster.master import MasterServer
    from seaweedfs_tpu.cluster.volume_server import VolumeServer
    from seaweedfs_tpu.shell import CommandEnv, commands

    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    vs = VolumeServer([str(tmp_path)], master.address, heartbeat_interval=0.3)
    vs.start()
    try:
        with CommandEnv(master.address) as env:
            buf = io.StringIO()
            commands()["ec.backend"].do([], env, buf)
    finally:
        vs.stop()
        master.stop()
    out = buf.getvalue()
    assert out.startswith(f"ec.backend: {vs.url}: ")
    sel = vs.store.encoder.selection
    assert f"backend={sel['backend']}" in out and f"source={sel['source']}" in out
    assert "device=cpu:cpu" in out  # what jax reported to the server


# -- BENCH_MODE=mesh smoke (tier-1) -------------------------------------------


def test_bench_mesh_smoke_schema_and_byte_verify(tmp_path):
    """Scaled-down run of bench.py's mesh harness on the forced 8-device
    CPU mesh: per-shape encode + both rebuild variants measured, every
    shape byte-verified."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import bench

    out = bench._measure_mesh(
        str(tmp_path),
        dat_bytes=2 * 64 * 1024 * 10 + 12345,
        large=64 * 1024,
        small=16 * 1024,
        buffer_size=16 * 1024,
        max_batch_bytes=1 << 20,
        shapes=[(4, 2)],
    )
    assert out["kind"] == "multichip" and out["n_devices"] == 8
    assert out["ok"] is True
    rec = out["shapes"]["4x2"]
    assert rec["match"] is True
    for key in ("encode_gbps", "rebuild_ring_gbps", "rebuild_alltoall_gbps"):
        assert rec[key] > 0
    assert out["single_device"]["encode_gbps"] > 0


# -- ingest persistent staging ring (ROADMAP follow-up 1) ---------------------


def test_inline_builder_reuses_staging_ring_across_polls(tmp_path, monkeypatch):
    """Steady-state polls must lease the SAME pooled buffers (no per-poll
    buffer churn) and reuse the builder-lifetime .dat handle."""
    from seaweedfs_tpu.ec import ingest, stripe

    monkeypatch.setattr(stripe, "_pool_free", [])
    large, small, buf = 64 * 1024, 16 * 1024, 16 * 1024
    base = str(tmp_path / "5")
    b = ingest.InlineStripeBuilder(base, _golden(), large, small, buffer_size=buf)
    rng = np.random.default_rng(9)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, large * 10 + 1, dtype=np.uint8).tobytes())
        f.flush()
        assert b.poll() == 1
        ring_ids = {id(r) for r in stripe._pool_free}
        dat_handle = b._dat
        assert len(ring_ids) == stripe.DEFAULT_PIPELINE_DEPTH + 1 and dat_handle is not None
        f.write(rng.integers(0, 256, large * 10, dtype=np.uint8).tobytes())
        f.flush()
        assert b.poll() == 1
        assert {id(r) for r in stripe._pool_free} == ring_ids
        assert b._dat is dat_handle
    b.abort()
    assert b._dat is None


def test_inline_builder_async_watermark_lands_before_seal(tmp_path):
    """The flusher-thread watermark keeps the fsync-before-record
    ordering: after polls cross the durable batch, the journal's last
    rows record must describe bytes already on disk, and seal still
    produces the warm-identical shard set."""
    from seaweedfs_tpu.ec import ingest

    large, small, buf = 64 * 1024, 16 * 1024, 16 * 1024
    base_i, base_w = str(tmp_path / "i"), str(tmp_path / "w")
    rng = np.random.default_rng(10)
    data = rng.integers(0, 256, 4 * large * 10 + 321, dtype=np.uint8).tobytes()
    b = ingest.InlineStripeBuilder(base_i, _golden(), large, small, buffer_size=buf)
    b._durable_batch = large * 10  # force a watermark per row
    with open(base_i + ".dat", "wb") as f:
        f.write(data)
        f.flush()
    assert b.poll() == 4
    if b._flusher is not None:
        b._flusher.shutdown(wait=True)  # drain the async watermark
        b._flusher = None
    records = ingest.read_journal(base_i)
    rows_records = [r for r in records if r.get("kind") == "rows"]
    assert rows_records and rows_records[-1]["rows"] >= 1
    for s in range(14):
        size = os.path.getsize(ingest.part_path(base_i, s))
        assert size >= rows_records[-1]["rows"] * large
    b.seal()
    with open(base_w + ".dat", "wb") as f:
        f.write(data)
    stripe.write_ec_files(base_w, large, small, buf, encoder=_golden())
    for s in range(14):
        assert (
            open(stripe.shard_file_name(base_i, s), "rb").read()
            == open(stripe.shard_file_name(base_w, s), "rb").read()
        ), s
