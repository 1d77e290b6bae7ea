"""Heterogeneous rebuild fusion: block-diagonal decode + fused batches.

Covers the fusion PR's acceptance surface without a live cluster: the
`Encoder.reconstruct_block` block-diagonal decode (byte-identity vs the
gf8 golden across backends, mixed geometries, tile-edge and odd widths,
overlap/bounds rejection), the `xorsched.apply_blocks` multi-program
executor (zero-copy caller outputs, thread-count variants, validation),
the heterogeneous `rebuild_ec_files_batch` path (mixed 10+4/12+3/20+4
storm byte-identical to the serial per-volume oracle, 2-missing and
1-missing in ONE batch, mid-batch failure unlinking only that block's
partials), the per-block schedule-cache keying under a mixed-signature
storm, and the fusion fields on the wire contract.
"""

import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import stripe
from seaweedfs_tpu.ops import gf8, xorsched
from seaweedfs_tpu.ops.rs_codec import Encoder
from seaweedfs_tpu.utils import native

LARGE, SMALL = 16384, 4096

# encode with numpy-backend encoders so schedule-cache assertions below
# see ONLY the decode compiles; matrices are identical across backends
B10 = Encoder(10, 4, backend="numpy")
B12 = Encoder(12, 3, backend="numpy", matrix_kind="cauchy")
B20 = Encoder(20, 4, backend="numpy", matrix_kind="cauchy")


def _backends():
    out = ["numpy", "xorsched"]
    if native.load() is not None:
        out.append("native")
    return out


def _block(enc, missing, col_start, width):
    survivors = [
        s for s in range(enc.total_shards) if s not in missing
    ][: enc.data_shards]
    return {
        "encoder": enc,
        "survivors": survivors,
        "wanted": list(missing),
        "col_start": col_start,
        "width": width,
    }


# -- Encoder.reconstruct_block -------------------------------------------------


@pytest.mark.parametrize("backend", _backends())
def test_reconstruct_block_mixed_geometries_byte_exact(backend):
    """Three signature blocks (10+4 2-missing, 12+3 1-missing, 20+4
    2-missing) packed side by side, widths chosen to land on tile edges
    and odd remainders — the fused result must equal each block's gf8
    golden decode, rows past a block's wanted count unconstrained."""
    e10 = Encoder(10, 4, backend=backend)
    e12 = Encoder(12, 3, backend=backend, matrix_kind="cauchy")
    e20 = Encoder(20, 4, backend=backend, matrix_kind="cauchy")
    widths = [513, 7, 4096]  # odd, sub-tile, exact-tile
    blocks, col = [], 0
    for enc, missing, w in zip(
        (e10, e12, e20), ([12, 13], [5], [20, 23]), widths
    ):
        blocks.append(_block(enc, missing, col, w))
        col += w
    rng = np.random.default_rng(3)
    staging = rng.integers(0, 256, size=(20, col), dtype=np.uint8)
    out = np.asarray(e10.reconstruct_block(staging, blocks))
    assert out.shape == (2, col) and out.dtype == np.uint8
    for b in blocks:
        enc = b["encoder"]
        m = enc.reconstruction_matrix(b["survivors"], b["wanted"])
        sub = staging[: enc.data_shards, b["col_start"]:b["col_start"] + b["width"]]
        golden = gf8.gf_mat_vec(m, sub)
        got = out[: len(b["wanted"]), b["col_start"]:b["col_start"] + b["width"]]
        assert (got == golden).all(), f"{enc.data_shards}+ block differs"


def test_reconstruct_block_rejects_overlap_bounds_and_empty():
    e10 = Encoder(10, 4, backend="numpy")
    staging = np.zeros((10, 100), dtype=np.uint8)
    with pytest.raises(ValueError):
        e10.reconstruct_block(staging, [])
    with pytest.raises(ValueError):
        e10.reconstruct_block(
            staging,
            [_block(e10, [13], 0, 60), _block(e10, [12], 50, 50)],  # overlap
        )
    with pytest.raises(ValueError):
        e10.reconstruct_block(staging, [_block(e10, [13], 60, 50)])  # past end


# -- xorsched.apply_blocks -----------------------------------------------------


def test_apply_blocks_matches_apply_per_block_and_threads():
    """Two different programs over different widths (tile edge, odd,
    tiny) through one apply_blocks call — equal to per-program apply for
    every thread setting, including caller-supplied zero-copy outputs."""
    e10 = Encoder(10, 4, backend="numpy")
    e12 = Encoder(12, 3, backend="numpy", matrix_kind="cauchy")
    m1 = e10.reconstruction_matrix(list(range(10)), [12, 13])
    m2 = e12.reconstruction_matrix(list(range(12)), [14])
    p1, p2 = xorsched.get_schedule(m1), xorsched.get_schedule(m2)
    rng = np.random.default_rng(11)
    for width1, width2 in [(p1.tile_sym, 3), (p1.tile_sym + 1, 513)]:
        in1 = list(rng.integers(0, 256, size=(10, width1), dtype=np.uint8))
        in2 = list(rng.integers(0, 256, size=(12, width2), dtype=np.uint8))
        want1 = np.stack(xorsched.apply(p1, in1))
        want2 = np.stack(xorsched.apply(p2, in2))
        for threads in (None, 1, 2, 0):
            got = xorsched.apply_blocks([p1, p2], [in1, in2], threads=threads)
            assert (np.stack(got[0]) == want1).all()
            assert (np.stack(got[1]) == want2).all()
        # zero-copy: rows of caller arrays are filled in place
        buf1 = np.zeros((2, width1), dtype=np.uint8)
        buf2 = np.zeros((1, width2), dtype=np.uint8)
        xorsched.apply_blocks(
            [p1, p2], [in1, in2],
            outputs_per_block=[list(buf1), list(buf2)], threads=2,
        )
        assert (buf1 == want1).all() and (buf2 == want2).all()


def test_apply_blocks_validates_outputs():
    e10 = Encoder(10, 4, backend="numpy")
    m = e10.reconstruction_matrix(list(range(10)), [13])
    p = xorsched.get_schedule(m)
    ins = [np.zeros(64, dtype=np.uint8)] * 10
    with pytest.raises(ValueError):
        xorsched.apply_blocks([p], [ins], outputs_per_block=[[np.zeros(63, dtype=np.uint8)]])
    with pytest.raises(ValueError):
        xorsched.apply_blocks([p], [ins], outputs_per_block=[[np.zeros(64, dtype=np.uint16)]])
    with pytest.raises(ValueError):
        xorsched.apply_blocks(
            [p], [ins],
            outputs_per_block=[[np.zeros((64, 2), dtype=np.uint8)[:, 0]]],
        )


# -- heterogeneous rebuild_ec_files_batch -------------------------------------


def _build_volume(dirpath, vid, size, enc, seed):
    base = os.path.join(dirpath, str(vid))
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    with open(base + ".idx", "wb"):
        pass
    stripe.write_ec_files(
        base, large_block_size=LARGE, small_block_size=SMALL, encoder=enc
    )
    stripe.write_sorted_file_from_idx(base)
    golden = {}
    for s in range(enc.total_shards):
        with open(stripe.shard_file_name(base, s), "rb") as f:
            golden[s] = f.read()
    os.unlink(base + ".dat")
    return base, golden


def _storm_jobs(tmp_path, specs, job_encoders=None):
    jobs, goldens = [], {}
    for i, (vid, size, missing, enc) in enumerate(specs):
        base, golden = _build_volume(str(tmp_path), vid, size, enc, seed=vid)
        goldens[base] = (golden, missing, enc)
        for s in missing:
            os.unlink(stripe.shard_file_name(base, s))
        present = [s for s in range(enc.total_shards) if s not in missing]
        jobs.append({
            "base": base,
            "sources": {
                s: stripe.LocalSlabSource(stripe.shard_file_name(base, s))
                for s in present
            },
            "shard_size": len(golden[0]),
            "missing": missing,
            "encoder": (job_encoders or {}).get(i, enc),
        })
    return jobs, goldens


MIXED_SPECS = [
    (41, 123_457, [12, 13], B10),  # 2-missing, odd size
    (42, 88_001, [3], B10),        # 1-missing, same geometry
    (43, 97_003, [0, 12], B12),    # converted geometry, 2-missing
    (44, 64_005, [20, 23], B20),   # converted geometry, 2-missing
    (45, 71_999, [7], B20),        # 1-missing
]


def test_batch_mixed_signatures_one_dispatch_matches_serial(tmp_path):
    """The acceptance storm in miniature: 10+4 with converted 12+3 and
    20+4 geometries, 2-missing and 1-missing in ONE batch, odd sizes so
    column spans hit tile edges. The fused single dispatch must leave
    every volume byte-identical to what `rebuild_ec_files_serial`
    produces for it alone."""
    jobs, goldens = _storm_jobs(tmp_path, MIXED_SPECS)
    try:
        res = stripe.rebuild_ec_files_batch(
            jobs, buffer_size=16384, max_batch_bytes=163_840
        )
    finally:
        for job in jobs:
            for src in job["sources"].values():
                src.close()
    assert not res["errors"], res["errors"]
    assert res["dispatch_groups"] == 1
    assert res["signature_groups"] == len(MIXED_SPECS)  # all distinct here
    assert res["volumes_fused"] == len(MIXED_SPECS)
    for base, (golden, missing, enc) in goldens.items():
        assert sorted(res["rebuilt"][base]) == sorted(missing)
        fused_bytes = {}
        for s in missing:
            with open(stripe.shard_file_name(base, s), "rb") as f:
                fused_bytes[s] = f.read()
            assert fused_bytes[s] == golden[s], f"{base} shard {s} vs golden"
            os.unlink(stripe.shard_file_name(base, s))
        assert sorted(stripe.rebuild_ec_files_serial(base, encoder=enc)) == (
            sorted(missing)
        )
        for s in missing:
            with open(stripe.shard_file_name(base, s), "rb") as f:
                assert f.read() == fused_bytes[s], (
                    f"{base} shard {s}: fused differs from serial oracle"
                )


def test_batch_mid_failure_unlinks_only_failed_block(tmp_path):
    """A survivor of ONE signature group dies mid-pipeline: that group's
    partials are unlinked and reported, while every other block of the
    same fused batch completes byte-exact — group-scoped isolation."""

    class Dying(stripe.SlabSource):
        def __init__(self, path):
            self._inner = stripe.LocalSlabSource(path)
            self._calls = 0

        def read_into(self, offset, out):
            self._calls += 1
            if self._calls > 1:
                raise IOError("holder died")
            self._inner.read_into(offset, out)

        def close(self):
            self._inner.close()

    specs = [
        (51, 90_000, [13], B10),
        (52, 80_000, [12, 13], B10),   # this group's survivor dies
        (53, 70_000, [0, 12], B12),
    ]
    jobs, goldens = _storm_jobs(tmp_path, specs)
    dying_base = jobs[1]["base"]
    jobs[1]["sources"][0].close()
    jobs[1]["sources"][0] = Dying(stripe.shard_file_name(dying_base, 0))
    try:
        res = stripe.rebuild_ec_files_batch(
            jobs, buffer_size=4096, max_batch_bytes=81_920
        )
    finally:
        for job in jobs:
            for src in job["sources"].values():
                src.close()
    assert res["dispatch_groups"] == 1
    assert list(res["errors"]) == [dying_base]
    for s in (12, 13):
        assert not os.path.exists(stripe.shard_file_name(dying_base, s))
    for base, (golden, missing, _) in goldens.items():
        if base == dying_base:
            continue
        assert sorted(res["rebuilt"][base]) == sorted(missing)
        for s in missing:
            with open(stripe.shard_file_name(base, s), "rb") as f:
                assert f.read() == golden[s]


def test_schedule_cache_keys_per_block_under_mixed_storm(tmp_path):
    """The small-fix satellite: the fused dispatch compiles ONE schedule
    per block sub-matrix (keyed individually in the LRU), not one giant
    composite program — so a re-run of the same storm is all hits and a
    storm sharing signatures re-uses entries across volumes."""
    job_encoders = {
        0: Encoder(10, 4, backend="xorsched"),
        1: Encoder(10, 4, backend="xorsched"),
        2: Encoder(12, 3, backend="xorsched", matrix_kind="cauchy"),
        3: Encoder(20, 4, backend="xorsched", matrix_kind="cauchy"),
        4: Encoder(20, 4, backend="xorsched", matrix_kind="cauchy"),
    }
    jobs, _ = _storm_jobs(tmp_path, MIXED_SPECS, job_encoders)
    n_sigs = len(MIXED_SPECS)
    xorsched.clear_schedule_cache()
    try:
        res = stripe.rebuild_ec_files_batch(
            jobs, buffer_size=16384, max_batch_bytes=163_840
        )
        assert not res["errors"] and res["dispatch_groups"] == 1
        info = xorsched.schedule_cache_info()
        assert info["size"] == n_sigs, info  # one entry PER BLOCK matrix
        assert info["misses"] == n_sigs, info
        first_hits = info["hits"]
        # identical storm again: every block schedule is a cache hit
        for job, (_, _, missing, _) in zip(jobs, MIXED_SPECS):
            for s in missing:
                os.unlink(stripe.shard_file_name(job["base"], s))
        res = stripe.rebuild_ec_files_batch(
            jobs, buffer_size=16384, max_batch_bytes=163_840
        )
        assert not res["errors"] and res["dispatch_groups"] == 1
        info = xorsched.schedule_cache_info()
        assert info["misses"] == n_sigs, info  # no recompiles
        assert info["size"] == n_sigs, info
        assert info["hits"] > first_hits, info
    finally:
        for job in jobs:
            for src in job["sources"].values():
                src.close()


# -- wire contract -------------------------------------------------------------


def test_wire_roundtrips_fusion_fields():
    from seaweedfs_tpu.pb import wire

    c = wire.codec()
    _, resp_cls = c.classes("weedtpu.VolumeServer", "VolumeEcShardsRebuildBatch")
    d = {
        "results": [], "dispatch_groups": 1, "wire_bytes": 9,
        "signature_groups": 3, "volumes_fused": 5, "block_order": [7, 9, 8],
    }
    assert c.to_dict(c.to_message(d, resp_cls)) == d
    _, status_cls = c.classes("weedtpu.Master", "RepairStatus")
    batch = {
        "target": "127.0.0.1:8080", "volumes": 4, "signature_groups": 2,
        "dispatch_groups": 1, "block_order": [5, 6, 7, 8],
        "block_missing": [2, 2, 1, 1], "wall_s": 0.25, "age_s": 3.5,
    }
    st = {"enabled": True, "batches": [batch], "fused_volumes_total": 12}
    got = c.to_dict(c.to_message(st, status_cls))
    assert got["batches"] == [batch]
    assert got["fused_volumes_total"] == 12
