"""The exact crossing (`rs_jax.apply_matrix`): a C-contiguous `(C, N)` uint8
slot whose result is one row goes to the device as its `(C * k, N / k)` view and
the result comes back as `(k, N / k)`, the handle the callers sync on giving
`(1, N)`. The rule
engages on the CPU backend too, so these run it: for every caller's shape
class the bytes are today's and `ops/gf8`'s; what the rule refuses crosses as
it did and says so (`weedtpu_codec_crossings_total{form}`, `form=` on the
dispatch spans)."""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from seaweedfs_tpu import stats
from seaweedfs_tpu.ec import stripe
from seaweedfs_tpu.obs import trace
from seaweedfs_tpu.ops import gf8, rs_jax
from seaweedfs_tpu.ops.rs_codec import Encoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENC = Encoder(10, 4, backend="jax")
ENC_12_3 = Encoder(12, 3, matrix_kind="cauchy", backend="jax")


def _forms():
    return {f: stats.CodecCrossings.labels(f).value for f in ("exact", "as_is")}


def _crossed(before):
    return {f: v - before[f] for f, v in _forms().items() if v != before[f]}


def _bytes(shape, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=shape, dtype=np.uint8)


def _as_today(m, shards):
    """The same apply with the shards on the device already, which cross (and
    are computed on) as `(C, N)`: what every apply did before."""
    before = _forms()
    out = np.asarray(rs_jax.apply_matrix(m, jnp.asarray(shards)))
    assert _crossed(before) == {"as_is": 1}
    return out


def _decode(lost):
    survivors = [s for s in range(14) if s not in lost][:10]
    return ENC.reconstruction_matrix(survivors, lost), survivors


def test_chunks_make_whole_tiles_of_any_row_count():
    assert [rs_jax.crossing_chunks(c) for c in (10, 12, 6, 3, 2, 32, 64, 1)] == [16, 8, 16, 32, 16, 1, 1, 32]
    for c in range(1, 40):
        assert (c * rs_jax.crossing_chunks(c)) % 32 == 0


# name -> (the call through the codec, its matrix, its (C, N) input, the form it crosses in)
def _shape_classes():
    m4, s4 = _decode([0, 3, 11, 13])
    m3, s3 = _decode([1, 5, 12])
    m1, s1 = _decode([7])
    ones = np.ones((1, 3), dtype=np.uint8)
    s12 = [s for s in range(15) if s != 5][:12]
    m12 = (ENC_12_3.reconstruction_matrix(s12, [5]), s12)
    cases = {
        # results of two rows or more: programs with temporaries, which the rule leaves as they were
        "encode_10p4": (lambda x: ENC.encode_parity_lazy(x, donate=True), ENC.parity_matrix, (10, 3 * 2048), "as_is"),
        "reconstruct_4from10": (lambda x: ENC.reconstruct_lazy(x, s4, [0, 3, 11, 13], donate=True), m4, (10, 4096), "as_is"),
        "encode_12p3": (lambda x: ENC_12_3.encode_parity_lazy(x, donate=True), ENC_12_3.parity_matrix, (12, 5 * 1024), "as_is"),
        "reconstruct_3from10": (lambda x: ENC.reconstruct_lazy(x, s3, [1, 5, 12], donate=True), m3, (10, 6144), "as_is"),
        "reconstruct_2from10": (lambda x: ENC.reconstruct_lazy(x, _decode([2, 13])[1], [2, 13], donate=True),
                                _decode([2, 13])[0], (10, 4096), "as_is"),
        "projection_2x2": (lambda x: ENC.project(m4[:2, :2], x), m4[:2, :2], (2, 2048), "as_is"),
        # one row out: the result the device pads most, from a program with no temporaries
        "reconstruct_1from10": (lambda x: ENC.reconstruct_lazy(x, s1, [7], donate=True), m1, (10, 2048), "exact"),
        # twelve rows are cut into 8 chunks: 1024 columns a grid step, not 2048
        "reconstruct_1from12": (lambda x: ENC_12_3.reconstruct_lazy(x, m12[1], [5], donate=True), m12[0], (12, 5 * 1024), "exact"),
        # three holder groups' projections XORed: 32 chunks a row, 4096 columns a step
        "projection_combine": (lambda x: ENC.project_lazy(ones, x, donate=True), ones, (3, 2 * 4096), "exact"),
    }
    for b in Encoder.RECONSTRUCT_BUCKETS:  # the served path's small reads, padded to a bucket
        cases[f"small_read_bucket_{b}"] = (lambda x: ENC._apply_bucketed(m1, x), m1, (10, b), "exact")
    return cases


@pytest.mark.parametrize("name", list(_shape_classes()))
def test_every_callers_shape_class_crosses_as_the_rule_says_with_todays_bytes(name):
    call, m, shape, form = _shape_classes()[name]
    x = _bytes(shape, seed=len(name))
    before = _forms()
    got = np.asarray(call(x))
    assert _crossed(before) == {form: 1}
    assert got.shape == (m.shape[0], shape[1])
    assert (got == gf8.gf_mat_vec(m, x)).all()
    assert (got == _as_today(m, x)).all()


@pytest.mark.parametrize("rows", [1, 2, 3, 4])
def test_the_exact_programs_give_todays_bytes_for_any_rows_out(rows):
    """The programs themselves, handed the `(C * k, N / k)` view (the probe
    does, for the four-row forms the rule leaves): flat and tiled, the bytes
    of the `(C, N)` form; rows that are neither C nor C * k are an error."""
    m, _ = _decode([0, 3, 11, 13][:rows])
    x = _bytes((10, 32 * 256), seed=rows)
    view = jnp.asarray(x.reshape(160, -1))
    flat = rs_jax.gf_apply(rs_jax.lifted_matrix(m), view)
    assert flat.shape == (rows * 16, x.shape[1] // 16)
    flat = np.asarray(flat).reshape(rows, -1)
    assert (flat == gf8.gf_mat_vec(m, x)).all()
    stack = jnp.asarray(np.stack([rs_jax._lifted_host(rs_jax._matrix_key(m))] * 32))
    tiled = np.asarray(rs_jax.gf_apply_tiled(stack, view)).reshape(rows, -1)
    assert (tiled == flat).all()
    for wrong in (x.reshape(20, -1), x.reshape(320, -1), x[:8]):
        with pytest.raises(ValueError, match="neither"):
            rs_jax.gf_apply(rs_jax.lifted_matrix(m), jnp.asarray(wrong))
        with pytest.raises(ValueError, match="neither"):
            rs_jax.gf_apply_tiled(stack, jnp.asarray(wrong))


@pytest.mark.parametrize("seam", [2, 3, 5, 8], ids=lambda t: f"seam_at_tile_{t}")
@pytest.mark.parametrize("rows", [1, 3])
def test_a_stack_of_matrices_crosses_as_the_rule_says_wherever_its_seam_lies(rows, seam):
    """32 tiles of 256 columns in 16 chunks of two tiles: a seam at an even
    tile is a chunk's edge, at an odd one it lies inside a chunk. One row out
    crosses exact, three as they did."""
    tiles, w = 32, 256
    lost_a, lost_b = ([0, 3, 11], [1, 2, 12]) if rows == 3 else ([5], [9])
    (ma, _), (mb, _) = _decode(lost_a), _decode(lost_b)
    stack = np.stack([ma] * seam + [mb] * (tiles - seam))
    x = _bytes((10, tiles * w), seed=seam)
    before = _forms()
    out = rs_jax.apply_matrix(stack, x)
    if rows == 1:
        assert _crossed(before) == {"exact": 1} and out.shape == (16, tiles * w // 16)
    else:
        assert _crossed(before) == {"as_is": 1} and out.shape == (rows, tiles * w)
    got = np.asarray(out).reshape(rows, -1)
    want = np.concatenate(
        [gf8.gf_mat_vec(ma, x[:, : seam * w]), gf8.gf_mat_vec(mb, x[:, seam * w:])], axis=1)
    assert (got == want).all()
    assert (got == _as_today(stack, x)).all()


@pytest.mark.parametrize("seam_tile", [4, 7], ids=["seam_on_a_chunk", "seam_inside_a_chunk"])
def test_a_packed_batch_of_the_rebuild_slot_class_crosses_exact(seam_tile):
    """`reconstruct_block` at a width whose 65,536-column tiles nest in the
    chunks (32 tiles, 16 chunks of two): one program, exact, the pipelines'
    handle gives each block its own decode."""
    tile = Encoder.BLOCK_TILE
    width = 32 * tile
    x = _bytes((10, width), seed=seam_tile)
    blocks = []
    for lost, c0, w in (([0], 0, seam_tile * tile - 17), ([13], seam_tile * tile, width - seam_tile * tile)):
        blocks.append({"survivors": _decode(lost)[1], "wanted": lost, "col_start": c0, "width": w})
    before = _forms()
    got = np.asarray(ENC.reconstruct_block(x, blocks))
    assert _crossed(before) == {"exact": 1} and got.shape == (1, width)
    for b in blocks:
        cols = slice(b["col_start"], b["col_start"] + b["width"])
        assert (got[:, cols] == gf8.gf_mat_vec(_decode(b["wanted"])[0], x[:, cols])).all()


def _fallbacks():
    wide = _bytes((10, 4096 + 2048), seed=3)
    return {
        # a tail batch: the first columns of a wider slot, each row a stride apart
        "strided_tail": (_decode([7])[0], wide[:, :4096]),
        "odd_width": (_decode([7])[0], _bytes((10, 2048 + 128), seed=4)),
        "width_off_the_12_row_grid": (ENC_12_3.parity_matrix, _bytes((12, 1024 + 512), seed=5)),
        # delta parity: one generator column applied to one row of changes
        "one_row_in": (ENC.parity_matrix[:, 3:4], _bytes((1, 4096), seed=6)),
        "batch_axis": (_decode([7])[0], _bytes((2, 10, 2048), seed=7)),
        # two rows out or more: the encodes' and the wider decodes' full slots
        "four_rows_out": (ENC.parity_matrix, _bytes((10, 4096), seed=10)),
        "three_rows_out_of_twelve": (ENC_12_3.parity_matrix, _bytes((12, 4096), seed=12)),
        "two_rows_out": (_decode([2, 13])[0], _bytes((10, 4096), seed=13)),
        "stack_of_four_rows_out": (np.stack([ENC.parity_matrix] * 16), _bytes((10, 16 * 256), seed=11)),
        "stack_whose_tiles_do_not_nest": (np.stack([_decode([7])[0]] * 3), _bytes((10, 3 * 2048), seed=8)),
    }


@pytest.mark.parametrize("name", list(_fallbacks()))
def test_what_the_rule_refuses_crosses_as_it_is(name):
    m, x = _fallbacks()[name]
    before = _forms()
    with trace.start("test.apply") as root:
        out = rs_jax.apply_matrix(m, x, donate=True)
    assert _crossed(before) == {"as_is": 1} and root.attrs["form"] == "as_is"
    assert out.shape == x.shape[:-2] + (m.shape[-2], x.shape[-1])
    got = np.asarray(out)
    if m.ndim == 3:
        want = np.concatenate([gf8.gf_mat_vec(t, c) for t, c in zip(m, np.split(x, len(m), axis=1))], axis=1)
    elif x.ndim == 3:
        want = np.stack([gf8.gf_mat_vec(m, b) for b in x])
    else:
        want = gf8.gf_mat_vec(m, x)
    assert (got == want).all()


def test_the_door_hands_back_a_device_array_in_the_shape_it_crossed_in():
    """What `benchmark/harness/chip_server.py`'s `broken_apply` wraps by name:
    a jax array it can alter; the codec's handle un-views whatever comes."""
    x = _bytes((10, 4096), seed=9)
    m, survivors = _decode([7])
    out = rs_jax.apply_matrix(m, x)
    assert out.shape == (16, 256) and hasattr(out, "at")
    sound = np.asarray(ENC.reconstruct_lazy(x, survivors, [7]))
    real = rs_jax.apply_matrix
    try:
        rs_jax.apply_matrix = lambda m, s, donate=False: (lambda o: o.at[..., 0].set(o[..., 0] ^ 1))(real(m, s, donate))
        broken = np.asarray(ENC.reconstruct_lazy(x, survivors, [7]))
    finally:
        rs_jax.apply_matrix = real
    assert broken.shape == sound.shape == (1, 4096)
    assert ((broken != sound).nonzero()[1] == np.arange(16) * 256).all()  # each chunk's first byte


def _dispatch_forms(kind):
    (t,) = trace.RING.snapshot(kind=f"{kind}.run")
    return sorted(s["attrs"].get("form") for s in trace.iter_spans(t) if s["name"] == f"{kind}.dispatch")


@pytest.mark.parametrize("geometry", ["10p4", "12p3"])
def test_a_bulk_runs_steady_batches_read_exact_and_its_tail_as_is(tmp_path, geometry):
    """write_ec_files and rebuild_ec_files on the jax backend: a full batch
    of a decode of ONE lost shard crosses exact, the narrower tail batch (a
    strided range of its slot) as it is, and so does every batch of an
    encode (three or four rows out) and of a decode of three lost shards;
    the span attribute and the counter agree; bytes as the numpy codec's."""
    enc, lost = (ENC, [3]) if geometry == "10p4" else (ENC_12_3, [5])
    k, total = enc.data_shards, enc.total_shards
    oracle_enc = Encoder(k, enc.parity_shards, matrix_kind=enc.matrix_kind, backend="numpy")
    size = 6 * k * 2048 + 777  # seven small rows, two a batch: three full batches and a tail of one
    base = os.path.join(str(tmp_path), "v")
    with open(base + ".dat", "wb") as f:
        f.write(_bytes(size, seed=11).tobytes())
    rows = dict(large_block_size=1 << 20, small_block_size=2048, buffer_size=2048)
    steady = ["as_is", "exact", "exact", "exact"]
    trace.RING.clear()
    before = _forms()
    stripe.write_ec_files(base, encoder=enc, max_batch_bytes=k * 2 * 2048, **rows)
    assert _dispatch_forms("encode") == ["as_is"] * 4
    assert _crossed(before) == {"as_is": 4}
    golden = [open(stripe.shard_file_name(base, s), "rb").read() for s in range(total)]
    oracle = os.path.join(str(tmp_path), "o")
    os.link(base + ".dat", oracle + ".dat")
    stripe.write_ec_files(oracle, encoder=oracle_enc, **rows)
    assert golden == [open(stripe.shard_file_name(oracle, s), "rb").read() for s in range(total)]

    for s in lost:
        os.unlink(stripe.shard_file_name(base, s))
    trace.RING.clear()
    before = _forms()
    assert stripe.rebuild_ec_files(base, encoder=enc, buffer_size=2048, max_batch_bytes=k * 2 * 2048) == lost
    assert _dispatch_forms("rebuild") == steady
    assert _crossed(before) == {"exact": 3, "as_is": 1}
    assert golden == [open(stripe.shard_file_name(base, s), "rb").read() for s in range(total)]

    for s in (0, 2, total - 1):
        os.unlink(stripe.shard_file_name(base, s))
    trace.RING.clear()
    before = _forms()
    assert stripe.rebuild_ec_files(base, encoder=enc, buffer_size=2048, max_batch_bytes=k * 2 * 2048) == [0, 2, total - 1]
    assert _dispatch_forms("rebuild") == ["as_is"] * 4
    assert _crossed(before) == {"as_is": 4}
    assert golden == [open(stripe.shard_file_name(base, s), "rb").read() for s in range(total)]
    text = stats.REGISTRY.expose()
    assert 'weedtpu_codec_crossings_total{form="exact"}' in text
    assert 'weedtpu_codec_crossings_total{form="as_is"}' in text


def test_the_probe_runs_to_its_end_with_exact_bytes(tmp_path):
    """`scripts/crossing_probe.py` at tiny sizes on the CPU backend: every
    line is there, the programs' bytes are `ops/gf8`'s, and no device time is
    claimed (the CPU's trace has no device plane)."""
    import json

    out = tmp_path / "probe.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "crossing_probe.py"), "--widths", "1048576",
         "--buckets", "4096", "--repeats", "2", "--warmup", "1", "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    report = json.loads(out.read_text())
    assert report["byte_exact"] is True and report["device"]["platform"] == "cpu"
    (slot,), (bucket,) = report["slots"], report["buckets"]
    for key in ("line1_as_staged", "line2_exact", "line3_flat", "line4_rows_from_threads", "line5_down_1xN",
                "line5_down_kxN/k", "line5_down_4xN", "line6_up_and_down_at_once", "line7_flat_4row_exact",
                "line7_flat_1row_as_staged", "line7_tiled_1row_exact", "line8_flat_1row_exact"):
        assert key in slot, key
    assert slot["line2_exact"]["shape"] == [160, 65536]
    assert all(v["byte_exact"] and v["device_ms"] is None for k, v in slot.items() if k.startswith("line7"))
    assert bucket["line7_flat_1row_exact"]["in"] == [160, 256]
    helped = subprocess.run([sys.executable, os.path.join(ROOT, "scripts", "crossing_probe.py"), "--help"],
                            env=env, capture_output=True, text=True, timeout=120)
    assert helped.returncode == 0 and all(f"line {i}" in helped.stdout for i in range(1, 9))
