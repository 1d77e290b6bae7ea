"""Codec tests: numpy-vs-jax backend equality (byte-for-byte), encode/
reconstruct round trips under every loss pattern up to 4 shards, verify(),
split/join — the golden-roundtrip pattern of the reference's ec_test.go
(SURVEY.md §4)."""

import itertools

import numpy as np
import pytest

from seaweedfs_tpu.ops.rs_codec import Encoder, new_encoder


def _shards(rng, n=10, size=1024):
    return [rng.integers(0, 256, size=size).astype(np.uint8) for _ in range(n)]


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(42)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("kind", ["vandermonde", "cauchy"])
def test_encode_verify_roundtrip(rng, backend, kind):
    enc = Encoder(10, 4, matrix_kind=kind, backend=backend)
    shards = enc.encode(_shards(rng))
    assert len(shards) == 14
    assert enc.verify(shards)
    # corrupt one byte -> verify fails
    bad = [s.copy() for s in shards]
    bad[12][7] ^= 0xFF
    assert not enc.verify(bad)


def test_numpy_jax_byte_identical(rng):
    data = _shards(rng, size=4096)
    a = Encoder(10, 4, backend="numpy").encode([d.copy() for d in data])
    b = Encoder(10, 4, backend="jax").encode([d.copy() for d in data])
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_reconstruct_all_loss_patterns_up_to_4(rng, backend):
    enc = Encoder(10, 4, backend=backend, pallas_interpret=True)
    orig = enc.encode(_shards(rng, size=257))
    patterns = list(itertools.combinations(range(14), 4))
    # all 1001 4-loss patterns on numpy is slow-ish; sample deterministically
    sel = patterns[::7] if backend == "numpy" else patterns[::3]
    for lost in sel:
        shards = [None if i in lost else orig[i].copy() for i in range(14)]
        got = enc.reconstruct(shards)
        for i in range(14):
            assert np.array_equal(got[i], orig[i]), f"shard {i}, lost={lost}"


def test_reconstruct_data_only(rng):
    enc = Encoder(10, 4, backend="numpy")
    orig = enc.encode(_shards(rng, size=100))
    shards = [None if i in (0, 5, 13) else orig[i].copy() for i in range(14)]
    got = enc.reconstruct_data(shards)
    for i in range(10):
        assert np.array_equal(got[i], orig[i])
    assert got[13] is None  # parity not repaired on data-only path


def test_too_few_shards_raises(rng):
    enc = Encoder(10, 4, backend="numpy")
    orig = enc.encode(_shards(rng, size=64))
    shards = [None if i < 5 else orig[i].copy() for i in range(14)]
    with pytest.raises(ValueError, match="too few"):
        enc.reconstruct(shards)


def test_split_join(rng):
    enc = Encoder(10, 4, backend="numpy")
    blob = bytes(rng.integers(0, 256, size=1000, dtype=np.uint8))
    parts = enc.split(blob)
    assert len(parts) == 10 and all(len(p) == 100 for p in parts)
    assert enc.join(parts, len(blob)) == blob


def test_factory_auto_backend():
    enc = new_encoder()
    assert enc.backend in ("numpy", "native", "xorsched", "jax")


def test_other_geometries(rng):
    for d, p in [(4, 2), (6, 3), (17, 3)]:
        enc = Encoder(d, p, backend="numpy")
        orig = enc.encode(_shards(rng, n=d, size=50))
        lost = list(range(p))
        shards = [None if i in lost else orig[i].copy() for i in range(d + p)]
        got = enc.reconstruct(shards)
        for i in range(d + p):
            assert np.array_equal(got[i], orig[i])


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("size", [999, 4096, 5000, 70_000])
def test_bucketed_reconstruct_matches_numpy(rng, backend, size):
    """Pad-and-mask bucketing on the accelerator backends must be exact:
    odd interval sizes reconstruct byte-identically to the numpy oracle."""
    data = _shards(rng, size=size)
    gold = Encoder(10, 4, backend="numpy")
    full = gold.encode([d.copy() for d in data])
    enc = Encoder(10, 4, backend=backend, pallas_interpret=True)
    assert enc._bucket_for(size) is not None  # the path under test
    lost = [0, 5, 11]
    holed = [None if i in lost else s.copy() for i, s in enumerate(full)]
    rec = enc.reconstruct(holed)
    for i in lost:
        np.testing.assert_array_equal(rec[i], full[i], err_msg=f"shard {i}")


def test_warm_reconstruct_precompiles_buckets(rng):
    enc = Encoder(10, 4, backend="jax")
    assert enc.warm_reconstruct() == len(Encoder.RECONSTRUCT_BUCKETS)
    assert Encoder(10, 4, backend="numpy").warm_reconstruct() == 0


def test_warm_decode_matrices_covers_single_loss_patterns():
    from seaweedfs_tpu.ops import rs_codec

    enc = Encoder(10, 4, backend="numpy")
    # local shards never need reconstructing -> excluded from prewarm
    assert enc.warm_decode_matrices(local_shards=[0, 1, 2]) == 11
    info = rs_codec._reconstruction_matrix.cache_info()
    # every prebuilt pattern is a cache hit when the serving path asks
    before = info.hits
    survivors = tuple(s for s in range(14) if s != 5)[:10]
    rs_codec._reconstruction_matrix("vandermonde", 10, 4, survivors, (5,))
    assert rs_codec._reconstruction_matrix.cache_info().hits == before + 1


def test_native_backend_matches_numpy_golden():
    """The C++ AVX2 backend must be byte-identical to the numpy golden
    path across encode, batched encode, reconstruct, and verify."""
    import numpy as np
    import pytest

    from seaweedfs_tpu.ops.rs_codec import Encoder
    from seaweedfs_tpu.utils import native as native_mod

    if native_mod.load() is None:
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(21)
    gold = Encoder(10, 4, backend="numpy")
    fast = Encoder(10, 4, backend="native")
    data = [rng.integers(0, 256, 4096, dtype=np.uint8) for _ in range(10)]
    g = gold.encode(data)
    f = fast.encode(data)
    assert all(np.array_equal(a, b) for a, b in zip(g, f))
    batch = rng.integers(0, 256, (3, 10, 2048), dtype=np.uint8)
    assert np.array_equal(gold.encode_batch(batch), fast.encode_batch(batch))
    # kill 4 shards, reconstruct
    shards = list(f)
    for i in (0, 5, 11, 13):
        shards[i] = None
    rec = fast.reconstruct(shards)
    assert all(np.array_equal(rec[i], g[i]) for i in range(14))
    assert fast.verify(rec)


def test_auto_backend_on_cpu_follows_evidence_rule():
    """auto on a CPU host is the pick_cpu_backend decision: the AVX2
    library by default, promoted to the compiled XOR-schedule backend
    only under fresh committed same-host BENCH evidence in which
    xorsched beat native in the same run (the r17 CPU-floor rule —
    fabricated-evidence decision table lives in test_xorsched.py)."""
    import pytest

    from seaweedfs_tpu.ops import rs_codec
    from seaweedfs_tpu.utils import native as native_mod

    if native_mod.load() is None:
        pytest.skip("native library unavailable")
    expected, dec = rs_codec.pick_cpu_backend()
    assert expected in ("native", "xorsched")
    enc = rs_codec.new_encoder()  # conftest pins cpu
    assert enc.backend == expected
    if expected == "xorsched":
        assert enc.selection["source"] == "cpu-bench-evidence"
        assert enc.selection["evidence_file"].startswith("BENCH_r")


def test_auto_backend_on_tpu_prefers_measured_fastest(monkeypatch):
    """On TPU, auto must resolve to the XLA bit-plane path, not pallas:
    no device record is committed, so nothing can promote a fused variant
    — and the XLA path is the one chip_smoke.py proves on the chip."""
    import jax

    from seaweedfs_tpu.ops.rs_codec import new_encoder

    class _FakeTpu:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_FakeTpu()])
    assert new_encoder().backend == "jax"
