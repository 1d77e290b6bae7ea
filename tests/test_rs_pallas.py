"""Fused Pallas kernel tests (interpret mode, asked for by name, on the CPU
mesh): byte equality with the XLA path and the host golden path across
shapes, padding edges, and the Encoder(backend="pallas") integration."""

import numpy as np
import pytest

import jax.numpy as jnp

from seaweedfs_tpu.ops import gf8, rs_jax, rs_pallas
from seaweedfs_tpu.ops.rs_codec import Encoder


@pytest.fixture(scope="module")
def parity_bits():
    return rs_jax.lifted_matrix(gf8.parity_matrix(10, 4))


@pytest.mark.parametrize("mxu", rs_pallas.VARIANTS)
@pytest.mark.parametrize(
    "shape",
    [
        (10, 128),
        (10, 100),  # sub-tile, needs padding
        (10, 8192),  # exactly one default tile
        (2, 10, 8321),  # batched, ragged
        (1, 10, 3 * 8192),
    ],
)
def test_fused_matches_xla(parity_bits, shape, mxu):
    """EVERY staged kernel variant (int8/bf16/u8/mplane/dma) must be
    byte-exact vs the XLA path across tile-edge and odd-size shapes."""
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=shape, dtype=np.uint8)
    got = np.asarray(rs_pallas.gf_apply_fused(
        parity_bits, jnp.asarray(data), mxu=mxu, interpret=True))
    want = np.asarray(rs_jax.gf_apply(parity_bits, jnp.asarray(data)))
    assert np.array_equal(got, want)


def test_every_variant_in_lowering_proof_shapes():
    """Each staged variant must be in test_tpu_compile.FUSED_SHAPES — a
    variant outside that table would meet the v5e's compiler for the first
    time on the chip."""
    import test_tpu_compile

    proven = {s.get("mxu", "int8") for s in test_tpu_compile.FUSED_SHAPES}
    assert proven >= set(rs_pallas.VARIANTS), (
        f"variants missing from FUSED_SHAPES: {set(rs_pallas.VARIANTS) - proven}"
    )


@pytest.mark.parametrize("mxu", rs_pallas.VARIANTS)
def test_variant_reconstruction_matrix(parity_bits, mxu):
    """Every variant must also serve arbitrary decode matrices (the
    rebuild path) — not just the 4x10 parity shape."""
    from seaweedfs_tpu.ops.rs_codec import _reconstruction_matrix

    lost = (1, 6, 12, 13)
    surv = tuple(i for i in range(14) if i not in lost)
    recon = _reconstruction_matrix("vandermonde", 10, 4, surv, lost)
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, size=(10, 500), dtype=np.uint8)
    enc = Encoder(10, 4, backend="numpy")
    shards = np.stack(enc.encode(list(data)))
    got = np.asarray(rs_pallas.apply_matrix(
        recon, shards[list(surv)], mxu=mxu, interpret=True))
    assert np.array_equal(got, shards[list(lost)])


def test_dma_chunk_divides_every_tile():
    for t in rs_pallas._TILE_STEPS:
        assert t % rs_pallas._dma_chunk(t) == 0
    assert rs_pallas._dma_chunk(8448) == 256  # non-2048-multiple width


def test_fused_reconstruction_matrix(parity_bits):
    """The kernel must work for arbitrary (R, C) matrices, not just 4x10."""
    from seaweedfs_tpu.ops.rs_codec import _reconstruction_matrix

    lost = (1, 6, 12, 13)
    surv = tuple(i for i in range(14) if i not in lost)
    recon = _reconstruction_matrix("vandermonde", 10, 4, surv, lost)
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, size=(10, 500), dtype=np.uint8)
    enc = Encoder(10, 4, backend="numpy")
    shards = np.stack(enc.encode(list(data)))
    got = np.asarray(rs_pallas.apply_matrix(recon, shards[list(surv)], interpret=True))
    assert np.array_equal(got, shards[list(lost)])


def test_encoder_pallas_backend_roundtrip():
    rng = np.random.default_rng(9)
    enc = Encoder(10, 4, backend="pallas", pallas_interpret=True)
    gold = Encoder(10, 4, backend="numpy")
    data = [rng.integers(0, 256, size=1000, dtype=np.uint8) for _ in range(10)]
    a = enc.encode([d.copy() for d in data])
    b = gold.encode([d.copy() for d in data])
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    lost = [0, 5, 11, 13]
    holes = [None if i in lost else a[i].copy() for i in range(14)]
    rec = enc.reconstruct(holes)
    for i in range(14):
        assert np.array_equal(rec[i], a[i])


def test_zero_length(parity_bits):
    data = np.zeros((10, 0), dtype=np.uint8)
    out = np.asarray(rs_pallas.gf_apply_fused(parity_bits, jnp.asarray(data), interpret=True))
    assert out.shape == (4, 0)
