import numpy as np
import pytest

from harness import peaks
from reference import gf8_ref


def test_parity_matrix_is_the_programs():
    from seaweedfs_tpu.ops import gf8

    assert np.array_equal(gf8_ref.parity_matrix(10, 4), gf8.parity_matrix(10, 4))
    assert np.array_equal(gf8_ref.GF_MUL_TABLE, gf8.GF_MUL_TABLE)


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 7])
def test_mat_vec_agrees_with_the_programs_golden_on_seeded_rows(seed):
    from seaweedfs_tpu.ops import gf8

    x = np.random.default_rng(seed).integers(0, 256, (10, 8192), dtype=np.uint8)
    want = gf8.gf_mat_vec(gf8.parity_matrix(10, 4), x)
    assert np.array_equal(gf8_ref.gf_mat_vec(gf8_ref.parity_matrix(), x), want)


def test_generator_is_systematic_and_any_ten_rows_invert():
    g = gf8_ref.generator_matrix(10, 14)
    assert np.array_equal(g[:10], np.eye(10, dtype=np.uint8))
    survivors = [r for r in range(14) if r not in (0, 3, 11, 13)]
    inv = gf8_ref.gf_mat_inv(g[survivors])
    assert np.array_equal(gf8_ref.gf_mat_mul(inv, g[survivors]), np.eye(10, dtype=np.uint8))


def test_bytes_from_shapes():
    assert peaks.rs_apply_bytes(10, 4, 6553600) == 14 * 6553600
    least = peaks.rs_apply_min_seconds("TPU v5 lite", 10, 4, 6553600)
    assert least == pytest.approx(14 * 6553600 / 819e9)
    # the int8 bit-plane form's operations need less time than the bytes: HBM bounds
    assert 2 * 80 * 32 * 6553600 / peaks.peak("TPU v5 lite", "int8_ops_per_s") < least


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary", "hbm_bytes_per_s")
