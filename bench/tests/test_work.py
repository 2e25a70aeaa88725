import pytest

from bench.work import peaks, spmv_min_bytes


def test_spmv_min_bytes_counts_values_x_and_y_once():
    # HPCG 104^3: 29,791,000 stored values, 1,124,864 rows and columns
    assert spmv_min_bytes(29_791_000, 1_124_864, 1_124_864) == 4 * (
        29_791_000 + 2 * 1_124_864)
    assert spmv_min_bytes(0, 3, 5) == 32


def test_peaks_table_has_the_v5e_with_its_source():
    v5e = peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in v5e["source"]


def test_a_device_missing_from_the_table_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        peaks("cpu")
