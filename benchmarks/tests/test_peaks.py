import pytest

from harness import peaks


def test_v5e_row_and_unknown_kind_raises():
    row = peaks.peaks_for("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["int8_ops_per_s"] == 393e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
