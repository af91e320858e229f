import pytest

from lib.peaks import peaks


def test_v5e_peaks_with_source():
    p = peaks("TPU v5 lite")
    assert (p.flops, p.hbm_bytes_per_s, p.hbm_bytes) == (197e12, 819e9, 16e9)
    assert "TPU v5e" in p.source


def test_unknown_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
