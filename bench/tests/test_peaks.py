import pytest

from bench.peaks import peaks


def test_v5e_peaks_and_unknown_device():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")
