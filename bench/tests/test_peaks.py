"""The table of peaks knows the v5e and refuses any other device."""
import pytest

from bench.peaks import peaks


def test_v5e():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks("cpu")
