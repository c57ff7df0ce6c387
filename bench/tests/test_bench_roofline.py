"""Peaks table and roofline floor."""
import pytest

from bench import roofline


def test_v5e_peaks_and_source():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="cpu"):
        roofline.peaks("cpu")


def test_floor_takes_the_larger_bound():
    p = roofline.peaks("TPU v5 lite")
    t, bound = roofline.floor_seconds(197e12, 1.0, p)
    assert bound == "flops" and t == pytest.approx(1.0)
    t, bound = roofline.floor_seconds(1.0, 819e9 * 2, p)
    assert bound == "bytes" and t == pytest.approx(2.0)

