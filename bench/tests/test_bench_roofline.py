"""The peaks table and the filter kernel's byte count."""

import pytest

from bench import roofline


def test_v5e_peaks_are_the_published_ones():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_range_mask_bytes_from_the_call_shape():
    # 3 float32 columns of a 65,536-row group, a uint8 mask, 2 bounds each
    assert roofline.range_mask_bytes(3, 65536) == \
        4 * 3 * 65536 + 65536 + 8 * 3
    # rows pad to the kernel's 2,048-row block
    assert roofline.range_mask_bytes(1, 2049) == 4 * 4096 + 4096 + 8


def test_least_time_is_bytes_over_bandwidth():
    assert roofline.least_seconds(819e9, "TPU v5 lite") == pytest.approx(1.0)
