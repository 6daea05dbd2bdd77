"""The yardstick's arithmetic: peaks by device kind, nominal flops, the
least HBM traffic, and the roofline time they give."""

import math

import numpy as np
import pytest

from perf import roofline


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no peaks for device_kind"):
        roofline.peak("TPU v9 imaginary")


def test_v5e_peaks_are_the_published_ones():
    chip = roofline.peak("TPU v5 lite")
    assert chip["bf16_flops_per_s"] == 197e12
    assert chip["hbm_bytes_per_s"] == 819e9
    assert chip["hbm_bytes"] == 16 * 2**30


@pytest.mark.parametrize("real, factor", [(False, 5.0), (True, 2.5)])
def test_transform_flops_is_heffte_count(real, factor):
    n = 512**3
    assert roofline.transform_flops((512,) * 3, real) == factor * n * 27


def test_512_c2c_roofline_is_bytes_bound():
    shape = (512,) * 3
    flops = roofline.transform_flops(shape, False)
    hbm = roofline.min_hbm_bytes(shape, np.complex64, shape, np.complex64)
    assert hbm == 2 * 2**30  # read 1 GiB once, write 1 GiB once
    chip = roofline.peak("TPU v5 lite")
    t = roofline.min_seconds(flops, hbm, chip)
    assert t == hbm / 819e9
    # the flop term at the bf16 peak is some thirty times smaller
    assert flops / 197e12 < t / 25
    assert math.isclose(t * 1e3, 2.622, rel_tol=1e-3)
