"""The benchmark's yardstick: peaks, the reference algorithm's squarings
and the operation and byte counts of a squaring."""

import math
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))

from mfbench import roofline  # noqa: E402


def test_squaring_counts_by_hand():
    # 2 x 2: four dot products of length 2, each 2 multiplies + 2 adds.
    assert roofline.squaring_flops(2) == 16
    # n = 2016: 2 * 2016^3 operations; read A twice and write A^2, f32.
    assert roofline.squaring_flops(2016) == 2 * 2016 ** 3
    assert roofline.squaring_bytes(2016) == 3 * 2016 * 2016 * 4
    assert roofline.squaring_bytes(10, itemsize=2) == 600


def test_expm_squarings_follow_higham():
    theta = roofline.THETA13
    assert roofline.expm_squarings(0.0) == 0
    assert roofline.expm_squarings(theta) == 0
    assert roofline.expm_squarings(theta * 1.0001) == 1
    assert roofline.expm_squarings(261.8 * 0.1) == 3     # tandem, t = 0.1
    assert roofline.expm_squarings(261.8 * 10.0) == 9    # tandem, t = 10


def test_roofline_share_and_bound():
    p = roofline.peaks("TPU v5 lite")
    n = 2016
    t_flops = 2 * n ** 3 / p["flops_per_s"]
    share, bound = roofline.roofline_share(1, n, 2 * t_flops, "TPU v5 lite")
    assert bound == "compute"            # n / 6 flop/B above the ridge
    assert share == pytest.approx(50.0)
    share, bound = roofline.roofline_share(10, 64, 1.0, "TPU v5 lite")
    assert bound == "memory"
    assert share == pytest.approx(100 * 10 * 3 * 64 * 64 * 4
                                  / p["hbm_bytes_per_s"])
    assert roofline.roofline_share(5, n, 0.0, "TPU v5 lite") is None
    assert math.isclose(p["flops_per_s"], 197e12)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
