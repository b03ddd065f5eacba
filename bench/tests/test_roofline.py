import pytest

import roofline


def test_known_kind_has_peaks():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "source", ""])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        roofline.peaks(kind)


def test_gains_min_time_is_bytes_bound():
    pk = dict(flops_per_s=100.0, hbm_bytes_per_s=10.0)
    # 2 slots x 4 partitions: 16 flops; 32 + 16 + 48 bytes
    assert roofline.gains_min_s(2, 3, 4, pk) == pytest.approx(96 / 10)
    pk = dict(flops_per_s=1.0, hbm_bytes_per_s=1e9)
    assert roofline.gains_min_s(2, 3, 4, pk) == pytest.approx(16.0)
