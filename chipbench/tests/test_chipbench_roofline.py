"""Work and bytes from shapes, counted by hand, and the peak table."""

import pytest

from chipbench import roofline


def test_pareto_mask_work():
    # B = 2 rows of G = 8: 2 * (8 * 3 + 8) ops; 2*8*(4+1+1) + 8*4 bytes
    assert roofline.pareto_mask_work(2, 8) == (64.0, 128.0)


def test_plan_argmin_work():
    # 4 * 2 * 8 ops; 2*8*(4+1) + 8*4 + 2*4 + 2*4 bytes
    assert roofline.plan_argmin_work(2, 8) == (64.0, 128.0)


def test_rbf_gram_work():
    # batch 3 of (n=4, d=2) x (m=5, d=2): 3*4*5*(3*2+2) ops;
    # 3 * ((4+5)*2 + 4*5) * 4 bytes
    assert roofline.rbf_gram_work(3, 4, 5, 2) == (480.0, 456.0)


def test_least_time_names_its_bound():
    t, bound = roofline.least_time(1.0, 819e9, "TPU v5 lite")
    assert bound == "bytes" and t == pytest.approx(1.0)
    t, bound = roofline.least_time(197e12, 1.0, "TPU v5 lite")
    assert bound == "operations" and t == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("TPU v99")


def test_share_of_known_calls():
    # one B = 256, G = 352 frontier: bytes-bound; 10 us of device time
    ops, nbytes = roofline.pareto_mask_work(256, 352)
    share = roofline.roofline_share([(256, 352)], "pareto_mask", 10e-6, "TPU v5 lite")
    assert share == pytest.approx(100 * nbytes / 819e9 / 10e-6)
    assert 0 < share < 100


def test_nothing_to_read_gives_nothing():
    assert roofline.roofline_share([], "rbf_gram", 1.0, "TPU v5 lite") is None
    assert roofline.roofline_share([(1, 8, 8, 2)], "rbf_gram", 0.0, "TPU v5 lite") is None
