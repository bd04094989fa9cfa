import pytest

from bench import roofline


def test_probe_work_counts_rows_bytes_and_dot_flops():
    flops, nbytes = roofline.probe_work(1000, 128)
    assert flops == 2 * 128 * 1000
    assert nbytes == 1000 * (128 * 4 + 4 + 4)


def test_probe_is_memory_bound_on_v5e():
    flops, nbytes = roofline.probe_work(10 ** 6, 960)
    t = roofline.least_seconds(flops, nbytes, "TPU v5 lite")
    assert t == pytest.approx(nbytes / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("TPU v9 imaginary")
