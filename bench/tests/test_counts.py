"""The peaks table and the work counts."""

import pytest

import counts


def test_known_device_kind_has_its_peaks():
    p = counts.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v9 imaginary")


def test_round_work_counts_bins_at_one_byte():
    nbytes, ops = counts.round_work(16, 2048, 64)
    assert nbytes == 16 * 2048 * 72
    assert ops == 16 * 2048 * 68


def test_least_time_names_the_bound():
    peak = counts.peaks("TPU v5 lite")
    t, bound = counts.least_time(819e9, 1.0, peak)
    assert (t, bound) == (1.0, "memory")
    t, bound = counts.least_time(1.0, 197e12, peak)
    assert (t, bound) == (1.0, "compute")
