"""The trace reduction, on a small trace recorded on a TPU v5e chip
(``data/tiny_trace.xplane.pb``: a 4-worker, 16-round cell of the harness,
traced with its ``training`` spans), and on hand-made intervals."""

import os

import pytest

import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny_trace.xplane.pb")


def test_union_merges_overlaps():
    assert tr._union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]


def test_subtract_counts_only_uncovered_time():
    a = [(0.0, 10.0)]
    b = [(1.0, 2.0), (5.0, 7.0)]
    assert tr._subtract(a, b) == pytest.approx(7.0)
    assert tr._subtract([(0.0, 1.0)], []) == pytest.approx(1.0)
    assert tr._subtract([(1.0, 2.0)], [(0.0, 3.0)]) == 0.0


def test_clip_keeps_the_window():
    assert tr._clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce_file(TRACE)


def test_recorded_trace_has_a_busy_device(recorded):
    dev = recorded.busiest()
    assert dev.name.startswith("/device:TPU:")
    assert 0.0 < dev.busy_s <= recorded.window_s
    assert sum(length for _, length in dev.gaps) == pytest.approx(recorded.window_s - dev.busy_s, rel=1e-6)


def test_recorded_trace_finds_the_kernels_and_the_chunk(recorded):
    dev = recorded.busiest()
    assert recorded.op_time(r"edge_scan", dev) > 0.0
    assert recorded.op_time(r"round_step|queue_ingest", dev) > 0.0
    assert recorded.module_time(r"chunk", dev) > 0.0


def test_recorded_breakdown_is_labelled(recorded):
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(label in ("training", "between trainings", "outside the spans") for label, _ in b["idle_gaps"])
    times = [t for _, t in b["device_ops"]]
    assert times == sorted(times, reverse=True)
