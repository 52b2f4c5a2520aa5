"""Window arithmetic: rates over the whole window, counters as changes over
the window."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import window  # noqa: E402


def row(t_arrive, hits=0, misses=0, conflicts=0, status="ok"):
    return window.Row(t_arrive, status,
                      dict(row_hits=hits, row_misses=misses,
                           row_conflicts=conflicts))


def test_rate_counts_the_in_flight_tail():
    rows = [row(104.0, hits=1_000_000),
            row(110.0, misses=500_000, conflicts=500_000)]
    t0, t1 = window.window_bounds(rows, 100.0)
    assert (t0, t1) == (100.0, 110.0)  # closes at the last arrival
    assert window.rate(rows, t0, t1) == pytest.approx(2.0 / 10.0)


def test_rate_leaves_out_failed_rows():
    rows = [row(1.0, hits=10**6), row(2.0, hits=10**6,
                                             status="error")]
    assert window.rate(rows, 0.0, 2.0) == pytest.approx(0.5)


def test_counter_delta_ignores_pre_window_samples():
    before = dict(counters=dict(worker_device_compiles=40))
    after = dict(counters=dict(worker_device_compiles=41))
    assert window.counter_delta(before, after,
                                "worker_device_compiles") == 1
    assert window.counter_delta(before, after, "absent") == 0


def test_span_share_is_the_union_inside_the_window():
    spans = [(0.0, 3.0), (2.0, 5.0), (8.0, 20.0)]
    assert window.span_share(spans, 1.0, 11.0) == pytest.approx(0.7)
