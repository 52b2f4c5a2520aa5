"""Device-trace reduction: busy time as a union, idle share, per-layer
program shares and labelled idle gaps, on a small recorded trace."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import devtrace, run, spec  # noqa: E402

TRACE = json.load(open(os.path.join(HERE, "fixtures", "trace_small.json")))


def sweep_union(intervals, t0, t1):
    """Union length by a sweep over start/end events (independent of
    devtrace.merge)."""
    events = sorted([(max(s, t0), 1) for s, e in intervals if e > t0 and s < t1]
                    + [(min(e, t1), -1) for s, e in intervals
                       if e > t0 and s < t1])
    depth, last, total = 0, None, 0.0
    for t, d in events:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def window():
    return TRACE["t0"] * 1e9, TRACE["t_stop"] * 1e9


def test_merge_and_gaps():
    assert devtrace.merge([(5, 9), (0, 2), (1, 3), (9, 10), (4, 4)]) == \
        [(0, 3), (5, 10)]
    assert devtrace.gaps([(2, 3), (5, 7)], 0, 10) == [(0, 2), (3, 5), (7, 10)]
    assert devtrace.covered([(0, 5), (3, 8)], 2, 6) == 4


def test_busy_is_the_union_of_operations():
    t0, t1 = window()
    plane = TRACE["devices"][0]
    ops = [(s, e) for s, e in plane["busy"]]
    assert devtrace.busy_ns(TRACE["devices"], t0, t1) == \
        pytest.approx(sweep_union(ops, t0, t1))


def test_reduce_plane_merges_and_ranks_programs():
    runs = [("jit_f(1)", 0, 10), ("jit_g(2)", 5, 12), ("jit_f(3)", 20, 30)]
    r = devtrace.reduce_plane("/device:TPU:0", runs)
    assert r["busy"] == [(0, 12), (20, 30)]
    assert r["program_ns"][0] == ("jit_f", 20)
    assert devtrace.top_programs([r]) == [["jit_f", 20 * 1e-9], ["jit_g", 7 * 1e-9]]


@pytest.mark.parametrize("metric", ["timing_device_share",
                                    "semexec_device_share",
                                    "device_idle_share"])
def test_layer_shares_from_the_trace(metric):
    t0, t1 = window()
    reader = spec.load_metric(metric)
    obs = run.Observation([], TRACE["t0"], TRACE["t_stop"], {}, {},
                          TRACE["spans"], TRACE)
    value = reader.read(obs)
    plane = TRACE["devices"][0]
    if metric == "device_idle_share":
        want = 1 - sweep_union(plane["busy"], t0, t1) / (t1 - t0)
    else:
        import re
        rx = re.compile("|".join(reader.PROGRAMS))
        runs = [(s, e) for m, s, e in plane["modules"] if rx.search(m)]
        want = sweep_union(runs, t0, t1) / (t1 - t0)
    assert value == pytest.approx(100 * want)
    assert 0.0 <= value <= 100.0


def test_no_trace_means_no_device_metric():
    obs = run.Observation([], 0.0, 1.0, {}, {}, [], None)
    for metric in ("timing_device_share", "semexec_device_share",
                   "device_idle_share"):
        assert spec.load_metric(metric).read(obs) is None


def test_program_not_run_reads_nothing():
    t0, t1 = window()
    assert devtrace.program_share(TRACE["devices"], ["no_such_program"],
                                  t0, t1) is None


def test_idle_gaps_are_labelled_by_seat_span():
    t0, t1 = window()
    spans = [(a * 1e9, b * 1e9) for a, b in TRACE["spans"]]
    gaps = devtrace.labelled_gaps(TRACE["devices"], spans, t0, t1)
    assert gaps and len(gaps) <= 10
    assert all(label in ("in run_chunk", "between chunks")
               for label, _ in gaps)
    lengths = [g for _, g in gaps]
    assert lengths == sorted(lengths, reverse=True)
    idle = (t1 - t0) * 1e-9 - devtrace.busy_ns(TRACE["devices"], t0, t1) * 1e-9
    assert sum(lengths) <= idle + 1e-9
