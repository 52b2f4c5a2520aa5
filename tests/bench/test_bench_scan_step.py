"""``scan_step_ns``: the device time of the DRAM-timing engine's batched
programs in the traced window over the scan steps the seat counted, on the
recorded trace; nothing to read where the program counts no steps."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import spec  # noqa: E402
from bench.run import Observation  # noqa: E402

FIXTURE = os.path.join(ROOT, "tests", "bench", "fixtures", "trace_small.json")


def observation(steps):
    trace = json.load(open(FIXTURE))
    counters = (lambda n: dict(worker_device_scan_steps=n)) \
        if steps is not None else (lambda n: {})
    return Observation(rows=[], t0=trace["t0"], t1=trace["t_stop"],
                       stats0=dict(counters=counters(4096)),
                       stats1=dict(counters=counters(4096 + (steps or 0))),
                       spans=[], trace=trace), trace


def scan_ns(trace):
    """Device time of the scan programs in the window, summed execution by
    execution (the fixture's executions do not overlap)."""
    t0, t1 = trace["t0"] * 1e9, trace["t_stop"] * 1e9
    total = 0.0
    for plane in trace["devices"]:
        for name, s, e in plane["modules"]:
            if "_scan_engine_batch" in name:
                total += max(0.0, min(e, t1) - max(s, t0))
    return total / len(trace["devices"])


@pytest.mark.parametrize("steps", [256, 65536, 1 << 22])
def test_step_time_over_counted_steps(steps):
    obs, trace = observation(steps)
    got = spec.load_metric("scan_step_ns").read(obs)
    want = scan_ns(trace) / steps
    assert want > 0
    assert got == pytest.approx(want)


@pytest.mark.parametrize("steps", [None, 0])
def test_silent_without_counted_steps(steps):
    """A program that does not count its steps (the parent of the counter),
    or a window with none, gives nothing to read."""
    obs, _ = observation(steps)
    assert spec.load_metric("scan_step_ns").read(obs) is None


def test_silent_untraced():
    obs, _ = observation(65536)
    obs.trace = None
    assert spec.load_metric("scan_step_ns").read(obs) is None
