"""``semexec_gathered_per_step``: the index elements the semantic engine's
steps gathered in the window over the steps the seat counted, on the
recorded trace's observation; nothing to read where the program does not
count what its steps gather, or counts no step."""
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


def observation(steps, gathered):
    """A window of ``steps`` steps that gathered ``gathered`` elements
    (None: the program counts neither)."""
    trace = json.load(open(FIXTURE))

    def counters(s, g):
        out = {}
        if steps is not None:
            out["worker_device_semexec_steps"] = s
        if gathered is not None:
            out["worker_device_semexec_gathered"] = g
        return out

    return Observation(
        rows=[], t0=trace["t0"], t1=trace["t_stop"],
        stats0=dict(counters=counters(40, 81_000_000)),
        stats1=dict(counters=counters(40 + (steps or 0),
                                      81_000_000 + (gathered or 0))),
        spans=[], trace=trace)


@pytest.mark.parametrize("steps,per_step", [(1, 2_058_704), (25, 993_743),
                                            (1000, 10_620_586)])
def test_gathered_over_counted_steps(steps, per_step):
    obs = observation(steps, steps * per_step)
    got = spec.load_metric("semexec_gathered_per_step").read(obs)
    assert got == pytest.approx(per_step)


@pytest.mark.parametrize("steps,gathered", [(25, None), (None, None),
                                            (0, 0)])
def test_silent_without_counted_gathers(steps, gathered):
    """A program that does not count what its steps gather (the counter
    is new), or a window with no step, gives nothing to read."""
    obs = observation(steps, gathered)
    assert spec.load_metric("semexec_gathered_per_step").read(obs) is None


def test_reads_untraced():
    """A program counter: the reading needs no device trace."""
    obs = observation(4, 4 * 993_743)
    obs.trace = None
    assert spec.load_metric("semexec_gathered_per_step").read(obs) \
        == pytest.approx(993_743)
