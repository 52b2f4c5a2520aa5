"""The readers of the program's stage spans and counters, on a hand-built
observation: counter deltas over the window, and the device's idle time
that no stage span but ``chunk`` covers."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import spec  # noqa: E402
from bench.run import Observation  # noqa: E402

S = 1e9  # ns a second

BEFORE = dict(worker_stage_chunk_n=3, worker_stage_graph_s=1.0,
              worker_stage_prepare_s=0.2, worker_stage_prepare_graph_s=0.1,
              worker_stage_semantics_s=0.3, worker_stage_semexec_wait_s=5.0,
              worker_stage_emit_s=0.1)
# over the 10 s window: graph +0.8 s, prepare + prepare_graph + semantics
# +0.5 s (semexec_wait's +4 s is not host work), emit +0.2 s
AFTER = dict(worker_stage_chunk_n=9, worker_stage_graph_s=1.8,
             worker_stage_prepare_s=0.35, worker_stage_prepare_graph_s=0.15,
             worker_stage_semantics_s=0.6, worker_stage_semexec_wait_s=9.0,
             worker_stage_emit_s=0.3)


def ring(rows):
    return dict(clock="unix_s", dropped=0,
                spans=[[i, parent, name, t0, t1, 0, "h:1"]
                       for i, (parent, name, t0, t1) in enumerate(rows)])


# Device idle in [100, 100.5], [103, 104] and [108, 110]; the seat is in
# no stage but ``chunk`` for 0.2 + 0.1 + 0.5 s of that, and in no span at
# all for 0.5 s: 1.3 s of the 10 s window.
SPANS = ring([
    (None, "chunk", 90.0, 95.0),  # warm-up, before the window
    (0, "graph", 90.5, 94.0),
    (None, "chunk", 100.2, 109.5),
    (2, "graph", 100.2, 100.5),
    (2, "prepare", 102.8, 103.6),
    (2, "semantics", 103.6, 103.9),
    (2, "timing", 104.0, 108.0),
    (6, "scan", 104.0, 108.0),
    (2, "finalize", 108.0, 109.0),
])
IDLE_PLANE = dict(device="/device:TPU:0",
                  busy=[(100.5 * S, 103 * S), (104 * S, 108 * S)],
                  program_ns=[], modules=[])
BUSY_PLANE = dict(device="/device:TPU:1", busy=[(99 * S, 111 * S)],
                  program_ns=[], modules=[])


def observation(counters=True, spans=True, planes=(IDLE_PLANE,)):
    stats1 = dict(counters=AFTER if counters else {})
    if spans:
        stats1["spans"] = SPANS
    trace = (dict(t0=100.0, t_stop=110.0, devices=list(planes))
             if planes is not None else None)
    return Observation(rows=[], t0=100.0, t1=110.0,
                       stats0=dict(counters=BEFORE if counters else {}),
                       stats1=stats1, spans=[], trace=trace)


@pytest.mark.parametrize("metric,expect", [
    ("graph_build_share", 8.0), ("semantics_host_share", 5.0),
    ("trace_emit_share", 2.0)])
def test_program_span_readers(metric, expect):
    assert spec.load_metric(metric).read(observation()) == \
        pytest.approx(expect)


@pytest.mark.parametrize("metric", ["graph_build_share",
                                    "semantics_host_share",
                                    "trace_emit_share"])
def test_program_span_readers_silent_without_stages(metric):
    """A program that records no stage spans (the parent of this reader)
    gives nothing to read, and the reader says so without raising."""
    assert spec.load_metric(metric).read(observation(counters=False)) is None


def sampled_outside_share(obs, step=1e-3):
    """The same share by sampling the window every millisecond
    (independent of the reader's interval arithmetic)."""
    stages = [(r[3], r[4]) for r in obs.stats1["spans"]["spans"]
              if r[2] != "chunk"]
    t0, t1 = obs.trace["t0"], obs.trace["t_stop"]
    n = int(round((t1 - t0) / step))
    shares = []
    for plane in obs.trace["devices"]:
        hits = 0
        for k in range(n):
            t = t0 + (k + 0.5) * step
            busy = any(s <= t * S < e for s, e in plane["busy"])
            staged = any(a <= t < b for a, b in stages)
            hits += not busy and not staged
        shares.append(hits / n)
    return 100.0 * sum(shares) / len(shares)


@pytest.mark.parametrize("planes,expect", [
    ((IDLE_PLANE,), 13.0), ((IDLE_PLANE, BUSY_PLANE), 6.5)])
def test_idle_outside_stages(planes, expect):
    obs = observation(planes=planes)
    got = spec.load_metric("idle_outside_stages_share").read(obs)
    assert got == pytest.approx(expect)
    assert got == pytest.approx(sampled_outside_share(obs), abs=0.05)


@pytest.mark.parametrize("kw", [dict(spans=False), dict(planes=None),
                                dict(planes=())])
def test_idle_outside_stages_silent_without_spans_or_trace(kw):
    reader = spec.load_metric("idle_outside_stages_share")
    assert reader.read(observation(**kw)) is None
