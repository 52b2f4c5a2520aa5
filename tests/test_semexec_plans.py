"""Reduce plans of the device semantic engine (repro.core.semexec).

- A plan reduces exactly as numpy's ``ufunc.at`` does, on random segment
  arrays with empty segments, a segment wider than 2**13 and every class
  boundary, for min, sum and max, over inputs stored in table order and
  over a vector the plan gathers from.
- Its blocks pad little: the width classes hold the Kronecker graph's
  destinations in about 1.1 slots an edge.
- HitGraph's masks, folded into its one value gather, are exact: with
  update filtering, partition skipping and update combining each on and
  off, one partition and several, the device engine equals the numpy
  engine in values, iterations, update counts, written sets and traces,
  on BFS, WCC (labels include 0) and SSSP with zero-weight edges.
"""
from __future__ import annotations

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.graphsim import default_config
from repro.core import semexec
from repro.core.accelerators import ACCELERATORS
from repro.core.trace import trace_stream_hash
from repro.graph.generators import GraphSpec
from repro.graph.problems import PROBLEMS

KINDS = ["min", "sum", "max"]
IDENTITY = {"min": np.inf, "sum": 0, "max": 0}


def _sizes(case: str, rng) -> np.ndarray:
    """Segment sizes (0: an empty segment) of each case."""
    if case == "empty":
        return rng.integers(0, 4, 400)
    if case == "wide":  # one segment past 2**13 among small ones
        return np.concatenate([[9000], rng.integers(0, 40, 300)])
    if case == "boundaries":  # every size up to 300: both sides of a width
        return np.concatenate([np.arange(0, 301), rng.integers(1, 20, 200)])
    if case == "rounded":  # more than 512 distinct sizes
        return np.concatenate([np.arange(1, 700), [0, 0]])
    raise ValueError(case)


def _segments(case: str, seed: int = 0):
    rng = np.random.default_rng(seed)
    sizes = _sizes(case, rng)
    rng.shuffle(sizes)
    seg = np.repeat(np.arange(len(sizes)), sizes)
    rng.shuffle(seg)
    return seg, len(sizes), sizes


def _inputs(kind: str, m: int, seed: int = 1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "sum":  # integers: every association order is exact
        return rng.integers(0, 1000, m).astype(np.int32)
    return (rng.random(m) * 100).astype(np.float32)


def _reference(seg, x, num_segments: int, kind: str) -> np.ndarray:
    out = np.full(num_segments, IDENTITY[kind], dtype=x.dtype)
    {"min": np.minimum, "sum": np.add, "max": np.maximum}[kind].at(
        out, seg, x)
    return out


@pytest.mark.parametrize("over_vector", [False, True],
                         ids=["table-order", "over-vector"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("case", ["empty", "wide", "boundaries", "rounded"])
def test_plan_reduces_like_ufunc_at(case, kind, over_vector):
    seg, n, sizes = _segments(case)
    x = _inputs(kind, len(seg))
    plan, slots = semexec.build_reduce_plan(seg, n, over_vector=over_vector)
    assert 0 < len(plan.axes) <= semexec._MAX_CLASSES
    widths = sorted(int(s.shape[ax]) for s, ax in zip(slots, plan.axes))
    live = sorted(set(sizes[sizes > 0]))
    assert widths[-1] >= max(live)  # the widest segment has a class
    if case == "boundaries":  # both sides of every width are exercised
        assert set(w + 1 for w in widths[:-1]) <= set(live)
    if over_vector:
        arg = jnp.asarray(x)
    else:
        arg = semexec.table_order(slots, x, IDENTITY[kind], x.dtype)
        assert sum(b.size for b in arg) >= len(seg)
    got = jax.jit(semexec.apply_reduce_plan, static_argnames="kind")(
        plan, arg, kind=kind)
    np.testing.assert_array_equal(np.asarray(got),
                                  _reference(seg, x, n, kind))


@pytest.mark.parametrize("kind", KINDS)
def test_plan_of_one_segment_over_a_vector_is_a_plain_reduction(kind):
    x = _inputs(kind, 5000)
    plan, slots = semexec.build_reduce_plan(np.zeros(5000, np.int64), 1,
                                            over_vector=True)
    assert plan.whole and plan.gathered() == 0 and slots == ()
    got = semexec.apply_reduce_plan(plan, jnp.asarray(x), kind)
    np.testing.assert_array_equal(
        np.asarray(got), _reference(np.zeros(5000, np.int64), x, 1, kind))


def test_plan_without_inputs_gives_the_identity():
    plan, slots = semexec.build_reduce_plan(np.zeros(0, np.int64), 7)
    assert slots == () and plan.gathered() == 0
    got = semexec.apply_reduce_plan(plan, (), "min")
    np.testing.assert_array_equal(np.asarray(got), np.full(7, np.inf))


def test_kronecker_destinations_pad_little():
    """The width classes hold a Kronecker graph's destinations (scale 13,
    edge factor 16) in at most 1.15 slots an edge, where power-of-two
    widths took 1.37; the segment gather is the only one over vertices."""
    g = GraphSpec("kron13", "rmat", 1 << 13, 16 << 13, False, 16, 0).build()
    plan, slots = semexec.build_reduce_plan(g.dst, g.n)
    slots_n = sum(s.size for s in slots)
    assert len(plan.axes) <= semexec._MAX_CLASSES
    assert g.m <= slots_n <= 1.15 * g.m
    assert plan.gathered() == g.n


# ---------------------------------------------------------------------------
# HitGraph: the masks folded into the one value gather
# ---------------------------------------------------------------------------

HG_OPTS = ("update_filtering", "partition_skipping", "update_combining")


@pytest.fixture(scope="module")
def kron():
    return GraphSpec("kron-hg", "rmat", 256, 4096, False, 16, 0).build()


def _hitgraph(g, prob: str, engine: str, ivl: int, opts: frozenset):
    cfg = dataclasses.replace(default_config("hitgraph"), interval_size=ivl,
                              n_pes=2, semexec=engine,
                              optimizations=opts | {"edge_sorting"})
    return ACCELERATORS["hitgraph"](cfg).prepare(
        g, PROBLEMS[prob], root=int(g.degrees_out.argmax()))


def _assert_same(host, dev):
    assert dev.layout["engine"] == "device"
    assert host.iterations == dev.iterations
    np.testing.assert_array_equal(host.values, dev.values)
    # update counts and written (changed) sets, iteration by iteration
    assert [s.to_dict() for s in host.stats] == [s.to_dict()
                                                 for s in dev.stats]
    assert trace_stream_hash(host.traces()) == trace_stream_hash(dev.traces())


# interval 64: four source partitions; 4096: one
@pytest.mark.parametrize("ivl", [64, 4096], ids=["k4", "k1"])
@pytest.mark.parametrize(
    "on", list(itertools.product([False, True], repeat=3)),
    ids=lambda on: "-".join(o.split("_")[1] if f else "no" + o.split("_")[1]
                            for o, f in zip(HG_OPTS, on)))
def test_hitgraph_folded_masks_bfs(kron, on, ivl):
    opts = frozenset(o for o, f in zip(HG_OPTS, on) if f)
    host = _hitgraph(kron, "bfs", "numpy", ivl, opts)
    dev = _hitgraph(kron, "bfs", "device", ivl, opts)
    _assert_same(host, dev)
    # the first iteration reads every source as active, the unreached
    # (+inf) ones too: with no mask and no combining, every edge updates
    if not opts:
        assert dev.stats[0].updates_written == kron.m


@pytest.mark.parametrize("ivl", [64, 4096], ids=["k4", "k1"])
@pytest.mark.parametrize("prob", ["wcc", "sssp"])
def test_hitgraph_folded_masks_min_problems(kron, prob, ivl):
    g = kron
    if prob == "sssp":  # zero-weight edges: candidates equal to the source
        w = g.weights.copy()
        w[::5] = 0.0
        g = dataclasses.replace(g, weights=w)
    for opts in (frozenset(HG_OPTS), frozenset()):
        host = _hitgraph(g, prob, "numpy", ivl, opts)
        dev = _hitgraph(g, prob, "device", ivl, opts)
        _assert_same(host, dev)
        assert dev.iterations > 1
