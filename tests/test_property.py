"""Property-based tests (hypothesis) on the system's invariants."""
from __future__ import annotations

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.dram import dram_config
from repro.core.engine import classify_fast, decode, simulate_channel_scan
from repro.core.trace import (
    Trace,
    coalesce,
    concat,
    proportional_interleave,
    round_robin,
    split_round_robin,
)
from repro.graph.partition import (
    horizontal_partition,
    interval_shard_partition,
    stride_mapping,
    vertical_partition,
)
from repro.graph.structure import from_edges

lines_st = st.lists(st.integers(0, 1 << 16), min_size=0, max_size=200)


def mk_trace(lines, writes=None):
    lines = np.asarray(lines, dtype=np.int64)
    if writes is None:
        writes = np.zeros(len(lines), dtype=bool)
    return Trace(lines, np.asarray(writes, dtype=bool))


# ---------------------------------------------------------------------------
# trace combinators
# ---------------------------------------------------------------------------


@given(lines_st)
def test_coalesce_idempotent(lines):
    t = coalesce(mk_trace(lines))
    t2 = coalesce(t)
    np.testing.assert_array_equal(t.lines, t2.lines)
    # no adjacent duplicates remain
    if t.n > 1:
        assert not np.any((t.lines[1:] == t.lines[:-1]) &
                          (t.is_write[1:] == t.is_write[:-1]))


@given(lines_st, lines_st)
def test_concat_and_merges_preserve_multiset(a, b):
    ta, tb = mk_trace(a), mk_trace(b)
    for merged in (concat(ta, tb), round_robin(ta, tb),
                   proportional_interleave(ta, tb)):
        assert merged.n == ta.n + tb.n
        np.testing.assert_array_equal(
            np.sort(merged.lines), np.sort(np.concatenate([ta.lines, tb.lines]))
        )


@given(lines_st, st.integers(1, 5))
def test_split_round_robin_partitions(lines, k):
    t = mk_trace(lines)
    parts = split_round_robin(t, k)
    assert sum(p.n for p in parts) == t.n
    np.testing.assert_array_equal(
        np.sort(np.concatenate([p.lines for p in parts]) if parts else np.array([])),
        np.sort(t.lines),
    )


@given(lines_st, st.integers(1, 5), st.integers(1, 9))
def test_split_round_robin_lazy_matches_eager_any_granularity(lines, k, g):
    """The lazy strided-split IR node and the eager slicing deal the same
    requests to the same channels for every (k, granularity)."""
    from repro.core.trace import _EagerLeaf, materialize

    t = mk_trace(lines, writes=np.asarray(lines, np.int64) % 2 == 0)
    lazy_parts = split_round_robin(_EagerLeaf(t), k, g)
    eager_parts = split_round_robin(t, k, g)
    for lp, ep in zip(lazy_parts, eager_parts):
        assert lp.n == ep.n and lp.write_bytes == ep.write_bytes
        m = materialize(lp)
        np.testing.assert_array_equal(m.lines, ep.lines)
        np.testing.assert_array_equal(m.is_write, ep.is_write)


@given(
    scheme=st.sampled_from(["row", "bank", "bank_xor"]),
    log_banks=st.integers(1, 5),
    log_lpr=st.integers(1, 6),
    nrows=st.integers(1, 4),
)
@settings(max_examples=30, deadline=None)
def test_address_mapping_bijection_property(scheme, log_banks, log_lpr, nrows):
    """Every AddressMapping is a bijection line -> (bank, row, col) on any
    whole number of row spans, for arbitrary pow2 geometry."""
    import dataclasses

    from repro.core.dram import (AddressMapping, decode_line_scalar,
                                 decode_lines, dram_config)

    cfg = dataclasses.replace(
        dram_config("default", mapping=AddressMapping(scheme)),
        ranks=1, banks_per_rank=1 << log_banks,
        row_buffer_bytes=64 << log_lpr,
    )
    n = cfg.lines_per_row * cfg.nbanks * nrows
    lines = np.arange(n, dtype=np.int64)
    bank, row = decode_lines(lines, cfg)
    seen = set()
    for i in range(n):
        b, r, c = decode_line_scalar(i, cfg)
        assert (bank[i], row[i]) == (b, r)  # vectorised == scalar reference
        seen.add((b, r, c))
    assert len(seen) == n  # bijective: every triple hit exactly once


@given(lines_st)
def test_round_robin_interleaves_fairly(lines):
    ta, tb = mk_trace(lines), mk_trace([l + 1 for l in lines])
    m = round_robin(ta, tb)
    if ta.n:
        # first two requests come from different streams
        assert m.lines[0] == ta.lines[0]


# ---------------------------------------------------------------------------
# DRAM engine invariants
# ---------------------------------------------------------------------------


@given(lines_st)
@settings(max_examples=30, deadline=None)
def test_classification_counts_sum(lines):
    cfg = dram_config("default")
    bank, row = decode(np.asarray(lines, dtype=np.int64), cfg)
    cls = classify_fast(bank, row, cfg.nbanks)
    assert len(cls) == len(lines)
    assert int((cls == 0).sum() + (cls == 1).sum() + (cls == 2).sum()) == len(lines)
    # brute-force oracle: per-bank last-row
    last = {}
    for i, (b, r) in enumerate(zip(bank, row)):
        want = 1 if b not in last else (0 if last[b] == r else 2)
        assert cls[i] == want, (i, b, r)
        last[b] = r


@given(lines_st)
@settings(max_examples=15, deadline=None)
def test_scan_engine_stats_match_classification(lines):
    if not lines:
        return
    cfg = dram_config("default")
    t = mk_trace(lines)
    rep = simulate_channel_scan(t, cfg)
    bank, row = decode(t.lines, cfg)
    cls = classify_fast(bank, row, cfg.nbanks)
    assert rep.hits == int((cls == 0).sum())
    assert rep.misses == int((cls == 1).sum())
    assert rep.conflicts == int((cls == 2).sum())
    # physical lower bound: the bus must carry every line
    assert rep.cycles >= t.n * cfg.tBL


@given(lines_st)
@settings(max_examples=10, deadline=None)
def test_scan_engine_monotone_in_prefix(lines):
    """Appending requests never reduces total cycles."""
    if len(lines) < 2:
        return
    cfg = dram_config("default")
    half = mk_trace(lines[: len(lines) // 2])
    full = mk_trace(lines)
    assert simulate_channel_scan(full, cfg).cycles >= simulate_channel_scan(half, cfg).cycles


# ---------------------------------------------------------------------------
# partitioning invariants
# ---------------------------------------------------------------------------

edges_st = st.lists(
    st.tuples(st.integers(0, 99), st.integers(0, 99)), min_size=1, max_size=300
)


@given(edges_st, st.sampled_from([16, 32, 64]))
@settings(max_examples=25, deadline=None)
def test_horizontal_partition_is_partition(edges, interval):
    g = from_edges(100, np.asarray(edges), dedup=False, name="h")
    parts = horizontal_partition(g, interval, by="src")
    all_idx = np.concatenate([parts.edge_idx[p] for p in range(parts.k)])
    assert len(all_idx) == g.m
    np.testing.assert_array_equal(np.sort(all_idx), np.arange(g.m))
    for p in range(parts.k):
        lo, hi = parts.interval(p)
        src, _ = parts.edges(p)
        assert np.all((src >= lo) & (src < hi))


@given(edges_st, st.sampled_from([16, 64]), st.integers(1, 4))
@settings(max_examples=25, deadline=None)
def test_vertical_partition_is_partition(edges, interval, chunks):
    g = from_edges(100, np.asarray(edges), dedup=False, name="v")
    parts = vertical_partition(g, interval, n_chunks=chunks)
    all_idx = np.concatenate(
        [parts.edge_idx[p][c] for p in range(parts.k) for c in range(chunks)]
    )
    np.testing.assert_array_equal(np.sort(all_idx), np.arange(g.m))
    for p in range(parts.k):
        lo, hi = parts.interval(p)
        for c in range(chunks):
            _, dst = parts.edges(p, c)
            assert np.all((dst >= lo) & (dst < hi))


@given(edges_st, st.sampled_from([16, 32]))
@settings(max_examples=25, deadline=None)
def test_interval_shard_partition_is_partition(edges, interval):
    g = from_edges(100, np.asarray(edges), dedup=False, name="s")
    shards = interval_shard_partition(g, interval)
    all_idx = np.concatenate(
        [shards.shard_edge_idx[i][j] for i in range(shards.q) for j in range(shards.q)]
    )
    np.testing.assert_array_equal(np.sort(all_idx), np.arange(g.m))


@given(st.integers(1, 2000), st.integers(1, 40))
def test_stride_mapping_is_permutation(n, q):
    perm = stride_mapping(n, q)
    assert len(perm) == n
    np.testing.assert_array_equal(np.sort(perm), np.arange(n))


# ---------------------------------------------------------------------------
# graph-layout invariants (vertex reordering + interval scaling)
# ---------------------------------------------------------------------------

reorders_st = st.sampled_from(["identity", "degree", "random", "bfs"])


@given(edges_st, st.integers(0, 40), reorders_st)
@settings(max_examples=40, deadline=None)
def test_reorder_is_bijection_on_vertex_range(edges, extra_isolated, reorder):
    """Every reorder is a bijection on [0, n) — including trailing isolated
    vertices no edge ever touches."""
    from repro.graph.layout import reorder_permutation

    n = 100 + extra_isolated
    g = from_edges(n, np.asarray(edges), dedup=False, name="bij")
    perm = reorder_permutation(g, reorder)
    np.testing.assert_array_equal(np.sort(perm), np.arange(n))


@given(edges_st, st.integers(1, 150), reorders_st, st.sampled_from([1, 2, 4]))
@settings(max_examples=30, deadline=None)
def test_partition_schemes_cover_each_edge_exactly_once(edges, interval,
                                                        reorder, scale):
    """All three partition schemes are exact covers for arbitrary graphs,
    interval sizes and layouts: the multiset of edge indices equals
    arange(m) — no edge dropped, none duplicated."""
    from repro.graph.layout import GraphLayout

    g = from_edges(100, np.asarray(edges), dedup=False, name="cover")
    lay = GraphLayout(reorder, scale)
    want = np.arange(g.m)
    h = horizontal_partition(g, interval, layout=lay)
    np.testing.assert_array_equal(
        np.sort(np.concatenate([h.edge_idx[p] for p in range(h.k)])), want)
    v = vertical_partition(g, interval, n_chunks=3, layout=lay)
    np.testing.assert_array_equal(
        np.sort(np.concatenate([v.edge_idx[p][c]
                                for p in range(v.k) for c in range(3)])), want)
    s = interval_shard_partition(g, interval, layout=lay)
    np.testing.assert_array_equal(
        np.sort(np.concatenate([s.shard_edge_idx[i][j]
                                for i in range(s.q)
                                for j in range(s.q)])), want)


@given(edges_st, reorders_st, st.sampled_from(["bfs", "wcc"]))
@settings(max_examples=10, deadline=None)
def test_reordered_accelerator_reaches_reference_fixed_point(edges, reorder,
                                                             prob):
    """Layout invariance on arbitrary graphs: a reordered AccuGraph run,
    mapped back to original ids, still reaches the reference fixed point
    bit for bit (min problems are order-independent)."""
    import dataclasses

    from repro.configs.graphsim import default_config
    from repro.core.accelerators.base import run_accelerator
    from repro.graph.problems import PROBLEMS, reference_solve

    g = from_edges(100, np.asarray(edges), name="lay")
    if g.m == 0:
        return
    root = int(g.src[0])
    ref, _ = reference_solve(g, PROBLEMS[prob], root=root)
    cfg = dataclasses.replace(default_config("accugraph"), interval_size=32,
                              reorder=reorder, engine="fast")
    rep = run_accelerator("accugraph", g, PROBLEMS[prob], root=root,
                          dram="default", config=cfg)
    np.testing.assert_array_equal(rep.values, ref)


# ---------------------------------------------------------------------------
# accelerator semantics == reference fixed point (random graphs)
# ---------------------------------------------------------------------------


@given(edges_st, st.sampled_from(["bfs", "wcc"]),
       st.sampled_from(["accugraph", "foregraph", "hitgraph", "thundergp"]))
@settings(max_examples=12, deadline=None)
def test_accelerators_reach_reference_fixed_point(edges, prob, accel):
    from repro.configs.graphsim import default_config
    from repro.core.accelerators.base import run_accelerator
    from repro.graph.problems import PROBLEMS, reference_solve

    g = from_edges(100, np.asarray(edges), name="rand")
    if g.m == 0:  # all edges were self-loops
        return
    root = int(g.src[0])
    ref, _ = reference_solve(g, PROBLEMS[prob], root=root)
    import dataclasses

    cfg = dataclasses.replace(default_config(accel), interval_size=64,
                              engine="fast")
    rep = run_accelerator(accel, g, PROBLEMS[prob], root=root, dram="default",
                          config=cfg)
    np.testing.assert_array_equal(rep.values, ref)


# ---------------------------------------------------------------------------
# kernel oracles (scatter_min / spmv_edges vs their numpy references)
# ---------------------------------------------------------------------------


@st.composite
def coo_graphs(draw, max_n=40, max_m=150):
    """Random COO edge sets with the degenerate shapes the semexec layouts
    produce: padding edges (src == -1), empty edge sets, isolated vertices
    (n can far exceed the touched id range)."""
    n = draw(st.integers(1, max_n))
    m = draw(st.integers(0, max_m))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=m).astype(np.int32)
    dst = rng.integers(0, n, size=m).astype(np.int32)
    # sprinkle padding edges the way the device layouts do
    pad_mask = rng.random(m) < 0.2
    src[pad_mask] = -1
    dst[pad_mask] = 0
    return n, src, dst, rng


@given(coo_graphs(), st.booleans(), st.floats(0.0, 8.0))
@settings(max_examples=60, deadline=None)
def test_scatter_min_matches_numpy_oracle(g, with_mask, reach_p):
    import jax.numpy as jnp
    from repro.kernels.edge_update.edge_update import sentinel_max
    from repro.kernels.edge_update.ops import scatter_min

    n, src, dst, rng = g
    m = len(src)
    delta = rng.random(m).astype(np.float32)
    # mix of reached and unreached (inf) vertices — the empty-frontier
    # extreme included when reach_p rounds to 0
    values = np.where(rng.random(n) * 8 < reach_p,
                      rng.random(n) * 10, np.inf).astype(np.float32)
    mask = rng.random(m) < 0.7 if with_mask else None
    out = np.asarray(scatter_min(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(delta),
        jnp.asarray(values),
        mask=None if mask is None else jnp.asarray(mask)))
    top = np.asarray(sentinel_max(np.float32))
    acc = np.full(n, top, dtype=np.float32)
    keep = src >= 0
    if mask is not None:
        keep &= mask
    sv = values[np.maximum(src, 0)]
    keep &= sv != top
    np.minimum.at(acc, dst[keep], (sv + delta)[keep])
    # min is order-independent and exact: bit equality, not allclose
    np.testing.assert_array_equal(out, acc)


@given(coo_graphs())
@settings(max_examples=60, deadline=None)
def test_spmv_edges_matches_numpy_oracle(g):
    import jax.numpy as jnp
    from repro.kernels.spmv.ops import spmv_edges

    n, src, dst, rng = g
    m = len(src)
    # padding edges carry weight 0 in the device layouts (src -1 is only a
    # scatter_min convention); make them no-ops the same way here
    w = rng.random(m).astype(np.float32)
    w[src < 0] = 0.0
    src = np.maximum(src, 0)
    x = rng.random(n).astype(np.float32)
    y = np.asarray(spmv_edges(jnp.asarray(src), jnp.asarray(dst),
                              jnp.asarray(w), jnp.asarray(x), n))
    ref = np.zeros(n, dtype=np.float32)
    np.add.at(ref, dst, w * x[src])
    # sums associate differently (segment_sum vs np.add.at): tolerance
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-6)
    assert y.shape == (n,)
