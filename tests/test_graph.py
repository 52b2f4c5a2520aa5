"""Graph substrate tests: structures, generators, partitioning invariants."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.graph import (
    from_edges,
    horizontal_partition,
    interval_shard_partition,
    vertical_partition,
)
from repro.graph.generators import PAPER_GRAPHS, grid_road, rmat
from repro.graph.partition import stride_mapping


def test_from_edges_dedup_and_selfloops():
    edges = np.array([[0, 1], [0, 1], [1, 1], [1, 2]])
    g = from_edges(4, edges)
    assert g.m == 2  # dup removed, self-loop removed
    assert set(zip(g.src.tolist(), g.dst.tolist())) == {(0, 1), (1, 2)}


def test_from_edges_undirected_symmetrises():
    g = from_edges(3, np.array([[0, 1]]), directed=False)
    assert set(zip(g.src.tolist(), g.dst.tolist())) == {(0, 1), (1, 0)}


def test_csr_csc_roundtrip(small_rmat):
    g = small_rmat
    indptr, indices, _ = g.csr
    assert indptr[-1] == g.m
    # CSR rebuild == edge set
    rebuilt = set()
    for v in range(g.n):
        for e in range(indptr[v], indptr[v + 1]):
            rebuilt.add((v, int(indices[e])))
    assert rebuilt == set(zip(g.src.tolist(), g.dst.tolist()))
    cptr, cidx, _ = g.csc
    assert cptr[-1] == g.m


def test_rmat_properties():
    g = rmat(10, edge_factor=8, seed=1)
    assert g.n == 1024
    assert 0 < g.m <= 8 * 1024
    assert g.degree_skewness > 1.0  # power-law-ish


def test_road_graph_properties():
    g = grid_road(32)
    assert abs(g.degree_skewness) < 1.5  # near-regular degrees
    assert g.avg_degree < 6


def test_undirected_kronecker_edges_unchanged_by_weights():
    """The undirected Kronecker graph carries Graph500's weights from a
    stream of their own: its edge list is the one it had without them."""
    import hashlib

    g = rmat(10, edge_factor=16, seed=16, directed=False)
    assert g.weighted
    h = hashlib.sha256(g.src.tobytes() + g.dst.tobytes()).hexdigest()
    assert h == ("72569011fa1915772ec00f355e0b7c32"
                 "95f8f910c68f1e40b236cf565e7ed369")
    plain = from_edges(g.n, np.stack([g.src, g.dst], axis=1), directed=False)
    np.testing.assert_array_equal(plain.src, g.src)
    np.testing.assert_array_equal(plain.dst, g.dst)


def test_undirected_kronecker_weights_are_graph500s():
    g = rmat(10, edge_factor=16, seed=16, directed=False)
    w = g.weights
    assert w.dtype == np.float32 and w.shape == g.src.shape
    assert w.min() >= 0.0 and w.max() < 1.0
    # no subnormal weight (the TPU flushes them to zero)
    assert np.all((w == 0) | (w >= np.finfo(np.float32).tiny))
    # both arcs of a pair carry one weight
    fwd = dict(zip(zip(g.src.tolist(), g.dst.tolist()), w.tolist()))
    assert all(fwd[(d, s)] == x for (s, d), x in fwd.items())
    # a stream of its own, keyed on the graph's seed
    other = rmat(10, edge_factor=16, seed=17, directed=False)
    assert not np.array_equal(other.weights[:100], w[:100])


def test_weighted_problems_use_the_graphs_own_weights():
    from repro.graph.problems import SSSP

    g = rmat(8, edge_factor=16, seed=16, directed=False)
    assert SSSP.prepare_graph(g).weights is g.weights
    # a graph without weights still gets the integer weights in [1, 64)
    d = SSSP.prepare_graph(rmat(8, edge_factor=16, seed=16))
    assert d.weights.min() >= 1 and np.all(d.weights == np.round(d.weights))


def test_from_edges_undirected_keeps_least_weight():
    edges = np.array([[0, 1], [1, 0], [0, 1], [2, 3], [3, 3]])
    w = np.array([0.5, 0.25, 0.75, 0.125, 0.0], dtype=np.float32)
    g = from_edges(4, edges, directed=False, weights=w)
    got = dict(zip(zip(g.src.tolist(), g.dst.tolist()), g.weights.tolist()))
    assert got == {(0, 1): 0.25, (1, 0): 0.25, (2, 3): 0.125, (3, 2): 0.125}
    # a directed graph keeps the first occurrence's weight, as before
    d = from_edges(4, edges, directed=True, weights=w)
    got = dict(zip(zip(d.src.tolist(), d.dst.tolist()), d.weights.tolist()))
    assert got == {(0, 1): 0.5, (1, 0): 0.25, (2, 3): 0.125}


@pytest.mark.parametrize("name,build,fingerprint", [
    ("rmat-directed", lambda: rmat(10, edge_factor=8, seed=3),
     "50ca02b3a8f6aef155e626b4ec7e00108d69bb517cca29a371446e4752bf5db1"),
    ("sd", lambda: PAPER_GRAPHS["sd"].build(),
     "36bd845794dd9b1d1c7cf009ec0ddd25756553443d7ec482cdb6f66f6e422615"),
    ("db", lambda: PAPER_GRAPHS["db"].build(),
     "3848b75b8f2a458cdcbe75397a2af770404813a717d086d11553db1f5b78476c"),
    ("yt", lambda: PAPER_GRAPHS["yt"].build(),
     "2df878e53cfbd17bc6acc8c1e2e2040317ad5929fab3a94670347556e7b9313d"),
    ("rd", lambda: PAPER_GRAPHS["rd"].build(),
     "a304b2a7affe4b67753afae497e45e6c64e6ffdf5f06b19053f3a01a7b6d05a1"),
    ("r21", lambda: PAPER_GRAPHS["r21"].build(),
     "3ca0fda59c83477769b252a452847528ab34013c2c015e1bea38cd51906586d3"),
])
def test_unweighted_graphs_unchanged(name, build, fingerprint):
    """Directed R-MAT and the paper suite draw no weights: their content
    fingerprints are the ones they had before Graph500's weights."""
    g = build()
    assert not g.weighted
    assert g.fingerprint == fingerprint


@pytest.mark.parametrize("name", ["sd", "db", "yt"])
def test_paper_suite_builds(name):
    g = PAPER_GRAPHS[name].build()
    assert g.n > 0 and g.m > 0
    root = PAPER_GRAPHS[name].root
    assert 0 <= root < g.n


@given(
    n=st.integers(8, 200),
    m=st.integers(1, 400),
    interval=st.integers(4, 64),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_horizontal_partition_covers_all_edges(n, m, interval, seed):
    rng = np.random.default_rng(seed)
    g = from_edges(n, rng.integers(0, n, size=(m, 2)))
    parts = horizontal_partition(g, interval, by="src")
    seen = np.concatenate([parts.edge_idx[p] for p in range(parts.k)]) if parts.k else []
    assert sorted(seen) == list(range(g.m))  # every edge exactly once
    for p in range(parts.k):
        lo, hi = parts.interval(p)
        s, _ = parts.edges(p)
        assert ((s >= lo) & (s < hi)).all()


@given(
    n=st.integers(8, 200),
    m=st.integers(1, 400),
    interval=st.integers(4, 64),
    chunks=st.integers(1, 4),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_vertical_partition_covers_all_edges(n, m, interval, chunks, seed):
    rng = np.random.default_rng(seed)
    g = from_edges(n, rng.integers(0, n, size=(m, 2)))
    parts = vertical_partition(g, interval, n_chunks=chunks)
    seen = np.concatenate(
        [parts.edge_idx[p][c] for p in range(parts.k) for c in range(chunks)]
    )
    assert sorted(seen.tolist()) == list(range(g.m))
    for p in range(parts.k):
        lo, hi = parts.interval(p)
        for c in range(chunks):
            _, d = parts.edges(p, c)
            assert ((d >= lo) & (d < hi)).all()
            # ThunderGP chunks are sorted by source
            s, _ = parts.edges(p, c)
            assert (np.diff(s) >= 0).all()


@given(
    n=st.integers(8, 300),
    m=st.integers(1, 500),
    interval=st.integers(4, 64),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_interval_shard_covers_all_edges(n, m, interval, seed):
    rng = np.random.default_rng(seed)
    g = from_edges(n, rng.integers(0, n, size=(m, 2)))
    sh = interval_shard_partition(g, interval)
    seen = np.concatenate(
        [sh.shard_edge_idx[i][j] for i in range(sh.q) for j in range(sh.q)]
    )
    assert sorted(seen.tolist()) == list(range(g.m))
    for i in range(sh.q):
        for j in range(sh.q):
            s, d = sh.shard(i, j)
            assert ((s // interval) == i).all()
            assert ((d // interval) == j).all()


@given(n=st.integers(2, 1000), q=st.integers(1, 16))
@settings(max_examples=50, deadline=None)
def test_stride_mapping_is_permutation(n, q):
    perm = stride_mapping(n, q)
    assert sorted(perm.tolist()) == list(range(n))


def test_stride_mapping_balances_skew(skewed_graph):
    g = skewed_graph
    interval = 512
    q = -(-g.n // interval)
    sizes_before = interval_shard_partition(g, interval).shard_sizes()
    g2 = g.renamed(stride_mapping(g.n, q))
    sizes_after = interval_shard_partition(g2, interval).shard_sizes()
    # stride mapping reduces the max/mean shard-size imbalance
    def imbalance(s):
        nz = s[s > 0]
        return nz.max() / max(nz.mean(), 1)

    assert imbalance(sizes_after) <= imbalance(sizes_before) * 1.05
