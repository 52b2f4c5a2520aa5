import os
import sys

# Tests run on the single real CPU device.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest

from repro.graph.generators import rmat, grid_road, preferential


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (full drivers)"
    )


@pytest.fixture(scope="session")
def small_rmat():
    return rmat(10, edge_factor=8, seed=3, name="small_rmat")


@pytest.fixture(scope="session")
def mid_rmat():
    return rmat(13, edge_factor=8, seed=5, name="mid_rmat")


@pytest.fixture(scope="session")
def road_graph():
    return grid_road(48, seed=7, name="road48")


@pytest.fixture(scope="session")
def skewed_graph():
    return preferential(4096, 6, seed=9, name="pa4096")


def bfs_oracle(n, src, dst, root):
    """Plain-python BFS levels oracle."""
    from collections import deque, defaultdict

    adj = defaultdict(list)
    for s, d in zip(src.tolist(), dst.tolist()):
        adj[s].append(d)
    level = np.full(n, np.inf, dtype=np.float32)
    level[root] = 0
    q = deque([root])
    while q:
        u = q.popleft()
        for v in adj[u]:
            if level[v] == np.inf:
                level[v] = level[u] + 1
                q.append(v)
    return level


def wcc_oracle(n, src, dst):
    """Union-find weakly-connected components, labelled by min vertex id."""
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for s, d in zip(src.tolist(), dst.tolist()):
        rs, rd = find(s), find(d)
        if rs != rd:
            parent[max(rs, rd)] = min(rs, rd)
    # label = min id in component
    labels = np.zeros(n, dtype=np.float32)
    roots = {}
    for v in range(n):
        r = find(v)
        if r not in roots:
            roots[r] = r  # since we always parent to min, root IS the min id
        labels[v] = roots[r]
    return labels
