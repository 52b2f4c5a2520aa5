"""Plain reference of Graph500 kernel 3: single-source shortest paths on the
weighted, undirected Kronecker graph, under HitGraph's and ThunderGP's
propagation.

Nothing it computes comes from the program under test.  It reuses the BFS
reference (:mod:`bench.reference.graphsim`) for what SSSP does not
change: the eager request streams, the DRAM timing and the row's
statistics.  It adds:

- a frozen copy of the generator with Graph500's edge weights: float32
  uniform in [0, 1), one per generated edge, drawn from a stream of their
  own beside the graph's seed, so the edge list is the BFS graph's; both
  arcs of an undirected edge carry its weight, and parallel edges keep
  their least weight;
- SSSP under the two models that take weighted edges, with 12-byte edge
  records (source, destination, weight); update records stay 8 bytes;
- a plain float32 Dijkstra as the self-check: the models' Bellman-Ford
  fixed point must equal it on every vertex.  A float32 sum of a
  non-negative weight never decreases, and rounding is monotone, so both
  find the least float32 path sum.

AccuGraph and ForeGraph take no weights: ``execute`` raises
``Unsupported`` for them, as for any scenario outside what the
configuration runs.

The one thing read of the program is whether its generator draws
Graph500 weights at all (``WEIGHT_STREAM``): a program without them would
run SSSP on other weights, another configuration, so the run stops as
this module loads, before anything starts.
"""
from __future__ import annotations

import dataclasses
import heapq
import math

import numpy as np

from bench.reference import graphsim as bfs
from bench.reference.graphsim import (  # noqa: F401  (bench.check reads them here)
    INF,
    Layout,
    Run,
    Timer,
    Unsupported,
    row_stats,
)

try:
    from repro.graph.generators import WEIGHT_STREAM  # noqa: F401
except ImportError as e:
    raise Unsupported("the program under test draws no Graph500 edge "
                      "weights: it cannot run Graph500 kernel 3") from e

WEIGHT_BYTES = 12  # an edge record: source, destination, float32 weight
UPDATE_BYTES = 8  # an update record: destination, value


# ---------------------------------------------------------------------------
# the weighted graph: a frozen copy of the seeded generator and its weights
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Graph(bfs.Graph):
    weights: np.ndarray  # float32, one per arc


def from_edges(n: int, edges: np.ndarray, weights: np.ndarray) -> Graph:
    """Undirected: self-loops dropped, both arcs of every edge stored with
    its weight, one arc per (src, dst) pair kept in (src, dst) order with
    the least weight of the pair's parallel edges."""
    src, dst = edges[:, 0], edges[:, 1]
    keep = src != dst
    src, dst, w = src[keep], dst[keep], weights[keep]
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    w = np.concatenate([w, w])
    key = src.astype(np.int64) * n + dst
    order = np.lexsort((w, key))  # by pair, then by weight
    key = key[order]
    first = np.concatenate(([True], key[1:] != key[:-1]))
    pick = order[first]
    return Graph(n, src[pick].astype(np.int32), dst[pick].astype(np.int32),
                 w[pick].astype(np.float32))


def kronecker(scale: int, edge_factor: int, seed: int, weight_stream: int,
              a: float, b: float, c: float) -> Graph:
    n = 1 << scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    for _ in range(scale):
        coin_ij = rng.random(m)
        coin_kl = rng.random(m)
        ii_bit = coin_ij > ab
        jj_bit = np.where(ii_bit, coin_kl > c_norm, coin_kl > a_norm)
        src = src * 2 + ii_bit
        dst = dst * 2 + jj_bit
    perm = rng.permutation(n)
    weights = np.random.default_rng([seed, weight_stream]).random(
        m, dtype=np.float32)
    return from_edges(n, np.stack([perm[src], perm[dst]], axis=1), weights)


def build_graph(recipe: dict) -> Graph:
    """The graph a configuration's recipe names: an undirected Kronecker
    graph with the recipe's ``weights`` (uniform float32 in [low, high)
    from stream ``[seed, stream]``, the least over parallel edges)."""
    w = recipe.get("weights") or {}
    if recipe["kind"] != "kronecker" or recipe["directed"]:
        raise Unsupported("SSSP on anything but the undirected Kronecker graph")
    if (w.get("distribution"), w.get("low"), w.get("high"), w.get("dtype"),
            w.get("parallel_edges")) != ("uniform", 0, 1, "float32", "min"):
        raise Unsupported(f"edge weights {w!r}")
    init = recipe["initiator"]
    return kronecker(recipe["scale"], recipe["edge_factor"], recipe["seed"],
                     w["stream"], init["A"], init["B"], init["C"])


def dijkstra(g: Graph, root: int) -> np.ndarray:
    """Plain Dijkstra in float32: the least path sum from ``root``, each
    sum rounded as the models round it; inf where unreachable."""
    order = np.argsort(g.src, kind="stable")
    indptr = np.zeros(g.n + 1, dtype=np.int64)
    np.cumsum(np.bincount(g.src, minlength=g.n), out=indptr[1:])
    nbrs, wts = g.dst[order], g.weights[order]
    dist = np.full(g.n, np.inf, dtype=np.float32)
    dist[root] = 0
    done = np.zeros(g.n, dtype=bool)
    heap = [(0.0, root)]
    while heap:
        _, u = heapq.heappop(heap)
        if done[u]:
            continue
        done[u] = True
        s, e = indptr[u], indptr[u + 1]
        v = nbrs[s:e]
        cand = dist[u] + wts[s:e]
        better = cand < dist[v]
        dist[v[better]] = cand[better]
        for d, x in zip(cand[better].tolist(), v[better].tolist()):
            heapq.heappush(heap, (d, x))
    return dist


# bench/check.py calls the reference's self-check ``bfs_levels``: here it is
# the SSSP distances of Dijkstra
bfs_levels = dijkstra


# ---------------------------------------------------------------------------
# the two models that take weights, SSSP with every optimization on
# ---------------------------------------------------------------------------


def hitgraph(g: Graph, root: int, ivl: int, p: int, max_iters: int) -> Run:
    """Edge-centric scatter/gather over source intervals: edges sorted by
    destination, update combining, update filtering, partition skipping;
    partition i on channel i % p."""
    n = g.n
    k = max(1, math.ceil(n / ivl))
    order, bounds = bfs._grouped(g.src // ivl, k)
    prep = []
    lays = [Layout() for _ in range(p)]
    for i in range(k):
        idx = order[bounds[i]:bounds[i + 1]]
        idx = idx[np.argsort(g.dst[idx], kind="stable")]
        src, dst, w = g.src[idx], g.dst[idx], g.weights[idx]
        route, jb = bfs._grouped(dst // ivl, k)
        prep.append((src, dst, w, route, jb))
        lo, hi = bfs._interval(i, ivl, n)
        lays[i % p].alloc(f"vals{i}", (hi - lo) * 4)
        lays[i % p].alloc(f"edges{i}", max(len(idx), 1) * WEIGHT_BYTES)
    for j in range(k):
        lays[j % p].alloc(f"upd{j}", max(g.m, 1) * UPDATE_BYTES)
    values = bfs._levels_init(n, root)
    active = np.ones(n, dtype=bool)
    dirty = np.ones(k, dtype=bool)
    phases, stats = [], []
    iters = 0
    for _ in range(max_iters):
        iters += 1
        st = bfs._iter_stats(k)
        scatter = [[] for _ in range(p)]
        upd_dst = [[] for _ in range(k)]
        upd_val = [[] for _ in range(k)]
        for i in range(k):
            if not dirty[i]:
                st["partitions_skipped"] += 1
                continue
            ch = i % p
            src, dst, w, route, jb0 = prep[i]
            lo, hi = bfs._interval(i, ivl, n)
            kept = active[src][route]
            routed = route[kept]
            jb = np.concatenate(([0], np.cumsum(kept, dtype=np.int64)))[jb0]
            dst_r = dst[routed]
            cand = values[src[routed]] + w[routed]
            for j in range(k):
                if jb[j] == jb[j + 1]:
                    continue
                d, v = dst_r[jb[j]:jb[j + 1]], cand[jb[j]:jb[j + 1]]
                jlo, jhi = bfs._interval(j, ivl, n)
                acc = np.full(jhi - jlo, INF, dtype=np.float32)
                np.minimum.at(acc, d - jlo, v)
                d = np.unique(d)
                upd_dst[j].append(d)
                upd_val[j].append(acc[d - jlo])
            scatter[ch].append(bfs.concat(
                bfs.seq_read(lays[ch][f"vals{i}"], (hi - lo) * 4),
                bfs.seq_read(lays[ch][f"edges{i}"], len(src) * WEIGHT_BYTES)))
            st["values_read"] += hi - lo
            st["edges_read"] += len(src)
        nupd = [sum(len(a) for a in upd_dst[j]) for j in range(k)]
        upd_writes = [[] for _ in range(p)]
        for j in range(k):
            if nupd[j]:
                st["updates_written"] += nupd[j]
                upd_writes[j % p].append(bfs.seq_write(
                    lays[j % p][f"upd{j}"], nupd[j] * UPDATE_BYTES))
        bfs._add_phase(phases, [bfs.proportional_interleave(
            bfs.concat(*scatter[ch]), bfs.concat(*upd_writes[ch]))
            for ch in range(p)])
        new_values = values.copy()
        changed_all = np.zeros(n, dtype=bool)
        any_change = False
        gather = [[] for _ in range(p)]
        for j in range(k):
            if not nupd[j]:
                continue
            ch = j % p
            lo, hi = bfs._interval(j, ivl, n)
            st["updates_read"] += nupd[j]
            d = np.concatenate(upd_dst[j])
            v = np.concatenate(upd_val[j])
            acc = np.full(hi - lo, INF, dtype=np.float32)
            np.minimum.at(acc, d - lo, v)
            old = new_values[lo:hi]
            nv = np.minimum(old, acc)
            changed = (nv < old).nonzero()[0] + lo
            new_values[lo:hi] = nv
            changed_all[changed] = True
            any_change |= bool(len(changed))
            gather[ch].append(bfs.concat(
                bfs.seq_read(lays[ch][f"vals{j}"], (hi - lo) * 4),
                bfs.proportional_interleave(
                    bfs.seq_read(lays[ch][f"upd{j}"], nupd[j] * UPDATE_BYTES),
                    bfs.random_write(lays[ch][f"vals{j}"], changed - lo, 4))))
            st["values_read"] += hi - lo
            st["values_written"] += len(changed)
        bfs._add_phase(phases, [bfs.concat(*trs) for trs in gather])
        dirty = np.zeros(k, dtype=bool)
        dirty[np.unique(changed_all.nonzero()[0] // ivl)] = True
        active = changed_all
        values = new_values
        stats.append(st)
        if not any_change:
            break
    sizes = [bounds[i + 1] - bounds[i] for i in range(k)]
    return Run(values, iters, phases, stats,
               dict(effective_interval=ivl, balance=bfs.balance(sizes)))


def thundergp(g: Graph, root: int, ivl: int, p: int, max_iters: int) -> Run:
    """Destination intervals, edges sorted by source, synchronous
    iterations; every iteration re-reads the same static streams.  One
    channel (one chunk per partition)."""
    if p != 1:
        raise Unsupported("ThunderGP with more than one channel")
    n = g.n
    k = max(1, math.ceil(n / ivl))
    order, bounds = bfs._grouped(g.dst // ivl, k)
    lay = Layout()
    lay.alloc("values", n * 4)
    parts = []
    for i in range(k):
        idx = order[bounds[i]:bounds[i + 1]]
        idx = idx[np.argsort(g.src[idx], kind="stable")]
        parts.append((g.src[idx], g.dst[idx], g.weights[idx],
                      np.unique(g.src[idx])))
        lo, hi = bfs._interval(i, ivl, n)
        lay.alloc(f"edges{i}", max(len(idx), 1) * WEIGHT_BYTES)
        lay.alloc(f"upd{i}", (hi - lo) * 4)
    static = []
    for i, (src, _, _, usrc) in enumerate(parts):
        lo, hi = bfs._interval(i, ivl, n)
        ni = hi - lo
        static.append((
            bfs.concat(bfs.seq_read(lay["values"] + lo * 4, ni * 4),
                       bfs.proportional_interleave(
                           bfs.seq_read(lay[f"edges{i}"],
                                        len(src) * WEIGHT_BYTES),
                           bfs.random_read(lay["values"], usrc, 4)),
                       bfs.seq_write(lay[f"upd{i}"], ni * 4)),
            bfs.concat(bfs.seq_read(lay[f"upd{i}"], ni * 4),
                       bfs.seq_write(lay["values"] + lo * 4, ni * 4))))
    values = bfs._levels_init(n, root)
    phases, stats = [], []
    iters = 0
    for _ in range(max_iters):
        iters += 1
        st = bfs._iter_stats(k)
        any_change = False
        new_values = values.copy()
        for i, (src, dst, w, usrc) in enumerate(parts):
            lo, hi = bfs._interval(i, ivl, n)
            ni = hi - lo
            acc = np.full(ni, INF, dtype=np.float32)
            np.minimum.at(acc, dst - lo, values[src] + w)
            st["values_read"] += ni + len(usrc)
            st["edges_read"] += len(src)
            st["updates_written"] += ni
            bfs._add_phase(phases, [static[i][0]])
            nv = np.minimum(new_values[lo:hi], acc)
            any_change |= bool((nv < new_values[lo:hi]).any())
            new_values[lo:hi] = nv
            st["updates_read"] += ni
            st["values_written"] += ni
            bfs._add_phase(phases, [static[i][1]])
        values = new_values
        stats.append(st)
        if not any_change:
            break
    sizes = [bounds[i + 1] - bounds[i] for i in range(k)]
    return Run(values, iters, phases, stats,
               dict(effective_interval=ivl, balance=bfs.balance(sizes)))


def execute(accel: str, g: Graph, root: int, interval: int, n_pes: int,
            max_iters: int) -> Run:
    if accel == "hitgraph":
        return hitgraph(g, root, interval, n_pes, max_iters)
    if accel == "thundergp":
        return thundergp(g, root, interval, n_pes, max_iters)
    raise Unsupported(f"SSSP on {accel!r}: it takes no edge weights")
