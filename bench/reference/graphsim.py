"""Plain reference of the simulated results the served campaign produces.

This is the yardstick that decides ``correct``.  It re-computes, for one
scenario (graph recipe + root, accelerator, DRAM preset and controller),
every simulated statistic of the result row: the BFS semantics under the
accelerator's propagation scheme, the off-chip request streams of the
paper's memory-access abstractions, and the DRAM timing of those streams.
It imports nothing of the program under test.

- Graphs are built from the configuration's recipe (a Kronecker graph:
  scale, edge factor, initiator, seed) by a frozen copy of the seeded
  generator, so the reference builds the edge list the recipe names.
- The four accelerator models run BFS in plain numpy with eagerly
  materialised request streams: no lazy trace IR, no host caches, no
  device semantic engine.  BFS levels of every model are checked against a
  plain frontier BFS before any row is compared.
- DRAM timing is a per-request Python loop over the per-bank state machine
  (open row, row ready, last data slot, last activate, one data bus per
  channel), written from the model's description; it shares no code with
  the program's ``lax.scan`` engine.  Identical request streams under one
  timing configuration are timed once (the model is deterministic).

Only what the benchmark's configurations run is modelled: BFS, every
optimization of each accelerator on, the configuration's interval sizes
and PEs, identity vertex order and interval scale 1, row-interleaved address
mapping, open or closed pages, HBM pseudo-channels.  A scenario outside
that raises ``Unsupported``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math

import numpy as np

LINE = 64
INF = np.float32(np.inf)
SCAN_CUTOFF = 2_000_000  # longer traces are timed analytically by the program


class Unsupported(ValueError):
    """A scenario this reference does not model."""


# ---------------------------------------------------------------------------
# graphs: frozen copies of the seeded generators
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Graph:
    n: int
    src: np.ndarray  # int32
    dst: np.ndarray  # int32

    @property
    def m(self) -> int:
        return int(self.src.shape[0])

    @property
    def degrees_out(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n).astype(np.int64)

    def renamed(self, perm: np.ndarray) -> "Graph":
        perm = perm.astype(np.int32)
        return Graph(self.n, perm[self.src], perm[self.dst])


def from_edges(n: int, edges: np.ndarray, directed: bool) -> Graph:
    """Self-loops dropped; undirected edges stored both ways; duplicates
    dropped (kept in (src, dst) key order)."""
    edges = np.asarray(edges, dtype=np.int64)
    if edges.size == 0:
        edges = edges.reshape(0, 2)
    src, dst = edges[:, 0], edges[:, 1]
    keep = src != dst
    src, dst = src[keep], dst[keep]
    if not directed:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    _, idx = np.unique(src.astype(np.int64) * n + dst, return_index=True)
    return Graph(n, src[idx].astype(np.int32), dst[idx].astype(np.int32))


def rmat(scale: int, edge_factor: int, seed: int, directed: bool,
         a: float = 0.57, b: float = 0.19, c: float = 0.19) -> Graph:
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
    return from_edges(n, np.stack([perm[src], perm[dst]], axis=1), directed)


def build_graph(recipe: dict) -> Graph:
    """The graph a configuration's recipe names: a Kronecker graph of
    ``2**scale`` vertices and ``edge_factor`` edges a vertex, drawn with
    the recipe's initiator and seed."""
    if recipe["kind"] != "kronecker":
        raise Unsupported(f"graph generator {recipe['kind']!r}")
    init = recipe["initiator"]
    return rmat(recipe["scale"], recipe["edge_factor"], recipe["seed"],
                recipe["directed"], init["A"], init["B"], init["C"])


def bfs_levels(g: Graph, root: int) -> np.ndarray:
    """Plain frontier BFS: hop count from ``root``, inf where unreachable."""
    order = np.argsort(g.src, kind="stable")
    indptr = np.zeros(g.n + 1, dtype=np.int64)
    np.cumsum(np.bincount(g.src, minlength=g.n), out=indptr[1:])
    nbrs = g.dst[order]
    level = np.full(g.n, np.inf, dtype=np.float32)
    level[root] = 0
    frontier = np.array([root])
    depth = 0
    while len(frontier):
        depth += 1
        starts, ends = indptr[frontier], indptr[frontier + 1]
        cand = np.concatenate([nbrs[s:e] for s, e in zip(starts, ends)]) \
            if len(frontier) else np.zeros(0, np.int32)
        cand = np.unique(cand)
        cand = cand[np.isinf(level[cand])]
        level[cand] = depth
        frontier = cand
    return level


# ---------------------------------------------------------------------------
# request streams (eager): line indices + write flags in program order
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Trace:
    lines: np.ndarray  # int64
    is_write: np.ndarray  # bool

    @property
    def n(self) -> int:
        return int(self.lines.shape[0])


def empty() -> Trace:
    return Trace(np.zeros(0, np.int64), np.zeros(0, bool))


def _span(base: int, nbytes: int, write: bool) -> Trace:
    if nbytes <= 0:
        return empty()
    lines = np.arange(base // LINE, (base + nbytes - 1) // LINE + 1,
                      dtype=np.int64)
    return Trace(lines, np.full(len(lines), write))


def seq_read(base: int, nbytes: int) -> Trace:
    return _span(base, nbytes, False)


def seq_write(base: int, nbytes: int) -> Trace:
    return _span(base, nbytes, True)


def _scattered(base: int, indices: np.ndarray, width: int,
               write: bool) -> Trace:
    """Element accesses at ``indices``; adjacent requests to one line merge
    (the cache-line abstraction)."""
    lines = (base + indices.astype(np.int64) * width) // LINE
    if len(lines):
        keep = np.ones(len(lines), dtype=bool)
        keep[1:] = lines[1:] != lines[:-1]
        lines = lines[keep]
    return Trace(lines, np.full(len(lines), write))


def random_read(base, indices, width) -> Trace:
    return _scattered(base, indices, width, False)


def random_write(base, indices, width) -> Trace:
    return _scattered(base, indices, width, True)


def concat(*traces: Trace) -> Trace:
    traces = [t for t in traces if t.n]
    if not traces:
        return empty()
    return Trace(np.concatenate([t.lines for t in traces]),
                 np.concatenate([t.is_write for t in traces]))


def _merged(traces, order_of) -> Trace:
    traces = [t for t in traces if t.n]
    if len(traces) < 2:
        return traces[0] if traces else empty()
    order = order_of([t.n for t in traces])
    return Trace(np.concatenate([t.lines for t in traces])[order],
                 np.concatenate([t.is_write for t in traces])[order])


def round_robin(*traces: Trace) -> Trace:
    """1:1 merge: stream i's j-th request at virtual time j*k + i."""
    def order(lengths):
        k = len(lengths)
        pos = np.concatenate([np.arange(n, dtype=np.float64) * k + i
                              for i, n in enumerate(lengths)])
        return np.argsort(pos, kind="stable")
    return _merged(traces, order)


def proportional_interleave(*traces: Trace) -> Trace:
    """Rate-proportional merge: stream i's j-th request at virtual time
    (j + 0.5) / len_i, ties to the lower stream index."""
    def order(lengths):
        pos = np.concatenate([(np.arange(n, dtype=np.float64) + 0.5) / n
                              for n in lengths])
        sub = np.concatenate([np.full(n, i, dtype=np.int32)
                              for i, n in enumerate(lengths)])
        return np.lexsort((sub, pos))
    return _merged(traces, order)


def split_round_robin(t: Trace, k: int, granularity: int) -> list[Trace]:
    """Deal a stream over k channels in ``granularity``-line blocks."""
    g = granularity
    out = []
    for i in range(k):
        full, rem = divmod(t.n, g * k)
        length = full * g + min(max(rem - i * g, 0), g)
        j = np.arange(length, dtype=np.int64)
        pos = (j // g) * (g * k) + i * g + (j % g)
        out.append(Trace(t.lines[pos], t.is_write[pos]))
    return out


def stream_hash(traces: list[Trace]) -> str:
    h = hashlib.sha256()
    for t in traces:
        h.update(t.lines.tobytes())
        h.update(t.is_write.tobytes())
    return h.hexdigest()


class Layout:
    """Regions placed one after another, each starting on a fresh 8 KiB
    row-buffer boundary."""

    def __init__(self):
        self.cursor = 0
        self.bases: dict[str, int] = {}

    def alloc(self, name: str, nbytes: int) -> None:
        self.bases[name] = self.cursor
        self.cursor = -(-(self.cursor + nbytes) // 8192) * 8192

    def __getitem__(self, name: str) -> int:
        return self.bases[name]


# ---------------------------------------------------------------------------
# partitioning
# ---------------------------------------------------------------------------


def _grouped(keys: np.ndarray, n_buckets: int) -> tuple[np.ndarray, np.ndarray]:
    order = np.argsort(keys, kind="stable")
    return order, np.searchsorted(keys[order], np.arange(n_buckets + 1))


def _interval(p: int, size: int, n: int) -> tuple[int, int]:
    return p * size, min(n, p * size + size)


def balance(counts, total_slots: int | None = None) -> dict:
    counts = np.asarray(counts, dtype=np.int64).ravel()
    mean = float(counts.mean())
    out = dict(partitions=int(counts.size), edges_min=int(counts.min()),
               edges_max=int(counts.max()),
               edges_cv=round(float(counts.std() / mean), 4) if mean else 0.0)
    if total_slots is not None:
        out["shard_fill"] = round(
            float((counts > 0).sum() / max(total_slots, 1)), 4)
    return out


def stride_mapping(n: int, q: int) -> np.ndarray:
    iv = math.ceil(n / q)
    v = np.arange(n, dtype=np.int64)
    return np.argsort(np.argsort((v % q) * iv + v // q)).astype(np.int32)


# ---------------------------------------------------------------------------
# the four accelerator models, BFS with every optimization on
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """A semantic execution: per-phase channel streams and counters."""

    values: np.ndarray
    iterations: int
    phases: list  # [phase][channel] -> Trace (phases with some request)
    stats: list  # per iteration: dict of counters
    layout: dict


def _iter_stats(total: int) -> dict:
    return dict(edges_read=0, values_read=0, values_written=0,
                updates_read=0, updates_written=0, partitions_skipped=0,
                partitions_total=total)


def _add_phase(phases: list, channel_traces: list) -> None:
    if any(t.n for t in channel_traces):
        phases.append(channel_traces)


def _levels_init(n: int, root: int) -> np.ndarray:
    v = np.full(n, np.inf, dtype=np.float32)
    v[root] = 0.0
    return v


def accugraph(g: Graph, root: int, ivl: int, max_iters: int) -> Run:
    """Pull-based, in-CSR per source interval, immediate propagation,
    partition and prefetch skipping."""
    n = g.n
    k = max(1, math.ceil(n / ivl))
    order, bounds = _grouped(g.src // ivl, k)
    parts = []
    lay = Layout()
    lay.alloc("values", n * 4)
    for p in range(k):
        idx = order[bounds[p]:bounds[p + 1]]
        idx = idx[np.argsort(g.dst[idx], kind="stable")]
        ud, inv = np.unique(g.dst[idx], return_inverse=True)
        parts.append((g.src[idx], ud, inv))
        lay.alloc(f"ptrs{p}", (n + 1) * 4)
        lay.alloc(f"neigh{p}", max(len(idx), 1) * 4)
    values = _levels_init(n, root)
    phases, stats = [], []
    dirty = np.ones(k, dtype=bool)
    onchip = -1
    iters = 0
    for _ in range(max_iters):
        iters += 1
        st = _iter_stats(k)
        iter_trace = []
        any_change = False
        for p in range(k):
            if not dirty[p]:
                st["partitions_skipped"] += 1
                continue
            dirty[p] = False
            src, ud, inv = parts[p]
            lo, hi = _interval(p, ivl, n)
            acc = np.full(len(ud), INF, dtype=np.float32)
            np.minimum.at(acc, inv, values[src] + np.float32(1.0))
            old = values[ud]
            new = np.minimum(old, acc)
            wchanged = ud[new < old]
            values[ud] = new
            if len(wchanged):
                any_change = True
                dirty[np.unique(wchanged // ivl)] = True
            streams = []
            if onchip != p:
                streams.append(seq_read(lay["values"] + lo * 4, (hi - lo) * 4))
                st["values_read"] += hi - lo
            onchip = p
            ptrs = seq_read(lay[f"ptrs{p}"], (n + 1) * 4)
            if k > 1:
                valptr = round_robin(seq_read(lay["values"], n * 4), ptrs)
                st["values_read"] += n
            else:
                valptr = ptrs
            neigh = seq_read(lay[f"neigh{p}"], len(src) * 4)
            st["edges_read"] += len(src)
            writes = random_write(lay["values"], wchanged, 4)
            st["values_written"] += len(wchanged)
            streams.append(proportional_interleave(valptr, neigh, writes))
            iter_trace.append(concat(*streams))
        _add_phase(phases, [concat(*iter_trace)] if iter_trace else [empty()])
        stats.append(st)
        if not any_change or not dirty.any():
            break
    sizes = [bounds[p + 1] - bounds[p] for p in range(k)]
    return Run(values, iters, phases, stats,
               dict(effective_interval=ivl, balance=balance(sizes)))


def foregraph(g: Graph, root: int, ivl: int, n_pes: int,
              max_iters: int) -> Run:
    """Interval-shard grid, stride-mapped ids, PEs on one channel with
    edge shuffling (padded shard groups), shard skipping."""
    n = g.n
    sperm = stride_mapping(n, max(1, -(-n // ivl)))
    g = g.renamed(sperm)
    root = int(sperm[root])
    q = max(1, math.ceil(n / ivl))
    order, bounds = _grouped((g.src // ivl).astype(np.int64) * q
                             + g.dst // ivl, q * q)
    sizes = np.diff(bounds).reshape(q, q)
    shard = {}
    lay = Layout()
    lay.alloc("values", n * 4)
    for i in range(q):
        for j in range(q):
            if sizes[i, j]:
                idx = order[bounds[i * q + j]:bounds[i * q + j + 1]]
                shard[i, j] = (g.src[idx], g.dst[idx])
                lay.alloc(f"sh{i}_{j}", int(sizes[i, j]) * 4)
    values = _levels_init(n, root)
    phases, stats = [], []
    dirty = np.ones(q, dtype=bool)
    shuffle = n_pes > 1
    iters = 0
    for _ in range(max_iters):
        iters += 1
        st = _iter_stats(q * q)
        any_change = False
        pe_traces = [[] for _ in range(n_pes)]
        for i in range(q):
            if not dirty[i]:
                st["partitions_skipped"] += q
                continue
            dirty[i] = False
            pe = i % n_pes
            lo_i, hi_i = _interval(i, ivl, n)
            pe_traces[pe].append(seq_read(lay["values"] + lo_i * 4,
                                          (hi_i - lo_i) * 4))
            st["values_read"] += hi_i - lo_i
            groups = ([list(range(jj, min(jj + n_pes, q)))
                       for jj in range(0, q, n_pes)] if shuffle
                      else [[j] for j in range(q)])
            for group in groups:
                group = [j for j in group if sizes[i, j] > 0]
                if not group:
                    continue
                pad = max(int(sizes[i, j]) for j in group)
                for j in group:
                    lo_j, hi_j = _interval(j, ivl, n)
                    src, dst = shard[i, j]
                    acc = np.full(hi_j - lo_j, INF, dtype=np.float32)
                    np.minimum.at(acc, dst - lo_j, values[src] + np.float32(1.0))
                    old = values[lo_j:hi_j]
                    nv = np.minimum(old, acc)
                    changed = (nv < old).nonzero()[0] + lo_j
                    values[lo_j:hi_j] = nv
                    if len(changed):
                        any_change = True
                        dirty[np.unique(changed // ivl)] = True
                    n_edges = pad if shuffle else int(sizes[i, j])
                    pe_traces[pe].append(concat(
                        seq_read(lay["values"] + lo_j * 4, (hi_j - lo_j) * 4),
                        seq_read(lay[f"sh{i}_{j}"], n_edges * 4),
                        seq_write(lay["values"] + lo_j * 4, (hi_j - lo_j) * 4)))
                    st["values_read"] += hi_j - lo_j
                    st["values_written"] += hi_j - lo_j
                    st["edges_read"] += n_edges
        pe_cat = [concat(*trs) for trs in pe_traces if trs]
        if pe_cat:
            _add_phase(phases, [pe_cat[0] if len(pe_cat) == 1
                                else proportional_interleave(*pe_cat)])
        stats.append(st)
        if not any_change or not dirty.any():
            break
    return Run(values[sperm], iters, phases, stats,
               dict(effective_interval=ivl,
                    balance=balance(sizes.ravel(), total_slots=q * q)))


def hitgraph(g: Graph, root: int, ivl: int, p: int, max_iters: int) -> Run:
    """Edge-centric scatter/gather over source intervals: edges sorted by
    destination, update combining, update filtering, partition skipping;
    partition i on channel i % p."""
    n = g.n
    k = max(1, math.ceil(n / ivl))
    order, bounds = _grouped(g.src // ivl, k)
    prep = []
    lays = [Layout() for _ in range(p)]
    for i in range(k):
        idx = order[bounds[i]:bounds[i + 1]]
        idx = idx[np.argsort(g.dst[idx], kind="stable")]
        src, dst = g.src[idx], g.dst[idx]
        route, jb = _grouped(dst // ivl, k)
        prep.append((src, dst, route, jb))
        lo, hi = _interval(i, ivl, n)
        lays[i % p].alloc(f"vals{i}", (hi - lo) * 4)
        lays[i % p].alloc(f"edges{i}", max(len(idx), 1) * 8)
    for j in range(k):
        lays[j % p].alloc(f"upd{j}", max(g.m, 1) * 8)
    values = _levels_init(n, root)
    active = np.ones(n, dtype=bool)
    dirty = np.ones(k, dtype=bool)
    phases, stats = [], []
    iters = 0
    for _ in range(max_iters):
        iters += 1
        st = _iter_stats(k)
        scatter = [[] for _ in range(p)]
        upd_dst = [[] for _ in range(k)]
        upd_val = [[] for _ in range(k)]
        for i in range(k):
            if not dirty[i]:
                st["partitions_skipped"] += 1
                continue
            ch = i % p
            src, dst, route, jb0 = prep[i]
            lo, hi = _interval(i, ivl, n)
            kept = active[src][route]
            routed = route[kept]
            jb = np.concatenate(([0], np.cumsum(kept, dtype=np.int64)))[jb0]
            dst_r = dst[routed]
            cand = values[src[routed]] + np.float32(1.0)
            for j in range(k):
                if jb[j] == jb[j + 1]:
                    continue
                d, v = dst_r[jb[j]:jb[j + 1]], cand[jb[j]:jb[j + 1]]
                jlo, jhi = _interval(j, ivl, n)
                acc = np.full(jhi - jlo, INF, dtype=np.float32)
                np.minimum.at(acc, d - jlo, v)
                d = np.unique(d)
                upd_dst[j].append(d)
                upd_val[j].append(acc[d - jlo])
            scatter[ch].append(concat(
                seq_read(lays[ch][f"vals{i}"], (hi - lo) * 4),
                seq_read(lays[ch][f"edges{i}"], len(src) * 8)))
            st["values_read"] += hi - lo
            st["edges_read"] += len(src)
        nupd = [sum(len(a) for a in upd_dst[j]) for j in range(k)]
        upd_writes = [[] for _ in range(p)]
        for j in range(k):
            if nupd[j]:
                st["updates_written"] += nupd[j]
                upd_writes[j % p].append(
                    seq_write(lays[j % p][f"upd{j}"], nupd[j] * 8))
        _add_phase(phases, [proportional_interleave(concat(*scatter[ch]),
                                                    concat(*upd_writes[ch]))
                            for ch in range(p)])
        new_values = values.copy()
        changed_all = np.zeros(n, dtype=bool)
        any_change = False
        gather = [[] for _ in range(p)]
        for j in range(k):
            if not nupd[j]:
                continue
            ch = j % p
            lo, hi = _interval(j, ivl, n)
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
            gather[ch].append(concat(
                seq_read(lays[ch][f"vals{j}"], (hi - lo) * 4),
                proportional_interleave(
                    seq_read(lays[ch][f"upd{j}"], nupd[j] * 8),
                    random_write(lays[ch][f"vals{j}"], changed - lo, 4))))
            st["values_read"] += hi - lo
            st["values_written"] += len(changed)
        _add_phase(phases, [concat(*trs) for trs in gather])
        dirty = np.zeros(k, dtype=bool)
        dirty[np.unique(changed_all.nonzero()[0] // ivl)] = True
        active = changed_all
        values = new_values
        stats.append(st)
        if not any_change:
            break
    sizes = [bounds[i + 1] - bounds[i] for i in range(k)]
    return Run(values, iters, phases, stats,
               dict(effective_interval=ivl, balance=balance(sizes)))


def thundergp(g: Graph, root: int, ivl: int, p: int, max_iters: int) -> Run:
    """Destination intervals, edges sorted by source, synchronous
    iterations; every iteration re-reads the same static streams.  One
    channel (one chunk per partition)."""
    if p != 1:
        raise Unsupported("ThunderGP with more than one channel")
    n = g.n
    k = max(1, math.ceil(n / ivl))
    order, bounds = _grouped(g.dst // ivl, k)
    lay = Layout()
    lay.alloc("values", n * 4)
    parts = []
    for i in range(k):
        idx = order[bounds[i]:bounds[i + 1]]
        idx = idx[np.argsort(g.src[idx], kind="stable")]
        parts.append((g.src[idx], g.dst[idx], np.unique(g.src[idx])))
        lo, hi = _interval(i, ivl, n)
        lay.alloc(f"edges{i}", max(len(idx), 1) * 8)
        lay.alloc(f"upd{i}", (hi - lo) * 4)
    static = []
    for i, (src, _, usrc) in enumerate(parts):
        lo, hi = _interval(i, ivl, n)
        ni = hi - lo
        static.append((
            concat(seq_read(lay["values"] + lo * 4, ni * 4),
                   proportional_interleave(
                       seq_read(lay[f"edges{i}"], len(src) * 8),
                       random_read(lay["values"], usrc, 4)),
                   seq_write(lay[f"upd{i}"], ni * 4)),
            concat(seq_read(lay[f"upd{i}"], ni * 4),
                   seq_write(lay["values"] + lo * 4, ni * 4))))
    values = _levels_init(n, root)
    phases, stats = [], []
    iters = 0
    for _ in range(max_iters):
        iters += 1
        st = _iter_stats(k)
        any_change = False
        new_values = values.copy()
        for i, (src, dst, usrc) in enumerate(parts):
            lo, hi = _interval(i, ivl, n)
            ni = hi - lo
            acc = np.full(ni, INF, dtype=np.float32)
            np.minimum.at(acc, dst - lo, values[src] + np.float32(1.0))
            st["values_read"] += ni + len(usrc)
            st["edges_read"] += len(src)
            st["updates_written"] += ni
            _add_phase(phases, [static[i][0]])
            nv = np.minimum(new_values[lo:hi], acc)
            any_change |= bool((nv < new_values[lo:hi]).any())
            new_values[lo:hi] = nv
            st["updates_read"] += ni
            st["values_written"] += ni
            _add_phase(phases, [static[i][1]])
        values = new_values
        stats.append(st)
        if not any_change:
            break
    sizes = [bounds[i + 1] - bounds[i] for i in range(k)]
    return Run(values, iters, phases, stats,
               dict(effective_interval=ivl, balance=balance(sizes)))


# ---------------------------------------------------------------------------
# DRAM devices and timing
# ---------------------------------------------------------------------------

# paper Tab. 3: standard, ranks, banks per rank, MT/s, GB/s per channel,
# row-buffer bytes (one channel each for the presets the cells use)
DRAMS = {
    "default": ("DDR4", 1, 16, 2400, 19.2, 8192),
    "ddr3": ("DDR3", 1, 8, 2133, 17.1, 8192),
    "hbm": ("HBM", 1, 16, 1000, 16.0, 2048),
}


@dataclasses.dataclass(frozen=True)
class Device:
    """One channel's timing: cycles of CAS, activate, precharge, row cycle
    and one 64-byte burst; banks; lines per row; page policy."""

    nbanks: int
    lines_per_row: int
    data_rate: int
    bw: float
    page_open: bool

    @property
    def tck_ns(self) -> float:
        return 2000.0 / self.data_rate

    def cycles(self, ns: float) -> int:
        return max(1, math.floor(ns / self.tck_ns + 0.5))

    @property
    def timings(self) -> tuple[int, int, int, int, int]:
        # tCL = tRCD = tRP = 11 ns, tRC = 28 ns, burst = 64 B at bw GB/s
        c = self.cycles
        return c(11.0), c(11.0), c(11.0), c(28.0), c(LINE / self.bw)


def device_for(dram: str, page_policy: str, pseudo_channels: bool) -> tuple[Device, int]:
    """The per-channel device and the number of channels a stream is dealt
    over (2 in HBM pseudo-channel mode: half the banks, half the bus)."""
    std, ranks, banks, rate, bw, row_bytes = DRAMS[dram]
    nb = ranks * banks
    split = 1
    if pseudo_channels:
        if std != "HBM":
            raise Unsupported("pseudo-channels outside HBM")
        nb, bw, split = nb // 2, bw / 2, 2
    return Device(nb, row_bytes // LINE, rate, bw, page_policy == "open"), split


def time_stream(banks: list, rows: list, dev: Device) -> tuple[int, int, int, int]:
    """Cycles, hits, misses, conflicts of one channel's request stream.

    Per bank: the open row, the cycle its row can serve a column, the end of
    its last data slot and its last activate.  The channel's data bus
    carries one 64-byte burst at a time.  A hit streams at the bus rate; a
    miss activates (no sooner than tRC after the bank's last activate, nor
    before its last data slot ends); a conflict precharges after the last
    data slot, then activates.  The controller looks 16 bursts ahead, so a
    precharge or activate may start that far before the bus frees.  Closed
    pages make every request a miss.  CAS latency is paid once, at the
    end."""
    tcl, trcd, trp, trc, tbl = dev.timings
    window = 16 * tbl
    nb = dev.nbanks
    open_row = [-1] * nb
    ready = [0] * nb
    last_data = [0] * nb
    last_act = [-(trc + 1)] * nb
    bus = hits = misses = conflicts = 0
    page_open = dev.page_open
    for b, r in zip(banks, rows):
        horizon = bus - window if bus > window else 0
        if page_open and open_row[b] == r:
            hits += 1
            start = ready[b]
        else:
            if not page_open or open_row[b] == -1:
                misses += 1
                act = max(last_act[b] + trc, last_data[b], horizon)
            else:
                conflicts += 1
                act = max(max(last_data[b], horizon) + trp, last_act[b] + trc)
            last_act[b] = act
            start = ready[b] = act + trcd
            open_row[b] = r
        if start < bus:
            start = bus
        bus = last_data[b] = start + tbl
    return bus + tcl, hits, misses, conflicts


class Timer:
    """Times streams under one device, once per distinct stream."""

    def __init__(self):
        self._memo: dict = {}

    def __call__(self, t: Trace, dev: Device) -> dict:
        if t.n > SCAN_CUTOFF:
            raise Unsupported(f"a stream of {t.n} requests (over the scan "
                              f"cutoff) is timed analytically by the program")
        key = (dev, hashlib.sha256(t.lines.tobytes()).digest())
        hit = self._memo.get(key)
        if hit is None:
            lpr, nb = dev.lines_per_row, dev.nbanks
            banks = ((t.lines // lpr) % nb).tolist()
            rows = (t.lines // (lpr * nb)).tolist()
            hit = self._memo[key] = time_stream(banks, rows, dev)
        cycles, hits, misses, conflicts = hit
        return dict(time_ns=cycles * dev.tck_ns, hits=hits, misses=misses,
                    conflicts=conflicts, nbytes=t.n * LINE)


# ---------------------------------------------------------------------------
# one scenario -> its row's simulated statistics
# ---------------------------------------------------------------------------

ACCELERATORS = ("accugraph", "foregraph", "hitgraph", "thundergp")


def execute(accel: str, g: Graph, root: int, interval: int, n_pes: int,
            max_iters: int) -> Run:
    if accel == "accugraph":
        return accugraph(g, root, interval, max_iters)
    if accel == "foregraph":
        return foregraph(g, root, interval, n_pes, max_iters)
    if accel == "hitgraph":
        return hitgraph(g, root, interval, n_pes, max_iters)
    if accel == "thundergp":
        return thundergp(g, root, interval, n_pes, max_iters)
    raise Unsupported(f"accelerator {accel!r}")


def degree_skewness(g: Graph) -> float:
    d = g.degrees_out.astype(np.float64)
    mu, sigma = d.mean(), d.std()
    return 0.0 if sigma == 0 else float(np.mean(((d - mu) / sigma) ** 3))


def row_stats(run: Run, g: Graph, dram: str, page_policy: str,
              pseudo_channels: bool, timer: Timer) -> dict:
    """Time every phase's channel streams (a phase lasts as long as its
    slowest channel) and return the row's simulated statistics, with the
    hash of the request streams in timing order."""
    dev, split = device_for(dram, page_policy, pseudo_channels)
    phase_ns, streams = [], []
    hits = misses = conflicts = nbytes = widest = 0
    for channel_traces in run.phases:
        if split > 1:
            channel_traces = [s for t in channel_traces
                              for s in split_round_robin(t, split, 1)]
        slowest = 0.0
        for t in channel_traces:
            if not t.n:
                continue
            streams.append(t)
            r = timer(t, dev)
            slowest = max(slowest, r["time_ns"])
            hits += r["hits"]
            misses += r["misses"]
            conflicts += r["conflicts"]
            nbytes += r["nbytes"]
        phase_ns.append(slowest)
        widest = max(widest, sum(1 for t in channel_traces if t.n))
    time_ns = float(sum(phase_ns))
    edges = sum(s["edges_read"] for s in run.stats)
    values_read = sum(s["values_read"] for s in run.stats)
    bal = run.layout["balance"]
    it = max(run.iterations, 1)
    return dict(
        n=g.n, m=g.m,
        runtime_s=time_ns * 1e-9,
        mteps=g.m / max(time_ns * 1e-3, 1e-12),
        mreps=edges / max(time_ns * 1e-3, 1e-12),
        iterations=run.iterations,
        bytes_per_edge=nbytes / max(g.m, 1),
        values_read_per_iteration=values_read / it,
        edges_read_per_iteration=edges / it,
        row_hits=hits, row_misses=misses, row_conflicts=conflicts,
        bw_utilization=nbytes / max(time_ns * dev.bw * max(widest, 1), 1e-9),
        avg_degree=g.m / max(g.n, 1),
        degree_skewness=degree_skewness(g),
        effective_interval=run.layout["effective_interval"],
        partitions=bal["partitions"],
        edges_per_partition_min=bal["edges_min"],
        edges_per_partition_max=bal["edges_max"],
        edges_per_partition_cv=bal["edges_cv"],
        shard_fill=bal.get("shard_fill"),
        partitions_skipped=sum(s["partitions_skipped"] for s in run.stats),
        trace_hash=stream_hash(streams)[:16],
    )
