"""Device-resident semantic execution (the ``semexec`` axis).

The accelerator models' semantic halves — the per-iteration edge
processing that decides values, update counts and changed sets — run
host-side in numpy by default (the seed's design: trace generation as
offline preprocessing, mirroring the paper's C++ environment).  This
module provides the ``device`` engine: the same semantics expressed as
fused, scatter-free JAX dispatches (see *reduce plans* below), with
graph state (value vectors, frontier bitmaps) resident on the device
across iterations.  Per iteration only small products cross the host
boundary — a changed bitmap, per-partition update counts, per-interval
dirty flags — exactly what trace assembly (which stays host-side: the
lazy trace IR needs eager lengths for merge orders) and the termination
logic consume.

Byte identity contract (tests/test_semexec.py):

- min problems (bfs/wcc/sssp) use f32 min-propagation, which is
  order-independent and exact, and the per-edge candidate arithmetic is
  the identical IEEE op sequence — so values, iteration counts, changed
  sets and therefore request traces are *bit-identical* to the numpy
  engine.
- acc problems (pr/spmv) have value-independent traces in all four
  models (update counts and changed destination sets are static for a
  single accumulation iteration), so traces stay byte-identical while
  values match to float tolerance (segment-sum association order differs
  from ``np.add.at``).

Reductions: every backend runs the same program.  The edge layouts are
static across iterations, so every per-segment min/sum/max is
precomputed host-side into a *reduce plan*: the segments grouped into
width classes, one dense block of slots each, with every per-edge input
stored by the layout builders in those blocks (*table order*).  A
reduction is then a dense row reduction of each block and one gather over
the segments, with no scatter and no gather over edges.  See
:func:`build_reduce_plan`.  (The Pallas kernels under ``repro.kernels``
are not on this path: the TPU compiler refuses their 1-D gathers.)

``resolve_engine`` maps a requested engine to the effective one: combos
without a device formulation fall back to numpy with a one-time warning.
Per-graph padded device layouts are built once and cached in
``hostcache.ARTIFACTS`` keyed on the graph fingerprint.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hostcache import ARTIFACTS
from repro.spans import span, spanned

ENGINES = ("numpy", "device")

# (accelerator -> problems) with a device formulation.  Everything a model
# supports is covered except weighted problems on models that don't take
# weights (those raise before engine resolution anyway).
SUPPORTED: dict[str, frozenset] = {
    "hitgraph": frozenset({"bfs", "wcc", "sssp", "pr", "spmv"}),
    "thundergp": frozenset({"bfs", "wcc", "sssp", "pr", "spmv"}),
    "accugraph": frozenset({"bfs", "wcc", "pr"}),
    "foregraph": frozenset({"bfs", "wcc", "pr"}),
}

_FALLBACK_WARNED: set[tuple[str, str]] = set()

# jitted step dispatches in this process; serve seats report the change
# over each chunk beside the timing engine's dispatch counters
_STEPS = dict(semexec_steps=0, semexec_gathered=0)


def step_stats() -> dict:
    """``semexec_steps``: the jitted per-iteration (or per-partition)
    steps this process has dispatched; ``semexec_gathered``: the index
    elements those steps gathered (each layout's static count a step)."""
    return dict(_STEPS)


def _dispatched(gathered: int) -> None:
    _STEPS["semexec_steps"] += 1
    _STEPS["semexec_gathered"] += gathered


def validate_engine(engine: str) -> None:
    if engine not in ENGINES:
        raise ValueError(
            f"unknown semantic engine {engine!r}; expected one of {ENGINES}")


def resolve_engine(accel: str, problem_name: str, requested: str) -> str:
    """Effective engine for (accelerator, problem): ``device`` when a
    device formulation exists, else ``numpy`` with a one-time warning."""
    validate_engine(requested)
    if requested == "numpy":
        return "numpy"
    if problem_name in SUPPORTED.get(accel, frozenset()):
        return "device"
    key = (accel, problem_name)
    if key not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(key)
        warnings.warn(
            f"semexec: no device formulation for {accel}/{problem_name}; "
            f"falling back to the numpy engine", UserWarning, stacklevel=2)
    return "numpy"


@spanned("semexec_wait")
def to_host(x) -> np.ndarray:
    """The host copy of a device result: blocks until the jitted steps
    that produce it have run (a ``semexec_wait`` span, like every step's
    host sync)."""
    return np.asarray(x)


# ---------------------------------------------------------------------------
# per-edge deltas and weights (host-side, one-time per graph layout)
# ---------------------------------------------------------------------------


def _min_delta(problem_name: str, w: np.ndarray | None, m: int) -> np.ndarray:
    """Additive per-edge delta of the min problems (cand = v[src] + delta)."""
    if problem_name == "bfs":
        return np.ones(m, dtype=np.float32)
    if problem_name == "wcc":
        return np.zeros(m, dtype=np.float32)
    if problem_name == "sssp":
        return np.asarray(w, dtype=np.float32)
    raise ValueError(problem_name)


def _acc_weight(problem_name: str, src: np.ndarray,
                w: np.ndarray | None, deg_out: np.ndarray) -> np.ndarray:
    """Multiplicative per-edge weight of the acc problems
    (cand = v[src] * w_eff)."""
    if problem_name == "pr":
        inv = (1.0 / np.maximum(deg_out, 1.0)).astype(np.float32)
        return inv[src]
    if problem_name == "spmv":
        return np.asarray(w, dtype=np.float32)
    raise ValueError(problem_name)


# ---------------------------------------------------------------------------
# reduce plans: segment reductions in table order
# ---------------------------------------------------------------------------
#
# XLA's CPU scatter lowering is a serial per-element loop (~8x slower than
# numpy's ufunc.at on this class of workload), and the TPU compiler refuses
# the Pallas scatter kernels' 1-D gathers.  But the segment-id arrays here
# (destination vertex, (source partition, destination) pair, interval) are
# *static* across iterations, so the reduction structure is precomputed
# host-side once per layout:
#
# - the non-empty segments are grouped into at most ``_MAX_CLASSES`` width
#   classes (:func:`_class_widths`); each class is a dense block of slots,
#   one row of ``width`` slots a segment, holding the positions of the
#   segment's inputs and -1 for padding;
# - the layout builders store every per-edge input in those blocks
#   (:func:`table_order`) with identity padding, so a reduction is one
#   dense row reduction a block, with no gather over edges;
# - a static inverse gather ``inv`` maps every segment id to its row in the
#   concatenated per-class results (empty segments map to a trailing
#   identity slot).
#
# A block with at least as many rows as slots a row is stored transposed,
# [width, rows], so that its long axis lies along the TPU's lanes.  A plan
# over a vector the step computes (a second stage over another plan's rows,
# vertices to intervals) gathers the vector into its blocks instead, and
# such a plan of a single segment is a plain reduction of the vector.
#
# Min is exact (order-independent); sums associate in a fixed per-row tree
# order (covered by the acc allclose contract).

_MAX_CLASSES = 16


def _class_widths(sizes: np.ndarray) -> np.ndarray:
    """Ascending row widths for the positive segment sizes ``sizes``: at
    most ``_MAX_CLASSES`` widths that minimise the blocks' slots, each
    segment in the narrowest width that holds it.  Dynamic programming over
    the distinct sizes, rounded up to 32 steps an octave where there are
    more than 512 of them."""
    u, c = np.unique(sizes, return_counts=True)
    if len(u) > 512:
        step = 1 << np.maximum(np.log2(u).astype(np.int64) - 5, 0)
        u, back = np.unique(-(-u // step) * step, return_inverse=True)
        c = np.bincount(back, weights=c)
    if len(u) <= _MAX_CLASSES:
        return u
    d = len(u)
    cc = np.concatenate([[0], np.cumsum(c)])
    a, b = np.arange(d + 1)[:, None], np.arange(d + 1)[None, :]
    # slots[a, b]: one class holding the sizes u[a:b], of width u[b - 1]
    slots = np.where(a < b, u[np.maximum(b - 1, 0)] * (cc[b] - cc[a]),
                     np.inf)
    best = np.full(d + 1, np.inf)
    best[0] = 0.0
    args = []
    for _ in range(_MAX_CLASSES):
        total = best[:, None] + slots
        args.append(total.argmin(axis=0))
        best = total.min(axis=0)
    widths, j = [], d
    for arg in reversed(args):
        widths.append(u[j - 1])
        j = arg[j]
    return np.array(widths[::-1])


@partial(jax.tree_util.register_dataclass, data_fields=["take", "inv"],
         meta_fields=["axes", "num_segments", "whole"])
@dataclasses.dataclass(frozen=True)
class ReducePlan:
    """A static segment reduction (see the notes above).  ``take``: for a
    plan over a vector, each block's gather from it (padding reads an
    identity appended to it), else empty; ``inv``: the row of each segment
    (None where there is no block); ``axes``: each block's reduced axis;
    ``whole``: a plan over a vector of one segment, a plain reduction."""
    take: tuple
    inv: jax.Array | None
    axes: tuple
    num_segments: int
    whole: bool = False

    def gathered(self) -> int:
        """Index elements an evaluation of the plan gathers."""
        n = sum(int(t.size) for t in self.take)
        return n + (0 if self.inv is None else int(self.inv.size))


def build_reduce_plan(seg, num_segments: int, *, over_vector: bool = False):
    """Precompute the reduction of inputs grouped by the static segment ids
    ``seg`` into ``num_segments`` results.  Returns ``(plan, slots)``:
    ``slots`` holds per block the position in ``seg`` of each slot's input
    (-1: padding), the order :func:`table_order` stores per-edge inputs
    in.  With ``over_vector`` the plan gathers its blocks from the vector
    it is evaluated on, for vectors the step computes."""
    seg = np.asarray(seg, dtype=np.int64)
    m = len(seg)
    if over_vector and num_segments == 1:
        return ReducePlan((), None, (), 1, whole=True), ()
    counts = np.bincount(seg, minlength=num_segments)
    nz = np.flatnonzero(counts)
    if not len(nz):
        return ReducePlan((), None, (), num_segments), ()
    order = np.argsort(seg, kind="stable")
    ptr = np.concatenate([[0], np.cumsum(counts)])
    widths = _class_widths(counts[nz])
    cls = np.searchsorted(widths, counts[nz])
    inv = np.full(num_segments, len(nz), dtype=np.int32)
    slots, axes, total = [], [], 0
    for c in np.unique(cls):
        width = int(widths[c])
        rows = nz[cls == c]
        col = np.arange(width)[None, :]
        live = col < counts[rows][:, None]
        blk = np.full((len(rows), width), -1, dtype=np.int64)
        blk[live] = order[(ptr[rows][:, None] + col)[live]]
        if len(rows) >= width:  # transposed: the long axis on the lanes
            slots.append(np.ascontiguousarray(blk.T))
            axes.append(0)
        else:
            slots.append(blk)
            axes.append(1)
        inv[rows] = total + np.arange(len(rows))
        total += len(rows)
    take = tuple(jnp.asarray(np.where(s >= 0, s, m).astype(np.int32))
                 for s in slots) if over_vector else ()
    return (ReducePlan(take, jnp.asarray(inv), tuple(axes), num_segments),
            tuple(slots))


def table_order(slots, a, fill, dtype) -> tuple:
    """``a`` stored in a plan's table order: per block, the input of each
    slot, ``fill`` in padding, on the device."""
    a = np.asarray(a)
    return tuple(
        jnp.asarray(np.where(s >= 0, a[np.maximum(s, 0)], fill).astype(dtype))
        for s in slots)


def row_segments(plan: ReducePlan, slots, seg) -> np.ndarray:
    """The segment id of each row of :func:`plan_rows`' results, for a
    second stage over the rows."""
    seg = np.asarray(seg)
    return np.concatenate(
        [seg[s[0] if ax == 0 else s[:, 0]] for s, ax in zip(slots, plan.axes)]
        + [np.zeros(0, dtype=seg.dtype)])


_PLAN_IDENTITY = {"min": np.inf, "sum": 0, "max": 0}
_PLAN_REDUCE = {"min": jnp.min, "sum": jnp.sum, "max": jnp.max}


def _identity(x, kind: str):
    leaves = jax.tree.leaves(x)
    dtype = leaves[0].dtype if leaves else jnp.float32
    return jnp.asarray(_PLAN_IDENTITY[kind], dtype)


def plan_rows(plan: ReducePlan, x, kind: str):
    """Each block's row results, concatenated in row order: ``x`` is the
    tuple of table-order blocks, or the vector a plan over a vector gathers
    from."""
    if plan.take:
        ext = jnp.concatenate([x, _identity(x, kind)[None]])
        x = tuple(jnp.take(ext, t, axis=0) for t in plan.take)
    red = _PLAN_REDUCE[kind]
    return jnp.concatenate([red(b, axis=ax) for b, ax in zip(x, plan.axes)])


def apply_reduce_plan(plan: ReducePlan, x, kind: str):
    """Evaluate a reduce plan (jit-traceable: every shape is static).  ``kind``
    is min | sum | max; the max identity is 0, so max plans are only valid
    for non-negative inputs (they are used on 0/1 flags here)."""
    ident = _identity(x, kind)
    if plan.whole:
        return _PLAN_REDUCE[kind](x)[None]
    if not plan.axes:
        return jnp.full(plan.num_segments, ident)
    rows = plan_rows(plan, x, kind)
    return jnp.take(jnp.concatenate([rows, ident[None]]), plan.inv, axis=0)


def _edge_gathers(src) -> int:
    """Index elements of the per-edge value gather over table-order
    source blocks."""
    return sum(int(s.size) for s in src)


# ---------------------------------------------------------------------------
# jitted per-iteration steps
# ---------------------------------------------------------------------------


def _min_candidates(values, src, delta):
    """Per-slot min candidates ``values[src] + delta``, +inf in padding
    (``delta``: table-order blocks or one scalar)."""
    if not isinstance(delta, tuple):
        delta = (delta,) * len(src)
    return tuple(jnp.where(s >= 0, jnp.take(values, jnp.maximum(s, 0)) + d,
                           jnp.inf) for s, d in zip(src, delta))


def _acc_candidates(values, src, w):
    """Per-slot acc candidates ``values[src] * w``, 0 in padding."""
    return tuple(jnp.where(s >= 0, jnp.take(values, jnp.maximum(s, 0)) * ws,
                           jnp.float32(0.0)) for s, ws in zip(src, w))


@partial(jax.jit, static_argnames=("ivl", "use_filter", "use_skip",
                                   "combine"))
def _hitgraph_min_step(values, active, proc, src, delta, plans, *, ivl,
                       use_filter, use_skip, combine):
    """One HitGraph scatter+gather iteration, fused: global masked
    segment-min plus the per-destination-partition update counts the trace
    assembly needs.

    The model's masks are properties of an edge's source, since partitions
    are source intervals of ``ivl`` vertices: partition skipping keeps the
    sources in processed partitions, update filtering the active sources.
    So one vector carries the value and the mask together: the value of a
    kept source and -1 for a dropped one (min-problem values are >= 0 or
    +inf), and a slot is kept where its one gather reads >= 0.  Unreached
    kept sources (+inf) still count as updates.  With update combining the
    count per partition j is the number of (source partition, destination)
    runs holding a kept edge: dst is sorted within each routed block, so
    runs are the rows of the ``dst`` plan."""
    n = values.shape[0]
    keep = jnp.ones(n, dtype=bool)
    if use_skip:
        keep &= jnp.repeat(proc, ivl)[:n]
    if use_filter:
        keep &= active
    enc = jnp.where(keep, values, -1.0)
    sv = tuple(jnp.where(s >= 0, jnp.take(enc, jnp.maximum(s, 0)), -1.0)
               for s in src)
    kept = tuple(v >= 0 for v in sv)
    cand = tuple(jnp.where(k, v + d, jnp.inf)
                 for k, v, d in zip(kept, sv, delta))
    if plans["up"] is None:
        acc = apply_reduce_plan(plans["dst"], cand, "min")
    else:
        acc = apply_reduce_plan(plans["up"],
                                plan_rows(plans["dst"], cand, "min"), "min")
    runs = plan_rows(plans["dst"], tuple(k.astype(jnp.int32) for k in kept),
                     "sum")
    if combine:
        runs = (runs > 0).astype(jnp.int32)
    nupd = apply_reduce_plan(plans["jid"], runs, "sum")
    return jnp.minimum(values, acc), acc < values, nupd


@jax.jit
def _jacobi_min_step(values, src, delta, plan):
    """ThunderGP's synchronous iteration: the per-(partition, chunk)
    partial accumulations combine to exactly the global segment-min
    (disjoint destination intervals, Jacobi source snapshot)."""
    acc = apply_reduce_plan(plan, _min_candidates(values, src, delta), "min")
    return jnp.minimum(values, acc), jnp.any(acc < values)


@jax.jit
def _acc_step(values, src, w, base, scale, plan):
    """Shared accumulation iteration: new = base + scale * A @ values,
    with A[dst, src] = w_eff."""
    y = apply_reduce_plan(plan, _acc_candidates(values, src, w), "sum")
    return base + scale * y


@jax.jit
def _gs_min_step(values, esrc, delta, plan):
    """One AccuGraph partition under Gauss-Seidel (live values): segment
    min over the destinations, +inf (a no-op) where the partition has no
    edge.  The changed flags cover every vertex; the host reads them at
    the partition's unique destinations."""
    acc = apply_reduce_plan(plan, _min_candidates(values, esrc, delta), "min")
    return jnp.minimum(values, acc), acc < values


@jax.jit
def _gs_acc_step(values, snapshot, esrc, ew, scale, plan):
    """One AccuGraph partition of an accumulation iteration (reads the
    pre-iteration snapshot, adds into the base-initialised values)."""
    acc = apply_reduce_plan(plan, _acc_candidates(snapshot, esrc, ew), "sum")
    return values + scale * acc


@jax.jit
def _fg_min_step(values, asrc, bsrc, csrc, delta, plans):
    """One ForeGraph source-interval visit, fused into three sequential
    segment-mins that reproduce the shard-order Gauss-Seidel exactly:
    shards (i, j<i) read the still-pristine source interval i and write
    disjoint intervals; shard (i, i) reads pre-state and writes interval
    i; shards (i, j>i) read the post-(i,i) interval i.  Returns the
    values and per-interval changed flags (the dirty bits)."""

    def sub(v, s, plan):
        if not s:  # no shard in this group
            return v, jnp.zeros(v.shape, dtype=bool)
        acc = apply_reduce_plan(plan, _min_candidates(v, s, delta), "min")
        return jnp.minimum(v, acc), acc < v

    v1, c1 = sub(values, asrc, plans["a"])
    v2, c2 = sub(v1, bsrc, plans["b"])
    v3, c3 = sub(v2, csrc, plans["c"])
    changed = (c1 | c2 | c3).astype(jnp.int32)
    return v3, apply_reduce_plan(plans["ipq"], changed, "max")


# ---------------------------------------------------------------------------
# HitGraph
# ---------------------------------------------------------------------------


def _build_hitgraph_min(g, problem, prep, k: int, ivl: int) -> dict:
    srcs, dsts, dls, ps = [], [], [], []
    for i in range(k):
        pi = prep[i]
        r = pi["route"]
        srcs.append(pi["src"][r])
        dsts.append(pi["dst"][r])
        ps.append(np.full(len(r), i, dtype=np.int64))
        if problem.name == "sssp":
            dls.append(pi["w"][r])
    gsrc = np.concatenate(srcs).astype(np.int32)
    gdst = np.concatenate(dsts).astype(np.int64)
    gpart = np.concatenate(ps)
    m = len(gsrc)
    delta = (np.concatenate(dls).astype(np.float32) if dls
             else _min_delta(problem.name, None, m))
    # the plan's rows are the (source partition, destination) runs update
    # combining collapses to; where no destination is reached from two
    # partitions (always with one partition) they are the destinations
    pairs, pair = np.unique(gpart * g.n + gdst, return_inverse=True)
    if len(pairs) == len(np.unique(gdst)):
        dst, slots = build_reduce_plan(gdst, g.n)
        row_dst = row_segments(dst, slots, gdst)
        up = None
    else:
        dst, slots = build_reduce_plan(pair, len(pairs))
        row_dst = pairs[row_segments(dst, slots, pair)] % g.n
        up, _ = build_reduce_plan(row_dst, g.n, over_vector=True)
    jid, _ = build_reduce_plan(row_dst // ivl, k, over_vector=True)
    src = table_order(slots, gsrc, -1, np.int32)
    gathered = _edge_gathers(src) + jid.gathered() + (
        dst.gathered() if up is None else up.gathered())
    return dict(
        src=src,
        delta=table_order(slots, delta, 0.0, np.float32),
        plans=dict(dst=dst, up=up, jid=jid),
        gathered=gathered,
    )


def _acc_layout(g, problem, src, dst, w) -> dict:
    """Table-order layout of one accumulation over every edge."""
    w_eff = _acc_weight(problem.name, src, w, g.degrees_out)
    plan, slots = build_reduce_plan(dst, g.n)
    src_t = table_order(slots, src, -1, np.int32)
    return dict(src=src_t, w=table_order(slots, w_eff, 0.0, np.float32),
                plan=plan, gathered=_edge_gathers(src_t) + plan.gathered())


def _build_hitgraph_acc(g, problem, parts, k: int, ivl: int) -> dict:
    # static trace products: update counts and changed (written) vertex
    # sets per destination partition — value-independent for a single
    # accumulation iteration
    nupd_plain = np.bincount(g.dst // ivl, minlength=k).astype(np.int64)
    pd = (g.src.astype(np.int64) // ivl) * g.n + g.dst
    u = np.unique(pd)
    nupd_combine = np.bincount((u % g.n) // ivl, minlength=k).astype(np.int64)
    ud_all = np.unique(g.dst)
    bounds = [parts.interval(j)[0] for j in range(k)] + [g.n]
    cuts = np.searchsorted(ud_all, bounds)
    changed_j = [ud_all[cuts[j]: cuts[j + 1]] for j in range(k)]
    return dict(
        _acc_layout(g, problem, g.src, g.dst, g.weights),
        nupd_plain=nupd_plain,
        nupd_combine=nupd_combine,
        changed_j=changed_j,
    )


class HitGraphDevice:
    """Device state + per-iteration steps for the HitGraph model."""

    def __init__(self, g, problem, prep, parts, k: int, ivl: int,
                 sort_opt: bool, weighted: bool,
                 filter_opt: bool, skip_opt: bool, combine_opt: bool):
        self.filter_opt = filter_opt
        self.skip_opt = skip_opt
        self.combine_opt = combine_opt
        self.ivl = min(ivl, g.n)  # the vertices of a source partition
        if problem.kind == "min":
            self.lay = ARTIFACTS.get_or_build(
                (g.fingerprint, "semexec.hitgraph", ivl, sort_opt, weighted,
                 problem.name),
                lambda: _build_hitgraph_min(g, problem, prep, k, ivl),
            )
        else:
            base = (1.0 - 0.85) / g.n if problem.name == "pr" else 0.0
            scale = 0.85 if problem.name == "pr" else 1.0
            self.base = jnp.float32(base)
            self.scale = jnp.float32(scale)
            self.lay = ARTIFACTS.get_or_build(
                (g.fingerprint, "semexec.hitgraph", ivl, sort_opt, weighted,
                 problem.name),
                lambda: _build_hitgraph_acc(g, problem, parts, k, ivl),
            )

    def min_step(self, values_dev, active: np.ndarray, proc: np.ndarray):
        lay = self.lay
        _dispatched(lay["gathered"])
        new, changed, nupd = _hitgraph_min_step(
            values_dev, jnp.asarray(active), jnp.asarray(proc),
            lay["src"], lay["delta"], lay["plans"], ivl=self.ivl,
            use_filter=self.filter_opt, use_skip=self.skip_opt,
            combine=self.combine_opt)
        with span("semexec_wait"):
            return new, np.asarray(changed), np.asarray(nupd).astype(np.int64)

    def acc_step(self, values_dev):
        lay = self.lay
        _dispatched(lay["gathered"])
        return _acc_step(values_dev, lay["src"], lay["w"], self.base,
                         self.scale, lay["plan"])

    def nupd_static(self) -> np.ndarray:
        return self.lay["nupd_combine" if self.combine_opt else "nupd_plain"]

    def changed_static(self, j: int) -> np.ndarray:
        return self.lay["changed_j"][j]


# ---------------------------------------------------------------------------
# AccuGraph
# ---------------------------------------------------------------------------


def _build_accugraph(g, problem, part_edges, k: int, ivl: int) -> dict:
    esrc, ew, plan, gathered = [], [], [], []
    ud_host, u_count = [], []
    for p in range(k):
        src, dst, udp, _inv = part_edges[p]
        pl, slots = build_reduce_plan(dst, g.n)
        esrc.append(table_order(slots, src, -1, np.int32))
        plan.append(pl)
        gathered.append(_edge_gathers(esrc[-1]) + pl.gathered())
        ud_host.append(np.asarray(udp))
        u_count.append(len(udp))
        if problem.kind == "acc":
            w_eff = _acc_weight(problem.name, src, None, g.degrees_out)
            ew.append(table_order(slots, w_eff, 0.0, np.float32))
    return dict(esrc=esrc, ew=ew, plan=plan, gathered=gathered,
                ud_host=ud_host, u_count=u_count)


class AccuGraphDevice:
    """Device state + per-partition Gauss-Seidel steps for AccuGraph."""

    def __init__(self, g, problem, part_edges, k: int, ivl: int):
        self.lay = ARTIFACTS.get_or_build(
            (g.fingerprint, "semexec.accugraph", ivl, problem.name),
            lambda: _build_accugraph(g, problem, part_edges, k, ivl),
        )
        if problem.kind == "min":
            self.delta = jnp.float32(1.0 if problem.name == "bfs" else 0.0)
        else:
            self.scale = jnp.float32(0.85 if problem.name == "pr" else 1.0)

    def ud_host(self, p: int) -> np.ndarray:
        return self.lay["ud_host"][p]

    def min_step(self, values_dev, p: int):
        lay = self.lay
        if lay["u_count"][p] == 0:
            return values_dev, np.zeros(0, dtype=bool)
        _dispatched(lay["gathered"][p])
        new, changed = _gs_min_step(values_dev, lay["esrc"][p], self.delta,
                                    lay["plan"][p])
        with span("semexec_wait"):
            return new, np.asarray(changed)[lay["ud_host"][p]]

    def acc_step(self, values_dev, snapshot_dev, p: int):
        lay = self.lay
        if lay["u_count"][p] == 0:
            return values_dev
        _dispatched(lay["gathered"][p])
        return _gs_acc_step(values_dev, snapshot_dev, lay["esrc"][p],
                            lay["ew"][p], self.scale, lay["plan"][p])


# ---------------------------------------------------------------------------
# ThunderGP
# ---------------------------------------------------------------------------


def _build_thundergp(g, problem, prep, k: int, p: int, ivl: int) -> dict:
    srcs = [prep[i][c]["src"] for i in range(k) for c in range(p)]
    dsts = [prep[i][c]["dst"] for i in range(k) for c in range(p)]
    gsrc = np.concatenate(srcs).astype(np.int32)
    gdst = np.concatenate(dsts).astype(np.int64)
    m = len(gsrc)
    if problem.name in ("sssp", "spmv"):
        w = np.concatenate(
            [prep[i][c]["w"] for i in range(k) for c in range(p)])
    else:
        w = None
    if problem.kind == "acc":
        return _acc_layout(g, problem, gsrc, gdst, w)
    plan, slots = build_reduce_plan(gdst, g.n)
    src = table_order(slots, gsrc, -1, np.int32)
    return dict(
        src=src,
        delta=table_order(slots, _min_delta(problem.name, w, m), 0.0,
                          np.float32),
        plan=plan,
        gathered=_edge_gathers(src) + plan.gathered(),
    )


class ThunderGPDevice:
    """Device state + synchronous iteration steps for ThunderGP."""

    def __init__(self, g, problem, prep, k: int, p: int, ivl: int,
                 weighted: bool):
        self.lay = ARTIFACTS.get_or_build(
            (g.fingerprint, "semexec.thundergp", ivl, p, weighted,
             problem.name),
            lambda: _build_thundergp(g, problem, prep, k, p, ivl),
        )
        if problem.kind == "acc":
            base = (1.0 - 0.85) / g.n if problem.name == "pr" else 0.0
            self.base = jnp.float32(base)
            self.scale = jnp.float32(0.85 if problem.name == "pr" else 1.0)

    def min_step(self, values_dev):
        lay = self.lay
        _dispatched(lay["gathered"])
        new, anyc = _jacobi_min_step(values_dev, lay["src"], lay["delta"],
                                     lay["plan"])
        with span("semexec_wait"):
            return new, bool(anyc)

    def acc_step(self, values_dev):
        lay = self.lay
        _dispatched(lay["gathered"])
        return _acc_step(values_dev, lay["src"], lay["w"], self.base,
                         self.scale, lay["plan"])


# ---------------------------------------------------------------------------
# ForeGraph
# ---------------------------------------------------------------------------


def _build_foregraph(g, problem, sizes, shard_edges, interval: int,
                     q: int) -> dict:
    def edges(pairs):
        es = [shard_edges[ij] for ij in pairs if sizes[ij]]
        src = (np.concatenate([s for s, _ in es]).astype(np.int32)
               if es else np.zeros(0, dtype=np.int32))
        dst = (np.concatenate([d for _, d in es]).astype(np.int64)
               if es else np.zeros(0, dtype=np.int64))
        return src, dst

    if problem.kind == "acc":
        src, dst = edges([(i, j) for i in range(q) for j in range(q)])
        return _acc_layout(g, problem, src, dst, None)

    def pack(i: int, js: list[int]):
        src, dst = edges([(i, j) for j in js])
        plan, slots = build_reduce_plan(dst, g.n)
        src_t = table_order(slots, src, -1, np.int32)
        return src_t, plan, _edge_gathers(src_t) + plan.gathered()

    ipq, _ = build_reduce_plan((np.arange(g.n) // interval), q,
                               over_vector=True)
    abc, plans, gathered = [], [], []
    for i in range(q):
        (a, pa, ga), (b, pb, gb), (c, pc, gc) = (
            pack(i, list(range(i))), pack(i, [i]),
            pack(i, list(range(i + 1, q))))
        abc.append((a, b, c))
        plans.append(dict(a=pa, b=pb, c=pc, ipq=ipq))
        gathered.append(ga + gb + gc + ipq.gathered())
    return dict(abc=abc, plans=plans, gathered=gathered)


class ForeGraphDevice:
    """Device state + per-source-interval fused steps for ForeGraph.

    ``min_step`` must be dispatched interval-by-interval with a host sync:
    a later interval's shard-skip decision reads dirty flags that earlier
    intervals of the *same* iteration may have set (immediate
    propagation)."""

    def __init__(self, g, problem, sizes, shard_edges, interval: int,
                 q: int):
        self.lay = ARTIFACTS.get_or_build(
            (g.fingerprint, "semexec.foregraph", interval, problem.name),
            lambda: _build_foregraph(g, problem, sizes, shard_edges,
                                     interval, q),
        )
        if problem.kind == "min":
            self.delta = jnp.float32(1.0 if problem.name == "bfs" else 0.0)
        else:
            base = (1.0 - 0.85) / g.n if problem.name == "pr" else 0.0
            self.base = jnp.float32(base)
            self.scale = jnp.float32(0.85 if problem.name == "pr" else 1.0)

    def min_step(self, values_dev, i: int):
        lay = self.lay
        _dispatched(lay["gathered"][i])
        new, flags = _fg_min_step(values_dev, *lay["abc"][i], self.delta,
                                  lay["plans"][i])
        with span("semexec_wait"):
            return new, np.asarray(flags).astype(bool)

    def acc_step(self, values_dev):
        lay = self.lay
        _dispatched(lay["gathered"])
        return _acc_step(values_dev, lay["src"], lay["w"], self.base,
                         self.scale, lay["plan"])
