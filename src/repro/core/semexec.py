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
precomputed host-side into a *reduce plan* — degree-class gather tables
(a bucketed-ELL layout of the reduction) — and evaluated as pure
gathers + dense row reductions, with no scatter over edges.  See
:func:`build_reduce_plan`.  (The Pallas
kernels under ``repro.kernels`` are not on this path: the TPU compiler
refuses their 1-D gathers.)

``resolve_engine`` maps a requested engine to the effective one: combos
without a device formulation fall back to numpy with a one-time warning.
Per-graph padded device layouts are built once and cached in
``hostcache.ARTIFACTS`` keyed on the graph fingerprint.
"""
from __future__ import annotations

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

_EDGE_BLOCK = 1024  # edge arrays pad to a multiple of this

_FALLBACK_WARNED: set[tuple[str, str]] = set()


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
# padding helpers (host-side, one-time per graph layout)
# ---------------------------------------------------------------------------


def _pow2(x: int, lo: int = 8) -> int:
    p = lo
    while p < x:
        p <<= 1
    return p


def _pad_to(a: np.ndarray, length: int, fill, dtype) -> np.ndarray:
    out = np.full(length, fill, dtype=dtype)
    out[: len(a)] = a
    return out


def _block_len(m: int) -> int:
    return max(-(-m // _EDGE_BLOCK) * _EDGE_BLOCK, _EDGE_BLOCK)


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
# reduce plans: scatter-free segment reductions
# ---------------------------------------------------------------------------
#
# XLA's CPU scatter lowering is a serial per-element loop (~8x slower than
# numpy's ufunc.at on this class of workload), and the TPU compiler refuses
# the Pallas scatter kernels' 1-D gathers.  But the segment-id arrays here
# (destination vertex, partition id, run id) are *static* across
# iterations, so the reduction structure can be precomputed host-side once
# per layout:
#
# - sort edge positions by segment id (stable), bucket the non-empty
#   segments by power-of-two degree class,
# - per class, store a [rows, K] gather table of edge positions, padded
#   with a sentinel position m that indexes an identity slot appended to
#   the per-edge candidate array,
# - store a static inverse gather ``inv`` mapping every segment id to its
#   row in the concatenated per-class results (empty segments map to a
#   trailing identity slot).
#
# Evaluation is then pure gathers + dense row reductions — no scatter at
# all — and is exact for min (order-independent) while sums associate in
# a fixed per-row tree order (covered by the acc allclose contract).


def build_reduce_plan(seg: np.ndarray, num_segments: int):
    """Precompute a scatter-free segment-reduction plan for a static
    segment-id array.  Returns ``(tables, inv)``: a tuple of int32 gather
    tables (one per degree class, padded with sentinel ``len(seg)``) and
    the int32 inverse gather over segment ids."""
    seg = np.asarray(seg)
    m = len(seg)
    order = np.argsort(seg, kind="stable")
    counts = np.bincount(seg, minlength=num_segments) if m else \
        np.zeros(num_segments, dtype=np.int64)
    ptr = np.zeros(num_segments + 1, dtype=np.int64)
    ptr[1:] = np.cumsum(counts)
    nz = np.flatnonzero(counts)
    tables: list = []
    offsets = np.full(num_segments, -1, dtype=np.int64)
    total = 0
    if len(nz):
        deg = counts[nz]
        cls = np.ceil(np.log2(deg)).astype(np.int64)  # deg <= 2**cls
        for c in np.unique(cls):
            K = 1 << int(c)
            rows = nz[cls == c]
            base = ptr[rows][:, None] + np.arange(K)[None, :]
            live = np.arange(K)[None, :] < counts[rows][:, None]
            tbl = np.full(base.shape, m, dtype=np.int64)
            tbl[live] = order[base[live]]
            tables.append(jnp.asarray(tbl.astype(np.int32)))
            offsets[rows] = total + np.arange(len(rows))
            total += len(rows)
    inv = np.where(offsets >= 0, offsets, total).astype(np.int32)
    return tuple(tables), jnp.asarray(inv)


_PLAN_IDENTITY = {"min": np.inf, "sum": 0, "max": 0}
_PLAN_REDUCE = {"min": jnp.min, "sum": jnp.sum, "max": jnp.max}


def apply_reduce_plan(plan, cand, kind: str):
    """Evaluate a reduce plan over per-edge candidates (jit-traceable:
    every shape is static).  ``kind`` is min | sum | max; the max identity
    is 0, so max plans are only valid for non-negative inputs (they are
    used on 0/1 flags here)."""
    tables, inv = plan
    ident = jnp.asarray(_PLAN_IDENTITY[kind], cand.dtype)
    ext = jnp.concatenate([cand, ident[None]])
    red = _PLAN_REDUCE[kind]
    parts = [red(jnp.take(ext, t, axis=0), axis=1) for t in tables]
    cat = jnp.concatenate(parts + [ident[None]])
    return jnp.take(cat, inv, axis=0)


# ---------------------------------------------------------------------------
# jitted per-iteration steps
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("use_filter", "use_skip", "combine"))
def _hitgraph_min_step(values, active, proc, src, delta, part, plans, *,
                       use_filter, use_skip, combine):
    """One HitGraph scatter+gather iteration, fused: global masked
    segment-min plus the per-destination-partition update counts the trace
    assembly needs.  ``kept`` reproduces the model's update-filtering
    (active-source bitmap) and partition-skipping masks; with update
    combining the count per partition j is the number of (source
    partition, destination) runs containing a kept edge — dst is sorted
    within each routed block, so runs == unique destinations."""
    kept = src >= 0
    if use_skip:
        kept &= jnp.take(proc, jnp.maximum(part, 0))
    if use_filter:
        kept &= jnp.take(active, jnp.maximum(src, 0))
    sv = jnp.take(values, jnp.maximum(src, 0))
    cand = jnp.where(kept, sv + delta, jnp.inf)
    acc = apply_reduce_plan(plans["dst"], cand, "min")
    ki = kept.astype(jnp.int32)
    if combine:
        run_has = apply_reduce_plan(plans["run"], ki, "max")
        nupd = apply_reduce_plan(plans["runj"], run_has, "sum")
    else:
        nupd = apply_reduce_plan(plans["jid"], ki, "sum")
    return jnp.minimum(values, acc), acc < values, nupd


@jax.jit
def _jacobi_min_step(values, src, delta, plan):
    """ThunderGP's synchronous iteration: the per-(partition, chunk)
    partial accumulations combine to exactly the global segment-min
    (disjoint destination intervals, Jacobi source snapshot)."""
    sv = jnp.take(values, jnp.maximum(src, 0))
    cand = jnp.where(src >= 0, sv + delta, jnp.inf)
    acc = apply_reduce_plan(plan, cand, "min")
    return jnp.minimum(values, acc), jnp.any(acc < values)


@jax.jit
def _acc_step(values, src, w, base, scale, plan):
    """Shared accumulation iteration: new = base + scale * A @ values,
    with A[dst, src] = w_eff."""
    y = apply_reduce_plan(plan, w * jnp.take(values, src), "sum")
    return base + scale * y


@jax.jit
def _gs_min_step(values, esrc, ud, delta, plan):
    """One AccuGraph partition under Gauss-Seidel (live values): segment
    min over the partition's unique destinations.  Padding edges carry
    cand=+inf and padding ud slots point at vertex 0 with acc=+inf, both
    exact no-ops."""
    sv = jnp.take(values, jnp.maximum(esrc, 0))
    cand = jnp.where(esrc >= 0, sv + delta, jnp.inf)
    acc = apply_reduce_plan(plan, cand, "min")
    changed = acc < jnp.take(values, ud)
    return values.at[ud].min(acc), changed


@jax.jit
def _gs_acc_step(values, snapshot, esrc, ud, ew, scale, plan):
    """One AccuGraph partition of an accumulation iteration (reads the
    pre-iteration snapshot, adds into the base-initialised values)."""
    sv = jnp.take(snapshot, jnp.maximum(esrc, 0))
    cand = jnp.where(esrc >= 0, sv * ew, jnp.float32(0.0))
    acc = apply_reduce_plan(plan, cand, "sum")
    return values.at[ud].add(scale * acc)


@jax.jit
def _fg_min_step(values, asrc, bsrc, csrc, delta, plans):
    """One ForeGraph source-interval visit, fused into three sequential
    segment-mins that reproduce the shard-order Gauss-Seidel exactly:
    shards (i, j<i) read the still-pristine source interval i and write
    disjoint intervals; shard (i, i) reads pre-state and writes interval
    i; shards (i, j>i) read the post-(i,i) interval i.  Returns the
    values and per-interval changed flags (the dirty bits)."""

    def sub(v, s, plan):
        sv = jnp.take(v, jnp.maximum(s, 0))
        cand = jnp.where(s >= 0, sv + delta, jnp.inf)
        acc = apply_reduce_plan(plan, cand, "min")
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
        ps.append(np.full(len(r), i, dtype=np.int32))
        if problem.name == "sssp":
            dls.append(pi["w"][r])
    gsrc = np.concatenate(srcs).astype(np.int32)
    gdst = np.concatenate(dsts).astype(np.int32)
    gpart = np.concatenate(ps)
    m = len(gsrc)
    delta = (np.concatenate(dls).astype(np.float32) if dls
             else _min_delta(problem.name, None, m))
    gjid = (gdst // ivl).astype(np.int32)
    # runs of equal (source partition, destination) in routed order — the
    # unit update combining collapses to (dst is ascending within each
    # routed block when edge sorting is on, which combining requires)
    if m:
        change = np.empty(m, dtype=bool)
        change[0] = True
        change[1:] = (gdst[1:] != gdst[:-1]) | (gpart[1:] != gpart[:-1])
        run_id = (np.cumsum(change) - 1).astype(np.int32)
        runs = int(run_id[-1]) + 1
        run_j = gjid[change]
    else:
        run_id = np.zeros(0, dtype=np.int32)
        runs = 1
        run_j = np.zeros(0, dtype=np.int32)
    L = _block_len(m)
    # padding edges land in segment 0 / run 0 of each plan with kept=0
    # candidates (inf for the min, 0 for the counts) — exact no-ops
    plans = dict(
        dst=build_reduce_plan(_pad_to(gdst, L, 0, np.int32), g.n),
        run=build_reduce_plan(_pad_to(run_id, L, 0, np.int32), runs),
        runj=build_reduce_plan(run_j, k),
        jid=build_reduce_plan(_pad_to(gjid, L, 0, np.int32), k),
    )
    return dict(
        src=jnp.asarray(_pad_to(gsrc, L, -1, np.int32)),
        delta=jnp.asarray(_pad_to(delta, L, 0.0, np.float32)),
        part=jnp.asarray(_pad_to(gpart, L, 0, np.int32)),
        plans=plans,
    )


def _build_hitgraph_acc(g, problem, parts, k: int, ivl: int) -> dict:
    w_eff = _acc_weight(problem.name, g.src, g.weights, g.degrees_out)
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
        src=jnp.asarray(g.src.astype(np.int32)),
        w=jnp.asarray(w_eff),
        plan=build_reduce_plan(g.dst, g.n),
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
        new, changed, nupd = _hitgraph_min_step(
            values_dev, jnp.asarray(active), jnp.asarray(proc),
            lay["src"], lay["delta"], lay["part"], lay["plans"],
            use_filter=self.filter_opt, use_skip=self.skip_opt,
            combine=self.combine_opt)
        with span("semexec_wait"):
            return new, np.asarray(changed), np.asarray(nupd).astype(np.int64)

    def acc_step(self, values_dev):
        lay = self.lay
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
    esrc, ud, ew, plan = [], [], [], []
    ud_host, u_count = [], []
    for p in range(k):
        src, _dst, udp, inv = part_edges[p]
        E = _pow2(len(src))
        U = _pow2(max(len(udp), 1), lo=1)
        esrc.append(jnp.asarray(_pad_to(src, E, -1, np.int32)))
        ud.append(jnp.asarray(_pad_to(udp, U, 0, np.int32)))
        plan.append(build_reduce_plan(_pad_to(inv, E, 0, np.int32), U))
        ud_host.append(np.asarray(udp))
        u_count.append(len(udp))
        if problem.kind == "acc":
            w_eff = _acc_weight(problem.name, src, None, g.degrees_out)
            ew.append(jnp.asarray(_pad_to(w_eff, E, 0.0, np.float32)))
    return dict(esrc=esrc, ud=ud, ew=ew, plan=plan,
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
        new, changed = _gs_min_step(values_dev, lay["esrc"][p],
                                    lay["ud"][p], self.delta, lay["plan"][p])
        with span("semexec_wait"):
            return new, np.asarray(changed)[: lay["u_count"][p]]

    def acc_step(self, values_dev, snapshot_dev, p: int):
        lay = self.lay
        if lay["u_count"][p] == 0:
            return values_dev
        return _gs_acc_step(values_dev, snapshot_dev, lay["esrc"][p],
                            lay["ud"][p], lay["ew"][p], self.scale,
                            lay["plan"][p])


# ---------------------------------------------------------------------------
# ThunderGP
# ---------------------------------------------------------------------------


def _build_thundergp(g, problem, prep, k: int, p: int, ivl: int) -> dict:
    srcs = [prep[i][c]["src"] for i in range(k) for c in range(p)]
    dsts = [prep[i][c]["dst"] for i in range(k) for c in range(p)]
    gsrc = np.concatenate(srcs).astype(np.int32)
    gdst = np.concatenate(dsts).astype(np.int32)
    m = len(gsrc)
    if problem.kind == "min":
        if problem.name == "sssp":
            w = np.concatenate(
                [prep[i][c]["w"] for i in range(k) for c in range(p)])
        else:
            w = None
        delta = _min_delta(problem.name, w, m)
        L = _block_len(m)
        return dict(
            src=jnp.asarray(_pad_to(gsrc, L, -1, np.int32)),
            delta=jnp.asarray(_pad_to(delta, L, 0.0, np.float32)),
            plan=build_reduce_plan(_pad_to(gdst, L, 0, np.int32), g.n),
        )
    if problem.name == "spmv":
        w = np.concatenate(
            [prep[i][c]["w"] for i in range(k) for c in range(p)])
    else:
        w = None
    w_eff = _acc_weight(problem.name, gsrc, w, g.degrees_out)
    return dict(src=jnp.asarray(gsrc), w=jnp.asarray(w_eff),
                plan=build_reduce_plan(gdst, g.n))


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
        new, anyc = _jacobi_min_step(values_dev, lay["src"], lay["delta"],
                                     lay["plan"])
        with span("semexec_wait"):
            return new, bool(anyc)

    def acc_step(self, values_dev):
        lay = self.lay
        return _acc_step(values_dev, lay["src"], lay["w"], self.base,
                         self.scale, lay["plan"])


# ---------------------------------------------------------------------------
# ForeGraph
# ---------------------------------------------------------------------------


def _build_foregraph(g, problem, sizes, shard_edges, interval: int,
                     q: int) -> dict:
    if problem.kind == "acc":
        pairs = [shard_edges[(i, j)] for i in range(q) for j in range(q)
                 if sizes[i, j]]
        gsrc = (np.concatenate([s for s, _ in pairs]).astype(np.int32)
                if pairs else np.zeros(0, dtype=np.int32))
        gdst = (np.concatenate([d for _, d in pairs]).astype(np.int32)
                if pairs else np.zeros(0, dtype=np.int32))
        w_eff = _acc_weight(problem.name, gsrc, None, g.degrees_out)
        return dict(src=jnp.asarray(gsrc), w=jnp.asarray(w_eff),
                    plan=build_reduce_plan(gdst, g.n))

    def pack(i: int, js: list[int]):
        es = [shard_edges[(i, j)] for j in js if sizes[i, j]]
        src = (np.concatenate([s for s, _ in es]).astype(np.int32)
               if es else np.zeros(0, dtype=np.int32))
        dst = (np.concatenate([d for _, d in es]).astype(np.int32)
               if es else np.zeros(0, dtype=np.int32))
        E = _pow2(len(src))
        return (jnp.asarray(_pad_to(src, E, -1, np.int32)),
                build_reduce_plan(_pad_to(dst, E, 0, np.int32), g.n))

    ipq = build_reduce_plan((np.arange(g.n) // interval).astype(np.int32), q)
    abc, plans = [], []
    for i in range(q):
        (a, pa), (b, pb), (c, pc) = (pack(i, list(range(i))), pack(i, [i]),
                                     pack(i, list(range(i + 1, q))))
        abc.append((a, b, c))
        plans.append(dict(a=pa, b=pb, c=pc, ipq=ipq))
    return dict(abc=abc, plans=plans)


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
        new, flags = _fg_min_step(values_dev, *lay["abc"][i], self.delta,
                                  lay["plans"][i])
        with span("semexec_wait"):
            return new, np.asarray(flags).astype(bool)

    def acc_step(self, values_dev):
        lay = self.lay
        return _acc_step(values_dev, lay["src"], lay["w"], self.base,
                         self.scale, lay["plan"])
