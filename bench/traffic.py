"""The one traffic generator: jobs from a configuration, a traffic file
and the seed.

A traffic file (``bench/traffic/<name>.json``) gives:

- ``clients``: closed-loop clients; each submits its next job when every
  row of its last one has arrived;
- ``graphs`` / ``accelerators``: the configuration's graphs and
  accelerators every job covers;
- ``memories``: the configuration's memory systems each job runs on (a
  memory system lists the accelerators it pairs with);
- ``warmup_jobs``: jobs run in set-up, before the window, with the
  program's analytic timing in place of the scan;
- ``roots``: the ``warmup`` roots per graph, the window's roots per
  ``draw``, the ``block`` in which the seed orders them, and the
  ``pool_seed`` (:class:`RootPool`).

Search keys follow Graph500 kernel 2: vertices with at least one edge
out.  Every run searches from the same roots; ``--seed`` orders the
window's roots inside each block and nothing else.  Warm-up roots and
window roots are disjoint, and no root repeats within a run, so no
scenario of the window can be served by the result cache or joined to
another in flight.
"""
from __future__ import annotations

import numpy as np


class RootPool:
    """The search keys of one graph, in the order the jobs take them.

    The warm-up's roots and the window's first ``draw`` roots are drawn
    together from ``pool_seed``; whenever the window has used them all it
    draws ``draw`` more, each draw from a seed of its own, from the
    vertices not drawn yet.  So the window never runs short, however fast
    the program, and a faster program searches from the same first roots.
    ``--seed`` orders each draw's roots within blocks of ``block``."""

    def __init__(self, out_degree: np.ndarray, roots: dict, seed: int,
                 salt: int):
        self.eligible = np.flatnonzero(out_degree >= 1)
        self.key = [int(roots["pool_seed"]), salt]
        self.draw, self.block = int(roots["draw"]), int(roots["block"])
        self.order = np.random.default_rng(seed)
        n_warm = int(roots["warmup"])
        first = self._draw(self.eligible, n_warm + self.draw, [])
        self.warmup = first[:n_warm].tolist()
        self.window: list[int] = []
        self._add(first[n_warm:])
        self.draws = 1

    def _draw(self, candidates: np.ndarray, size: int, key: list):
        if len(candidates) < size:
            raise RuntimeError(f"{len(candidates)} vertices with an edge out "
                               f"left to draw; the traffic asks for {size}")
        return np.random.default_rng(self.key + key).choice(
            candidates, size=size, replace=False)

    def _add(self, roots: np.ndarray) -> None:
        roots = roots.copy()
        for at in range(0, len(roots), self.block):
            roots[at:at + self.block] = self.order.permutation(
                roots[at:at + self.block])
        self.window += roots.tolist()

    def window_root(self, i: int) -> int:
        while i >= len(self.window):
            rest = np.setdiff1d(self.eligible, self.warmup + self.window)
            self._add(self._draw(rest, self.draw, [self.draws]))
            self.draws += 1
        return self.window[i]


class Jobs:
    """Job ``k`` of a phase (``warmup`` or ``window``) as a list of sweep
    specs; every graph of a job gets its phase's next fresh root."""

    def __init__(self, config: dict, traffic: dict, pools: dict,
                 control: bool = False):
        self.config = config
        self.traffic = traffic
        self.pools = pools  # graph -> RootPool
        self.used = {g: {"warmup": 0, "window": 0} for g in pools}
        self.control = control

    def _root(self, graph: str, phase: str) -> int:
        i = self.used[graph][phase]
        self.used[graph][phase] = i + 1
        pool = self.pools[graph]
        if phase == "window":
            return pool.window_root(i)
        if i >= len(pool.warmup):
            raise RuntimeError(f"{graph}: {len(pool.warmup)} warm-up roots; "
                               f"the traffic runs more warm-up jobs")
        return pool.warmup[i]

    def specs(self, k: int, phase: str) -> list:
        """One sweep spec per memory system and accelerator preset (the
        configuration's interval size and PEs), in the configuration's
        order."""
        from repro.sweep.spec import ConfigOverride, SweepSpec

        t, c = self.traffic, self.config
        gspecs = tuple(graph_spec(g, c["graphs"][g], self._root(g, phase))
                       for g in t["graphs"])
        # the control: the program's analytic host timing in place of the
        # exact scan, a path of the program's own.  Warm-up jobs time the
        # same way: they warm the semantics and trace emission, and the
        # scan's programs are compiled by shape (bench.seat.warm_scan)
        engine = "fast" if self.control or phase == "warmup" else None
        out = []
        for mem in t["memories"]:
            m = c["memories"][mem]
            presets: dict = {}  # (interval, PEs) -> accelerators
            for a in t["accelerators"]:
                if a in m["accelerators"]:
                    p = c["accelerators"][a]
                    presets.setdefault((p["interval_size"], p["n_pes"]),
                                       []).append(a)
            for (interval, n_pes), names in presets.items():
                out.append(SweepSpec(
                    name=f"{phase}{k}-{mem}-{interval}x{n_pes}",
                    accelerators=tuple(names), graphs=gspecs,
                    problems=(c["problem"],), drams=(m["dram"],),
                    page_policies=(m["page_policy"],),
                    pseudo_channels=(m["pseudo_channels"],),
                    overrides=(ConfigOverride(interval_size=interval,
                                              n_pes=n_pes, engine=engine),),
                    engines=(c["semantic_engine"],)))
        return out


def graph_spec(name: str, recipe: dict, root: int):
    """The program's recipe of a configuration's graph, searched from
    ``root``.  The program's Kronecker generator takes the vertex and edge
    counts; its initiator is its own, and the reference builds the graph
    with the configuration's, so a configuration that asks for another
    initiator than the program draws is not correct."""
    from repro.graph.generators import GraphSpec

    if recipe["kind"] != "kronecker":
        raise ValueError(f"{name}: unknown graph generator {recipe['kind']!r}")
    n = 1 << recipe["scale"]
    return GraphSpec(name, "rmat", n, recipe["edge_factor"] * n,
                     recipe["directed"], recipe["seed"], root)
