"""The comparison that decides ``correct``.

Once the window has closed and the server has stopped, a sample of the
window's rows, drawn from the seed and always holding the row with the
most simulated requests, is recomputed by the configuration's plain
reference (``bench/reference``) and compared field by field.  Every
simulated statistic is deterministic, so every comparison is exact: each
number below has the limit 0.

- ``rows_failed``: rows of the window's jobs that did not come back ok;
- ``reference_bfs_off``: vertices whose BFS level under the reference's
  accelerator model differs from a plain frontier BFS (the reference
  checks itself before it judges);
- ``semantic_fields_off``: (row, field) pairs that differ among the
  semantic results: iterations, values and edges read per iteration,
  partitions skipped, the partition layout, the graph's size and degree
  statistics, and the semantic engine that ran;
- ``stream_fields_off``: among the request streams' results: bytes per
  edge (the number of requests) and the row hits, misses and conflicts
  (the order of requests);
- ``timing_fields_off``: among the DRAM timing's results: runtime, MTEPS,
  MREPS and bandwidth utilization;
- ``runtime_rel_gap``: the largest relative gap of a row's simulated
  runtime from the reference's.
"""
from __future__ import annotations

import dataclasses

import numpy as np

SEMANTIC = ("iterations", "values_read_per_iteration",
            "edges_read_per_iteration", "partitions_skipped", "n", "m",
            "effective_interval", "partitions", "edges_per_partition_min",
            "edges_per_partition_max", "edges_per_partition_cv",
            "shard_fill", "avg_degree", "degree_skewness")
STREAM = ("bytes_per_edge", "row_hits", "row_misses", "row_conflicts")
TIMING = ("runtime_s", "mteps", "mreps", "bw_utilization")

LIMITS = dict(rows_failed=0, reference_bfs_off=0, semantic_fields_off=0,
              stream_fields_off=0, timing_fields_off=0, runtime_rel_gap=0.0)


@dataclasses.dataclass(frozen=True)
class Scenario:
    """What the reference needs to recompute one row."""

    graph: str
    root: int
    accelerator: str
    dram: str
    page_policy: str
    pseudo_channels: bool


def sample(rows: list, k: int, seed: int) -> list:
    """Up to ``k`` of the window's ok rows, drawn from the seed, with the
    row of the most simulated requests always among them."""
    ok = [r for r in rows if r.status == "ok"]
    if len(ok) <= k:
        return ok
    biggest = max(range(len(ok)), key=lambda i: ok[i].requests)
    rest = [i for i in range(len(ok)) if i != biggest]
    pick = np.random.default_rng(seed).choice(len(rest), size=k - 1,
                                              replace=False)
    return [ok[biggest]] + [ok[rest[i]] for i in sorted(pick)]


def compare(rows: list, attempted: int, checked: list, config: dict,
            graphs: dict, ref) -> dict:
    """The numbers compared, from the window's ``rows`` (``attempted`` of
    them were asked for) and the ``checked`` sample, by the configuration's
    reference module ``ref``; ``graphs`` maps a graph name to the
    reference's build of it."""
    runs: dict = {}
    timer = ref.Timer()
    out = dict(rows_failed=attempted - sum(r.status == "ok" for r in rows),
               reference_bfs_off=0, semantic_fields_off=0,
               stream_fields_off=0, timing_fields_off=0,
               runtime_rel_gap=0.0)
    for r in checked:
        s = r.scenario
        g = graphs[s.graph]
        key = (s.graph, s.root, s.accelerator)
        if key not in runs:
            preset = config["accelerators"][s.accelerator]
            run = runs[key] = ref.execute(
                s.accelerator, g, s.root, preset["interval_size"],
                preset["n_pes"], config["max_iters"])
            out["reference_bfs_off"] += int(
                (run.values != ref.bfs_levels(g, s.root)).sum())
        want = ref.row_stats(runs[key], g, s.dram, s.page_policy,
                             s.pseudo_channels, timer)
        got = r.row
        out["semantic_fields_off"] += sum(got.get(f) != want[f]
                                          for f in SEMANTIC)
        out["semantic_fields_off"] += got.get("engine") != config["semantic_engine"]
        out["stream_fields_off"] += sum(got.get(f) != want[f] for f in STREAM)
        out["timing_fields_off"] += sum(got.get(f) != want[f] for f in TIMING)
        gap = abs(got.get("runtime_s", 0.0) - want["runtime_s"]) \
            / max(want["runtime_s"], 1e-30)
        out["runtime_rel_gap"] = max(out["runtime_rel_gap"], gap)
    return out


def verdict(numbers: dict) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())
