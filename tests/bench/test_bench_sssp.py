"""Graph500 kernel 3 (``g500-s15.sssp-ddr4``): the SSSP reference agrees
with the program and with its own Dijkstra, a whole rehearsal run comes
out correct, and the control and a broken timed path come out not
correct."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import check, spec, traffic  # noqa: E402
from bench.reference import graphsim_sssp as ref  # noqa: E402

CELL = "g500-s15.sssp-ddr4"
# interval sizes that cut the rehearsal graph into partitions, so the
# reference's partitioned paths are held to the program's too
PARTITIONED = {"hitgraph": {"interval_size": 256, "n_pes": 1},
               "thundergp": {"interval_size": 256, "n_pes": 1}}


def rehearsal_graph():
    c = spec.load_cell(CELL)
    (graph, recipe), = c.config["rehearse"]["graphs"].items()
    return c, graph, recipe, ref.build_graph(recipe)


def test_reference_builds_the_programs_weighted_graph():
    _, graph, recipe, g = rehearsal_graph()
    pg = traffic.graph_spec(graph, recipe, 0).build()
    np.testing.assert_array_equal(pg.src, g.src)
    np.testing.assert_array_equal(pg.dst, g.dst)
    np.testing.assert_array_equal(pg.weights, g.weights)
    assert g.weights.dtype == np.float32


@pytest.mark.parametrize("presets,nth_root", [
    ("cell", 3), ("cell", 11), ("partitioned", 3),
])
def test_reference_matches_program(presets, nth_root):
    from repro.sweep.results import scenario_row
    from repro.sweep.runner import execute_scenario
    from repro.sweep.spec import ConfigOverride, SweepSpec

    c, graph, recipe, g = rehearsal_graph()
    accels = c.config["accelerators"] if presets == "cell" else PARTITIONED
    root = int(np.flatnonzero(g.degrees_out)[nth_root])
    gs = traffic.graph_spec(graph, recipe, root)
    dist = ref.dijkstra(g, root)
    for mem in c.config["memories"].values():
        for a in mem["accelerators"]:
            p = accels[a]
            sweep = SweepSpec("t", (a,), (gs,), problems=("sssp",),
                              drams=(mem["dram"],),
                              page_policies=(mem["page_policy"],),
                              pseudo_channels=(mem["pseudo_channels"],),
                              overrides=(ConfigOverride(
                                  interval_size=p["interval_size"],
                                  n_pes=p["n_pes"]),),
                              engines=(c.config["semantic_engine"],))
            (s,) = sweep.expand()[0]
            rec = execute_scenario(s, with_trace_hash=True)
            got = scenario_row(s, rec)
            run = ref.execute(a, g, root, p["interval_size"], p["n_pes"],
                              c.config["max_iters"])
            # the model's Bellman-Ford fixed point is Dijkstra's, and the
            # program's values are both
            np.testing.assert_array_equal(run.values, dist)
            assert run.iterations > 1
            want = ref.row_stats(run, g, mem["dram"], mem["page_policy"],
                                 mem["pseudo_channels"], ref.Timer())
            assert rec["trace_hash"] == want["trace_hash"]
            assert got["engine"] == "device"
            assert (got["partitions"] > 1) == (presets == "partitioned")
            for f in check.SEMANTIC + check.STREAM + check.TIMING:
                assert got[f] == want[f], (s.scenario_id, f)


def test_dijkstra_is_a_shortest_path_tree():
    """Every vertex's distance is its best in-arc's sum, the root's is 0,
    and unreachable vertices stay inf: Dijkstra's answer is the fixed
    point the models iterate to."""
    _, _, _, g = rehearsal_graph()
    root = int(np.flatnonzero(g.degrees_out)[0])
    dist = ref.dijkstra(g, root)
    best = np.full(g.n, np.inf, dtype=np.float32)
    np.minimum.at(best, g.dst, dist[g.src] + g.weights)
    best[root] = 0
    np.testing.assert_array_equal(best, dist)
    assert np.isfinite(dist).sum() > 1


def test_reference_refuses_what_it_does_not_model():
    _, _, recipe, g = rehearsal_graph()
    for accel in ("accugraph", "foregraph"):
        with pytest.raises(ref.Unsupported):
            ref.execute(accel, g, 0, 1024, 1, 10)
    with pytest.raises(ref.Unsupported):
        ref.build_graph(dict(recipe, directed=True))
    with pytest.raises(ref.Unsupported):
        ref.build_graph(dict(recipe, weights=dict(recipe["weights"],
                                                  high=64)))
    other = ref.build_graph(dict(recipe, weights=dict(recipe["weights"],
                                                      stream=4)))
    np.testing.assert_array_equal(other.src, g.src)
    assert not np.array_equal(other.weights, g.weights)


def rehearse(tmp_path, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 15), "--seconds", "2", "--trace", "0",
         "--rehearse", "--out", str(tmp_path / "run"), *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_rehearsal_is_correct(tmp_path):
    out, err = rehearse(tmp_path)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"sim_mreq_per_s", "setup_s"}
    assert all(c["value"] == 0 for c in out["checks"].values())
    assert "check reference_bfs_off: 0 (limit 0)" in err


def test_control_is_not_correct(tmp_path):
    out, _ = rehearse(tmp_path, "--control")
    assert out["correct"] is False
    assert out["checks"]["timing_fields_off"]["value"] > 0
    assert out["checks"]["runtime_rel_gap"]["value"] > 0


def test_answer_altered_in_the_seat_is_not_correct(tmp_path):
    out, _ = rehearse(tmp_path, "--fault", "alter")
    assert out["correct"] is False
    assert out["checks"]["stream_fields_off"]["value"] > 0
