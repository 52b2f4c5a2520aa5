"""``correct``: the plain reference agrees with the program, a whole
rehearsal run comes out correct, and the control and a broken timed path
come out not correct."""
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
from bench.reference import graphsim as ref  # noqa: E402


CELL = "g500-s16.bfs-batch"
# interval sizes that cut the rehearsal graph into partitions, so the
# reference's partitioned paths are held to the program's too
PARTITIONED = {"accugraph": {"interval_size": 256, "n_pes": 1},
               "foregraph": {"interval_size": 128, "n_pes": 4},
               "hitgraph": {"interval_size": 256, "n_pes": 1},
               "thundergp": {"interval_size": 256, "n_pes": 1}}


@pytest.mark.parametrize("presets,nth_root", [
    ("cell", 3), ("cell", 11), ("partitioned", 3),
])
def test_reference_matches_program(presets, nth_root):
    from repro.sweep.runner import execute_scenario
    from repro.sweep.results import scenario_row
    from repro.sweep.spec import ConfigOverride, SweepSpec

    c = spec.load_cell(CELL)
    accels = c.config["accelerators"] if presets == "cell" else PARTITIONED
    (graph, recipe), = c.config["rehearse"]["graphs"].items()
    g = ref.build_graph(recipe)
    root = int(np.flatnonzero(g.degrees_out)[nth_root])
    gs = traffic.graph_spec(graph, recipe, root)
    levels = ref.bfs_levels(g, root)
    for mem in c.config["memories"].values():
        for a in mem["accelerators"]:
            p = accels[a]
            sweep = SweepSpec("t", (a,), (gs,), drams=(mem["dram"],),
                              page_policies=(mem["page_policy"],),
                              pseudo_channels=(mem["pseudo_channels"],),
                              overrides=(ConfigOverride(
                                  interval_size=p["interval_size"],
                                  n_pes=p["n_pes"]),),
                              engines=("numpy",))
            (s,) = sweep.expand()[0]
            rec = execute_scenario(s, with_trace_hash=True)
            got = scenario_row(s, rec)
            run = ref.execute(a, g, root, p["interval_size"], p["n_pes"],
                              c.config["max_iters"])
            assert np.array_equal(run.values, levels)
            want = ref.row_stats(run, g, mem["dram"], mem["page_policy"],
                                 mem["pseudo_channels"], ref.Timer())
            assert rec["trace_hash"] == want["trace_hash"]
            assert (got["partitions"] > 1) == (presets == "partitioned")
            for f in check.SEMANTIC + check.STREAM + check.TIMING:
                assert got[f] == want[f], (s.scenario_id, f)


def test_reference_reads_the_initiator():
    c = spec.load_cell(CELL)
    (_, recipe), = c.config["rehearse"]["graphs"].items()
    g = ref.build_graph(recipe)
    other = dict(recipe, initiator=dict(A=0.45, B=0.15, C=0.15))
    assert not np.array_equal(ref.build_graph(other).src, g.src)
    with pytest.raises(ref.Unsupported):
        ref.build_graph(dict(recipe, kind="road"))


def test_timer_models_row_buffer_states():
    dev = ref.Device(nbanks=2, lines_per_row=4, data_rate=2000, bw=64.0,
                     page_open=True)
    tcl, trcd, trp, trc, tbl = dev.timings
    assert (tcl, trcd, trp, trc, tbl) == (11, 11, 11, 28, 1)
    # miss, hit, conflict in bank 0
    cycles, hits, misses, conflicts = ref.time_stream([0, 0, 0], [0, 0, 1],
                                                      dev)
    assert (hits, misses, conflicts) == (1, 1, 1)
    closed = ref.Device(2, 4, 2000, 64.0, page_open=False)
    assert ref.time_stream([0, 0, 0], [0, 0, 1], closed)[1:] == (0, 3, 0)


def rehearse(tmp_path, *extra, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 11), "--seconds", "2", "--trace",
         str(trace), "--rehearse", "--out", str(tmp_path / "run"), *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_rehearsal_is_correct(tmp_path):
    out, err = rehearse(tmp_path, trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    # set-up compiled every scan shape the window can use
    assert out["metrics"]["window_compiles"]["value"] == 0
    assert {"seat_busy_share", "semantics_hit_share"} <= set(out["metrics"])
    # a rehearsal prints no device metric
    assert not {"timing_device_share", "semexec_device_share",
                "device_idle_share"} & set(out["metrics"])
    assert list(out)[-1] == "checks"
    assert "check runtime_rel_gap: 0.0 (limit 0.0)" in err


def test_control_is_not_correct(tmp_path):
    out, _ = rehearse(tmp_path, "--control")
    assert out["correct"] is False
    assert out["checks"]["timing_fields_off"]["value"] > 0
    assert out["checks"]["runtime_rel_gap"]["value"] > 0


def test_answer_altered_in_the_seat_is_not_correct(tmp_path):
    out, _ = rehearse(tmp_path, "--fault", "alter")
    assert out["correct"] is False
    assert out["checks"]["stream_fields_off"]["value"] > 0
