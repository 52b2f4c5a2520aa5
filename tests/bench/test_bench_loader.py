"""The benchmark finds each cell's pieces by name, and its file keeps to
the benchmark's format."""
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import spec, traffic  # noqa: E402

BM = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BM["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_pieces_found_by_name(cell):
    c = spec.load_cell(cell)
    w = next(w for w in BM["workloads"] if w["name"] == cell)
    assert c.config_name == w["config"] and c.traffic_name == w["traffic"]
    assert set(c.traffic["graphs"]) <= set(c.config["graphs"])
    assert set(c.traffic["memories"]) <= set(c.config["memories"])
    assert set(c.traffic["accelerators"]) <= set(c.config["accelerators"])
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for entry, reader in c.per_layer:
        assert callable(reader.read)
        assert reader.LAYER == entry["layer"]
        assert entry["moves"] in names


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")


def test_metric_is_a_file_of_its_own(tmp_path, monkeypatch):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "rows.per.job.py").write_text(
        "LAYER = 'x'\ndef read(obs):\n    return 3.0\n")
    monkeypatch.setattr(spec, "BENCH", str(tmp_path))
    assert spec.load_metric("rows.per.job").read(None) == 3.0


def test_benchmark_file_format():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= BM["run_seconds"] <= 51
    for p in BM["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BM["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in BM["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def _degrees(n=400, seed=0):
    d = np.random.default_rng(seed).integers(0, 3, size=n)
    return d


ROOTS = dict(warmup=4, draw=8, block=4, pool_seed=7)


def test_roots_follow_graph500_rule_and_are_disjoint():
    deg = _degrees()
    pool = traffic.RootPool(deg, ROOTS, seed=2**31 + 5, salt=0)
    win = [pool.window_root(i) for i in range(40)]  # five draws
    assert len(pool.warmup) == 4 and pool.draws == 5
    assert all(deg[r] >= 1 for r in pool.warmup + win)
    assert not set(pool.warmup) & set(win) and len(set(win)) == 40


def test_seed_orders_the_same_roots():
    deg = _degrees()
    a = [traffic.RootPool(deg, ROOTS, seed=1, salt=0).window_root(i)
         for i in range(16)]
    p = traffic.RootPool(deg, ROOTS, seed=2, salt=0)
    b = [p.window_root(i) for i in range(16)]
    assert a != b
    for at in range(0, 16, 4):  # same set in every block, another order
        assert sorted(a[at:at + 4]) == sorted(b[at:at + 4])


def test_later_draws_keep_the_first_roots():
    deg = _degrees()
    first = np.random.default_rng([7, 0]).choice(
        np.flatnonzero(deg >= 1), size=12, replace=False)
    p = traffic.RootPool(deg, ROOTS, seed=3, salt=0)
    assert p.warmup == first[:4].tolist()
    assert sorted(p.window_root(i) for i in range(8)) == sorted(first[4:])
    small = traffic.RootPool(np.ones(14), ROOTS, seed=3, salt=0)
    with pytest.raises(RuntimeError):
        small.window_root(10)  # 14 vertices: no second draw of 8


def _pools(c):
    return {g: traffic.RootPool(np.ones(64), dict(warmup=1, draw=2, block=2,
                                                  pool_seed=3), 1, i)
            for i, g in enumerate(c.traffic["graphs"])}


def test_jobs_take_fresh_roots_at_the_configured_presets():
    c = spec.load_cell("g500-s16.bfs-batch")
    jobs = traffic.Jobs(c.config, c.traffic, _pools(c))
    seen = set()
    for k in range(5):
        scen = [s for sp in jobs.specs(k, "window") for s in sp.expand()[0]]
        assert len(scen) == 6
        assert len({s.graph.root for s in scen}) == 1
        assert scen[0].graph.root not in seen
        seen.add(scen[0].graph.root)
        for s in scen:
            p = c.config["accelerators"][s.accelerator]
            assert s.config.interval_size == p["interval_size"]
            assert s.config.n_pes == p["n_pes"]
            assert s.config.engine != "fast"
    # warm-up jobs time analytically; the scan is compiled by shape
    warm = [s for sp in jobs.specs(0, "warmup") for s in sp.expand()[0]]
    assert warm[0].graph.root not in seen
    assert all(s.config.engine == "fast" for s in warm)
    with pytest.raises(RuntimeError):
        jobs.specs(1, "warmup")


def test_control_jobs_time_analytically():
    c = spec.load_cell("g500-s16.bfs-batch")
    jobs = traffic.Jobs(c.config, c.traffic, _pools(c), control=True)
    scen = [s for sp in jobs.specs(0, "window") for s in sp.expand()[0]]
    assert len(scen) == 6 and all(s.config.engine == "fast" for s in scen)


def test_graph_recipe_names_the_programs_graph():
    recipe = dict(kind="kronecker", scale=10, edge_factor=16,
                  initiator=dict(A=0.57, B=0.19, C=0.19), directed=False,
                  seed=16)
    gs = traffic.graph_spec("g", recipe, 7)
    assert (gs.kind, gs.n, gs.target_m, gs.root) == ("rmat", 1024, 16384, 7)
    with pytest.raises(ValueError):
        traffic.graph_spec("g", dict(recipe, kind="road"), 7)
