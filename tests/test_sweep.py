"""repro.sweep: spec expansion, content-addressed cache, runner, results."""
import dataclasses

import numpy as np
import pytest

from repro.configs.graphsim import default_config
from repro.core import hostcache
from repro.core.accelerators.base import run_accelerator
from repro.core.dram import dram_config
from repro.graph.generators import GraphSpec
from repro.graph.problems import PROBLEMS
from repro.sweep import (
    ConfigOverride,
    ResultCache,
    SweepSpec,
    execute_scenario,
    result_rows,
    run_sweep,
    scenario_hash,
    write_csv,
)
from repro.sweep import cache as cache_mod
from repro.sweep import runner as runner_mod
from repro.sweep.runner import graph_memo_stats

TINY = GraphSpec("tiny", "uniform", 256, 1024, True, 1, 0)
TINY2 = GraphSpec("tiny2", "uniform", 200, 800, True, 2, 0)
BROKEN = GraphSpec("broken", "no-such-generator", 64, 128, True, 1, 0)


def tiny_spec(accels=("accugraph",), problems=("bfs",), graphs=(TINY,), **kw):
    return SweepSpec(name="t", accelerators=tuple(accels), graphs=tuple(graphs),
                     problems=tuple(problems), **kw)


# ---- spec expansion / invalid-combination filtering ------------------------


def test_expand_cross_product_order():
    spec = tiny_spec(accels=("accugraph", "hitgraph"), problems=("bfs", "pr"),
                     graphs=(TINY, TINY2))
    scenarios, skipped = spec.expand()
    assert not skipped
    ids = [(s.graph.name, s.accelerator, s.problem) for s in scenarios]
    assert ids == [
        ("tiny", "accugraph", "bfs"), ("tiny", "accugraph", "pr"),
        ("tiny", "hitgraph", "bfs"), ("tiny", "hitgraph", "pr"),
        ("tiny2", "accugraph", "bfs"), ("tiny2", "accugraph", "pr"),
        ("tiny2", "hitgraph", "bfs"), ("tiny2", "hitgraph", "pr"),
    ]


def test_expand_filters_weighted_on_unsupported():
    spec = tiny_spec(accels=("accugraph", "foregraph", "hitgraph", "thundergp"),
                     problems=("bfs", "sssp"))
    scenarios, skipped = spec.expand()
    ran = {(s.accelerator, s.problem) for s in scenarios}
    assert ("hitgraph", "sssp") in ran and ("thundergp", "sssp") in ran
    assert ("accugraph", "sssp") not in ran and ("foregraph", "sssp") not in ran
    reasons = {(sk.accelerator, sk.problem): sk.reason for sk in skipped}
    assert "weighted" in reasons[("accugraph", "sssp")]


def test_expand_filters_multichannel_on_single_channel_accel():
    spec = tiny_spec(accels=("accugraph", "hitgraph"),
                     drams=(("default", 1), ("default", 4)))
    scenarios, skipped = spec.expand()
    assert {(s.accelerator, s.dram.channels) for s in scenarios} == {
        ("accugraph", 1), ("hitgraph", 1), ("hitgraph", 4)}
    # the explicit channel axis also pairs PEs with channels (Tab. 7 setup)
    assert {s.config.n_pes for s in scenarios if s.accelerator == "hitgraph"} == {1, 4}
    assert any(sk.accelerator == "accugraph" and "multi-channel" in sk.reason
               for sk in skipped)


def test_expand_rejects_unknown_names():
    with pytest.raises(ValueError, match="unknown accelerator.*'bogus'"):
        tiny_spec(accels=("bogus",)).expand()
    with pytest.raises(ValueError, match="unknown DRAM preset"):
        tiny_spec(drams=("nodram",)).expand()
    with pytest.raises(ValueError, match="unknown graph"):
        tiny_spec(graphs=("nograph",)).expand()
    with pytest.raises(ValueError, match="channel counts"):
        tiny_spec(drams=(("default", 0),)).expand()


def test_expand_filters_model_rejected_config():
    spec = tiny_spec(accels=("foregraph",),
                     overrides=(ConfigOverride(label="huge", interval_size=1 << 20),))
    scenarios, skipped = spec.expand()
    assert not scenarios
    assert "65,536" in skipped[0].reason


# ---- scenario hashing / cache ----------------------------------------------


def test_scenario_hash_stable_and_sensitive():
    base = tiny_spec().scenarios()[0]
    again = tiny_spec().scenarios()[0]
    assert scenario_hash(base) == scenario_hash(again)

    other_cfg = dataclasses.replace(base, config=dataclasses.replace(
        base.config, interval_size=512))
    other_dram = dataclasses.replace(base, dram=dram_config("hbm"))
    other_graph = dataclasses.replace(base, graph=dataclasses.replace(TINY, seed=9))
    hashes = {scenario_hash(s) for s in (base, other_cfg, other_dram, other_graph)}
    assert len(hashes) == 4

    # the override label is presentation-only: not part of the identity
    labelled = dataclasses.replace(base, label="ablation-x")
    assert scenario_hash(labelled) == scenario_hash(base)


def test_scenario_hash_differs_by_root_alone():
    """The graph memo ignores the root; the result cache must not."""
    base = tiny_spec().scenarios()[0]
    rerooted = tiny_spec(graphs=(dataclasses.replace(TINY, root=5),)).scenarios()[0]
    assert rerooted.graph.build_key() == base.graph.build_key()
    assert scenario_hash(rerooted) != scenario_hash(base)


def test_engine_version_invalidates_hash(monkeypatch):
    s = tiny_spec().scenarios()[0]
    h1 = scenario_hash(s)
    monkeypatch.setattr(cache_mod, "ENGINE_VERSION", "test-bump")
    assert scenario_hash(s) != h1


def test_result_cache_roundtrip(tmp_path):
    cache = ResultCache(str(tmp_path / "cache"))
    assert cache.get("ab" * 32) is None
    cache.put("ab" * 32, {"status": "ok", "x": 1})
    assert cache.get("ab" * 32) == {"status": "ok", "x": 1}
    assert ("ab" * 32) in cache
    disabled = ResultCache(None)
    disabled.put("cd" * 32, {"status": "ok"})
    assert disabled.get("cd" * 32) is None


def test_sim_report_serialization_roundtrip():
    rec = execute_scenario(tiny_spec().scenarios()[0])
    assert rec["status"] == "ok"
    from repro.core.metrics import SimReport

    rep = SimReport.from_dict(rec["report"])
    assert rep.to_dict() == rec["report"]
    assert rep.runtime_s > 0 and rep.iterations >= 1
    assert len(rep.per_iteration) == rep.iterations


# ---- runner ----------------------------------------------------------------


def test_sweep_rows_match_direct_execution():
    spec = tiny_spec(accels=("accugraph", "hitgraph"))
    result = run_sweep(spec)
    rows = result_rows(result)
    assert len(rows) == 2
    g = TINY.build()
    for row in rows:
        rep = run_accelerator(row["accelerator"], g, PROBLEMS["bfs"], root=TINY.root,
                              dram=dram_config("default"),
                              config=default_config(row["accelerator"]))
        assert row["runtime_s"] == rep.runtime_s
        assert row["mteps"] == rep.mteps
        assert row["iterations"] == rep.iterations
        assert row["bytes_per_edge"] == rep.bytes_per_edge


def test_second_run_is_all_cache_hits(tmp_path):
    spec = tiny_spec(accels=("accugraph", "foregraph"))
    cache_dir = str(tmp_path / "cache")
    first = run_sweep(spec, cache_dir=cache_dir)
    assert first.n_executed == 2 and first.n_cached == 0
    second = run_sweep(spec, cache_dir=cache_dir)
    assert second.all_cached and second.n_executed == 0
    assert result_rows(second) == result_rows(first)


def test_cache_invalidation_on_config_change(tmp_path):
    cache_dir = str(tmp_path / "cache")
    run_sweep(tiny_spec(), cache_dir=cache_dir)
    changed = tiny_spec(overrides=(ConfigOverride(interval_size=512),))
    result = run_sweep(changed, cache_dir=cache_dir)
    assert result.n_executed == 1 and result.n_cached == 0


def test_resume_after_interrupt(tmp_path):
    """A pre-populated cache short-circuits the already-done scenarios."""
    cache_dir = str(tmp_path / "cache")
    run_sweep(tiny_spec(accels=("accugraph",)), cache_dir=cache_dir)
    resumed = run_sweep(tiny_spec(accels=("accugraph", "foregraph", "thundergp")),
                        cache_dir=cache_dir)
    assert resumed.n_cached == 1 and resumed.n_executed == 2
    statuses = {r.scenario.accelerator: r.status for r in resumed.results}
    assert statuses == {"accugraph": "cached", "foregraph": "ok", "thundergp": "ok"}


def test_interrupted_sweep_resumes_with_identical_csv(tmp_path):
    """Kill the sweep mid-run (after two scenarios were recorded); the
    re-run must serve exactly those two from the cache — no re-execution —
    and its exported CSV must be byte-identical to an uninterrupted run."""
    spec = tiny_spec(accels=("accugraph", "foregraph", "hitgraph", "thundergp"))
    ref = run_sweep(spec, cache_dir=str(tmp_path / "ref_cache"))
    assert ref.n_executed == 4 and ref.n_errors == 0
    ref_csv = str(tmp_path / "ref.csv")
    write_csv(ref_csv, result_rows(ref))

    cache_dir = str(tmp_path / "cache")
    done = 0

    def kill_after_two(msg):
        nonlocal done
        if " ok " in msg:
            done += 1
            if done == 2:
                raise KeyboardInterrupt  # the worker dies mid-sweep

    with pytest.raises(KeyboardInterrupt):
        run_sweep(spec, cache_dir=cache_dir, progress=kill_after_two)

    resumed = run_sweep(spec, cache_dir=cache_dir)
    assert resumed.n_cached == 2 and resumed.n_executed == 2
    assert resumed.n_errors == 0
    res_csv = str(tmp_path / "resumed.csv")
    write_csv(res_csv, result_rows(resumed))
    assert open(res_csv, "rb").read() == open(ref_csv, "rb").read()


def test_error_isolation_and_errors_not_cached(tmp_path):
    spec = tiny_spec(graphs=(BROKEN, TINY))
    cache_dir = str(tmp_path / "cache")
    result = run_sweep(spec, cache_dir=cache_dir)
    assert result.n_errors == 1 and result.n_executed == 2
    by_graph = {r.scenario.graph.name: r for r in result.results}
    assert by_graph["broken"].status == "error"
    assert "no-such-generator" in by_graph["broken"].record["error"]
    assert by_graph["tiny"].status == "ok"
    rows = result_rows(result)
    assert "error" in rows[0] and rows[1]["runtime_s"] > 0
    # errors are not cached: the broken scenario re-executes, the good one not
    again = run_sweep(spec, cache_dir=cache_dir)
    assert again.n_cached == 1 and again.n_errors == 1


@pytest.fixture
def cold_graphs():
    """No graph or host artifact cached."""
    runner_mod._GRAPHS.clear()
    hostcache.clear_all()
    yield
    runner_mod._GRAPHS.clear()
    hostcache.clear_all()


def _record(s):
    rec = execute_scenario(s)
    assert rec["status"] == "ok"
    return {k: v for k, v in rec.items() if k != "wall_s"}


def test_graph_memo_builds_once_across_roots(cold_graphs):
    a = tiny_spec().scenarios()[0]
    b = tiny_spec(graphs=(dataclasses.replace(TINY, root=5),)).scenarios()[0]
    before = graph_memo_stats()
    shared = [_record(a), _record(b)]
    after = graph_memo_stats()
    assert {k: after[k] - before[k] for k in after} == dict(hits=1, misses=1)
    assert runner_mod._graph(a.graph) is runner_mod._graph(b.graph)
    assert shared[0]["report"] != shared[1]["report"]  # the root matters

    fresh = []
    for s in (a, b):
        runner_mod._GRAPHS.clear()
        hostcache.clear_all()
        fresh.append(_record(s))
    assert fresh == shared


@pytest.mark.parametrize("field,value", [
    ("name", "tiny-b"), ("kind", "rmat"), ("n", 512), ("target_m", 2048),
    ("directed", False), ("seed", 9)])
def test_graph_memo_misses_on_any_build_field(field, value, cold_graphs):
    other = dataclasses.replace(TINY, **{field: value})
    assert other.build_key() != TINY.build_key()
    before = graph_memo_stats()
    g, h = runner_mod._graph(TINY), runner_mod._graph(other)
    after = graph_memo_stats()
    assert {k: after[k] - before[k] for k in after} == dict(hits=0, misses=2)
    assert g is not h


def test_duplicate_scenarios_execute_once(tmp_path):
    # "all" optimizations override == the default config -> same hash
    spec = tiny_spec(overrides=(ConfigOverride(),
                                ConfigOverride(label="all",
                                               optimizations=frozenset({"all"}))))
    result = run_sweep(spec)
    assert len(result.results) == 2
    assert result.results[0].hash == result.results[1].hash
    r0, r1 = result_rows(result)
    assert r0["runtime_s"] == r1["runtime_s"]


def test_batch_mode_matches_scenario_mode():
    """Batch execution (cross-scenario grouped DRAM dispatches) must yield
    byte-identical result rows to per-scenario execution."""
    spec = tiny_spec(accels=("accugraph", "hitgraph", "thundergp"),
                     problems=("bfs", "pr"))
    scenario = run_sweep(spec, mode="scenario")
    batch = run_sweep(spec, mode="batch")
    assert result_rows(scenario) == result_rows(batch)


def test_batch_mode_error_isolation(tmp_path):
    spec = tiny_spec(graphs=(BROKEN, TINY))
    result = run_sweep(spec, cache_dir=str(tmp_path / "cache"), mode="batch")
    assert result.n_errors == 1 and result.n_executed == 2
    by_graph = {r.scenario.graph.name: r for r in result.results}
    assert by_graph["broken"].status == "error"
    assert "no-such-generator" in by_graph["broken"].record["error"]
    assert by_graph["tiny"].status == "ok"


def test_batch_mode_uses_few_dispatches():
    from repro.core.engine import dispatch_stats, reset_dispatch_stats
    from repro.sweep.runner import execute_scenarios_batch

    scenarios = tiny_spec(accels=("accugraph", "foregraph", "thundergp"),
                          problems=("bfs", "pr")).scenarios()
    reset_dispatch_stats()
    records = [execute_scenario(s) for s in scenarios]
    n_seq = dispatch_stats()["dispatches"]
    reset_dispatch_stats()
    records_b = execute_scenarios_batch(scenarios)
    n_bat = dispatch_stats()["dispatches"]
    assert [r["report"] for r in records] == [r["report"] for r in records_b]
    assert n_bat * 5 <= n_seq  # the acceptance-criterion floor


def test_run_sweep_rejects_unknown_mode():
    with pytest.raises(ValueError, match="unknown mode"):
        run_sweep(tiny_spec(), mode="warp")


@pytest.mark.slow
def test_parallel_matches_serial_byte_identical(tmp_path):
    spec = tiny_spec(accels=("accugraph", "foregraph", "thundergp"),
                     problems=("bfs", "pr"))
    serial = run_sweep(spec, workers=0)
    parallel = run_sweep(spec, workers=2)
    assert result_rows(serial) == result_rows(parallel)
    p_ser, p_par = str(tmp_path / "ser.csv"), str(tmp_path / "par.csv")
    write_csv(p_ser, result_rows(serial))
    write_csv(p_par, result_rows(parallel))
    assert open(p_ser, "rb").read() == open(p_par, "rb").read()


# ---- results / CLI ---------------------------------------------------------


def test_write_csv_union_of_keys(tmp_path):
    path = str(tmp_path / "x.csv")
    write_csv(path, [dict(a=1, b=2), dict(a=3, error="boom")])
    lines = open(path).read().splitlines()
    assert lines[0] == "a,b,error"
    assert lines[1] == "1,2,"
    assert lines[2] == "3,,boom"


def test_rank_spearman():
    from repro.sweep import rank, spearman

    assert rank({"a": 3.0, "b": 1.0, "c": 2.0}) == ["b", "c", "a"]
    assert spearman(["a", "b", "c"], ["a", "b", "c"]) == pytest.approx(1.0)
    assert spearman(["a", "b", "c"], ["c", "b", "a"]) == pytest.approx(-1.0)


def test_cli_list(capsys):
    from repro.sweep.__main__ import main

    rc = main(["--accels", "accugraph,hitgraph", "--graphs", "sd",
               "--problems", "bfs,sssp", "--list"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "run  sd/hitgraph/sssp" in out
    assert "skip sd/accugraph/sssp" in out


def test_cli_unknown_name_clean_error(tmp_path, capsys):
    from repro.sweep.__main__ import main

    rc = main(["--accels", "bogus", "--graphs", "sd", "--cache", "",
               "--out", str(tmp_path)])
    assert rc == 2
    assert "unknown accelerator" in capsys.readouterr().err


def test_cli_end_to_end(tmp_path, capsys):
    from repro.sweep.__main__ import main

    args = ["--accels", "accugraph", "--graphs", "sd", "--problems", "bfs",
            "--cache", str(tmp_path / "cache"), "--out", str(tmp_path / "out")]
    assert main(args) == 0
    assert (tmp_path / "out" / "sweep.csv").exists()
    capsys.readouterr()
    assert main(args) == 0  # second run: all cached
    assert "1 cached, 0 executed" in capsys.readouterr().out


# ---- lazy (indexable) expansion --------------------------------------------


def test_point_at_matches_expand_order():
    spec = tiny_spec(accels=("accugraph", "hitgraph", "foregraph"),
                     problems=("bfs", "sssp"),
                     drams=("default", ("hbm", 4)),
                     page_policies=("open", "closed"))
    lazy = [spec.point_at(i) for i in range(spec.n_points)]
    streamed = list(spec.iter_points())
    assert lazy == streamed
    scenarios = [p for p in lazy if not hasattr(p, "reason")]
    assert scenarios == spec.scenarios()
    # byte-identical addressing: same hashes either way
    assert [scenario_hash(s) for s in scenarios] == \
        [scenario_hash(s) for s in spec.scenarios()]


def test_scenario_at_none_for_filtered_points():
    spec = tiny_spec(accels=("accugraph", "foregraph"), problems=("sssp",))
    # foregraph has no weighted support: its sssp points are filtered
    vals = [spec.scenario_at(i) for i in range(spec.n_points)]
    assert any(v is None for v in vals)
    assert [v for v in vals if v is not None] == spec.scenarios()
    with pytest.raises(IndexError):
        spec.point_at(spec.n_points)


def test_expand_skip_dedup_matches_lazy_stream():
    spec = tiny_spec(accels=("accugraph", "foregraph"),
                     problems=("sssp",), mappings=("row", "bank_xor@32"))
    scenarios, skipped = spec.expand()
    raw_skips = [p for p in spec.iter_points() if hasattr(p, "reason")]
    assert len(skipped) <= len(raw_skips)  # deduped per dram block
    assert {s.reason for s in skipped} == {s.reason for s in raw_skips}


# ---- bulk cache probe / memoization ----------------------------------------


def test_lookup_many_matches_individual_gets(tmp_path):
    cache = ResultCache(str(tmp_path))
    recs = {f"{i:02x}" + "0" * 62: dict(status="ok", runtime_s=float(i))
            for i in range(8)}
    for h, r in list(recs.items())[:5]:
        cache.put(h, r)
    missing = list(recs)[5:]
    got = cache.lookup_many(list(recs))
    assert got == {h: r for h, r in list(recs.items())[:5]}
    assert all(cache.get(h) == got.get(h) for h in got)
    assert all(cache.get(h) is None for h in missing)
    # disabled cache: bulk probe is an empty dict, like get() is None
    assert ResultCache(None).lookup_many(list(recs)) == {}


def test_lookup_many_quarantines_corrupt_files(tmp_path):
    cache = ResultCache(str(tmp_path))
    good, bad = "aa" + "0" * 62, "ab" + "0" * 62
    cache.put(good, dict(status="ok", runtime_s=1.0))
    cache.put(bad, dict(status="ok", runtime_s=2.0))
    with open(cache.path(bad), "w") as f:
        f.write("{truncated")
    got = cache.lookup_many([good, bad])
    assert list(got) == [good]
    import os
    assert os.path.exists(cache.path(bad) + ".bad")  # same as get()


def test_memo_capacity_serves_hits_after_file_deletion(tmp_path):
    import os

    cache = ResultCache(str(tmp_path), memo_capacity=4)
    h = "cc" + "0" * 62
    rec = dict(status="ok", runtime_s=3.0)
    cache.put(h, rec)
    os.unlink(cache.path(h))
    assert cache.get(h) == rec  # memoized: content addresses are immutable
    assert cache.lookup_many([h]) == {h: rec}
    # default capacity 0 keeps the old read-through behaviour
    cold = ResultCache(str(tmp_path))
    assert cold.get(h) is None


def test_memo_capacity_evicts_fifo(tmp_path):
    cache = ResultCache(str(tmp_path), memo_capacity=2)
    hs = [f"d{i:01x}" + "0" * 62 for i in range(3)]
    for i, h in enumerate(hs):
        cache.put(h, dict(status="ok", runtime_s=float(i)))
    assert hs[0] not in cache._memo and hs[2] in cache._memo
    # evicted entries still resolve from disk
    assert cache.get(hs[0]) == dict(status="ok", runtime_s=0.0)
