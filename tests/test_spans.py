"""Stage spans (repro.spans): the recorder, the seat's stage totals, the
scheduler's span ring and ``/stats``, and that recording changes no
result."""
import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from repro import spans
from repro.core import hostcache
from repro.graph.generators import GraphSpec
from repro.serve import ServeClient, SweepServer
from repro.serve import worker as worker_mod
from repro.serve.metrics import SpanRing
from repro.sweep import SweepSpec
from repro.sweep import runner as runner_mod
from repro.sweep.runner import execute_scenarios_batch

TINY = GraphSpec("tiny", "uniform", 256, 1024, True, 1, 0)
ALL_ACCELS = ("accugraph", "foregraph", "hitgraph", "thundergp")


def tiny_spec(accels=("accugraph",), drams=("default",),
              engines=("numpy",), graphs=(TINY,)):
    return SweepSpec(name="t", accelerators=tuple(accels), graphs=graphs,
                     problems=("bfs",), drams=tuple(drams),
                     engines=tuple(engines))


@pytest.fixture
def cold():
    """No graph, artifact or semantics cached: every stage runs."""
    runner_mod._GRAPHS.clear()
    hostcache.clear_all()
    yield
    runner_mod._GRAPHS.clear()
    hostcache.clear_all()


class FakeClock:
    def __init__(self, ticks):
        self._ticks = iter(ticks)

    def time(self):
        return float(next(self._ticks))


def record(tree, clock_ticks, monkeypatch):
    """Enter ``tree`` (nested ``(name, [children])``) inside a scope, the
    clock reading ``clock_ticks`` in order."""
    monkeypatch.setattr(spans, "time", FakeClock(clock_ticks))

    def enter(node):
        name, children = node
        with spans.span(name):
            for c in children:
                enter(c)

    with spans.collect() as rows:
        for node in tree:
            enter(node)
    return rows


# (tree, clock ticks in enter/exit order, rows' (parent, name), totals)
CASES = {
    "flat": (
        [("a", []), ("b", [])], [0, 4, 4, 10],
        [(None, "a"), (None, "b")],
        dict(a=(4.0, 1), b=(6.0, 1))),
    "nested": (
        [("chunk", [("timing", [("emit", []), ("scan", [])]),
                    ("finalize", [])])],
        [0, 1, 2, 3, 3, 5, 6, 6, 9, 10],
        [(None, "chunk"), (0, "timing"), (1, "emit"), (1, "scan"),
         (0, "finalize")],
        dict(chunk=(2.0, 1), timing=(2.0, 1), emit=(1.0, 1), scan=(2.0, 1),
             finalize=(3.0, 1))),
    "repeated": (
        [("chunk", [("semantics", [("semexec_wait", []),
                                   ("semexec_wait", [])])])],
        [0, 1, 2, 4, 5, 8, 9, 12],
        [(None, "chunk"), (0, "semantics"), (1, "semexec_wait"),
         (1, "semexec_wait")],
        dict(chunk=(4.0, 1), semantics=(3.0, 1), semexec_wait=(5.0, 2))),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_nesting_parents_and_self_time(case, monkeypatch):
    tree, ticks, shape, totals = CASES[case]
    rows = record(tree, ticks, monkeypatch)
    assert [r[0] for r in rows] == list(range(len(rows)))
    assert [(r[1], r[2]) for r in rows] == shape
    got = spans.stage_totals(rows)
    assert {k: (v["s"], v["n"]) for k, v in got.items()} == totals
    root_s = sum(r[4] - r[3] for r in rows if r[1] is None)
    assert sum(v["s"] for v in got.values()) == pytest.approx(root_s)


def test_span_outside_a_scope_records_nothing():
    with spans.collect() as rows:
        pass
    with spans.span("graph") as s:
        pass
    assert rows == [] and s._row is None
    assert getattr(spans._local, "scope", None) is None


@pytest.mark.parametrize("where", ["other_thread", "after_inner_scope"])
def test_scope_belongs_to_its_thread_and_nests(where):
    with spans.collect() as outer:
        with spans.span("chunk"):
            if where == "other_thread":
                def elsewhere():
                    with spans.span("graph"):
                        pass

                t = threading.Thread(target=elsewhere)
                t.start()
                t.join(timeout=10)
                assert not t.is_alive()
            else:
                with spans.collect() as inner:
                    with spans.span("graph"):
                        pass
                assert [r[2] for r in inner] == ["graph"]
            with spans.span("finalize"):
                pass
    assert [(r[1], r[2]) for r in outer] == [(None, "chunk"), (0, "finalize")]


@pytest.mark.parametrize("capacity,chunks,dropped", [
    (16, [3, 4], 0), (5, [3, 4], 2), (4, [3, 4, 2], 5), (7, [7], 0)])
def test_span_ring_bound_and_dropped(capacity, chunks, dropped):
    ring = SpanRing(capacity)
    for c, n in enumerate(chunks):
        rows = [[0, None, "chunk", 1.0 * c, c + 0.9]]
        rows += [[i, 0, "prepare", c + 0.1 * i, c + 0.1 * i + 0.05]
                 for i in range(1, n)]
        ring.add(rows, c, "seat:1")
    snap = ring.snapshot()
    assert snap["clock"] == "unix_s" and snap["dropped"] == dropped
    kept = snap["spans"]
    assert len(kept) == min(capacity, sum(chunks))
    ids = [r[0] for r in kept]
    assert ids == sorted(set(ids))  # unique, oldest first
    assert ids[-1] == sum(chunks) - 1
    by_id = {r[0]: r for r in kept}
    for sid, parent, name, t0, t1, chunk, seat in kept:
        assert seat == "seat:1" and t0 <= t1
        if parent is not None and parent in by_id:
            assert by_id[parent][2] == "chunk" and by_id[parent][5] == chunk


STAGES = {"chunk", "graph", "prepare", "prepare_graph", "semantics",
          "finalize"}


@pytest.mark.parametrize("mode,engine", [
    ("batch", "numpy"), ("batch", "device"), ("scenario", "device")])
def test_run_chunk_stage_totals_cover_the_chunk(mode, engine, cold):
    scenarios, _ = tiny_spec(accels=ALL_ACCELS, drams=("default", "hbm"),
                             engines=(engine,)).expand()
    out = worker_mod.run_chunk(scenarios, mode, None, True, None, 17)
    assert [r["status"] for r in out["records"]] == ["ok"] * len(scenarios)
    stages, rows = out["stages"], out["spans"]
    expect = STAGES | {"emit", "scan", "trace_hash"}
    expect |= {"timing"} if mode == "batch" else set()
    expect |= {"semexec_wait"} if engine == "device" else set()
    assert set(stages) == expect
    (root,) = [r for r in rows if r[1] is None]
    assert root[2] == "chunk" and stages["chunk"]["n"] == 1
    total = sum(v["s"] for v in stages.values())
    assert total == pytest.approx(root[4] - root[3], rel=0.05)
    assert out["chunk"] == 17 and out["seat"].endswith(f":{os.getpid()}")
    assert out["graph_memo"] == dict(hits=len(scenarios) - 1, misses=1)
    assert stages["graph"]["n"] == 1
    assert stages["prepare"]["n"] == len(scenarios)
    by_id = {r[0]: r for r in rows}
    for sid, parent, name, t0, t1 in rows:
        if name == "semexec_wait":
            assert by_id[parent][2] == "semantics"
        if name == "scan" and mode == "batch":
            assert by_id[parent][2] == "timing"
        if parent is not None:  # children lie inside their parent
            assert by_id[parent][3] <= t0 <= t1 <= by_id[parent][4]


@pytest.mark.parametrize("accels", [("accugraph", "foregraph"),
                                    ("hitgraph", "thundergp")])
def test_spans_leave_batch_records_byte_identical(accels, cold):
    scenarios, _ = tiny_spec(accels=accels, drams=("default", "hbm"),
                             engines=("numpy", "device")).expand()

    def run():
        runner_mod._GRAPHS.clear()
        hostcache.clear_all()
        recs = execute_scenarios_batch(scenarios, with_trace_hash=True)
        return [json.dumps({k: v for k, v in r.items() if k != "wall_s"},
                           sort_keys=True) for r in recs]

    plain = run()
    with spans.collect() as rows:
        spanned = run()
    assert rows and spanned == plain


class ThreadPool:
    """Runs chunks on threads of this process (real execution)."""

    size = 1

    def __init__(self):
        self._ex = ThreadPoolExecutor(max_workers=1)
        self.submitted = 0

    def submit(self, fn, *args):
        self.submitted += 1
        return self._ex.submit(fn, *args)

    def shutdown(self, wait=True, cancel_pending=False):
        self._ex.shutdown(wait=wait)

    def stats(self):
        return dict(size=1, busy=0, chunks_submitted=self.submitted)


def test_served_job_reports_stage_counters_and_spans(tmp_path, cold, capsys):
    from repro.serve.__main__ import main as serve_main

    server = SweepServer(port=0, cache_dir=str(tmp_path / "cache"),
                         chunk_size=2, quiet=True,
                         pool_factory=ThreadPool).start()
    try:
        client = ServeClient(server.address)
        client.wait_ready()
        res = client.run(tiny_spec(accels=ALL_ACCELS, engines=("device",)))
        assert res.statuses == ["ok"] * 4
        stats = client.stats()
        c = stats["counters"]
        assert c["worker_stage_chunk_n"] == 2  # 4 scenarios, 2 a chunk
        for name in ("graph", "prepare", "semantics", "semexec_wait",
                     "timing", "emit", "scan", "finalize"):
            assert c[f"worker_stage_{name}_n"] >= 1
            assert c[f"worker_stage_{name}_s"] >= 0.0
        assert (c["worker_graph_memo_hits"], c["worker_graph_memo_misses"]) \
            == (3, 1)
        assert "execute_s" not in stats["latency"]
        ring = stats["spans"]
        assert ring["clock"] == "unix_s" and ring["dropped"] == 0
        rows = ring["spans"]
        assert all(len(r) == 7 for r in rows)
        assert sorted({r[5] for r in rows}) == [0, 1]  # dispatch indices
        assert len(rows) == sum(v for k, v in c.items()
                                if k.startswith("worker_stage_")
                                and k.endswith("_n"))
        assert serve_main(["--stats", "--address", server.address]) == 0
        printed = json.loads(capsys.readouterr().out)
        assert printed["spans"] == dict(clock="unix_s", dropped=0,
                                        spans=len(rows))
        client.shutdown()
        server.wait()
    finally:
        server.close()


def test_remote_seat_forwards_stages_and_spans(cold):
    from repro.distributed.remote import RemoteWorkerPool, WorkerHostAgent

    pool = RemoteWorkerPool(heartbeat_s=0.1, task_deadline_s=60.0,
                            stall_deadline_s=5.0)
    agent = WorkerHostAgent(pool.address, seats=1, name="h1",
                            heartbeat_s=0.1, reconnect_backoff_s=0.05,
                            pool=ThreadPool())
    t = threading.Thread(target=agent.run, daemon=True)
    t.start()
    try:
        deadline = time.time() + 30
        while pool.size < 1 and time.time() < deadline:
            time.sleep(0.02)
        scenarios, _ = tiny_spec().expand()
        out = pool.submit(worker_mod.run_chunk, scenarios, "batch", None,
                          False, None, 5).result(timeout=120)
        assert [r["status"] for r in out["records"]] == ["ok"]
        assert out["chunk"] == 5 and out["seat"]
        assert out["stages"]["chunk"]["n"] == 1
        assert out["graph_memo"] == dict(hits=0, misses=1)
        assert [r[2] for r in out["spans"] if r[1] is None] == ["chunk"]
    finally:
        agent.stop()
        pool.shutdown(wait=False, cancel_pending=True)
