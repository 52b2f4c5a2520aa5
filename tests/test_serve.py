"""repro.serve: wire protocol, scheduler dedup/join/drain, HTTP lifecycle,
execution policy (timeout/retry), and CLI byte-identity."""
import dataclasses
import json
import os
import signal
import subprocess
import sys
import threading
import time
from concurrent.futures import Future

import pytest

from repro.graph.generators import GraphSpec
from repro.serve import (
    ProtocolError,
    ServeClient,
    SweepScheduler,
    SweepServer,
    dump_event,
    parse_event,
    spec_from_wire,
    spec_to_wire,
)
from repro.sweep import ExecutionPolicy, SweepSpec
from repro.sweep import runner as runner_mod
from repro.sweep.runner import execute_scenario_policied
from repro.sweep.spec import AddressMapping, ConfigOverride

TINY = GraphSpec("tiny", "uniform", 256, 1024, True, 1, 0)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def tiny_spec(accels=("accugraph",), problems=("bfs",), graphs=(TINY,),
              drams=("default",), **kw):
    return SweepSpec(name="t", accelerators=tuple(accels), graphs=tuple(graphs),
                     problems=tuple(problems), drams=tuple(drams), **kw)


def collect_events(job, timeout=120.0):
    """Drain a job's event queue until a terminal event (or fail)."""
    from repro.serve import TERMINAL_EVENTS
    events = []
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            ev = job.events.get(timeout=1.0)
        except Exception:
            continue
        events.append(ev)
        if ev["type"] in TERMINAL_EVENTS:
            return events
    pytest.fail(f"job {job.id} produced no terminal event in {timeout}s")


class GatedPool:
    """In-process stand-in for WorkerPool: runs chunks in threads (real
    execution, this process), each gated on a per-chunk Event when gates
    are provided — makes in-flight overlap deterministic in tests."""

    def __init__(self, size=1, gates=None):
        self.size = size
        self.gates = gates  # list[threading.Event] indexed by chunk order
        self.chunks = []  # scenario lists, in dispatch order
        self._threads = []

    def submit(self, fn, *args):
        fut = Future()
        n = len(self.chunks)
        self.chunks.append(list(args[0]))
        gate = self.gates[n] if self.gates and n < len(self.gates) else None

        def run():
            if gate is not None:
                gate.wait(timeout=60)
            try:
                fut.set_result(fn(*args))
            except BaseException as e:  # surfaced via fut in the scheduler
                fut.set_exception(e)

        t = threading.Thread(target=run, daemon=True)
        self._threads.append(t)
        t.start()
        return fut

    def shutdown(self, wait=True, cancel_pending=False):
        if self.gates:
            for g in self.gates:
                g.set()
        if wait:
            for t in self._threads:
                t.join(timeout=60)

    def stats(self):
        return dict(size=self.size, busy=0,
                    chunks_submitted=len(self.chunks), utilization=0.0)


def wait_for(cond, timeout=30.0, what="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.02)
    pytest.fail(f"timed out waiting for {what}")


# ---- wire protocol ----------------------------------------------------------


def test_spec_wire_roundtrip_rich():
    spec = SweepSpec(
        name="rich",
        accelerators=("accugraph", "hitgraph"),
        graphs=(TINY, "sd"),
        problems=("bfs", "pr"),
        drams=("default", ("hbm", 4)),
        mappings=("row", "bank_xor@32", AddressMapping("bank", 16)),
        page_policies=("open", "closed"),
        pseudo_channels=(False, True),
        overrides=(ConfigOverride(engine="scan"),),
        reorders=("identity", "degree"),
        interval_scales=(1, 2),
    )
    back = spec_from_wire(spec_to_wire(spec))
    # AddressMapping objects normalize to their label token on the wire;
    # everything else roundtrips structurally, and the expansion (what the
    # cache keys hash) is identical either way
    assert back == dataclasses.replace(
        spec, mappings=("row", "bank_xor@32", "bank@16"))
    assert back.expand() == spec.expand()
    # wire form is plain JSON all the way down
    json.loads(json.dumps(spec_to_wire(spec)))


def test_spec_wire_rejects_unknown_fields():
    wire = spec_to_wire(tiny_spec())
    wire["warp_speed"] = True
    with pytest.raises(ProtocolError, match="warp_speed"):
        spec_from_wire(wire)


def test_event_framing_roundtrip():
    ev = dict(type="row", job_id="job-000001", index=3, status="ok",
              row=dict(graph="tiny", cycles=123), done=4, total=8)
    line = dump_event(ev)
    assert line.endswith(b"\n") and b"\n" not in line[:-1]
    assert parse_event(line) == ev
    with pytest.raises(ProtocolError):
        parse_event(b"not json\n")


# ---- scheduler: dedup, in-flight join, cancel, drain ------------------------


def scheduler(tmp_path, pool, **kw):
    kw.setdefault("chunk_size", 1)
    return SweepScheduler(cache_dir=str(tmp_path / "cache"),
                          pool_factory=lambda: pool, **kw)


def test_scheduler_executes_and_caches(tmp_path):
    sched = scheduler(tmp_path, GatedPool())
    try:
        job = sched.submit(tiny_spec())
        events = collect_events(job)
        assert [e["type"] for e in events] == ["job", "row", "done"]
        assert events[1]["status"] == "ok"
        assert events[1]["row"]["graph"] == "tiny"
        # second submission: pure cache hit, nothing dispatched
        job2 = sched.submit(tiny_spec())
        events2 = collect_events(job2)
        assert events2[1]["status"] == "cached"
        assert events2[1]["row"] == events[1]["row"]
        stats = sched.stats()
        assert stats["counters"]["executed_ok"] == 1
        assert stats["counters"]["cache_hits"] == 1
    finally:
        sched.close()


def test_scheduler_inflight_join_across_jobs(tmp_path):
    gate = threading.Event()
    pool = GatedPool(gates=[gate])
    sched = scheduler(tmp_path, pool)
    try:
        job_a = sched.submit(tiny_spec())
        wait_for(lambda: len(pool.chunks) == 1, what="chunk dispatch")
        # identical scenario while the first is mid-flight: must join, not
        # re-queue
        job_b = sched.submit(tiny_spec())
        assert sched.metrics.get("inflight_joins") == 1
        gate.set()
        ev_a = collect_events(job_a)
        ev_b = collect_events(job_b)
        assert ev_a[1]["status"] == "ok" and ev_b[1]["status"] == "ok"
        assert ev_a[1]["row"] == ev_b[1]["row"]
        # one execution total, for two jobs
        assert sum(len(c) for c in pool.chunks) == 1
        assert sched.stats()["counters"]["executed_ok"] == 1
    finally:
        sched.close()


def test_scheduler_dedups_within_one_submission(tmp_path):
    pool = GatedPool()
    sched = scheduler(tmp_path, pool)
    try:
        # duplicate axis values expand to identical scenarios
        job = sched.submit(tiny_spec(graphs=(TINY, TINY)))
        events = collect_events(job)
        rows = [e for e in events if e["type"] == "row"]
        assert len(rows) == 2  # both indices get their row...
        assert rows[0]["row"] == rows[1]["row"]
        assert sum(len(c) for c in pool.chunks) == 1  # ...from one execution
        assert sched.metrics.get("dedup_joins") == 1
    finally:
        sched.close()


def test_scheduler_cancel_drops_queued_work(tmp_path):
    gate = threading.Event()
    # chunk_size=1, size=1 -> at most 2 chunks in flight (both gated);
    # the other 2 scenarios stay queued behind them
    pool = GatedPool(size=1, gates=[gate, gate])
    sched = scheduler(tmp_path, pool, mode="scenario")
    try:
        job = sched.submit(tiny_spec(
            accels=("accugraph", "hitgraph", "thundergp", "foregraph")))
        wait_for(lambda: len(pool.chunks) == 2, what="two gated dispatches")
        assert sched.cancel(job.id)
        assert not sched.cancel(job.id)  # second cancel is a no-op
        events = collect_events(job)
        assert events[-1]["type"] == "cancelled"
        gate.set()
        wait_for(lambda: sched.stats()["queue"]["inflight_chunks"] == 0,
                 what="inflight to settle")
        stats = sched.stats()
        assert stats["counters"]["scenarios_cancelled"] == 2
        # the queued-but-never-started scenarios were dropped, not executed
        assert sum(len(c) for c in pool.chunks) == 2
    finally:
        sched.close()


def test_scheduler_drain_persists_completed_and_resumes(tmp_path):
    gate = threading.Event()
    # 2 chunks dispatch and block on the gate; 2 scenarios stay queued and
    # must never dispatch once the drain begins
    pool = GatedPool(size=1, gates=[gate, gate])
    sched = scheduler(tmp_path, pool, mode="scenario")
    accels = ("accugraph", "hitgraph", "thundergp", "foregraph")
    job = sched.submit(tiny_spec(accels=accels))
    wait_for(lambda: len(pool.chunks) == 2, what="two gated dispatches")
    # drain releases the gate via pool.shutdown: the running chunks finish,
    # deliver, and persist; the queued ones are abandoned
    sched.drain()
    events = collect_events(job, timeout=10)
    assert events[-1]["type"] == "interrupted"
    done_first = events[-1]["completed"]
    assert done_first == 2
    assert sched.stats()["draining"]
    with pytest.raises(RuntimeError):
        sched.submit(tiny_spec())

    # a fresh scheduler over the same cache dir resumes from what was
    # persisted: completed scenarios come back as cache hits (journal
    # recovery off — this test pins the cache path; test_faults covers
    # journal-driven resumption)
    sched2 = scheduler(tmp_path, GatedPool(), mode="scenario", resume=False)
    try:
        job2 = sched2.submit(tiny_spec(accels=accels))
        events2 = collect_events(job2)
        assert events2[-1]["type"] == "done"
        statuses = [e["status"] for e in events2 if e["type"] == "row"]
        assert statuses.count("cached") == done_first
        assert statuses.count("ok") == len(accels) - done_first
    finally:
        sched2.close()


def test_scheduler_errors_not_cached(tmp_path):
    broken = GraphSpec("broken", "no-such-generator", 64, 128, True, 1, 0)
    sched = scheduler(tmp_path, GatedPool())
    try:
        job = sched.submit(tiny_spec(graphs=(broken,)))
        events = collect_events(job)
        assert events[1]["status"] == "error"
        assert "error" in events[1]["row"]
        # errors are retried on the next submission, not served from cache
        job2 = sched.submit(tiny_spec(graphs=(broken,)))
        assert collect_events(job2)[1]["status"] == "error"
        assert sched.stats()["counters"]["executed_error"] == 2
        assert sched.stats()["counters"].get("cache_hits", 0) == 0
    finally:
        sched.close()


def test_scheduler_counts_seat_device_work(tmp_path):
    """Seats report each chunk's device dispatches and compiles; the
    scheduler sums them into worker_device_* counters."""
    sched = scheduler(tmp_path, GatedPool(), chunk_size=2)
    try:
        job = sched.submit(tiny_spec(accels=("accugraph", "hitgraph")))
        assert [e["status"] for e in collect_events(job)[1:-1]] == ["ok"] * 2
        c = sched.stats()["counters"]
        assert c["worker_device_dispatches"] >= 1
        assert c["worker_device_traces"] >= 2
        assert c["worker_device_requests"] > 0
        # each dispatch walks its bucket length L, at least the 256 floor
        assert c["worker_device_scan_steps"] >= \
            256 * c["worker_device_dispatches"]
        assert c["worker_device_host_traces"] == 0  # all under SCAN_CUTOFF
        assert c.get("timing_fallbacks", 0) == 0
    finally:
        sched.close()


def test_timing_fallback_is_flagged_in_the_stream(tmp_path, monkeypatch):
    """A failed batched timing pass re-times per scenario: the rows stay
    right, and the stream and the counters say which path ran."""
    from repro.core import engine

    def broken(items):
        raise RuntimeError("batched dispatch refused")

    monkeypatch.setattr(engine, "simulate_many", broken)
    sched = scheduler(tmp_path, GatedPool(), chunk_size=2)
    try:
        job = sched.submit(tiny_spec(accels=("accugraph", "hitgraph")))
        rows = collect_events(job)[1:-1]
        assert [e["status"] for e in rows] == ["ok", "ok"]
        assert all(e["timing_fallback"] for e in rows)
        assert sched.stats()["counters"]["timing_fallbacks"] == 2
    finally:
        sched.close()


# ---- execution policy: timeout + bounded retry ------------------------------


def test_policy_retry_recovers_flaky(monkeypatch):
    (scn,), _ = tiny_spec().expand()
    calls = dict(n=0)
    real = runner_mod.execute_scenario

    def flaky(scenario, with_trace_hash=False):
        calls["n"] += 1
        if calls["n"] < 3:
            return dict(status="error", error="transient", wall_s=0.0)
        return real(scenario, with_trace_hash=with_trace_hash)

    monkeypatch.setattr(runner_mod, "execute_scenario", flaky)
    rec = execute_scenario_policied(
        scn, ExecutionPolicy(timeout_s=30.0, retries=2, backoff_s=0.0))
    assert rec["status"] == "ok"
    assert rec["attempts"] == 3


def test_policy_retries_exhausted(monkeypatch):
    (scn,), _ = tiny_spec().expand()
    monkeypatch.setattr(
        runner_mod, "execute_scenario",
        lambda scenario, with_trace_hash=False: dict(
            status="error", error="always", wall_s=0.0))
    rec = execute_scenario_policied(
        scn, ExecutionPolicy(timeout_s=None, retries=2, backoff_s=0.0))
    assert rec["status"] == "error"
    assert rec["attempts"] == 3


def test_policy_timeout_bounds_scenario(monkeypatch):
    (scn,), _ = tiny_spec().expand()

    def stuck(scenario, with_trace_hash=False):
        time.sleep(30)

    monkeypatch.setattr(runner_mod, "execute_scenario", stuck)
    t0 = time.time()
    rec = execute_scenario_policied(
        scn, ExecutionPolicy(timeout_s=0.2, retries=0))
    assert time.time() - t0 < 5
    assert rec["status"] == "error" and rec["timed_out"]


def test_policy_cli_flags():
    from repro.sweep.__main__ import add_policy_args, build_policy
    import argparse
    ap = argparse.ArgumentParser()
    add_policy_args(ap)
    args = ap.parse_args(["--timeout-per-scenario", "2.5", "--retries", "3",
                          "--retry-backoff", "0.1"])
    pol = build_policy(args)
    assert pol == ExecutionPolicy(timeout_s=2.5, retries=3, backoff_s=0.1)
    assert build_policy(ap.parse_args([])) is None


def test_sweep_cli_timeout_flag(tmp_path, capsys, monkeypatch):
    from repro.sweep.__main__ import main as sweep_main

    def stuck(scenario, with_trace_hash=False):
        time.sleep(30)

    monkeypatch.setattr(runner_mod, "execute_scenario", stuck)
    rc = sweep_main([
        "--accels", "accugraph", "--graphs", "sd", "--problems", "bfs",
        "--workers", "0", "--timeout-per-scenario", "0.2",
        "--cache", "", "--out", str(tmp_path)])
    assert rc == 1  # timeout surfaced as an error row, not a hang
    out = capsys.readouterr().out
    assert "error" in out


# ---- HTTP server lifecycle --------------------------------------------------


def test_server_submit_stream_stats_shutdown(tmp_path):
    server = SweepServer(port=0, cache_dir=str(tmp_path / "cache"),
                         chunk_size=2, quiet=True,
                         pool_factory=lambda: GatedPool(size=2)).start()
    try:
        client = ServeClient(server.address)
        health = client.wait_ready()
        assert health["status"] == "ok"
        res = client.run(tiny_spec(accels=("accugraph", "hitgraph")))
        assert res.outcome == "done"
        assert res.statuses == ["ok", "ok"]
        assert [r["accelerator"] for r in res.rows] == ["accugraph", "hitgraph"]
        res2 = client.run(tiny_spec(accels=("accugraph", "hitgraph")))
        assert res2.statuses == ["cached", "cached"]
        assert res2.rows == res.rows
        stats = client.stats()
        assert stats["counters"]["executed_ok"] == 2
        assert stats["counters"]["cache_hits"] == 2
        assert stats["jobs"]["completed"] == 2
        assert "row_s" in stats["latency"]
        status = client.job_status(res.job_id)
        assert status["finished"] and status["done"] == 2
        client.shutdown()
        server.wait()
    finally:
        server.close()


def test_server_concurrent_overlap_shares_work(tmp_path):
    hold = threading.Event()
    pool = GatedPool(size=1, gates=[hold, hold, hold])
    server = SweepServer(port=0, cache_dir=str(tmp_path / "cache"),
                         chunk_size=1, quiet=True,
                         pool_factory=lambda: pool).start()
    try:
        client = ServeClient(server.address)
        client.wait_ready()
        spec_a = tiny_spec(accels=("accugraph", "hitgraph"))
        spec_b = tiny_spec(accels=("hitgraph", "thundergp"))  # overlaps on hitgraph
        results = {}

        def run(name, spec):
            results[name] = ServeClient(server.address).run(spec)

        ta = threading.Thread(target=run, args=("a", spec_a))
        ta.start()
        wait_for(lambda: client.stats()["jobs"]["submitted"] >= 1,
                 what="job A submitted")
        tb = threading.Thread(target=run, args=("b", spec_b))
        tb.start()
        wait_for(lambda: client.stats()["jobs"]["submitted"] >= 2,
                 what="job B submitted")
        hold.set()
        ta.join(timeout=120)
        tb.join(timeout=120)
        assert results["a"].statuses.count("ok") + results["a"].n_cached == 2
        assert results["b"].statuses.count("ok") + results["b"].n_cached == 2
        # the shared hitgraph row is identical on both streams
        row_a = next(r for r in results["a"].rows if r["accelerator"] == "hitgraph")
        row_b = next(r for r in results["b"].rows if r["accelerator"] == "hitgraph")
        assert row_a == row_b
        stats = client.stats()
        # provably shared: B's hitgraph joined A's in-flight entry, and the
        # union of both grids (3 unique scenarios) executed exactly once each
        assert stats["counters"]["inflight_joins"] == 1
        assert stats["counters"]["executed_ok"] == 3
        assert sum(len(c) for c in pool.chunks) == 3
        client.shutdown()
        server.wait()
    finally:
        server.close()


def test_server_rejects_bad_spec(tmp_path):
    server = SweepServer(port=0, cache_dir=str(tmp_path / "cache"),
                         quiet=True, pool_factory=lambda: GatedPool()).start()
    try:
        client = ServeClient(server.address)
        client.wait_ready()
        from repro.serve import ServeError
        with pytest.raises(ServeError, match="unknown accelerator"):
            client.run(tiny_spec(accels=("warpdrive",)))
        with pytest.raises(ServeError):
            client.job_status("job-999999")
    finally:
        server.close()


# ---- byte-identity and the full subprocess lifecycle ------------------------

AXES = ["--accels", "accugraph,hitgraph", "--graphs", "sd",
        "--problems", "bfs", "--drams", "default"]


def test_server_rows_byte_identical_to_cli(tmp_path):
    """The acceptance bar: a served sweep writes the same bytes as
    ``python -m repro.sweep`` for the same spec (fresh caches on both
    sides, so every row is computed, none cached)."""
    from repro.serve.__main__ import main as serve_main
    from repro.sweep.__main__ import main as sweep_main

    cli_out = tmp_path / "cli"
    rc = sweep_main(AXES + ["--workers", "0",
                            "--cache", str(tmp_path / "cli_cache"),
                            "--out", str(cli_out)])
    assert rc == 0

    server = SweepServer(port=0, cache_dir=str(tmp_path / "srv_cache"),
                         chunk_size=1, quiet=True,
                         pool_factory=lambda: GatedPool()).start()
    try:
        srv_out = tmp_path / "srv"
        rc = serve_main(["--submit", "--address", server.address,
                         "--out", str(srv_out)] + AXES)
        assert rc == 0
    finally:
        server.close()

    cli_csv = (cli_out / "sweep.csv").read_bytes()
    srv_csv = (srv_out / "sweep.csv").read_bytes()
    assert cli_csv == srv_csv
    assert json.loads((cli_out / "sweep.json").read_text()) == \
        json.loads((srv_out / "sweep.json").read_text())


def spawn_server(tmp_path, cache, *extra_args):
    port_file = tmp_path / "port"
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.serve", "--port", "0",
         "--port-file", str(port_file), "--cache", str(cache),
         "--workers", "1", "--chunk-size", "1", "--quiet", *extra_args],
        env=env, cwd=os.path.dirname(SRC),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    deadline = time.time() + 120
    while not port_file.exists() or not port_file.read_text().strip():
        if proc.poll() is not None:
            pytest.fail(f"server died: {proc.stderr.read().decode()}")
        if time.time() > deadline:
            proc.kill()
            pytest.fail("server never wrote its port file")
        time.sleep(0.1)
    address = port_file.read_text().strip()
    port_file.unlink()
    return proc, address


@pytest.mark.slow
def test_sigterm_drains_and_resume_completes(tmp_path):
    """SIGTERM mid-job: the server drains (exit 0), completed rows are in
    the cache, and a re-submission resumes from them."""
    cache = tmp_path / "cache"
    spec = tiny_spec(
        accels=("accugraph", "foregraph", "hitgraph", "thundergp"),
        drams=("default", "hbm"))  # 8 scenarios, 1 worker, chunk=1

    proc, address = spawn_server(tmp_path, cache)
    client = ServeClient(address)
    client.wait_ready(deadline_s=60)

    events = []
    fired = threading.Event()

    def stream():
        for ev in client.submit(spec):
            events.append(ev)
            if ev["type"] == "row" and not fired.is_set():
                os.kill(proc.pid, signal.SIGTERM)  # mid-job, >=1 row done
                fired.set()

    t = threading.Thread(target=stream)
    t.start()
    t.join(timeout=180)
    assert not t.is_alive(), "stream never terminated after SIGTERM"
    assert proc.wait(timeout=60) == 0, "drain must exit cleanly"

    assert events[-1]["type"] == "interrupted"
    done_first = events[-1]["completed"]
    assert 1 <= done_first < 8
    rows_streamed = sum(e["type"] == "row" for e in events)
    assert rows_streamed == done_first  # completed rows reached the client

    # resume: same cache, fresh server; completed work is not redone.
    # --no-resume pins the cache-resumption path: with journal recovery on,
    # the restarted server would race this resubmission by re-running the
    # interrupted job itself (that path is covered in test_faults).
    proc2, address2 = spawn_server(tmp_path, cache, "--no-resume")
    try:
        client2 = ServeClient(address2)
        client2.wait_ready(deadline_s=60)
        res = client2.run(spec)
        assert res.outcome == "done"
        assert len(res.rows) == 8
        assert res.statuses.count("cached") == done_first
        assert res.statuses.count("ok") == 8 - done_first
        client2.shutdown()
        assert proc2.wait(timeout=60) == 0
    finally:
        if proc2.poll() is None:
            proc2.kill()


def test_scheduler_counts_semexec_steps(tmp_path):
    """Seats report the device semantic engine's jitted steps; over a job
    of fresh SSSP executions the counter grows by the rows' iterations
    (one step an iteration for HitGraph and ThunderGP)."""
    kron = GraphSpec("kron-steps", "rmat", 512, 8192, False, 23, 5)
    sched = scheduler(tmp_path, GatedPool(), chunk_size=2)
    try:
        job = sched.submit(tiny_spec(accels=("hitgraph", "thundergp"),
                                     problems=("sssp",), graphs=(kron,),
                                     engines=("device",)))
        rows = collect_events(job)[1:-1]
        assert [e["status"] for e in rows] == ["ok"] * 2
        assert all(e["row"]["engine"] == "device" for e in rows)
        steps = sum(e["row"]["iterations"] for e in rows)
        assert steps > 2
        c = sched.stats()["counters"]
        assert c["worker_device_semexec_steps"] == steps
    finally:
        sched.close()
