"""Multi-host sweep serving: the serve wire codec, the RemoteWorkerPool /
WorkerHostAgent pair, scheduler integration (byte-identical rows, chunk
re-dispatch on host loss, poison parity), remote-site fault injection
(drop / delay / disconnect), host re-registration, and the real
subprocess topology (server + two worker-host agents, one SIGKILLed
mid-campaign)."""
import json
import os
import signal
import subprocess
import sys
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

import pytest

from repro.distributed.faults import FaultPlan, FaultRule
from repro.distributed.remote import (
    RemoteWorkerPool,
    WorkerHostAgent,
    parse_address,
)
from repro.distributed.workpool import WorkerLost
from repro.graph.generators import GraphSpec
from repro.serve import worker as worker_mod
from repro.serve.protocol import (
    ProtocolError,
    chunk_from_wire,
    chunk_to_wire,
    policy_from_wire,
    policy_to_wire,
    scenario_from_wire,
    scenario_to_wire,
)
from repro.serve.scheduler import SweepScheduler
from repro.sweep import ExecutionPolicy, SweepSpec
from repro.sweep.cache import scenario_hash
from repro.sweep.results import result_rows
from repro.sweep.runner import run_sweep

TINY = GraphSpec("tiny", "uniform", 256, 1024, True, 1, 0)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def tiny_spec(accels=("accugraph",), problems=("bfs",), graphs=(TINY,),
              drams=("default",), **kw):
    return SweepSpec(name="t", accelerators=tuple(accels),
                     graphs=tuple(graphs), problems=tuple(problems),
                     drams=tuple(drams), **kw)


def wait_for(cond, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    pytest.fail(f"timed out waiting for {what}")


def collect_events(job, timeout=120.0):
    from repro.serve import TERMINAL_EVENTS
    events = []
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            ev = job.events.get(timeout=1.0)
        except Exception:
            continue
        events.append(ev)
        if ev["type"] in TERMINAL_EVENTS:
            return events
    pytest.fail(f"job {job.id} produced no terminal event in {timeout}s")


# ---- wire codec -------------------------------------------------------------


def test_scenario_wire_roundtrip_is_hash_identical():
    spec = tiny_spec(accels=("accugraph", "hitgraph", "foregraph",
                             "thundergp"),
                     problems=("bfs", "pr"), drams=("default", "hbm"))
    scenarios, _ = spec.expand()
    assert scenarios
    for s in scenarios:
        wire = scenario_to_wire(s)
        # the wire form must actually be JSON, not merely dict-shaped
        back = scenario_from_wire(json.loads(json.dumps(wire)))
        assert back == s
        assert scenario_hash(back) == scenario_hash(s)


def test_policy_wire_roundtrip_carries_fault_plan():
    assert policy_from_wire(policy_to_wire(None)) is None
    plan = FaultPlan(seed=3, rules=(FaultRule("scenario", "error", at=(1,)),))
    p = ExecutionPolicy(timeout_s=2.5, retries=2, backoff_s=0.1,
                        fault_plan=plan)
    back = policy_from_wire(json.loads(json.dumps(policy_to_wire(p))))
    assert (back.timeout_s, back.retries, back.backoff_s) == (2.5, 2, 0.1)
    assert back.fault_plan == plan


def test_chunk_wire_roundtrip():
    scenarios, _ = tiny_spec().expand()
    ev = json.loads(json.dumps(chunk_to_wire(
        7, scenarios, "batch", ExecutionPolicy(retries=1), True, None, 41)))
    chunk_id, back, mode, policy, hashes, inject, dispatch = \
        chunk_from_wire(ev)
    assert chunk_id == 7 and dispatch == 41 and back == list(scenarios)
    assert mode == "batch" and policy.retries == 1
    assert hashes is True and inject is None
    with pytest.raises(ProtocolError):
        chunk_from_wire(dict(type="chunk", chunk="x"))


def test_parse_address():
    assert parse_address("10.0.0.2:8732") == ("10.0.0.2", 8732)
    assert parse_address(":8732") == ("127.0.0.1", 8732)
    with pytest.raises(ValueError):
        parse_address("no-port")


# ---- in-process remote pool + agent ----------------------------------------


class InlinePool:
    """Agent-side local-pool stand-in: executes chunks on threads in this
    very process — the remote plumbing is exercised end to end without
    paying spawn-worker startup per test."""

    def __init__(self, seats=2):
        self.size = seats
        self._ex = ThreadPoolExecutor(max_workers=seats)

    def submit(self, fn, *args):
        return self._ex.submit(fn, *args)

    def shutdown(self, wait=True, cancel_pending=False, grace_s=None):
        self._ex.shutdown(wait=False)


class LosingPool(InlinePool):
    """Local pool whose first ``fail_first`` chunks die as WorkerLost —
    the host is healthy, its worker wasn't."""

    def __init__(self, seats=1, fail_first=1, reason="crash"):
        super().__init__(seats)
        self.fail_first = fail_first
        self.reason = reason
        self.losses = 0

    def submit(self, fn, *args):
        if self.losses < self.fail_first:
            self.losses += 1
            fut = Future()
            fut.set_exception(WorkerLost(self.reason, -1, "injected locally"))
            return fut
        return super().submit(fn, *args)


def make_remote_pool(**kw):
    kw.setdefault("heartbeat_s", 0.1)
    kw.setdefault("task_deadline_s", 10.0)
    kw.setdefault("stall_deadline_s", 1.0)
    return RemoteWorkerPool(**kw)


def start_agent(address, name, seats=2, pool=None):
    agent = WorkerHostAgent(address, seats=seats, name=name,
                            heartbeat_s=0.1, reconnect_backoff_s=0.05,
                            pool=pool or InlinePool(seats))
    t = threading.Thread(target=agent.run, daemon=True)
    t.start()
    return agent, t


def test_remote_pool_executes_chunks_and_tracks_hosts():
    pool = make_remote_pool()
    agent = thread = None
    try:
        assert pool.size == 0  # no hosts yet: capacity is live, not fixed
        agent, thread = start_agent(pool.address, "h1", seats=2)
        wait_for(lambda: pool.size == 2, what="host registration")
        scenarios, _ = tiny_spec().expand()
        out = pool.submit(worker_mod.run_chunk, scenarios, "scenario", None,
                          False, None).result(timeout=120)
        assert [r["status"] for r in out["records"]] == ["ok"]
        s = pool.stats()
        assert s["size"] == 2 and s["alive"] == 1
        assert s["hosts"]["h1"]["chunks_done"] == 1
        assert s["workers_lost"] == 0
    finally:
        if agent:
            agent.stop()
        pool.shutdown(wait=False, cancel_pending=True)


def test_remote_pool_rejects_foreign_callables():
    pool = make_remote_pool()
    try:
        with pytest.raises(TypeError):
            pool.submit(print, "not a chunk")
    finally:
        pool.shutdown(wait=False, cancel_pending=True)


def test_chunks_queue_until_a_host_arrives():
    """submit() before any host exists must park the chunk, not fail —
    the scheduler dispatches into an empty pool at startup."""
    pool = make_remote_pool()
    agent = None
    try:
        scenarios, _ = tiny_spec().expand()
        fut = pool.submit(worker_mod.run_chunk, scenarios, "scenario", None,
                          False, None)
        assert pool.stats()["queued"] == 1
        agent, _ = start_agent(pool.address, "late", seats=1)
        out = fut.result(timeout=120)
        assert [r["status"] for r in out["records"]] == ["ok"]
    finally:
        if agent:
            agent.stop()
        pool.shutdown(wait=False, cancel_pending=True)


def test_host_death_fails_inflight_chunks_as_workerlost():
    class BlockingPool(InlinePool):
        def __init__(self):
            super().__init__(1)
            self.started = threading.Event()
            self.release = threading.Event()

        def submit(self, fn, *args):
            def blocked():
                self.started.set()
                self.release.wait(30)
                return fn(*args)
            return self._ex.submit(blocked)

    pool = make_remote_pool()
    local = BlockingPool()
    agent, _ = start_agent(pool.address, "doomed", seats=1, pool=local)
    try:
        wait_for(lambda: pool.size == 1, what="registration")
        scenarios, _ = tiny_spec().expand()
        fut = pool.submit(worker_mod.run_chunk, scenarios, "scenario", None,
                          False, None)
        assert local.started.wait(30), "chunk never reached the host"
        agent.stop()  # the host vanishes mid-chunk (downlink closes)
        with pytest.raises(WorkerLost) as ei:
            fut.result(timeout=30)
        assert ei.value.reason in ("crash", "stall")
        assert "doomed" in ei.value.detail
        assert pool.stats()["workers_lost"] == 1
    finally:
        local.release.set()
        pool.shutdown(wait=False, cancel_pending=True)


def test_local_worker_loss_is_forwarded_loss_for_loss():
    """A host whose *local* pool loses a worker reports the chunk lost with
    the local reason — the scheduler can't tell a lost host from a lost
    process, so its recovery is identical."""
    pool = make_remote_pool()
    agent, _ = start_agent(pool.address, "flaky", seats=1,
                           pool=LosingPool(fail_first=1, reason="hang"))
    try:
        wait_for(lambda: pool.size == 1, what="registration")
        scenarios, _ = tiny_spec().expand()
        fut = pool.submit(worker_mod.run_chunk, scenarios, "scenario", None,
                          False, None)
        with pytest.raises(WorkerLost) as ei:
            fut.result(timeout=30)
        assert ei.value.reason == "hang" and "flaky" in ei.value.detail
        # the host itself is fine: the next chunk runs
        out = pool.submit(worker_mod.run_chunk, scenarios, "scenario", None,
                          False, None).result(timeout=120)
        assert [r["status"] for r in out["records"]] == ["ok"]
    finally:
        agent.stop()
        pool.shutdown(wait=False, cancel_pending=True)


def test_drop_fault_reclaimed_by_liveness_deadline():
    plan = FaultPlan(seed=0, rules=(FaultRule("remote", "drop", at=(0,)),))
    pool = make_remote_pool(task_deadline_s=1.0, fault_plan=plan)
    agent, _ = start_agent(pool.address, "h1", seats=1)
    try:
        wait_for(lambda: pool.size == 1, what="registration")
        scenarios, _ = tiny_spec().expand()
        t0 = time.monotonic()
        fut = pool.submit(worker_mod.run_chunk, scenarios, "scenario", None,
                          False, None)
        with pytest.raises(WorkerLost) as ei:
            fut.result(timeout=30)
        assert ei.value.reason == "hang"
        assert time.monotonic() - t0 < 20
        assert pool.stats()["workers_lost"] == 1
    finally:
        agent.stop()
        pool.shutdown(wait=False, cancel_pending=True)


def test_disconnect_fault_severs_then_host_reregisters():
    plan = FaultPlan(seed=0,
                     rules=(FaultRule("remote", "disconnect", at=(0,)),))
    pool = make_remote_pool(fault_plan=plan)
    agent, _ = start_agent(pool.address, "h1", seats=1)
    try:
        wait_for(lambda: pool.size == 1, what="registration")
        scenarios, _ = tiny_spec().expand()
        fut = pool.submit(worker_mod.run_chunk, scenarios, "scenario", None,
                          False, None)
        # assignment 0 delivers the chunk then severs the downlink: the
        # chunk fails as lost and the agent re-registers with backoff
        with pytest.raises(WorkerLost):
            fut.result(timeout=30)
        wait_for(lambda: pool.size == 1, what="re-registration")
        wait_for(lambda: agent.sessions >= 2, what="second session")
        assert pool.stats()["respawns"] >= 1
        # assignment 1 is clean: the re-registered host executes it
        out = pool.submit(worker_mod.run_chunk, scenarios, "scenario", None,
                          False, None).result(timeout=120)
        assert [r["status"] for r in out["records"]] == ["ok"]
    finally:
        agent.stop()
        pool.shutdown(wait=False, cancel_pending=True)


# ---- scheduler integration --------------------------------------------------


def remote_scheduler(tmp_path, pool, **kw):
    kw.setdefault("chunk_size", 2)
    kw.setdefault("mode", "scenario")
    return SweepScheduler(cache_dir=str(tmp_path / "cache"),
                          pool_factory=lambda: pool, **kw)


def test_scheduler_rows_byte_identical_across_two_hosts(tmp_path):
    """The acceptance bar: a campaign served by two worker hosts produces
    exactly the rows of the single-process CLI path."""
    spec = tiny_spec(accels=("accugraph", "hitgraph", "foregraph"),
                     drams=("default", "hbm"))
    pool = make_remote_pool()
    sched = remote_scheduler(tmp_path, pool)
    a1, _ = start_agent(pool.address, "h1", seats=1)
    a2, _ = start_agent(pool.address, "h2", seats=1)
    try:
        wait_for(lambda: pool.size == 2, what="both hosts")
        job = sched.submit(spec)
        events = collect_events(job, timeout=300)
        assert events[-1]["type"] == "done"
        rows = [e["row"] for e in sorted(
            (e for e in events if e["type"] == "row"),
            key=lambda e: e["index"])]
        clean = result_rows(run_sweep(spec, cache_dir=None, mode="scenario"))
        assert rows == clean
        # both hosts actually participated
        hosts = pool.stats()["hosts"]
        assert hosts["h1"]["chunks_done"] >= 1
        assert hosts["h2"]["chunks_done"] >= 1
    finally:
        a1.stop()
        a2.stop()
        sched.close()


def test_scheduler_redispatches_after_host_kill(tmp_path):
    """Killing a host mid-chunk re-dispatches its scenarios to the
    survivor; the campaign still completes with ok rows."""
    class BlockOnce(InlinePool):
        def __init__(self):
            super().__init__(1)
            self.first = threading.Event()
            self.release = threading.Event()
            self._n = 0

        def submit(self, fn, *args):
            self._n += 1
            if self._n == 1:
                def blocked():
                    self.first.set()
                    self.release.wait(60)
                    return fn(*args)
                return self._ex.submit(blocked)
            return super().submit(fn, *args)

    spec = tiny_spec(accels=("accugraph", "hitgraph"))
    pool = make_remote_pool()
    sched = remote_scheduler(tmp_path, pool, chunk_size=1)
    doomed_local = BlockOnce()
    doomed, _ = start_agent(pool.address, "doomed", seats=1,
                            pool=doomed_local)
    survivor = None
    try:
        wait_for(lambda: pool.size == 1, what="doomed host")
        job = sched.submit(spec)
        assert doomed_local.first.wait(60), "no chunk reached doomed host"
        survivor, _ = start_agent(pool.address, "survivor", seats=1)
        wait_for(lambda: "survivor" in pool.stats()["hosts"],
                 what="survivor host")
        doomed.stop()  # dies holding a chunk
        events = collect_events(job, timeout=300)
        assert events[-1]["type"] == "done"
        statuses = [e["status"] for e in events if e["type"] == "row"]
        assert sorted(statuses) == ["ok", "ok"]
        s = sched.stats()
        assert s["faults"]["chunks_lost"] >= 1
        assert s["faults"]["scenarios_redispatched"] >= 1
    finally:
        doomed_local.release.set()
        if survivor:
            survivor.stop()
        sched.close()


def test_remote_poison_parity(tmp_path):
    """A chunk that is dropped on every dispatch trips the scheduler's
    poison breaker exactly as a crash-looping local worker does."""
    plan = FaultPlan(seed=0, rules=(FaultRule("remote", "drop"),))
    pool = make_remote_pool(task_deadline_s=0.5, fault_plan=plan)
    sched = remote_scheduler(tmp_path, pool, poison_threshold=2)
    agent, _ = start_agent(pool.address, "h1", seats=1)
    try:
        wait_for(lambda: pool.size == 1, what="registration")
        job = sched.submit(tiny_spec())
        events = collect_events(job, timeout=120)
        assert events[-1]["type"] == "done"
        rows = [e for e in events if e["type"] == "row"]
        assert len(rows) == 1 and rows[0]["status"] == "error"
        assert rows[0]["poison"] is True
        assert sched.stats()["faults"]["scenarios_poisoned"] == 1
    finally:
        agent.stop()
        sched.close()


# ---- the real topology: server + subprocess worker hosts --------------------


def _read_addr_file(path, proc, deadline_s=120):
    deadline = time.monotonic() + deadline_s
    while not path.exists() or not path.read_text().strip():
        if proc.poll() is not None:
            pytest.fail(f"process died: {proc.stderr.read().decode()}")
        if time.monotonic() > deadline:
            proc.kill()
            pytest.fail(f"{path} never written")
        time.sleep(0.1)
    return path.read_text().strip()


def spawn_multihost_server(tmp_path, cache, *extra_args):
    port_file = tmp_path / "port"
    worker_port_file = tmp_path / "worker_port"
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.serve", "--port", "0",
         "--port-file", str(port_file), "--cache", str(cache),
         "--chunk-size", "1", "--quiet",
         "--worker-listen", "127.0.0.1:0",
         "--worker-port-file", str(worker_port_file), *extra_args],
        env=env, cwd=os.path.dirname(SRC),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    address = _read_addr_file(port_file, proc)
    pool_address = _read_addr_file(worker_port_file, proc)
    return proc, address, pool_address


def spawn_worker_host(pool_address, name, seats=1):
    env = dict(os.environ, PYTHONPATH=SRC)
    return subprocess.Popen(
        [sys.executable, "-m", "repro.serve", "worker",
         "--connect", pool_address, "--seats", str(seats),
         "--name", name, "--quiet"],
        env=env, cwd=os.path.dirname(SRC),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)


@pytest.mark.slow
def test_multihost_server_two_hosts_sigkill_one(tmp_path):
    """The full topology: a server with --worker-listen, two subprocess
    worker hosts, a client campaign.  One host is SIGKILLed mid-campaign;
    re-dispatch converges and the rows are byte-identical to the CLI
    path."""
    from repro.serve import ServeClient

    cache = tmp_path / "cache"
    spec = tiny_spec(accels=("accugraph", "foregraph", "hitgraph",
                             "thundergp"), drams=("default", "hbm"))
    proc, address, pool_address = spawn_multihost_server(
        tmp_path, cache, "--worker-deadline", "60")
    w1 = spawn_worker_host(pool_address, "w1", seats=1)
    w2 = spawn_worker_host(pool_address, "w2", seats=1)
    try:
        client = ServeClient(address)
        client.wait_ready(deadline_s=60)
        wait_for(lambda: client.stats()["workers"].get("size", 0) == 2,
                 timeout=60, what="both hosts registered")

        result = {}

        def run():
            result["res"] = client.run(spec)

        t = threading.Thread(target=run)
        t.start()

        # SIGKILL w1 the moment it holds a chunk (its pid is in /stats)
        def w1_busy():
            hosts = client.stats()["workers"].get("hosts", {})
            return hosts.get("w1", {}).get("busy", 0) >= 1

        wait_for(w1_busy, timeout=120, what="w1 holding a chunk")
        os.kill(w1.pid, signal.SIGKILL)

        t.join(timeout=600)
        assert not t.is_alive(), "campaign never finished"
        res = result["res"]
        assert res.outcome == "done"
        statuses = res.statuses
        assert len(statuses) == 8 and set(statuses) <= {"ok", "cached"}
        clean = result_rows(run_sweep(spec, cache_dir=None, mode="scenario"))
        assert res.rows == clean
        stats = client.stats()
        assert stats["faults"]["workers_lost"] >= 1
        client.shutdown()
        assert proc.wait(timeout=60) == 0
        assert w2.wait(timeout=60) == 0  # clean shutdown handshake
    finally:
        for p in (w1, w2, proc):
            if p.poll() is None:
                p.kill()
