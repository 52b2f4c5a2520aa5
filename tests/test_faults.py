"""Fault tolerance: supervised worker pool (crash/hang/stall detection,
respawn, retirement), scheduler re-dispatch + poison circuit breaker +
corrupt-record validation + cancel-during-dispatch, crash-safe job
journal + recovery, execution-policy backoff/jitter/audit, cache
checksum quarantine, and the deterministic fault-injection harness that
drives it all."""
import json
import os
import signal
import subprocess
import sys
import threading
import time
from concurrent.futures import Future

import pytest

from repro.distributed.faults import (
    FaultAction,
    FaultPlan,
    FaultRule,
    plan_from_json,
    plan_to_json,
    probe,
)
from repro.distributed.workpool import WorkerLost, WorkerPool
from repro.graph.generators import GraphSpec
from repro.serve.journal import JobJournal
from repro.serve.scheduler import SweepScheduler
from repro.sweep import ExecutionPolicy, SweepSpec
from repro.sweep.cache import ResultCache, scenario_hash
from repro.sweep.results import scenario_row
from repro.sweep.runner import execute_scenario_policied

TINY = GraphSpec("tiny", "uniform", 256, 1024, True, 1, 0)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def tiny_spec(accels=("accugraph",), problems=("bfs",), graphs=(TINY,),
              drams=("default",), **kw):
    return SweepSpec(name="t", accelerators=tuple(accels),
                     graphs=tuple(graphs), problems=tuple(problems),
                     drams=tuple(drams), **kw)


def collect_events(job, timeout=120.0):
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


def wait_for(cond, timeout=30.0, what="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.02)
    pytest.fail(f"timed out waiting for {what}")


# ---- fault plans: determinism, serialization --------------------------------


def test_plan_json_roundtrip():
    plan = FaultPlan(seed=7, rules=(
        FaultRule("worker.chunk", "crash", at=(1, 3)),
        FaultRule("worker.chunk", "hang", match="poison"),
        FaultRule("scenario", "error", times=2, prob=0.5),
        FaultRule("worker.chunk", "delay", delay_s=0.2, exitcode=7),
    ))
    assert plan_from_json(plan_to_json(plan)) == plan
    # plans also ride inside pickled policies; firing counters reset
    import pickle
    clone = pickle.loads(pickle.dumps(plan))
    assert clone == plan and clone._fired == {}


def test_plan_rejects_garbage():
    with pytest.raises(ValueError):
        FaultRule("worker.chunk", "explode")
    with pytest.raises(ValueError):
        FaultRule("worker.chunk", "crash", prob=1.5)
    with pytest.raises(ValueError):
        plan_from_json('{"rules": [{"site": "x", "kind": "nope"}]}')
    with pytest.raises(ValueError):
        plan_from_json("[1, 2]")


def test_plan_occurrence_and_match_selection():
    plan = FaultPlan(seed=0, rules=(
        FaultRule("worker.chunk", "crash", at=(2,)),
        FaultRule("scenario", "error", match="hitgraph", times=1),
    ))
    assert plan.action("worker.chunk", index=0) is None
    assert plan.action("worker.chunk", index=2).kind == "crash"
    assert plan.action("nowhere", index=2) is None
    assert plan.action("scenario", index=0, keys=("tiny/accugraph/bfs",)) is None
    a = plan.action("scenario", index=0, keys=("tiny/hitgraph/bfs",))
    assert a is not None and a.kind == "error"
    # times=1: the rule is spent
    assert plan.action("scenario", index=1, keys=("tiny/hitgraph/bfs",)) is None


def test_plan_prob_is_seeded_and_deterministic():
    rules = (FaultRule("worker.chunk", "crash", prob=0.5),)
    fired_a = [FaultPlan(seed=3, rules=rules).action("worker.chunk", index=i)
               is not None for i in range(64)]
    fired_b = [FaultPlan(seed=3, rules=rules).action("worker.chunk", index=i)
               is not None for i in range(64)]
    assert fired_a == fired_b
    assert 0 < sum(fired_a) < 64  # actually probabilistic, not all-or-nothing
    fired_c = [FaultPlan(seed=4, rules=rules).action("worker.chunk", index=i)
               is not None for i in range(64)]
    assert fired_a != fired_c  # seed moves the schedule


# ---- supervised worker pool -------------------------------------------------


def make_pool(**kw):
    kw.setdefault("heartbeat_s", 0.1)
    kw.setdefault("task_deadline_s", 2.0)
    kw.setdefault("stall_deadline_s", 1.0)
    kw.setdefault("max_respawns", 3)
    kw.setdefault("respawn_backoff_s", 0.05)
    return WorkerPool(kw.pop("workers", 1), **kw)


def test_pool_crash_is_workerlost_and_respawns():
    pool = make_pool()
    try:
        assert pool.submit(probe, None, 1).result(timeout=60)["value"] == 1
        fut = pool.submit(probe, FaultAction("worker.chunk", "crash"), 2)
        with pytest.raises(WorkerLost) as ei:
            fut.result(timeout=60)
        assert ei.value.reason == "crash"
        assert "13" in ei.value.detail  # the injected exit code
        # the slot respawned: the pool keeps serving
        r = pool.submit(probe, None, 3).result(timeout=60)
        assert r["value"] == 3
        s = pool.stats()
        assert s["workers_lost"] == 1 and s["respawns"] == 1
    finally:
        pool.shutdown(wait=False, cancel_pending=True)


def test_pool_hang_hits_liveness_deadline():
    pool = make_pool(task_deadline_s=1.0)
    try:
        t0 = time.time()
        fut = pool.submit(probe, FaultAction("worker.chunk", "hang"), 0)
        with pytest.raises(WorkerLost) as ei:
            fut.result(timeout=60)
        assert ei.value.reason == "hang"
        assert time.time() - t0 < 30  # killed at the deadline, not at HANG_S
    finally:
        pool.shutdown(wait=False, cancel_pending=True)


def test_pool_stall_detected_by_heartbeat():
    # SIGSTOP freezes the whole process including its heartbeat thread —
    # no task deadline is set, so only heartbeat staleness can catch it
    pool = make_pool(task_deadline_s=None, stall_deadline_s=1.0)
    try:
        fut = pool.submit(probe, FaultAction("worker.chunk", "stall"), 0)
        with pytest.raises(WorkerLost) as ei:
            fut.result(timeout=60)
        assert ei.value.reason == "stall"
    finally:
        pool.shutdown(wait=False, cancel_pending=True)


def test_pool_retires_slot_and_breaks_after_respawn_budget():
    pool = make_pool(max_respawns=1)
    try:
        for i in range(2):  # initial worker + its one respawn
            with pytest.raises(WorkerLost):
                pool.submit(probe, FaultAction("worker.chunk", "crash"),
                            i).result(timeout=60)
        wait_for(lambda: pool.stats()["retired"] == 1, what="slot retirement")
        with pytest.raises(WorkerLost) as ei:
            pool.submit(probe, None, 9)
        assert ei.value.reason == "broken"
    finally:
        pool.shutdown(wait=False, cancel_pending=True)


def test_pool_reports_seat_device_from_initializer():
    """A serve seat opens its device at start-up and the pool reports it:
    a seat that came up on the wrong platform is visible in /stats."""
    from repro.serve.worker import init_worker

    pool = make_pool(initializer=init_worker)
    try:
        assert pool.submit(probe, None, 1).result(timeout=120)["value"] == 1
        assert pool.stats()["seats"] == [
            dict(platform="cpu", kind="cpu", count=1)]
    finally:
        pool.shutdown(wait=False, cancel_pending=True)


def test_pool_shutdown_bounded_with_hung_worker():
    pool = make_pool(task_deadline_s=1.0)
    pool.submit(probe, None, 0).result(timeout=60)  # worker is ready
    fut = pool.submit(probe, FaultAction("worker.chunk", "hang"), 0)
    time.sleep(0.5)  # monitor assigns the hang to the worker
    t0 = time.time()
    pool.shutdown(wait=True, cancel_pending=True)
    assert time.time() - t0 < 30  # a wedged worker cannot wedge the drain
    with pytest.raises(WorkerLost):
        fut.result(timeout=1)


# ---- scheduler: re-dispatch, poison breaker, corrupt records, cancel --------


class ManualPool:
    """Fully test-controlled pool stand-in: every submitted chunk parks as
    a (fn, args, future) triple; the test completes it (``run``), fails it
    with a WorkerLost (``lose``) or corrupts its records (``run_corrupt``)
    at a deterministic point."""

    def __init__(self, size=1):
        self.size = size
        self.calls = []

    def submit(self, fn, *args):
        fut = Future()
        self.calls.append((fn, args, fut))
        return fut

    def run(self, i):
        fn, args, fut = self.calls[i]
        fut.set_result(fn(*args))

    def run_corrupt(self, i):
        from repro.distributed.faults import corrupt_records
        fn, args, fut = self.calls[i]
        out = fn(*args)
        out["records"] = corrupt_records(out["records"])
        fut.set_result(out)

    def lose(self, i, reason="crash"):
        _, _, fut = self.calls[i]
        fut.set_exception(WorkerLost(reason, 0, "injected by test"))

    def chunk_sizes(self):
        return [len(args[0]) for _, args, _ in self.calls]

    def shutdown(self, wait=True, cancel_pending=False):
        for _, _, fut in self.calls:
            if not fut.done():
                fut.cancel()

    def stats(self):
        return dict(size=self.size, busy=0, chunks_submitted=len(self.calls),
                    utilization=0.0)


def scheduler(tmp_path, pool, **kw):
    kw.setdefault("chunk_size", 4)
    kw.setdefault("mode", "scenario")
    return SweepScheduler(cache_dir=str(tmp_path / "cache"),
                          pool_factory=lambda: pool, **kw)


def test_lost_chunk_redispatches_scenarios_as_singletons(tmp_path):
    pool = ManualPool()
    sched = scheduler(tmp_path, pool)
    try:
        job = sched.submit(tiny_spec(accels=("accugraph", "hitgraph")))
        wait_for(lambda: len(pool.calls) == 1, what="first dispatch")
        assert pool.chunk_sizes() == [2]
        pool.lose(0, "crash")
        # both scenarios are suspects now: they re-dispatch one per chunk
        wait_for(lambda: len(pool.calls) == 3, what="singleton re-dispatches")
        assert pool.chunk_sizes() == [2, 1, 1]
        pool.run(1)
        pool.run(2)
        events = collect_events(job)
        assert events[-1]["type"] == "done"
        statuses = [e["status"] for e in events if e["type"] == "row"]
        assert statuses == ["ok", "ok"]
        s = sched.stats()
        assert s["faults"]["chunks_lost"] == 1
        assert s["faults"]["scenarios_redispatched"] == 2
        assert s["faults"]["scenarios_poisoned"] == 0
    finally:
        sched.close()


def test_poison_scenario_trips_circuit_breaker(tmp_path):
    pool = ManualPool()
    sched = scheduler(tmp_path, pool, poison_threshold=2)
    try:
        job = sched.submit(tiny_spec())
        wait_for(lambda: len(pool.calls) == 1, what="dispatch 1")
        pool.lose(0, "crash")
        wait_for(lambda: len(pool.calls) == 2, what="re-dispatch")
        pool.lose(1, "hang")
        events = collect_events(job)
        assert events[-1]["type"] == "done"
        rows = [e for e in events if e["type"] == "row"]
        assert len(rows) == 1 and rows[0]["status"] == "error"
        assert rows[0]["poison"] is True
        row = rows[0]["row"]
        assert row["poison"] is True and row["attempts"] == 2
        assert "quarantined" in row["error"]
        assert sched.stats()["faults"]["scenarios_poisoned"] == 1
        # poison is an error record: never cached — a resubmission retries
        (scn,), _ = tiny_spec().expand()
        assert ResultCache(str(tmp_path / "cache")).get(
            scenario_hash(scn)) is None
        job2 = sched.submit(tiny_spec())
        wait_for(lambda: len(pool.calls) == 3, what="post-poison retry")
        pool.run(2)
        events2 = collect_events(job2)
        assert [e["status"] for e in events2 if e["type"] == "row"] == ["ok"]
    finally:
        sched.close()


def test_corrupt_worker_records_requeue_then_recover(tmp_path):
    pool = ManualPool()
    sched = scheduler(tmp_path, pool)
    try:
        job = sched.submit(tiny_spec())
        wait_for(lambda: len(pool.calls) == 1, what="dispatch 1")
        pool.run_corrupt(0)  # status ok, garbage report payload
        wait_for(lambda: len(pool.calls) == 2, what="re-dispatch")
        pool.run(1)
        events = collect_events(job)
        statuses = [e["status"] for e in events if e["type"] == "row"]
        assert statuses == ["ok"]
        s = sched.stats()
        assert s["counters"]["corrupt_records"] == 1
        assert s["faults"]["scenarios_redispatched"] == 1
    finally:
        sched.close()


def test_chunk_shape_mismatch_treated_as_lost(tmp_path):
    pool = ManualPool()
    sched = scheduler(tmp_path, pool, poison_threshold=99)
    try:
        job = sched.submit(tiny_spec(accels=("accugraph", "hitgraph")))
        wait_for(lambda: len(pool.calls) == 1, what="dispatch 1")
        _, _, fut = pool.calls[0]
        fut.set_result(dict(records=[dict(status="ok")], hostcache={}))
        wait_for(lambda: len(pool.calls) == 3, what="re-dispatches")
        pool.run(1)
        pool.run(2)
        events = collect_events(job)
        assert [e["status"] for e in events if e["type"] == "row"] == \
            ["ok", "ok"]
    finally:
        sched.close()


def test_cancel_during_dispatch_drops_lost_chunk(tmp_path):
    """Satellite: cancelling a job whose chunk is mid-flight must stop
    delivery immediately, and when that chunk's worker dies the orphaned
    scenarios are dropped — never re-dispatched, never cached."""
    pool = ManualPool()
    sched = scheduler(tmp_path, pool)
    try:
        job = sched.submit(tiny_spec())
        wait_for(lambda: len(pool.calls) == 1, what="dispatch")
        assert sched.cancel(job.id)
        events = collect_events(job, timeout=10)
        assert events[-1]["type"] == "cancelled"
        pool.lose(0, "crash")  # the in-flight chunk dies after the cancel
        # no re-dispatch: nobody subscribes to the scenario any more
        time.sleep(0.3)
        assert len(pool.calls) == 1
        s = sched.stats()
        assert s["faults"]["scenarios_redispatched"] == 0
        assert s["counters"]["scenarios_cancelled"] == 1
        (scn,), _ = tiny_spec().expand()
        assert ResultCache(str(tmp_path / "cache")).get(
            scenario_hash(scn)) is None
        # and the queue table is clean: a resubmission starts fresh
        job2 = sched.submit(tiny_spec())
        wait_for(lambda: len(pool.calls) == 2, what="fresh dispatch")
        pool.run(1)
        assert collect_events(job2)[-1]["type"] == "done"
    finally:
        sched.close()


def test_injected_chunk_faults_are_dispatch_indexed(tmp_path):
    """The scheduler consults the plan at dispatch time: occurrence indices
    refer to its global dispatch counter, so the schedule is deterministic
    and visible in /stats."""
    plan = FaultPlan(seed=1, rules=(
        FaultRule("worker.chunk", "crash", at=(0,)),))
    pool = ManualPool()
    sched = scheduler(tmp_path, pool, fault_plan=plan, poison_threshold=3)
    try:
        job = sched.submit(tiny_spec())
        wait_for(lambda: len(pool.calls) == 1, what="dispatch 0")
        # dispatch 0 carries the injected crash action
        _, args0, _ = pool.calls[0]
        assert args0[4] is not None and args0[4].kind == "crash"
        pool.lose(0, "crash")  # what the real pool would observe
        wait_for(lambda: len(pool.calls) == 2, what="dispatch 1")
        _, args1, _ = pool.calls[1]
        assert args1[4] is None  # at=(0,): the retry dispatch is clean
        pool.run(1)
        events = collect_events(job)
        assert [e["status"] for e in events if e["type"] == "row"] == ["ok"]
        assert sched.stats()["faults"]["faults_injected"] == 1
    finally:
        sched.close()


# ---- job journal ------------------------------------------------------------


def test_journal_roundtrip_and_torn_line(tmp_path):
    j = JobJournal(tmp_path)
    j.record_job("job-1", "a", dict(name="a"))
    j.record_job("job-2", "b", dict(name="b"))
    j.record_end("job-1", "done")
    assert [op["id"] for op in j.load_open()] == ["job-2"]
    # a crash mid-append tears the final line: it must be ignored
    with open(j.path, "a") as f:
        f.write('{"op": "end", "id": "job-2", "outc')
    assert [op["id"] for op in j.load_open()] == ["job-2"]
    assert len(j.load()) == 3
    # compaction keeps only open jobs and drops the torn tail
    assert j.compact() == 2
    ops = j.load()
    assert len(ops) == 1 and ops[0]["id"] == "job-2"


def test_journal_missing_file_is_empty(tmp_path):
    j = JobJournal(tmp_path / "nope")
    assert j.load() == [] and j.load_open() == []
    assert j.compact() == 0


def test_scheduler_recovers_open_jobs_from_journal(tmp_path):
    pool = ManualPool()
    sched = scheduler(tmp_path, pool, chunk_size=1)
    job = sched.submit(tiny_spec(accels=("accugraph", "hitgraph")))
    jid = job.id
    wait_for(lambda: len(pool.calls) >= 1, what="first dispatch")
    pool.run(0)  # one scenario persists to the cache; the other never runs
    wait_for(lambda: job.done >= 1, what="first row")
    sched.close()  # hard stop: no drain, no journal end op

    pool2 = ManualPool()
    sched2 = scheduler(tmp_path, pool2, chunk_size=1)
    try:
        rec = sched2.get_job(jid)
        assert rec is not None and rec.recovered
        # recovery re-executes only the unfinished tail
        wait_for(lambda: len(pool2.calls) == 1, what="recovery dispatch")
        assert pool2.chunk_sizes() == [1]
        pool2.run(0)
        wait_for(lambda: rec.finished, what="recovered job finishing")
        assert rec.counts["cached"] == 1 and rec.counts["ok"] == 1
        assert sched2.stats()["jobs"]["recovered"] == 1
        # fresh submissions never collide with the recovered id space
        fresh = sched2.submit(tiny_spec(accels=("foregraph",)))
        assert fresh.id != jid
    finally:
        sched2.close()

    # the finish was journaled: a third scheduler re-opens only the still
    # unfinished fresh job, never the completed one
    sched3 = scheduler(tmp_path, ManualPool())
    try:
        assert sched3.get_job(jid) is None
        open3 = sched3.get_job(fresh.id)
        assert open3 is not None and open3.recovered
        assert sched3.stats()["jobs"]["recovered"] == 1
    finally:
        sched3.close()


def test_scheduler_resume_false_skips_recovery(tmp_path):
    pool = ManualPool()
    sched = scheduler(tmp_path, pool)
    job = sched.submit(tiny_spec())
    wait_for(lambda: len(pool.calls) == 1, what="dispatch")
    sched.close()
    sched2 = scheduler(tmp_path, ManualPool(), resume=False)
    try:
        assert sched2.get_job(job.id) is None
        assert sched2.stats()["jobs"]["recovered"] == 0
    finally:
        sched2.close()


def test_cancelled_jobs_are_not_recovered(tmp_path):
    pool = ManualPool()
    sched = scheduler(tmp_path, pool)
    job = sched.submit(tiny_spec())
    wait_for(lambda: len(pool.calls) == 1, what="dispatch")
    sched.cancel(job.id)
    sched.close()
    sched2 = scheduler(tmp_path, ManualPool())
    try:
        assert sched2.get_job(job.id) is None
    finally:
        sched2.close()


# ---- execution policy: jittered backoff + audit trail -----------------------


def test_backoff_is_exponential_with_deterministic_jitter():
    p = ExecutionPolicy(retries=3, backoff_s=0.2)
    for attempt in (1, 2, 3):
        base = 0.2 * 2 ** (attempt - 1)
        d = p.backoff_for(attempt, key="tiny/accugraph/bfs")
        assert 0.5 * base <= d < 1.5 * base
        # deterministic: the same scenario sleeps the same schedule
        assert d == p.backoff_for(attempt, key="tiny/accugraph/bfs")
    # different scenarios desynchronise
    assert p.backoff_for(1, key="a") != p.backoff_for(1, key="b")


def test_error_rows_carry_attempts_and_last_error():
    broken = GraphSpec("broken", "no-such-generator", 64, 128, True, 1, 0)
    (scn,), _ = tiny_spec(graphs=(broken,)).expand()
    rec = execute_scenario_policied(
        scn, ExecutionPolicy(retries=2, backoff_s=0.0))
    assert rec["status"] == "error" and rec["attempts"] == 3
    assert "last_error" in rec and "\n" not in rec["last_error"]
    row = scenario_row(scn, rec)
    assert row["attempts"] == 3
    assert row["last_error"] == rec["last_error"]
    assert "poison" not in row


def test_fault_plan_drives_policy_retries():
    # first attempt fails by injection, the retry runs clean
    plan = FaultPlan(seed=0, rules=(
        FaultRule("scenario", "error", at=(0,)),))
    (scn,), _ = tiny_spec().expand()
    rec = execute_scenario_policied(
        scn, ExecutionPolicy(retries=1, backoff_s=0.0, fault_plan=plan))
    assert rec["status"] == "ok" and rec["attempts"] == 2


def test_fault_plan_exhausts_retries_with_audit():
    plan = FaultPlan(seed=0, rules=(FaultRule("scenario", "error"),))
    (scn,), _ = tiny_spec().expand()
    rec = execute_scenario_policied(
        scn, ExecutionPolicy(retries=1, backoff_s=0.0, fault_plan=plan))
    assert rec["status"] == "error" and rec["attempts"] == 2
    assert rec["last_error"].startswith("injected fault")


# ---- supervision clock + timeout itimer + journal durability regressions ----


def test_supervision_survives_wall_clock_step(monkeypatch):
    """Satellite regression: every supervision deadline is measured on
    ``time.monotonic()`` — an NTP/DST step of the wall clock must not make
    healthy workers look stale or hung."""
    import inspect
    from repro.distributed import workpool as wp_mod
    assert "time.time(" not in inspect.getsource(wp_mod)
    pool = make_pool(stall_deadline_s=0.5)
    try:
        assert pool.submit(probe, None, 1).result(timeout=60)["value"] == 1
        real = time.time
        monkeypatch.setattr(time, "time", lambda: real() + 3600.0)
        time.sleep(1.0)  # several stall deadlines under the stepped clock
        assert pool.submit(probe, None, 2).result(timeout=60)["value"] == 2
        s = pool.stats()
        assert s["workers_lost"] == 0 and s["respawns"] == 0
    finally:
        pool.shutdown(wait=False, cancel_pending=True)


def test_timeout_restores_outer_itimer_and_handler():
    """Satellite regression: ``_execute_with_timeout`` must hand back the
    SIGALRM timer it displaced (minus elapsed time) and the outer handler —
    a caller with its own alarm keeps it."""
    from repro.sweep.runner import _execute_with_timeout

    (scn,), _ = tiny_spec().expand()

    def outer_handler(signum, frame):  # pragma: no cover - must not fire
        pytest.fail("outer alarm fired during the bounded scenario")

    prev = signal.signal(signal.SIGALRM, outer_handler)
    try:
        signal.setitimer(signal.ITIMER_REAL, 120.0)
        rec = _execute_with_timeout(scn, 60.0, False)
        assert rec["status"] == "ok"
        assert "timeout_enforced" not in rec  # main thread: bound applied
        remaining, _ = signal.getitimer(signal.ITIMER_REAL)
        assert 0 < remaining < 120.0  # rearmed, elapsed time deducted
        assert signal.getsignal(signal.SIGALRM) is outer_handler
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, prev)


def test_timeout_off_main_thread_is_flagged_not_faked():
    """Satellite regression: off the main thread SIGALRM cannot fire, so
    the scenario runs unbounded and the record (and exported row) says
    ``timeout_enforced: false`` instead of claiming the bound held."""
    from repro.sweep.runner import _execute_with_timeout

    (scn,), _ = tiny_spec().expand()
    out = {}
    t = threading.Thread(
        target=lambda: out.update(rec=_execute_with_timeout(scn, 60.0,
                                                            False)))
    t.start()
    t.join(timeout=120)
    assert not t.is_alive()
    rec = out["rec"]
    assert rec["status"] == "ok" and rec["timeout_enforced"] is False
    assert scenario_row(scn, rec)["timeout_enforced"] is False


def test_journal_fsyncs_directory_entry(tmp_path, monkeypatch):
    """Satellite regression: the first append fsyncs the journal's
    *directory* (the file's existence must survive a crash, not just its
    bytes), later appends don't pay it again, and compaction re-syncs
    after its rename."""
    import stat

    synced_dirs = []
    real_fsync = os.fsync

    def spy(fd):
        if stat.S_ISDIR(os.fstat(fd).st_mode):
            synced_dirs.append(fd)
        return real_fsync(fd)

    monkeypatch.setattr(os, "fsync", spy)
    j = JobJournal(tmp_path)
    j.record_job("job-1", "a", dict(name="a"))
    assert len(synced_dirs) == 1  # creation made durable
    j.record_end("job-1", "done")
    j.record_job("job-2", "b", dict(name="b"))
    assert len(synced_dirs) == 1  # steady-state appends skip the dirfd
    assert j.compact() == 2
    assert len(synced_dirs) == 2  # the compaction rename made durable


# ---- SIGTERM drain under load with a hung, fault-injected worker ------------


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
def test_sigterm_drain_with_hung_worker_then_journal_resume(tmp_path):
    """Satellite: SIGTERM while a fault-injected worker is hung — the
    stream must end ``interrupted`` (drain bounded by the liveness
    deadline, not the hang), the journal must survive, and a restarted
    server must resume the job to the same rows a fault-free run makes."""
    from repro.serve import ServeClient, ServeError
    from repro.sweep.results import result_rows
    from repro.sweep.runner import run_sweep

    cache = tmp_path / "cache"
    spec = tiny_spec(accels=("accugraph", "foregraph"), drams=("default",
                                                               "hbm"))
    plan = json.dumps(dict(seed=0, rules=[
        dict(site="worker.chunk", kind="hang", at=[0])]))
    proc, address = spawn_server(tmp_path, cache, "--worker-deadline", "3",
                                 "--faults", plan)
    client = ServeClient(address)
    client.wait_ready(deadline_s=60)

    events = []
    job_seen = threading.Event()

    def stream():
        for ev in client.submit(spec):
            events.append(ev)
            if ev["type"] == "job":
                job_seen.set()

    t = threading.Thread(target=stream)
    t.start()
    assert job_seen.wait(timeout=60), "no job header"
    # the very first dispatch hangs; SIGTERM lands while it is wedged
    wait_for(lambda: client.stats()["counters"].get("faults_injected", 0) >= 1,
             timeout=60, what="injected hang")
    os.kill(proc.pid, signal.SIGTERM)
    t.join(timeout=120)
    assert not t.is_alive(), "stream never terminated"
    assert proc.wait(timeout=60) == 0, "drain must exit cleanly"
    assert events[-1]["type"] == "interrupted"
    jid = events[0]["job_id"]

    # crash-safe journal: the interrupted job is still open on disk
    journal = JobJournal(cache)
    assert [op["id"] for op in journal.load_open()] == [jid]

    # restart (no fault plan): the server recovers the job from the journal
    # and finishes it without the client resubmitting anything
    proc2, address2 = spawn_server(tmp_path, cache)
    try:
        client2 = ServeClient(address2)
        client2.wait_ready(deadline_s=60)

        def recovered_finished():
            try:
                return client2.job_status(jid).get("finished")
            except ServeError:
                return False

        wait_for(recovered_finished, timeout=180,
                 what="journal-recovered job finishing")
        status = client2.job_status(jid)
        assert status["recovered"] and status["done"] == status["total"] == 4
        # resubmission is pure cache hits, byte-identical to a fault-free run
        res = client2.run(spec)
        assert res.outcome == "done"
        assert res.statuses == ["cached"] * 4
        clean = result_rows(run_sweep(spec, cache_dir=None, mode="scenario"))
        assert res.rows == clean
        client2.shutdown()
        assert proc2.wait(timeout=60) == 0
    finally:
        if proc2.poll() is None:
            proc2.kill()
