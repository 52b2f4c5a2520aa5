"""Runs one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python3 bench/run.py --workload <cell> --rehearse ...

The cell's configuration, traffic and metrics are found by name from
``BENCHMARK.json`` (:mod:`bench.spec`).  This process is the client and
never opens a JAX backend: it starts the sweep server
(:mod:`bench.server`) with its one worker seat on the chip, submits jobs
through ``repro.serve.client.ServeClient`` and times every row as it
arrives.

- Set-up: server and seat spawn, device open, the client's build of the
  graphs (for the search keys and the reference), the warm-up jobs (timed
  analytically) and the compile of every shape of the timing scan.
- Window: a closed loop of the traffic's clients.  No job is submitted
  after ``--seconds``; the window closes when the last row in flight
  arrives.  With ``--trace 1`` the seat's profiler records the window.
- Afterwards the server drains and stops, and a sample of the window's
  rows is recomputed by the plain reference (:mod:`bench.check`).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, last, the
numbers compared with their limits; the same numbers end standard error.
A seat that is not on the TPU (outside ``--rehearse``), or fewer chips
than the cell asks for, ends the run with exit code 1 and no result.

``--rehearse`` runs the cell's small graphs with the seat on the CPU and
prints no device metric.  ``--control`` (the program's analytic host
timing in place of the exact scan) and ``--fault alter`` (row hits
changed where the seat produces them) must come out not correct.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import importlib
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
# JAX's persistent compile cache for the seat: a fixed path in the checkout
COMPILE_CACHE = os.path.join(ROOT, ".bench", "jax_cache")


class Refused(Exception):
    """The run cannot be measured here (no chip, too few chips)."""


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Server:
    """``python -m bench.server`` in its own process group, driven over its
    standard input and output."""

    def __init__(self, run_dir: str, platform: str, fault: str | None,
                 traced: bool):
        os.makedirs(COMPILE_CACHE, exist_ok=True)
        env = dict(os.environ, JAX_PLATFORMS=platform,
                   JAX_COMPILATION_CACHE_DIR=COMPILE_CACHE,
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
                   # no eviction: the cache holds this checkout's programs
                   # only, a bounded set (one per shape bucket)
                   JAX_COMPILATION_CACHE_MAX_SIZE="-1",
                   PYTHONPATH=os.pathsep.join([SRC, ROOT]))
        if traced:
            # the device trace keeps each program's executions and drops
            # its per-operation events (every step of the timing scan's
            # loop: millions a second, gigabytes a window)
            env["LIBTPU_INIT_ARGS"] = (env.get("LIBTPU_INIT_ARGS", "")
                                       + " --xla_enable_hlo_trace=false")
        cmd = [sys.executable, "-m", "bench.server",
               "--cache", os.path.join(run_dir, "result-cache")]
        if fault:
            cmd += ["--fault", fault]
        self.log = open(os.path.join(run_dir, "server.log"), "w")
        self.proc = subprocess.Popen(
            cmd, env=env, cwd=ROOT, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=self.log, text=True,
            start_new_session=True)
        self.address = self._read()["address"]
        self.lock = threading.Lock()

    def _read(self) -> dict:
        for line in self.proc.stdout:
            if line.startswith("BENCH "):
                return json.loads(line[len("BENCH "):])
        raise RuntimeError(f"the bench server exited "
                           f"(rc={self.proc.wait()}); see server.log")

    def command(self, **cmd):
        with self.lock:
            self.proc.stdin.write(json.dumps(cmd) + "\n")
            self.proc.stdin.flush()
            out = self._read()
        if not out["ok"]:
            raise RuntimeError(out["error"])
        return out["result"]

    def seat(self, fn: str, *args):
        return self.command(cmd="seat", fn=fn, args=list(args))

    def stop(self) -> None:
        """Drain; kill the process group (seat included) if that fails."""
        try:
            if self.proc.poll() is None:
                self.command(cmd="stop")
                self.proc.wait(timeout=120)
        except Exception as e:
            say(f"server stop: {e}")
        finally:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
            self.log.close()


def check_seat(seat: dict, platform: str, chips: int) -> None:
    """Refuse a seat on another platform than asked, or with fewer chips
    than the cell needs: its numbers would not be the chip's."""
    if seat["platform"] != platform:
        raise Refused(f"the seat is on {seat['platform']}, not {platform}")
    if seat["count"] < chips:
        raise Refused(f"{seat['count']} chips; the cell asks for {chips}")


def wait_seat(client, deadline_s: float) -> dict:
    deadline = time.time() + deadline_s
    while True:
        workers = client.stats()["workers"]
        if workers.get("seats"):
            return workers["seats"][0]
        if workers.get("retired", 0) >= workers.get("size", 1):
            raise Refused("the worker seat could not open its device "
                          "(see server.log)")
        if time.time() > deadline:
            raise Refused("the worker seat never came up")
        time.sleep(0.2)


def run_job(client, specs, rows: list, lock) -> int:
    """Submit one job's specs, in order, and collect every row as it
    arrives; returns the number of rows asked for.  Each spec is queued
    (its stream's header has arrived) before the next is submitted, so a
    job's scenarios reach the scheduler in the same order every run."""
    from bench.check import Scenario
    from bench.window import Row

    asked = 0

    def collect(stream, scen):
        for ev in stream:
            if ev["type"] != "row":
                continue
            t = time.time()
            s = scen[ev["index"]]
            with lock:
                rows.append(Row(t, ev["status"], ev["row"],
                                Scenario(s.graph.name, s.graph.root,
                                         s.accelerator, s.dram.name,
                                         s.dram.page_policy,
                                         s.dram.pseudo_channels)))

    threads = []
    for spec in specs:
        scen, _ = spec.expand()
        asked += len(scen)
        stream = client.submit(spec)
        next(stream)  # the job header: queued
        threads.append(threading.Thread(target=collect, args=(stream, scen)))
        threads[-1].start()
    for th in threads:
        th.join()
    return asked


def closed_loop(client, jobs, phase: str, clients: int, *,
                n_jobs: int | None = None, until: float | None = None):
    """``clients`` closed-loop clients; each submits its next job when its
    last one has streamed every row.  Stops after ``n_jobs`` jobs, or
    submits nothing after host time ``until``."""
    rows: list = []
    lock = threading.Lock()
    state = dict(next=0, asked=0)
    errors: list = []

    def loop():
        try:
            while True:
                with lock:
                    k = state["next"]
                    if (n_jobs is not None and k >= n_jobs) or \
                            (until is not None and time.time() >= until):
                        return
                    state["next"] = k + 1
                    specs = jobs.specs(k, phase)
                asked = run_job(client, specs, rows, lock)
                with lock:
                    state["asked"] += asked
        except Exception as e:  # surfaces in the caller
            errors.append(e)

    threads = [threading.Thread(target=loop) for _ in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise errors[0]
    return rows, state["asked"], state["next"]


@dataclasses.dataclass
class Observation:
    """What the per-layer readers read."""

    rows: list
    t0: float  # window open (host time)
    t1: float  # window close
    stats0: dict  # /stats as the window opened
    stats1: dict  # and as it closed
    spans: list  # the seat's run_chunk spans (host time)
    trace: dict | None  # the seat's device-trace reduction, --trace 1 only


def load_config(cell, rehearse: bool) -> dict:
    config = copy.deepcopy(cell.config)
    if rehearse:
        small = config.get("rehearse", {})
        config["graphs"].update(small.get("graphs", {}))
        if "warm_scan" in small:
            config["warm_scan"] = small["warm_scan"]
    return config


def reference(config: dict):
    """The configuration's plain reference, ``bench/reference/<name>.py``."""
    return importlib.import_module(f"bench.reference.{config['reference']}")


def measure(args, cell) -> dict:
    from bench import check, traffic, window
    from repro.serve.client import ServeClient

    t_start = time.time()
    run_dir = args.out or os.path.join(ROOT, ".bench", "runs", cell.name)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    config = load_config(cell, args.rehearse)
    ref = reference(config)
    plan = cell.traffic
    platform = "cpu" if args.rehearse else "tpu"
    traced = bool(args.trace) and not args.rehearse
    server = Server(run_dir, platform, args.fault, traced)
    try:
        # the client's own build of the graphs, while the seat starts
        graphs, pools = {}, {}
        for i, g in enumerate(plan["graphs"]):
            graphs[g] = ref.build_graph(config["graphs"][g])
            pools[g] = traffic.RootPool(graphs[g].degrees_out, plan["roots"],
                                        args.seed, i)
        client = ServeClient(server.address, timeout=1200.0)
        client.wait_ready(deadline_s=120)
        seat = wait_seat(client, deadline_s=900)
        say(f"seat: {json.dumps(seat, sort_keys=True)}")
        check_seat(seat, platform, 1 if args.rehearse else cell.chips)
        jobs = traffic.Jobs(config, plan, pools, control=args.control)
        closed_loop(client, jobs, "warmup", plan["clients"],
                    n_jobs=plan["warmup_jobs"])
        if "warm_scan" in config:
            mems = [[m["dram"], m["page_policy"], m["pseudo_channels"]]
                    for m in (config["memories"][k] for k in plan["memories"])]
            warm = server.seat("warm_scan", mems,
                               config["warm_scan"]["max_len"],
                               config["warm_scan"]["max_batch"])
            say(f"warm_scan: {json.dumps(warm)}")

        trace = None
        if traced:
            t_trace0 = server.seat("trace_start",
                                   os.path.join(run_dir, "profile"))
        stats0 = client.stats()
        t0 = time.time()
        setup_s = t0 - t_start
        rows, attempted, n_jobs = closed_loop(
            client, jobs, "window", plan["clients"], until=t0 + args.seconds)
        t0, t1 = window.window_bounds(rows, t0)
        if traced:
            trace = server.seat("trace_stop", os.path.join(run_dir, "profile"))
            trace["t0"] = t_trace0
            say(f"trace: {trace['trace_bytes']} bytes written")
        stats1 = client.stats()
        spans = server.command(cmd="spans")
        if trace is not None:
            with open(os.path.join(run_dir, "trace.json"), "w") as f:
                json.dump(dict(trace, spans=spans), f)
        device = server.seat("device_report")
    finally:
        server.stop()

    say(f"setup {setup_s:.3f}s; window {t1 - t0:.3f}s: {n_jobs} jobs, "
        f"{len(rows)} of {attempted} rows")
    t_check = time.time()
    numbers = check.compare(rows, attempted,
                            check.sample(rows, plan["check_rows"], args.seed),
                            config, graphs, ref)
    say(f"reference check of {min(len(rows), plan['check_rows'])} rows: "
        f"{time.time() - t_check:.3f}s")
    obs = Observation(rows, t0, t1, stats0, stats1, spans, trace)
    return dict(setup_s=setup_s, obs=obs, numbers=numbers,
                attempted=attempted, device=device)


def end_to_end(cell, m: dict) -> dict:
    from bench import window

    obs = m["obs"]
    values = dict(
        sim_mreq_per_s=lambda: window.rate(obs.rows, obs.t0, obs.t1),
        setup_s=lambda: m["setup_s"],
    )
    return {e["name"]: dict(value=values[e["name"]](), unit=e["unit"])
            for e in cell.end_to_end}


def per_layer(cell, m: dict, rehearse: bool) -> dict:
    out = {}
    for entry, reader in cell.per_layer:
        if rehearse and entry["source"] == "device_trace":
            continue
        value = reader.read(m["obs"])
        if value is not None:
            out[entry["name"]] = dict(value=value, unit=entry["unit"])
    return out


def device_block(m: dict, traced: bool) -> tuple[dict, dict | None]:
    from bench import devtrace

    d = dict(m["device"])
    trace = m["obs"].trace
    if not traced or trace is None:
        return d, None
    t0_ns, t1_ns = trace["t0"] * 1e9, trace["t_stop"] * 1e9
    planes = trace["devices"]
    d["busy_s"] = devtrace.busy_ns(planes, t0_ns, t1_ns) * 1e-9
    d["window_s"] = (t1_ns - t0_ns) * 1e-9
    spans_ns = [(a * 1e9, b * 1e9) for a, b in m["obs"].spans]
    breakdown = dict(
        device_ops=devtrace.top_programs(planes),
        idle_gaps=devtrace.labelled_gaps(planes, spans_ns, t0_ns, t1_ns))
    return d, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's small graphs, seat on the CPU")
    ap.add_argument("--control", action="store_true",
                    help="analytic host timing in place of the exact scan")
    ap.add_argument("--fault", choices=("alter",), default=None,
                    help="change row hits where the seat produces them")
    ap.add_argument("--out", default="",
                    help="run directory (result cache, server log)")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        say(f"error: no repro package under {SRC}; run from a checkout")
        return 2
    # this process is a client: it must never hold the chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [SRC, ROOT]
    from bench import check
    from bench.spec import load_cell

    cell = load_cell(args.workload)
    try:
        m = measure(args, cell)
    except Refused as e:
        say(f"refused: {e}")
        return 1
    metrics = per_layer(cell, m, args.rehearse) if args.trace \
        else end_to_end(cell, m)
    device, breakdown = device_block(m, bool(args.trace))
    numbers = m["numbers"]
    correct = check.verdict(numbers)
    checks = {k: dict(value=v, limit=check.LIMITS[k])
              for k, v in numbers.items()}
    result = dict(correct=correct, attempted=m["attempted"],
                  failed=numbers["rows_failed"], metrics=metrics,
                  device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
