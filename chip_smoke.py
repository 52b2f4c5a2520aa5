"""Smoke run of the served simulation campaign on one TPU chip.

    python3 chip_smoke.py                # on a machine with one TPU chip
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse   # tiny, on the CPU

Starts ``python -m repro.serve`` as a subprocess with one worker seat
(``--workers 1``: a chip belongs to one process) under
``JAX_PLATFORMS=tpu``, then acts as its client through the normal
submit path:

(a) the 8 golden scenarios; their trace hashes must equal
    ``benchmarks/golden_hashes_tiny.json``;
(b) a real-size grid: the ``lj`` and ``r24`` graphs (the suite's largest
    at about a million edges each) x all four accelerators x {bfs, pr} x
    {numpy, device} semantic engines on DDR4, plus ``lj`` x {hitgraph,
    thundergp} x bfs on HBM with closed pages and pseudo-channels;
(c) the same grid again, which must execute nothing (all cached).

It fails (non-zero exit, no result line) on an error row, a row that
fell back to per-scenario timing, a device-engine row that did not run on
the device engine or whose simulated statistics differ from its numpy
twin, a golden-hash mismatch, or a seat whose platform is not the TPU.
Earlier lines report the seat, the wall time of each phase with the XLA
programs it compiled or loaded from the persistent compile cache and the
seconds that took, the device-dispatch counters and the traces the host
fast engine timed.  The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

This process never initialises a JAX backend.  The result cache, the
server's log and a copy of the rows go under ``--out``; JAX's compile
cache goes where ``repro.runtime.enable_compile_cache`` puts it.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")
GOLDEN = os.path.join(HERE, "benchmarks", "golden_hashes_tiny.json")

ACCELS = ("accugraph", "foregraph", "hitgraph", "thundergp")
# scenarios per seat dispatch: the seat batches the DRAM timing of a whole
# chunk, so larger chunks mean fewer, fuller device dispatches
CHUNK_SIZE = 8


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def grid_specs(graphs: tuple[str, ...], hbm_graph: str):
    from repro.sweep.spec import SweepSpec

    both = ("numpy", "device")
    return [
        SweepSpec(name="chip-grid", accelerators=ACCELS, graphs=graphs,
                  problems=("bfs", "pr"), drams=("default",), engines=both),
        SweepSpec(name="chip-hbm", accelerators=("hitgraph", "thundergp"),
                  graphs=(hbm_graph,), problems=("bfs",), drams=("hbm",),
                  page_policies=("closed",), pseudo_channels=(True,),
                  engines=both),
    ]


def golden_spec():
    from repro.graph.generators import GraphSpec
    from repro.sweep.spec import SweepSpec

    return SweepSpec(name="golden", accelerators=ACCELS,
                     graphs=(GraphSpec("tiny", "uniform", 256, 1024, True,
                                       1, 0),),
                     problems=("bfs",), drams=("default", "hbm"))


class Server:
    """``python -m repro.serve`` in its own process group."""

    def __init__(self, out: str, platform: str):
        self.port_file = os.path.join(out, "port")
        self.log_path = os.path.join(out, "server.log")
        cmd = [sys.executable, "-m", "repro.serve", "--port", "0",
               "--port-file", self.port_file,
               "--cache", os.path.join(out, "result-cache"),
               "--workers", "1", "--trace-hashes",
               "--chunk-size", str(CHUNK_SIZE), "--worker-deadline", "1000"]
        env = dict(os.environ, JAX_PLATFORMS=platform)
        env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(cmd, env=env, cwd=HERE,
                                     stdout=self.log, stderr=self.log,
                                     start_new_session=True)
        self.client = None

    def connect(self, deadline_s: float):
        from repro.serve.client import ServeClient

        deadline = time.time() + deadline_s
        while not (os.path.exists(self.port_file)
                   and open(self.port_file).read().strip()):
            check(self.proc.poll() is None,
                  f"server exited early (rc={self.proc.returncode})")
            check(time.time() < deadline, "server never wrote its port file")
            time.sleep(0.1)
        self.client = ServeClient(open(self.port_file).read().strip(),
                                  timeout=1200.0)
        self.client.wait_ready(deadline_s=60)
        return self.client

    def wait_seat(self, deadline_s: float) -> dict:
        """Block until the seat reports its device; fail if every seat
        retired (the seat could not open its device)."""
        deadline = time.time() + deadline_s
        while True:
            workers = self.client.stats()["workers"]
            if workers.get("seats"):
                return workers["seats"][0]
            check(workers.get("retired", 0) < workers.get("size", 1),
                  "the worker seat failed to start (see server.log)")
            check(time.time() < deadline, "the worker seat never came up")
            time.sleep(0.2)

    def stop(self) -> int:
        """Drain; kill the whole process group if the drain hangs."""
        try:
            if self.client is not None and self.proc.poll() is None:
                self.client.shutdown()
            return self.proc.wait(timeout=120)
        except Exception:
            return -1
        finally:
            if self.proc.poll() is None:
                os.killpg(self.proc.pid, signal.SIGKILL)
                self.proc.wait()
            else:
                try:  # seats are in the server's group
                    os.killpg(self.proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            self.log.close()


def device_counters(stats: dict) -> dict:
    c = stats["counters"]
    return {k[len("worker_device_"):]: v for k, v in c.items()
            if k.startswith("worker_device_")}


def executed(stats: dict) -> int:
    c = stats["counters"]
    return c.get("executed_ok", 0) + c.get("executed_error", 0)


def check_rows(res, spec, paired: bool = True) -> list[dict]:
    """Every row ok and none re-timed per scenario; with ``paired``, every
    device row ran on the device engine and has the simulated statistics
    of its numpy twin."""
    scenarios, _ = spec.expand()
    check(res.outcome == "done", f"{spec.name}: job ended {res.outcome!r}")
    check(len(res.row_events) == len(scenarios),
          f"{spec.name}: {len(res.row_events)} rows of {len(scenarios)}")
    twins: dict = {}
    for ev in res.row_events:
        s = scenarios[ev["index"]]
        row = ev["row"]
        check(ev["status"] in ("ok", "cached"),
              f"{s.scenario_id}: {ev['status']} row: {row.get('error')}")
        check(not ev.get("timing_fallback"),
              f"{s.scenario_id}: batched timing fell back per scenario")
        check(row["engine"] == s.config.semexec,
              f"{s.scenario_id}: asked for {s.config.semexec}, "
              f"ran {row['engine']}")
        twin = dataclasses.replace(
            s, config=dataclasses.replace(s.config, semexec="numpy"))
        twins.setdefault(twin, {})[s.config.semexec] = row
    for twin, pair in twins.items() if paired else ():
        check(set(pair) == {"numpy", "device"},
              f"no numpy/device pair for {twin.scenario_id}")
        diff = {k: (v, pair["device"].get(k))
                for k, v in pair["numpy"].items()
                if k != "engine" and v != pair["device"].get(k)}
        check(not diff, f"{twin.scenario_id}: device differs from numpy: "
                        f"{diff}")
    return res.rows


def run(args) -> dict:
    from repro.core.engine import SCAN_CUTOFF

    platform = "cpu" if args.rehearse else "tpu"
    graphs, hbm_graph = (("sd", "db"), "sd") if args.rehearse \
        else (("lj", "r24"), "lj")
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    shutil.rmtree(os.path.join(out, "result-cache"), ignore_errors=True)
    with contextlib.suppress(FileNotFoundError):  # a stale server address
        os.remove(os.path.join(out, "port"))

    t0 = time.time()
    server = Server(out, platform)
    try:
        client = server.connect(deadline_s=300)
        seat = server.wait_seat(deadline_s=600)
        t_setup = time.time() - t0
        say(f"seat: {json.dumps(seat, sort_keys=True)}")
        check(seat["platform"] == platform,
              f"seat came up on {seat['platform']}, not {platform}")
        say(f"setup (server spawn, seat spawn + import + device open): "
            f"{t_setup:.3f}s")

        phases = []

        def phase(name: str, fn):
            before = client.stats()
            t = time.time()
            result = fn()
            wall = time.time() - t
            after = client.stats()
            db, da = device_counters(before), device_counters(after)
            delta = {k: da.get(k, 0) - db.get(k, 0) for k in da}
            ran = executed(after) - executed(before)
            compile_s = delta.get("compile_s", 0.0)
            say(f"phase {name}: wall {wall:.3f}s, executed {ran}, "
                f"XLA programs {delta.get('compiles', 0)} "
                f"({delta.get('compile_cache_hits', 0)} from the "
                f"persistent cache; {compile_s:.3f}s), wall less compiles "
                f"{wall - compile_s:.3f}s")
            say(f"  device: dispatches {delta.get('dispatches', 0)}, "
                f"traces {delta.get('traces', 0)}, "
                f"requests {delta.get('requests', 0)}; host fast-engine "
                f"traces (> SCAN_CUTOFF={SCAN_CUTOFF} requests) "
                f"{delta.get('host_traces', 0)}")
            phases.append(dict(name=name, wall_s=wall, executed=ran,
                               **delta))
            return result, ran

        # (a) golden trace hashes
        gspec = golden_spec()
        golden = json.load(open(GOLDEN))

        def run_golden():
            res = client.run(gspec)
            check_rows(res, gspec, paired=False)
            scen, _ = gspec.expand()
            served = {scen[e["index"]].scenario_id: e.get("trace_hash")
                      for e in res.row_events}
            bad = {k: (v, golden.get(k)) for k, v in served.items()
                   if golden.get(k) != v}
            check(len(served) == 8 and not bad,
                  f"golden trace hashes differ: {bad}")
            return len(served)

        n_golden, _ = phase("a-golden", run_golden)
        say(f"  golden: {n_golden}/8 trace hashes match")

        # (b) real-size grid, (c) the same grid again
        specs = grid_specs(graphs, hbm_graph)

        def run_grid():
            rows = []
            for spec in specs:
                res = client.run(spec)
                for sk in res.skipped:
                    say(f"  skip {sk['graph']}/{sk['accelerator']}/"
                        f"{sk['problem']}/{sk['dram']}: {sk['reason']}")
                rows += check_rows(res, spec)
            return rows

        rows, ran_b = phase("b-grid", run_grid)
        n_dev = sum(r["engine"] == "device" for r in rows)
        say(f"  grid: {len(rows)} rows ({n_dev} device-engine rows, each "
            f"equal to its numpy twin); graphs {', '.join(graphs)}")
        for r in rows:
            say(f"  row {r['graph']}/{r['accelerator']}/{r['problem']}/"
                f"{r['dram']}{'-pc' if r['pseudo_channels'] else ''}"
                f"/{r['page_policy']}/{r['engine']}: iterations "
                f"{r['iterations']}, runtime_s {r['runtime_s']}, "
                f"hits/misses/conflicts {r['row_hits']}/{r['row_misses']}/"
                f"{r['row_conflicts']}")
        check(ran_b == len(rows), f"grid executed {ran_b} of {len(rows)}")
        with open(os.path.join(out, "rows.json"), "w") as f:
            json.dump(rows, f, indent=1)

        _, ran_c = phase("c-resubmit", run_grid)
        check(ran_c == 0, f"resubmit executed {ran_c} scenarios")
        say("  resubmit: 0 executed, every row cached")

        with open(os.path.join(out, "phases.json"), "w") as f:
            json.dump(dict(seat=seat, setup_s=t_setup, phases=phases,
                           stats=client.stats()), f, indent=1)
    finally:
        rc = server.stop()
    check(rc == 0, f"server drain exited {rc}")
    say(f"server drained (exit 0); total {time.time() - t0:.3f}s")
    return seat


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="rehearsal on the CPU: small graphs, seat on "
                         "JAX_PLATFORMS=cpu")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"),
                    help="result cache, server log and rows")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"error: no repro package under {SRC}; run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    # this process is a client: it must never hold the chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        seat = run(args)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(dict(ok=True, device=dict(
        platform=seat["platform"], kind=seat["kind"], count=seat["count"]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
