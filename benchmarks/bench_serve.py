"""Sweep-server bench: throughput, row latency, and work-collapse rate.

Starts a real ``python -m repro.serve`` server process, then drives it the
way a sweep campaign does: several concurrent clients submitting
*overlapping* scenario grids (adjacent sweeps share most of their axis
product — the paper's tables differ in one axis at a time).  The server
must collapse that overlap three ways: on-disk cache hits, in-flight joins
across clients, and duplicate collapse within a submission.  Measured:

- **jobs/s** and **rows/s** over the whole campaign,
- **p50/p95 row latency** (submit-to-row, from the server's ``/stats``
  histograms — what a dashboard polling the server would see),
- **collapse rate** — the fraction of submitted scenarios that never hit
  a worker because the cache, an in-flight entry, or an intra-job dedup
  already covered them,
- worker host-cache warmth across jobs (hits accumulated over the
  campaign's chunks).

``--tiny`` is the CI smoke: one tiny job with ``--trace-hashes`` on, every
streamed row's trace fingerprint must match
``benchmarks/golden_hashes_tiny.json`` (the same goldens the host bench
checks — proof the served path simulates the exact same traces), a
resubmission must be 100% cached, and the server must drain cleanly.

    PYTHONPATH=src python -m benchmarks.bench_serve          # full campaign
    PYTHONPATH=src python -m benchmarks.bench_serve --tiny   # CI smoke
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

from repro.graph.generators import GraphSpec
from repro.serve.client import ServeClient
from repro.sweep.spec import SweepSpec

GOLDEN = os.path.join(os.path.dirname(__file__), "golden_hashes_tiny.json")

TINY_SPEC = SweepSpec(
    name="serve-tiny",
    accelerators=("accugraph", "foregraph", "hitgraph", "thundergp"),
    graphs=(GraphSpec("tiny", "uniform", 256, 1024, True, 1, 0),),
    problems=("bfs",),
    drams=("default", "hbm"),
)


def start_server(cache_dir: str, workers: int, trace_hashes: bool,
                 chunk_size: int = 2):
    """Spawn ``python -m repro.serve`` and wait for its port file."""
    port_file = os.path.join(cache_dir, "port")
    cmd = [sys.executable, "-m", "repro.serve", "--port", "0",
           "--port-file", port_file, "--cache", os.path.join(cache_dir, "c"),
           "--workers", str(workers), "--chunk-size", str(chunk_size),
           "--quiet"]
    if trace_hashes:
        cmd.append("--trace-hashes")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(cmd, env=env)
    deadline = time.time() + 180
    while not os.path.exists(port_file) or not open(port_file).read().strip():
        if proc.poll() is not None:
            raise RuntimeError(f"server exited early: rc={proc.returncode}")
        if time.time() > deadline:
            proc.kill()
            raise RuntimeError("server never wrote its port file")
        time.sleep(0.1)
    address = open(port_file).read().strip()
    client = ServeClient(address)
    client.wait_ready(deadline_s=60)
    return proc, client


def stop_server(proc, client) -> int:
    client.shutdown()
    return proc.wait(timeout=120)


# ---- CI smoke ---------------------------------------------------------------


def run_tiny(out: str) -> int:
    tmp = tempfile.mkdtemp(prefix="bench_serve_")
    # one seat: on a chip host a second seat would contend for the chip
    proc, client = start_server(tmp, workers=1, trace_hashes=True)
    scenarios, _ = TINY_SPEC.expand()
    golden = json.load(open(GOLDEN))

    print(f"[bench_serve] tiny: {len(scenarios)} scenarios -> "
          f"http://{client.host}:{client.port}")
    t0 = time.time()
    res = client.run(TINY_SPEC)
    wall = time.time() - t0
    assert res.outcome == "done", f"job ended {res.outcome!r}"
    assert res.statuses == ["ok"] * len(scenarios), res.statuses

    served = {scenarios[ev["index"]].scenario_id: ev["trace_hash"]
              for ev in res.row_events}
    mismatches = {sid: (h, golden.get(sid))
                  for sid, h in served.items() if golden.get(sid) != h}
    assert not mismatches, f"served trace hashes diverged: {mismatches}"
    print(f"  golden: {len(served)}/{len(golden)} trace hashes match "
          f"({wall:.1f}s)")

    res2 = client.run(TINY_SPEC)
    assert res2.statuses == ["cached"] * len(scenarios), res2.statuses
    assert [e["trace_hash"] for e in res2.row_events] == \
        [e["trace_hash"] for e in res.row_events]
    print("  resubmit: 8/8 cached, fingerprints stable")

    stats = client.stats()
    rc = stop_server(proc, client)
    assert rc == 0, f"server drain exited {rc}"
    print("  clean shutdown (exit 0)")

    result = dict(
        mode="tiny",
        scenarios=len(scenarios),
        wall_s=round(wall, 3),
        golden_hashes_checked=len(served),
        golden_ok=True,
        resubmit_all_cached=True,
        clean_shutdown=True,
        counters=stats["counters"],
    )
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"  wrote {out}")
    return 0


# ---- full campaign ----------------------------------------------------------


def campaign_specs() -> list[SweepSpec]:
    """Overlapping sweeps the way a study submits them: each job varies one
    axis of a base grid, so consecutive jobs share most scenarios."""
    base = dict(graphs=("sd", "db"), problems=("bfs",), drams=("default",))
    jobs = [
        SweepSpec(name="base", accelerators=("accugraph", "hitgraph"), **base),
        # same grid again from a second client (pure overlap)
        SweepSpec(name="again", accelerators=("accugraph", "hitgraph"), **base),
        # widen the accelerator axis (half overlap)
        SweepSpec(name="accels",
                  accelerators=("accugraph", "hitgraph", "thundergp",
                                "foregraph"), **base),
        # add a problem (half overlap with the widened grid)
        SweepSpec(name="problems",
                  accelerators=("accugraph", "hitgraph", "thundergp",
                                "foregraph"),
                  graphs=("sd", "db"), problems=("bfs", "pr"),
                  drams=("default",)),
        # swing the memory axis (overlaps on the default-DRAM half)
        SweepSpec(name="drams",
                  accelerators=("accugraph", "hitgraph", "thundergp",
                                "foregraph"),
                  graphs=("sd", "db"), problems=("bfs", "pr"),
                  drams=("default", "hbm")),
    ]
    return jobs


def run_full(out: str, workers: int) -> int:
    tmp = tempfile.mkdtemp(prefix="bench_serve_")
    proc, client = start_server(tmp, workers=workers, trace_hashes=False,
                                chunk_size=4)
    specs = campaign_specs()
    n_submitted = sum(len(s.expand()[0]) for s in specs)
    uniq = {scn.scenario_id for s in specs for scn in s.expand()[0]}
    print(f"[bench_serve] campaign: {len(specs)} jobs, {n_submitted} "
          f"scenario submissions over {len(uniq)} unique scenarios, "
          f"{workers} workers")

    results = {}
    t0 = time.time()

    def submit(spec):
        results[spec.name] = ServeClient(f"{client.host}:{client.port}"
                                         ).run(spec)

    # first two jobs race each other (in-flight joins); the rest arrive
    # staggered like an interactive study would submit them
    threads = [threading.Thread(target=submit, args=(s,)) for s in specs]
    threads[0].start()
    threads[1].start()
    for t in threads[2:]:
        time.sleep(0.3)
        t.start()
    for t in threads:
        t.join(timeout=1800)
    wall = time.time() - t0

    bad = {name: r.outcome for name, r in results.items()
           if r.outcome != "done" or r.n_errors}
    assert not bad, f"campaign jobs failed: {bad}"
    rows_total = sum(len(r.rows) for r in results.values())

    stats = client.stats()
    c = stats["counters"]
    collapsed = (c.get("cache_hits", 0) + c.get("inflight_joins", 0)
                 + c.get("dedup_joins", 0))
    executed = c.get("executed_ok", 0) + c.get("executed_error", 0)
    rc = stop_server(proc, client)
    assert rc == 0, f"server drain exited {rc}"

    result = dict(
        mode="full",
        workload=dict(
            jobs=len(specs),
            scenario_submissions=n_submitted,
            unique_scenarios=len(uniq),
            workers=workers,
        ),
        wall_s=round(wall, 3),
        jobs_per_s=round(len(specs) / wall, 4),
        rows_per_s=round(rows_total / wall, 3),
        row_latency_s=stats["latency"].get("row_s", {}),
        worker_stage_s={k[len("worker_stage_"):]: round(v, 6)
                        for k, v in c.items()
                        if k.startswith("worker_stage_")
                        and k.endswith("_s")},
        queue_wait_s=stats["latency"].get("queue_wait_s", {}),
        collapse=dict(
            submitted=c.get("scenarios_submitted", 0),
            executed=executed,
            cache_hits=c.get("cache_hits", 0),
            inflight_joins=c.get("inflight_joins", 0),
            dedup_joins=c.get("dedup_joins", 0),
            collapse_rate=round(
                collapsed / max(1, c.get("scenarios_submitted", 0)), 4),
        ),
        worker_hostcache={
            k: v for k, v in c.items() if k.startswith("worker_hostcache")},
        counters=c,
    )
    # every unique scenario must have executed exactly once
    assert executed == len(uniq), (executed, len(uniq))
    assert executed + collapsed == c.get("scenarios_submitted", 0)
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"  {rows_total} rows in {wall:.1f}s; executed {executed} of "
          f"{n_submitted} submitted (collapse rate "
          f"{result['collapse']['collapse_rate']:.0%})")
    print(f"  row latency p50={result['row_latency_s'].get('p50')}s "
          f"p95={result['row_latency_s'].get('p95')}s")
    print(f"  wrote {out}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: one tiny job, golden trace hashes")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)
    if args.tiny:
        return run_tiny(args.out)
    return run_full(args.out, args.workers)


if __name__ == "__main__":
    raise SystemExit(main())
