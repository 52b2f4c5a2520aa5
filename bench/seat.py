"""The benchmark's side of the worker seat.

:class:`SeatPool` is the pool the bench server hands to
``SweepServer(pool_factory=...)``.  It wraps the program's own
:class:`repro.distributed.workpool.WorkerPool` (one seat, as deployed) and
routes every chunk through :func:`run_chunk_spanned`, which runs the
program's ``run_chunk`` inside the seat and records its span on the
host clock.  The other functions here run inside the seat on request:
the set-up's compile of every scan shape, the profiler (only the seat
holds the chip, so only it can trace it) and the device report.

``fault="alter"`` is the broken timed path of the benchmark's own tests:
every finished record's simulated row-hit count is changed where the seat
produces it.
"""
from __future__ import annotations

import glob
import os
import threading
import time


def run_chunk_spanned(fn, fault, *args):
    t0 = time.time()
    out = fn(*args)
    t1 = time.time()
    if fault == "alter":
        for rec in out["records"]:
            if rec.get("status") == "ok":
                rec["report"]["timing"]["hits"] += 1
    out["bench_span"] = (t0, t1)
    return out


class SeatPool:
    """What the scheduler asks of a pool (``submit``, ``size``, ``stats``,
    ``shutdown``) over one program ``WorkerPool``, plus the seat spans and
    a way to run a function in the seat."""

    def __init__(self, inner, fault: str | None = None):
        self.inner = inner
        self.size = inner.size
        self.fault = fault
        self._lock = threading.Lock()
        self._spans: list[tuple[float, float]] = []

    def submit(self, fn, *args):
        fut = self.inner.submit(run_chunk_spanned, fn, self.fault, *args)
        fut.add_done_callback(self._record)
        return fut

    def _record(self, fut) -> None:
        if fut.cancelled() or fut.exception() is not None:
            return
        with self._lock:
            self._spans.append(tuple(fut.result()["bench_span"]))

    def spans(self) -> list:
        with self._lock:
            return list(self._spans)

    def call(self, fn, *args, timeout: float = 600.0):
        """Run ``fn(*args)`` in the seat, after the chunks before it."""
        return self.inner.submit(fn, *args).result(timeout=timeout)

    def stats(self) -> dict:
        return self.inner.stats()

    def shutdown(self, *args, **kwargs) -> None:
        self.inner.shutdown(*args, **kwargs)


# ---- run inside the seat ----------------------------------------------------


def device_report() -> dict:
    """The seat's device as JAX reports it, with the peak bytes in use on
    its fullest device (0 where the backend keeps no count)."""
    import jax

    devices = jax.devices()
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return dict(platform=devices[0].platform, kind=devices[0].device_kind,
                count=len(devices), memory_peak_bytes=peak)


def warm_scan(memories: list, max_len: int, max_batch: int) -> dict:
    """Compile the DRAM-timing scan for every padded ``[B, L]`` shape the
    program can dispatch under each memory system (``[dram, page policy,
    pseudo-channels]``): L each power of two from the program's smallest
    bucket to ``max_len``, B each power of two up to ``max_batch`` and the
    program's batch limit at that L.  Compiled ahead of time, the programs
    are ready for the window without running: no compile is left for the
    window whatever its roots' trace lengths.  A program whose scan is
    no longer bucketed so warms nothing, and ``window_compiles`` shows
    what its window compiles."""
    import jax
    import jax.numpy as jnp

    from repro.core import engine
    from repro.core.dram import dram_config

    scan = getattr(engine, "_scan_engine_batch", None)
    bucket = getattr(engine, "_pow2_bucket", None)
    limit = getattr(engine, "MAX_BATCH_ELEMS", None)
    if scan is None or bucket is None or limit is None:
        return dict(programs=0, seconds=0.0)
    t0 = time.time()
    shapes = set()
    for dram, policy, pc in memories:
        cfg = dram_config(dram, page_policy=policy,
                          pseudo_channels=pc).pseudo_channel_view()
        t = cfg.timing_cycles()
        L = bucket(0)
        while L <= max_len:
            B = 1
            while B <= min(max_batch, max(1, limit // L)):
                shapes.add((cfg.nbanks, t["tCL"], t["tRCD"], t["tRP"],
                            t["tRC"], t["tBL"], cfg.page_open, B, L))
                B *= 2
            L *= 2
    for nbanks, tcl, trcd, trp, trc, tbl, page_open, B, L in sorted(shapes):
        x = jax.ShapeDtypeStruct((B, L), jnp.int32)
        scan.lower(x, x, nbanks, tcl, trcd, trp, trc, tbl,
                   lookahead=16 * tbl, page_open=page_open).compile()
    return dict(programs=len(shapes), seconds=time.time() - t0)


def _bench_clock_marker(x):
    return x + 1


def _clock_marker() -> float:
    """Run a tiny program named ``_bench_clock_marker`` and return the host
    time at its middle; its execution in the device trace ties the
    profiler's clock to the host's."""
    import jax
    import jax.numpy as jnp

    marker = jax.jit(_bench_clock_marker)
    x = jnp.zeros(8, jnp.int32)
    marker(x).block_until_ready()  # compiled outside the mark
    t0 = time.time()
    marker(x).block_until_ready()
    return (t0 + time.time()) / 2


def trace_start(log_dir: str) -> float:
    """Start the profiler with the host and Python tracers off (the device
    planes are what the metrics read), mark the clocks; returns the host
    time the trace started."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    t0 = time.time()
    _clock_marker()
    return t0


def trace_stop(log_dir: str) -> dict:
    """Stop the profiler and reduce the device planes of its trace to what
    :mod:`bench.devtrace` reads: per device, every program execution, on
    the host clock (in ns)."""
    import jax

    from bench import devtrace

    t_mark = _clock_marker()
    t_stop = time.time()
    jax.profiler.stop_trace()
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    raw = []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                raw.append((plane.name, [(e.name, e.start_ns, e.end_ns)
                                         for e in line.events]))
    # the last marker execution on the first device: its middle is t_mark
    marks = [(s + e) / 2 for n, s, e in (raw[0][1] if raw else ())
             if "_bench_clock_marker" in n]
    if not marks:
        raise RuntimeError("the clock marker is not in the device trace")
    offset = t_mark * 1e9 - marks[-1]
    devices = [devtrace.reduce_plane(
        name, [(n, s + offset, e + offset) for n, s, e in mods])
        for name, mods in raw]
    size = sum(os.path.getsize(f) for f in files)
    for f in files:
        os.remove(f)
    return dict(t_stop=t_stop, trace_bytes=size, devices=devices)
