"""What runs inside a sweep-server worker process.

Workers are long-lived (see :class:`repro.distributed.WorkerPool`): the
first chunk pays module import + XLA compilation, every later chunk reuses
the process's warm state — the ``hostcache`` artifact/semantics caches,
the runner's graph memo, and jitted timing kernels.  ``init_worker`` runs
once per process and resizes the host caches for that lifetime;
``run_chunk`` executes one scenario chunk and reports the host-cache
hit/miss delta, the graph-memo delta, the device-work delta (dispatches,
XLA compiles) and the stage spans (:mod:`repro.spans`) it produced, so
the server can aggregate worker warmth, device work and where the seat's
host time goes in ``/stats``.
"""
from __future__ import annotations

import os
import socket

from repro import spans
from repro.sweep.runner import ExecutionPolicy, execute_chunk, graph_memo_stats
from repro.sweep.spec import Scenario

# Long-lived workers see many jobs over many graphs; hold more offline
# artifacts than a one-shot sweep worker would.
ARTIFACTS_CAPACITY = 64
SEMANTICS_CAPACITY = 16

# XLA programs this process compiled or loaded from the persistent
# compile cache (``compile_cache_hits`` of them loaded), and the seconds
# both took
_COMPILES = dict(compiles=0, compile_s=0.0, compile_cache_hits=0)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES["compiles"] += 1
        _COMPILES["compile_s"] += duration
    elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
        _COMPILES["compile_cache_hits"] += 1


def device_counters() -> dict:
    """:func:`repro.core.engine.dispatch_stats`, the semantic engine's
    :func:`repro.core.semexec.step_stats`, and this process's XLA program
    count, seconds and persistent-cache hits."""
    from repro.core.engine import dispatch_stats
    from repro.core.semexec import step_stats

    return {**dispatch_stats(), **step_stats(), **_COMPILES}


def init_worker(artifacts_capacity: int = ARTIFACTS_CAPACITY,
                semantics_capacity: int = SEMANTICS_CAPACITY) -> dict:
    """Per-process warm-up: point the compile cache at its directory,
    resize host caches, pre-import the hot path so the first job does not
    pay import latency inside its first chunk, and open the device.
    Returns the seat's :func:`repro.runtime.device_info`, which the pool
    reports in ``/stats``; a seat asked for the TPU (``JAX_PLATFORMS=tpu``)
    that cannot open it raises here and never takes a chunk."""
    import jax

    from repro import runtime
    from repro.core import hostcache

    runtime.enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    hostcache.configure(artifacts_capacity=artifacts_capacity,
                        semantics_capacity=semantics_capacity)
    import repro.core.accelerators  # noqa: F401  (registers the models)
    import repro.core.engine  # noqa: F401
    import repro.core.semexec  # noqa: F401  (device semantic-execution path)

    return runtime.device_info()  # raises if JAX_PLATFORMS names an absent TPU


def run_chunk(
    scenarios: list[Scenario],
    mode: str,
    policy: ExecutionPolicy | None,
    with_trace_hash: bool,
    inject=None,
    chunk: int | None = None,
) -> dict:
    """Execute one chunk; returns ``{"records": [...], "hostcache": delta,
    "graph_memo": delta, "device": delta, "stages": totals, "spans":
    rows, "seat": label}``.  The deltas are this chunk's host-cache
    hit/miss, graph-memo hit/miss and :func:`device_counters`
    contributions (cumulative worker counters would double-count across
    chunks); ``stages`` is :func:`repro.spans.stage_totals` of the chunk's
    ``spans`` (rows ``[id, parent, name, t0, t1]`` on this host's clock,
    the root a ``chunk`` span), and ``seat`` names this process
    (``host:pid``).  ``chunk`` is the scheduler's dispatch index, echoed
    back for the spans it labels.

    ``inject`` is an optional :class:`repro.distributed.faults.FaultAction`
    resolved by the scheduler at dispatch time: pre-work faults (crash /
    hang / stall / delay) fire before the chunk executes, ``corrupt``
    mangles the finished records — so the scheduler's recovery paths are
    exercised against the real worker protocol."""
    from repro.core.hostcache import stats_all

    if inject is not None:
        from repro.distributed import faults

        faults.apply_pre(inject)
    before, d_before = stats_all(), device_counters()
    g_before = graph_memo_stats()
    with spans.collect() as rows, spans.span("chunk"):
        records = execute_chunk(scenarios, mode=mode, policy=policy,
                                with_trace_hash=with_trace_hash)
    if inject is not None and inject.kind == "corrupt":
        from repro.distributed import faults

        records = faults.corrupt_records(records)
    after, d_after = stats_all(), device_counters()
    g_after = graph_memo_stats()
    delta = {
        cache: {k: after[cache][k] - before[cache][k]
                for k in ("hits", "misses")}
        for cache in after
    }
    device = {k: d_after[k] - d_before[k] for k in d_after}
    return dict(records=records, hostcache=delta,
                graph_memo={k: g_after[k] - g_before[k] for k in g_after},
                device=device, stages=spans.stage_totals(rows), spans=rows,
                seat=f"{socket.gethostname()}:{os.getpid()}", chunk=chunk)
