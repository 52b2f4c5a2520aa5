"""Simulation-as-a-service: a persistent sweep server over the runner.

``python -m repro.serve`` starts a local HTTP server that keeps the
expensive state of ``repro.sweep`` warm between requests — a spawn-worker
pool whose processes hold host caches and compiled timing kernels, plus
the shared content-addressed result cache.  Clients submit
:class:`~repro.sweep.SweepSpec` grids and stream result rows back
incrementally as JSONL; overlapping grids from concurrent clients dedup
against both the on-disk cache and each other's in-flight work, so no
scenario is ever simulated twice.

Layers (each usable on its own):

- :mod:`repro.serve.protocol` — wire format: spec <-> JSON, event framing;
- :mod:`repro.serve.scheduler` — queue, dedup, in-flight join, dispatch,
  drain; transport-agnostic (tests drive it directly);
- :mod:`repro.serve.worker` — what runs inside a pool worker process;
- :mod:`repro.serve.server` — the HTTP/JSONL front + SIGTERM handling;
- :mod:`repro.serve.client` — thin stdlib client (``ServeClient``);
- :mod:`repro.serve.metrics` — counters/histograms behind ``/stats``.

Rows are byte-identical to ``python -m repro.sweep`` output for the same
spec and cache state: both paths share the runner, the cache keys, and
:func:`repro.sweep.results.scenario_row`.

Besides grid sweeps, the scheduler runs **adaptive search jobs**
(``POST /search`` / :meth:`SweepScheduler.submit_search`): the
:mod:`repro.sweep.search` loop proposes probe batches that dedup and
execute through the same entry table and warm worker pool, streaming
``proposal``/``progress``/``row`` events and finishing with a
``search_result`` payload.  Search jobs journal with ``kind: "search"``
and resume after a crash like sweeps do — already-executed probes come
back from the cache, so the search continues where it left off.

Partial failure is survivable at every layer: crashed/hung workers are
detected and respawned by the supervised pool
(:mod:`repro.distributed.workpool`), their chunks re-dispatched (with a
poison-scenario circuit breaker), accepted jobs are journaled
(:mod:`repro.serve.journal`) so a restarted server resumes unfinished
work from the journal plus the cache, and every recovery path is
exercised deterministically through
:mod:`repro.distributed.faults`.
"""
from repro.serve.client import (
    JobResult,
    SearchJobResult,
    ServeClient,
    ServeError,
)
from repro.serve.journal import JobJournal
from repro.serve.protocol import (
    ProtocolError,
    dump_event,
    parse_event,
    search_from_wire,
    search_to_wire,
    spec_from_wire,
    spec_to_wire,
)
from repro.serve.scheduler import (
    TERMINAL_EVENTS,
    JobState,
    SearchJobState,
    SweepScheduler,
)
from repro.serve.server import SweepServer

__all__ = [
    "JobJournal",
    "JobResult",
    "JobState",
    "ProtocolError",
    "SearchJobResult",
    "SearchJobState",
    "ServeClient",
    "ServeError",
    "SweepScheduler",
    "SweepServer",
    "TERMINAL_EVENTS",
    "dump_event",
    "parse_event",
    "search_from_wire",
    "search_to_wire",
    "spec_from_wire",
    "spec_to_wire",
]
