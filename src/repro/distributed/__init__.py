"""Distribution: the persistent spawn-based worker pool the sweep server
shards scenario chunks across (:mod:`repro.distributed.workpool`), its
multi-host counterpart that dispatches chunks to remote worker hosts
over the serve wire format (:mod:`repro.distributed.remote`), and the
deterministic fault-injection harness that exercises their recovery
paths (:mod:`repro.distributed.faults`).

Exports resolve lazily: :mod:`~repro.distributed.remote` pulls in jax
(through :mod:`repro.serve`), and spawn-context worker children import
this package on their way to ``workpool`` — they must not pay (or
require) the jax import just to run the worker loop.
"""
from __future__ import annotations

__all__ = ["WorkerPool", "WorkerLost", "RemoteWorkerPool",
           "WorkerHostAgent", "FaultPlan", "FaultRule"]

_LAZY = {
    "WorkerPool": ("repro.distributed.workpool", "WorkerPool"),
    "WorkerLost": ("repro.distributed.workpool", "WorkerLost"),
    "RemoteWorkerPool": ("repro.distributed.remote", "RemoteWorkerPool"),
    "WorkerHostAgent": ("repro.distributed.remote", "WorkerHostAgent"),
    "FaultPlan": ("repro.distributed.faults", "FaultPlan"),
    "FaultRule": ("repro.distributed.faults", "FaultRule"),
}


def __getattr__(name: str):
    try:
        module, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    import importlib

    value = getattr(importlib.import_module(module), attr)
    globals()[name] = value  # cache: resolve each name once
    return value


def __dir__():
    return sorted(set(globals()) | set(__all__))
