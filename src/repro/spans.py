"""Stage spans: where a worker seat's host time goes inside a chunk.

``span(name)`` marks one stage of the work a chunk crosses (graph build,
prepare, semantic execution, trace emission, timing dispatch, ...).  Each
span records its name, its start and end on the host clock
(``time.time()``, the clock the benchmark maps device traces onto), and
the span it is nested in.  Recording is scoped: :func:`collect` opens a
scope on the calling thread, and only spans entered inside it are kept.
Outside a scope ``span`` checks one attribute and records nothing, so the
CLI's ``run_sweep`` and library callers pay nothing and keep nothing.

A scope drains into plain rows ``[id, parent, name, t0, t1]`` (``id`` the
span's index in the scope, ``parent`` that of the enclosing span or
``None``), and :func:`stage_totals` folds them into each stage's self
seconds (its duration less the part its child spans cover) and count.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time

_local = threading.local()


class _Scope:
    __slots__ = ("rows", "stack")

    def __init__(self):
        self.rows: list[list] = []
        self.stack: list[int] = []


class span:
    """Context manager recording one stage span in the thread's open scope
    (see :func:`collect`); a no-op when none is open."""

    __slots__ = ("name", "_row")

    def __init__(self, name: str):
        self.name = name
        self._row = None

    def __enter__(self):
        scope = getattr(_local, "scope", None)
        if scope is not None:
            parent = scope.stack[-1] if scope.stack else None
            self._row = [len(scope.rows), parent, self.name, time.time(), None]
            scope.rows.append(self._row)
            scope.stack.append(self._row[0])
        return self

    def __exit__(self, *exc):
        row = self._row
        if row is not None:
            row[4] = time.time()
            _local.scope.stack.pop()
            self._row = None
        return False


def spanned(name: str):
    """Decorator: every call of the function is one ``name`` span."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


@contextlib.contextmanager
def collect():
    """Record the spans entered on this thread inside the ``with`` block;
    yields the list their rows are appended to (complete once the block
    exits).  A nested ``collect`` takes over until it exits."""
    outer = getattr(_local, "scope", None)
    scope = _local.scope = _Scope()
    try:
        yield scope.rows
    finally:
        _local.scope = outer


def stage_totals(rows: list[list]) -> dict[str, dict]:
    """``{name: {"s": self seconds, "n": spans}}`` over drained rows."""
    child_s = [0.0] * len(rows)
    for _id, parent, _name, t0, t1 in rows:
        if parent is not None:
            child_s[parent] += t1 - t0
    out: dict[str, dict] = {}
    for (_id, _parent, name, t0, t1), inner in zip(rows, child_s):
        tot = out.setdefault(name, dict(s=0.0, n=0))
        tot["s"] += (t1 - t0) - inner
        tot["n"] += 1
    return out
