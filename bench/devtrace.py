"""Reduction of a device trace to the benchmark's device metrics.

Each device plane of the profiler's trace has an ``XLA Modules`` line:
every execution of a compiled program on that device, with its start and
end.  That is what the metrics read.  (The plane's ``XLA Ops`` line holds
every operation, including each step of the DRAM-timing scan's loop: some
five million events a second of scan on a v5e, too many to keep, so it is
not read.)  The seat reduces each plane with :func:`reduce_plane` before
anything leaves it; the rest works on that reduction and on plain
``(start, end)`` intervals, so the arithmetic is checked on a small
recorded trace without JAX.

- busy time is the union of the program executions inside the window;
- a layer's device share is the union of its programs' executions inside
  the window, over the window (a metric file names its programs);
- an idle gap is a stretch of the window in which no program ran.
"""
from __future__ import annotations

import re
from collections import Counter


def merge(intervals) -> list[tuple[int, int]]:
    """Union of ``(start, end)`` intervals as sorted, disjoint intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals, t0, t1) -> float:
    """Length of the union of ``intervals`` inside ``[t0, t1]``."""
    return float(sum(min(e, t1) - max(s, t0) for s, e in merge(intervals)
                     if e > t0 and s < t1))


def gaps(busy, t0, t1) -> list[tuple[float, float]]:
    """The stretches of ``[t0, t1]`` that no interval of ``busy`` covers."""
    out, at = [], t0
    for s, e in merge(busy):
        if e <= t0 or s >= t1:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < t1:
        out.append((at, t1))
    return out


def reduce_plane(name: str, modules, top: int = 10) -> dict:
    """One device plane's ``(program, start_ns, end_ns)`` executions,
    reduced to: the merged busy intervals, the device time of the ``top``
    programs by name (all shapes of a program together) and every
    execution."""
    per_program: Counter = Counter()
    for m, s, e in modules:
        per_program[re.sub(r"\(\d+\)$", "", m)] += e - s
    return dict(
        device=name,
        busy=merge((s, e) for _, s, e in modules),
        program_ns=per_program.most_common(top),
        modules=[(m, s, e) for m, s, e in modules],
    )


def program_share(planes: list, patterns, t0_ns: float, t1_ns: float) -> float | None:
    """Share of the window ``[t0_ns, t1_ns]`` in which a program whose name
    matches one of ``patterns`` ran, averaged over the devices; None where
    no device ran such a program."""
    rx = re.compile("|".join(patterns))
    shares, seen = [], False
    for p in planes:
        runs = [(s, e) for m, s, e in p["modules"] if rx.search(m)]
        seen |= bool(runs)
        shares.append(covered(runs, t0_ns, t1_ns) / (t1_ns - t0_ns))
    return sum(shares) / len(shares) if seen else None


def busy_ns(planes: list, t0_ns: float, t1_ns: float) -> float:
    """Device-busy nanoseconds in the window, averaged over the devices."""
    if not planes:
        return 0.0
    return sum(covered(p["busy"], t0_ns, t1_ns) for p in planes) / len(planes)


def top_programs(planes: list, n: int = 10) -> list:
    """``[name, seconds]`` of the programs that took the most device time,
    summed over the devices."""
    total: Counter = Counter()
    for p in planes:
        for name, ns in p["program_ns"]:
            total[name] += ns
    return [[name, ns * 1e-9] for name, ns in total.most_common(n)]


def labelled_gaps(planes: list, spans, t0_ns: float, t1_ns: float,
                  n: int = 10) -> list:
    """The ``n`` longest idle gaps of the first device, each labelled by
    what the seat was doing at its middle: inside ``run_chunk`` or
    between chunks (``spans`` are the seat's chunk spans, in ns)."""
    if not planes:
        return []
    out = []
    for s, e in gaps(planes[0]["busy"], t0_ns, t1_ns):
        mid = (s + e) / 2
        inside = any(a <= mid <= b for a, b in spans)
        out.append(["in run_chunk" if inside else "between chunks",
                    (e - s) * 1e-9])
    out.sort(key=lambda g: -g[1])
    return out[:n]
