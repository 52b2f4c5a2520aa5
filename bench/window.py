"""Window arithmetic: what the client's clock and the server's counters say
about the measured window.

The window opens at the first submission after set-up and closes at the
arrival of the last row in flight, so a rate is taken over the whole
window, the tail after the last submission included.  A counter is read as its change between the
snapshot taken as the window opened and the one taken as it closed, so
nothing counted during set-up leaks in.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Row:
    t_arrive: float  # host time the row's event arrived at the client
    status: str
    row: dict
    scenario: object = None  # what the reference needs (bench.check)

    @property
    def requests(self) -> int:
        """Simulated DRAM requests of the row: every request is a row hit,
        a row miss or a row conflict."""
        r = self.row
        return int(r.get("row_hits", 0) + r.get("row_misses", 0)
                   + r.get("row_conflicts", 0))


def window_bounds(rows: list[Row], t_open: float) -> tuple[float, float]:
    """From the window's first submission to its last row's arrival."""
    return t_open, max([t_open] + [r.t_arrive for r in rows])


def rate(rows: list[Row], t0: float, t1: float) -> float:
    """Simulated requests of the completed rows per second of window, in
    millions."""
    return sum(r.requests for r in rows if r.status == "ok") / (t1 - t0) / 1e6


def counter_delta(before: dict, after: dict, name: str) -> float:
    return after["counters"].get(name, 0) - before["counters"].get(name, 0)


def span_share(spans, t0: float, t1: float) -> float:
    """Share of ``[t0, t1]`` covered by the union of ``spans``."""
    from bench.devtrace import covered

    return covered(spans, t0, t1) / (t1 - t0)
