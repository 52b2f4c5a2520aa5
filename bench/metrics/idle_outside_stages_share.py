"""Share of the traced window in which the device was idle and the worker
seat was in no named stage: not inside any of the program's stage spans
but ``chunk`` (``/stats`` ``spans``, on the seat's host clock, which the
device trace is mapped onto).  That is the time between chunks, the
pool's transport and the code of ``run_chunk`` outside its stages; it
also checks that the two clocks agree.  Averaged over the devices; None
where the program records no spans or nothing was traced."""
from bench import devtrace

LAYER = "serve.worker seat (outside stages)"


def _intersection(a, b) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(obs):
    ring = obs.stats1.get("spans")
    if obs.trace is None or not obs.trace["devices"] or ring is None:
        return None
    t0, t1 = obs.trace["t0"] * 1e9, obs.trace["t_stop"] * 1e9
    stages = devtrace.merge((row[3] * 1e9, row[4] * 1e9)
                            for row in ring["spans"] if row[2] != "chunk")
    shares = []
    for plane in obs.trace["devices"]:
        idle = devtrace.gaps(plane["busy"], t0, t1)
        outside = sum(e - s for s, e in idle) - _intersection(idle, stages)
        shares.append(outside / (t1 - t0))
    return 100.0 * sum(shares) / len(shares)
