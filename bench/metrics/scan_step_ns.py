"""Device time of one sequential step of the DRAM-timing engine: the union
of the ``_scan_engine_batch`` executions in the traced window (the programs
``timing_device_share`` reads), over the steps the seat dispatched in the
window (Δ``worker_device_scan_steps``, the padded length L of each
dispatch, summed), in nanoseconds.  A program that does not count its
steps gives nothing to read."""
from bench import devtrace, window
from bench.metrics.timing_device_share import PROGRAMS

LAYER = "core.engine DRAM timing"


def read(obs):
    if obs.trace is None:
        return None
    steps = window.counter_delta(obs.stats0, obs.stats1,
                                 "worker_device_scan_steps")
    t0, t1 = obs.trace["t0"] * 1e9, obs.trace["t_stop"] * 1e9
    share = devtrace.program_share(obs.trace["devices"], PROGRAMS, t0, t1)
    if not steps or share is None:
        return None
    return share * (t1 - t0) / steps
