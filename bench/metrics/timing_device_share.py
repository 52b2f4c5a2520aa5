"""Share of the traced window in which the device ran the DRAM-timing scan
(``repro.core.engine._scan_engine_batch``)."""
from bench import devtrace

LAYER = "core.engine DRAM timing"
PROGRAMS = (r"_scan_engine_batch",)


def read(obs):
    if obs.trace is None:
        return None
    share = devtrace.program_share(obs.trace["devices"], PROGRAMS,
                                   obs.trace["t0"] * 1e9,
                                   obs.trace["t_stop"] * 1e9)
    return None if share is None else 100.0 * share
