"""Share of the traced window in which the device ran a jitted step of the
device semantic engine (``repro.core.semexec``)."""
from bench import devtrace

LAYER = "core.semexec"
PROGRAMS = (r"_hitgraph_min_step", r"_jacobi_min_step", r"_acc_step",
            r"_gs_min_step", r"_gs_acc_step", r"_fg_min_step")


def read(obs):
    if obs.trace is None:
        return None
    share = devtrace.program_share(obs.trace["devices"], PROGRAMS,
                                   obs.trace["t0"] * 1e9,
                                   obs.trace["t_stop"] * 1e9)
    return None if share is None else 100.0 * share
