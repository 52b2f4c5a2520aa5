"""Share of the window the worker seat spent on the host half of
``Accelerator.prepare``: the self seconds of the program's ``prepare``,
``prepare_graph`` and ``semantics`` stage spans, Δ``worker_stage_<name>_s``
over the window.  The host's waits on the device semantic engine's steps
(``semexec_wait`` spans, children of ``semantics``) are not in it.  None
where the program records no stage spans."""
from bench import window

LAYER = "core.accelerators prepare (host)"
STAGES = ("prepare", "prepare_graph", "semantics")


def read(obs):
    if "worker_stage_chunk_n" not in obs.stats1["counters"]:
        return None
    s = sum(window.counter_delta(obs.stats0, obs.stats1, f"worker_stage_{n}_s")
            for n in STAGES)
    return 100.0 * s / (obs.t1 - obs.t0)
