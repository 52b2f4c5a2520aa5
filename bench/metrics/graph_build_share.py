"""Share of the window the worker seat spent building graphs: the self
seconds of the program's ``graph`` stage span (a miss of the runner's
graph memo, ``repro.sweep.runner._graph``), Δ``worker_stage_graph_s``
over the window.  None where the program records no stage spans."""
from bench import window

LAYER = "sweep.runner graph memo"
STAGES = ("graph",)


def read(obs):
    if "worker_stage_chunk_n" not in obs.stats1["counters"]:
        return None
    s = sum(window.counter_delta(obs.stats0, obs.stats1, f"worker_stage_{n}_s")
            for n in STAGES)
    return 100.0 * s / (obs.t1 - obs.t0)
