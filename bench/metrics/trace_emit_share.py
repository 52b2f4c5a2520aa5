"""Share of the window the worker seat spent emitting DRAM request traces
into padded batches for timing: the self seconds of the program's
``emit`` stage span (``repro.core.engine.TraceBatch.from_traces``),
Δ``worker_stage_emit_s`` over the window.  None where the program records
no stage spans."""
from bench import window

LAYER = "core.engine TraceBatch emission"
STAGES = ("emit",)


def read(obs):
    if "worker_stage_chunk_n" not in obs.stats1["counters"]:
        return None
    s = sum(window.counter_delta(obs.stats0, obs.stats1, f"worker_stage_{n}_s")
            for n in STAGES)
    return 100.0 * s / (obs.t1 - obs.t0)
