"""Share of the window in which the worker seat was inside the program's
``run_chunk``: the union of the spans the benchmark's pool wrapper records
around it in the seat, over the window."""
from bench import window

LAYER = "serve.worker seat"


def read(obs):
    return 100.0 * window.span_share(obs.spans, obs.t0, obs.t1)
