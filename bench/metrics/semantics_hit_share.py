"""Share of the window's semantic-execution lookups that the seat's
``SEMANTICS`` host cache answered (rows of one root and accelerator on two
memory systems share one execution)."""
from bench import window

LAYER = "sweep.runner host caches"


def read(obs):
    hits = window.counter_delta(obs.stats0, obs.stats1,
                                "worker_hostcache_semantics_hits")
    misses = window.counter_delta(obs.stats0, obs.stats1,
                                  "worker_hostcache_semantics_misses")
    return 100.0 * hits / (hits + misses) if hits + misses else None
