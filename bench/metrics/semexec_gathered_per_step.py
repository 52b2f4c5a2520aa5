"""Index elements one jitted step of the device semantic engine
(``repro.core.semexec``) gathers: Δ``worker_device_semexec_gathered`` over
Δ``worker_device_semexec_steps`` in the window, each layout's static count
summed at every dispatch.  A program that does not count what its steps
gather, or a window with no step, gives nothing to read."""
from bench import window

LAYER = "core.semexec"
COUNTER = "worker_device_semexec_gathered"


def read(obs):
    if COUNTER not in obs.stats1["counters"]:
        return None
    steps = window.counter_delta(obs.stats0, obs.stats1,
                                 "worker_device_semexec_steps")
    if not steps:
        return None
    return window.counter_delta(obs.stats0, obs.stats1, COUNTER) / steps
