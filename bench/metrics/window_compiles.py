"""XLA programs the seat compiled or loaded from the persistent compile
cache during the window (``worker_device_compiles``); set-up should leave
none."""
from bench import window

LAYER = "serve.worker seat (XLA)"


def read(obs):
    return window.counter_delta(obs.stats0, obs.stats1,
                                "worker_device_compiles")
