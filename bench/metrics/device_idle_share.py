"""Share of the traced window in which no operation ran on the device: one
less the union of the device operations' intervals over the window."""
from bench import devtrace

LAYER = "device (TPU v5e)"


def read(obs):
    if obs.trace is None or not obs.trace["devices"]:
        return None
    t0, t1 = obs.trace["t0"] * 1e9, obs.trace["t_stop"] * 1e9
    return 100.0 * (1.0 - devtrace.busy_ns(obs.trace["devices"], t0, t1)
                    / (t1 - t0))
