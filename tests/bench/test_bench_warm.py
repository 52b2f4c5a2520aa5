"""Set-up compiles every scan shape the window can dispatch, so the window
itself compiles nothing."""
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import seat  # noqa: E402


def test_warmed_shapes_compile_nothing_when_dispatched():
    import jax

    from repro.core.dram import dram_config
    from repro.core.engine import simulate_batch
    from repro.core.trace import Trace

    mems = [["default", "open", False], ["hbm", "closed", True]]
    out = seat.warm_scan(mems, 512, 2)
    assert out["programs"] == 2 * 2 * 2  # two memories, L 256/512, B 1/2
    compiles = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    rng = np.random.default_rng(0)
    for dram, policy, pc in mems:
        cfg = dram_config(dram, page_policy=policy,
                          pseudo_channels=pc).pseudo_channel_view()
        for sizes in ([300], [100, 500], [200, 40]):
            traces = [Trace(rng.integers(0, 1 << 20, n).astype(np.int64),
                            np.zeros(n, bool)) for n in sizes]
            simulate_batch(traces, cfg)
    assert compiles == []
