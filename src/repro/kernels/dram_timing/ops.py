"""Public op: DRAM timing via the Pallas kernel (TPU) or scan oracle (CPU).

``simulate_trace`` times one trace; ``simulate_trace_batch`` times many in
ONE device dispatch (one trace a lane of the kernel), matching the batched
engine path in ``repro.core.engine.simulate_batch``.
"""
from __future__ import annotations

import numpy as np

from repro.core.dram import DRAMConfig
from repro.kernels._platform import resolve_pallas
from repro.core.engine import TraceBatch, decode
from repro.core.trace import Trace
from repro.kernels.dram_timing.dram_timing import (
    dram_timing_pallas,
    dram_timing_pallas_batch,
)
from repro.kernels.dram_timing.ref import dram_timing_ref, dram_timing_ref_batch


def _timing_kwargs(cfg: DRAMConfig) -> dict:
    t = cfg.timing_cycles()
    return dict(nbanks=cfg.nbanks, tCL=t["tCL"], tRCD=t["tRCD"], tRP=t["tRP"],
                tRC=t["tRC"], tBL=t["tBL"], lookahead=16 * t["tBL"],
                page_open=cfg.page_open)


def _result(out: np.ndarray) -> dict:
    return dict(cycles=int(out[0]), hits=int(out[1]), misses=int(out[2]),
                conflicts=int(out[3]))


def simulate_trace(
    trace: Trace,
    cfg: DRAMConfig,
    *,
    use_pallas: bool | None = None,
    block: int = 512,
    interpret: bool | None = None,
) -> dict:
    """Time a single-channel trace; returns cycles + row-buffer stats.

    ``use_pallas=None`` auto-selects via ``kernels._platform``: the compiled
    Pallas kernel on TPU backends, interpret-mode Pallas elsewhere; pass
    ``use_pallas=False`` for the scan oracle."""
    if trace.n == 0:
        return dict(cycles=0, hits=0, misses=0, conflicts=0)
    use_pallas, interpret = resolve_pallas(use_pallas, interpret)
    bank, row = decode(trace.lines, cfg)
    kw = _timing_kwargs(cfg)
    if use_pallas:
        pad = (-len(bank)) % block
        if pad:
            bank = np.concatenate([bank, np.full(pad, -1, dtype=bank.dtype)])
            row = np.concatenate([row, np.zeros(pad, dtype=row.dtype)])
        out = dram_timing_pallas(bank, row, block=block, interpret=interpret,
                                 **kw)
    else:
        out = dram_timing_ref(bank, row, **kw)
    return _result(np.asarray(out))


def simulate_trace_batch(
    traces: list[Trace],
    cfg: DRAMConfig,
    *,
    use_pallas: bool | None = None,
    block: int = 512,
    interpret: bool | None = None,
) -> list[dict]:
    """Time many single-channel traces with ONE kernel dispatch.

    Traces are packed into a [B, L] request batch padded with bank == -1
    (L = longest trace rounded up to a multiple of ``block``); each batch
    row runs the same bank state machine from a cold device.  Returns one
    stats dict per trace, in order, identical to ``simulate_trace``."""
    if not traces:
        return []
    use_pallas, interpret = resolve_pallas(use_pallas, interpret)
    assert block & (block - 1) == 0, "block must be a power of two"
    # min_len=block makes the pow2 bucket a block multiple, as the grid needs
    batch = TraceBatch.from_traces(traces, cfg, min_len=block, pad_batch=False)
    bank, row = batch.bank, batch.row
    kw = _timing_kwargs(cfg)
    if use_pallas:
        out = dram_timing_pallas_batch(bank, row, block=block,
                                       interpret=interpret, **kw)
    else:
        out = dram_timing_ref_batch(bank, row, **kw)
    out = np.asarray(out)
    # all-padding rows (empty traces) report tCL warm-up cycles; mask to 0
    return [
        dict(cycles=0, hits=0, misses=0, conflicts=0) if t.n == 0
        else _result(out[i])
        for i, t in enumerate(traces)
    ]
