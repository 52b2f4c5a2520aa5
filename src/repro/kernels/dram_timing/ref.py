"""Pure-jnp oracles for the dram_timing Pallas kernel: the lax.scan engine
from repro.core.engine (the simulation environment's ground truth), in
single-trace and batched (vmapped) form.  ``page_open=False`` selects the
closed-page variant, matching the kernel's static flag.  The batched
oracle vmaps the per-trace scan, not ``_scan_engine_batch``, which is the
kernel itself where it is lowered for a TPU."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.engine import _scan_engine


def dram_timing_ref(bank, row, *, nbanks, tCL, tRCD, tRP, tRC, tBL, lookahead,
                    page_open=True):
    """Returns int32[4]: (total_cycles, hits, misses, conflicts)."""
    cycles, hits, misses, conflicts = _scan_engine(
        jnp.asarray(bank), jnp.asarray(row), nbanks, tCL, tRCD, tRP, tRC, tBL,
        lookahead, page_open,
    )
    return jnp.stack([cycles, hits, misses, conflicts]).astype(jnp.int32)


def dram_timing_ref_batch(bank, row, *, nbanks, tCL, tRCD, tRP, tRC, tBL,
                          lookahead, page_open=True):
    """Batched oracle on [B, L] request arrays: int32[B, 4] per-trace
    (total_cycles, hits, misses, conflicts), matching the batched kernel's
    output layout."""
    scan = partial(_scan_engine, nbanks=nbanks, tCL=tCL, tRCD=tRCD, tRP=tRP,
                   tRC=tRC, tBL=tBL, lookahead=lookahead, page_open=page_open)
    cycles, hits, misses, conflicts = jax.vmap(scan)(jnp.asarray(bank),
                                                     jnp.asarray(row))
    return jnp.stack([cycles, hits, misses, conflicts], axis=1).astype(jnp.int32)
