"""Pallas TPU kernel: DRAM bank state-machine timing engine, lane-major.

TPU adaptation of Ramulator's sequential bank state machines (see DESIGN.md
§Hardware adaptation).  Every trace of a ``[B, L]`` batch is one lane of the
TPU's 128-wide vector axis, so one sequential step advances up to 128
traces at once:

- the request stream is transposed on the device to ``[L, lanes]`` and
  streamed from HBM in ``[block, 128]`` VMEM tiles along an ``"arbitrary"``
  (sequential) grid axis; the serial chain never touches HBM;
- the per-bank state (open row, row-ready time, last data slot, last
  activate) is a ``[banks, 128]`` int32 tile, banks on sublanes (padded to a
  multiple of 8), carried in registers across an in-kernel ``fori_loop`` over
  the block's requests and kept in VMEM scratch between grid steps;
- the per-lane counters (bus free, hits, misses, conflicts) are ``[1, 128]``
  rows;
- gathers and scatters of the bank state become one-hot selects on
  ``iota(banks) == bank``: a read is a masked sublane sum, a write a masked
  ``where``.  A padding request (``bank == -1``) matches no bank, so its
  reads give 0 and every write and counter stays gated, as in the scan.

Batches wider than 128 traces take one lane group per step of a leading
``"parallel"`` grid axis.  The arithmetic is the int32 ``max``/``where``
chain of ``repro.core.engine._scan_engine_impl``, so every report equals the
scan's; ``ref.py`` re-exports the scan, single and vmapped, as the oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # traces per lane group: the vector unit's lane width
SUBLANES = 8  # int32 tile height; bank state and request blocks align to it


def _kernel(bank_ref, row_ref, out_ref, state_ref, count_ref, *, banks,
            tCL, tRCD, tRP, tRC, tBL, lookahead, page_open, block, n_blocks):
    """One grid step: advance one lane group by ``block`` requests.

    state_ref: (4, banks, 128) int32 VMEM scratch, banks on sublanes:
       0=open_row, 1=row_ready, 2=last_data, 3=last_act
    count_ref: (8, 128) int32 VMEM scratch, one row per lane counter:
       0=bus_free, 1=hits, 2=misses, 3=conflicts
    Scratch persists across the sequential grid axis; its first step
    resets it, so every lane starts from a cold, precharged device.
    """
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        state_ref[0] = jnp.full((banks, LANES), -1, jnp.int32)
        state_ref[1] = jnp.zeros((banks, LANES), jnp.int32)
        state_ref[2] = jnp.zeros((banks, LANES), jnp.int32)
        state_ref[3] = jnp.full((banks, LANES), -(tRC + 1), jnp.int32)
        count_ref[...] = jnp.zeros((SUBLANES, LANES), jnp.int32)

    bank_ids = jax.lax.broadcasted_iota(jnp.int32, (banks, LANES), 0)

    def body(i, carry):
        open_row, row_ready, last_data, last_act, bus_free, hits, misses, confs = carry
        b = bank_ref[pl.ds(i, 1), :]
        r = row_ref[pl.ds(i, 1), :]
        onehot = bank_ids == b  # no bank matches a padding request
        valid = b >= 0

        def read(state):
            return jnp.sum(jnp.where(onehot, state, 0), axis=0, keepdims=True)

        cur = read(open_row)
        if page_open:
            is_hit = (cur == r) & valid
            is_miss = (cur == -1) & valid
            is_conf = valid & ~is_hit & ~is_miss
        else:
            # closed-page policy: every access auto-precharges, so each
            # valid request activates (a miss) and conflicts cannot occur
            is_hit = jnp.zeros_like(valid)
            is_miss = valid
            is_conf = jnp.zeros_like(valid)

        ready_b, data_b, act_b = read(row_ready), read(last_data), read(last_act)
        horizon = jnp.maximum(bus_free - lookahead, 0)
        t_pre = jnp.maximum(data_b, horizon)
        t_act_conf = jnp.maximum(t_pre + tRP, act_b + tRC)
        t_act_miss = jnp.maximum(jnp.maximum(act_b + tRC, data_b), horizon)
        t_act = jnp.where(is_conf, t_act_conf, t_act_miss)
        new_row_ready = jnp.where(is_hit, ready_b, t_act + tRCD)

        slot_start = jnp.maximum(new_row_ready, bus_free)
        slot_end = slot_start + tBL
        bus_free = jnp.where(valid, slot_end, bus_free)

        open_row = jnp.where(onehot, r, open_row)
        row_ready = jnp.where(onehot, new_row_ready, row_ready)
        last_data = jnp.where(onehot, slot_end, last_data)
        last_act = jnp.where(onehot & ~is_hit, t_act, last_act)
        return (open_row, row_ready, last_data, last_act, bus_free,
                hits + is_hit.astype(jnp.int32),
                misses + is_miss.astype(jnp.int32),
                confs + is_conf.astype(jnp.int32))

    carry = (state_ref[0], state_ref[1], state_ref[2], state_ref[3],
             count_ref[0:1, :], count_ref[1:2, :], count_ref[2:3, :],
             count_ref[3:4, :])
    # not unrolled: a deeper unroll saves a few ns a step on the chip but
    # multiplies the seconds every program takes to trace and lower
    carry = jax.lax.fori_loop(0, block, body, carry)
    state_ref[0], state_ref[1], state_ref[2], state_ref[3] = carry[:4]
    for k in range(4):
        count_ref[k:k + 1, :] = carry[4 + k]

    @pl.when(step == n_blocks - 1)
    def _finalize():
        out_ref[...] = count_ref[...]
        out_ref[0:1, :] = count_ref[0:1, :] + tCL  # total cycles


def dram_timing_lanes(bank, row, *, nbanks, tCL, tRCD, tRP, tRC, tBL,
                      lookahead, page_open, block, interpret=False):
    """Time ``[B, L]`` bank/row request arrays (padding ``bank == -1``; L a
    multiple of ``block``, ``block`` a multiple of 8) in one ``pallas_call``.
    Transposes and pads the lanes on the device.  Returns int32[4, B]: per
    trace (total_cycles, hits, misses, conflicts)."""
    if bank.ndim != 2 or bank.shape != row.shape:
        raise ValueError(f"expected [B, L] bank and row, got {bank.shape} "
                         f"and {row.shape}")
    b_sz, n = bank.shape
    if block % SUBLANES or n % block:
        raise ValueError(f"L={n} must be a multiple of block={block}, and "
                         f"block a multiple of {SUBLANES}")
    groups = -(-b_sz // LANES)
    lanes = ((0, 0), (0, groups * LANES - b_sz))
    bank_t = jnp.pad(bank.astype(jnp.int32).T, lanes, constant_values=-1)
    row_t = jnp.pad(row.astype(jnp.int32).T, lanes)
    out = _lanes_call(
        bank_t, row_t, banks=-(-nbanks // SUBLANES) * SUBLANES, tCL=tCL,
        tRCD=tRCD, tRP=tRP, tRC=tRC, tBL=tBL, lookahead=lookahead,
        page_open=page_open, block=block, interpret=interpret)
    return out[:4, :b_sz]


# A jit of its own: its trace is cached by the padded [L, lanes] shape, so
# the batch sizes that pad to one lane group trace the kernel once.
@functools.partial(
    jax.jit,
    static_argnames=("banks", "tCL", "tRCD", "tRP", "tRC", "tBL",
                     "lookahead", "page_open", "block", "interpret"),
)
def _lanes_call(bank_t, row_t, *, banks, tCL, tRCD, tRP, tRC, tBL,
                lookahead, page_open, block, interpret):
    n, width = bank_t.shape
    groups, n_blocks = width // LANES, n // block
    kernel = functools.partial(
        _kernel, banks=banks, tCL=tCL, tRCD=tRCD, tRP=tRP, tRC=tRC, tBL=tBL,
        lookahead=lookahead, page_open=page_open, block=block,
        n_blocks=n_blocks,
    )
    return pl.pallas_call(
        kernel,
        grid=(groups, n_blocks),
        in_specs=[
            pl.BlockSpec((block, LANES), lambda g, i: (i, g)),
            pl.BlockSpec((block, LANES), lambda g, i: (i, g)),
        ],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda g, i: (0, g)),
        out_shape=jax.ShapeDtypeStruct((SUBLANES, groups * LANES), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((4, banks, LANES), jnp.int32),
            pltpu.VMEM((SUBLANES, LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(bank_t, row_t)


@functools.partial(
    jax.jit,
    static_argnames=("nbanks", "tCL", "tRCD", "tRP", "tRC", "tBL",
                     "lookahead", "page_open", "block", "interpret"),
)
def dram_timing_pallas_batch(
    bank: jnp.ndarray,
    row: jnp.ndarray,
    *,
    nbanks: int,
    tCL: int,
    tRCD: int,
    tRP: int,
    tRC: int,
    tBL: int,
    lookahead: int,
    page_open: bool = True,
    block: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """Batched kernel entry: bank/row are [B, L] with L a multiple of
    `block` and padding requests marked bank == -1.  Returns int32[B, 4]:
    per-trace (total_cycles, hits, misses, conflicts) from ONE dispatch.

    ``page_open=False`` compiles the closed-page variant (every request
    activates; no conflicts) — a trace-time branch, zero cost in-kernel.
    """
    return dram_timing_lanes(
        bank, row, nbanks=nbanks, tCL=tCL, tRCD=tRCD, tRP=tRP, tRC=tRC,
        tBL=tBL, lookahead=lookahead, page_open=page_open, block=block,
        interpret=interpret,
    ).T


def dram_timing_pallas(
    bank: jnp.ndarray,
    row: jnp.ndarray,
    *,
    nbanks: int,
    tCL: int,
    tRCD: int,
    tRP: int,
    tRC: int,
    tBL: int,
    lookahead: int,
    page_open: bool = True,
    block: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """Single-trace entry (batch of one): returns int32[4]:
    (total_cycles, hits, misses, conflicts).

    bank/row must be pre-padded to a multiple of `block` with bank == -1.
    """
    out = dram_timing_pallas_batch(
        bank.reshape(1, -1), row.reshape(1, -1), nbanks=nbanks, tCL=tCL,
        tRCD=tRCD, tRP=tRP, tRC=tRC, tBL=tBL, lookahead=lookahead,
        page_open=page_open, block=block, interpret=interpret,
    )
    return out[0]
