"""Per-kernel validation: sweep shapes/dtypes in interpret mode and
assert_allclose against each kernel's pure-jnp ref.py oracle (deliverable c).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dram import dram_config
from repro.core.engine import decode
from repro.core.trace import Trace
from repro.graph.generators import rmat, uniform_random
from repro.kernels.attention.ops import flash_attention
from repro.kernels.attention.ref import attention_ref
from repro.kernels.dram_timing.ops import simulate_trace, simulate_trace_batch
from repro.kernels.dram_timing.ref import dram_timing_ref, dram_timing_ref_batch
from repro.kernels.edge_update.edge_update import sentinel_max
from repro.kernels.edge_update.ops import relax_step, scatter_min
from repro.kernels.edge_update.ref import edge_update_ref
from repro.kernels.spmv.ops import spmv, spmv_edges
from repro.kernels.spmv.ref import spmv_coo_ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,nq,nkv,hd",
    [
        (1, 128, 2, 2, 64),
        (2, 256, 4, 2, 64),   # GQA group 2
        (1, 256, 4, 1, 32),   # MQA, head_dim padding 32 -> 128
        (2, 384, 8, 8, 128),  # seq padding 384 -> 512 under 128-blocks
    ],
)
def test_flash_attention_matches_ref(b, s, nq, nkv, hd, dtype):
    rng = np.random.default_rng(hash((b, s, nq, nkv, hd)) % 2**31)
    q = jnp.asarray(rng.normal(size=(b, s, nq, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, nkv, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, nkv, hd)), dtype)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    # oracle on expanded heads
    group = nq // nkv
    ke = jnp.repeat(k, group, axis=2)
    ve = jnp.repeat(v, group, axis=2)

    def flat(t):
        return jnp.moveaxis(t, 2, 1).reshape(b * nq, s, hd)

    ref = attention_ref(flat(q), flat(ke), flat(ve), causal=True)
    ref = jnp.moveaxis(ref.reshape(b, nq, s, hd), 1, 2).reshape(b, s, nq * hd)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_attention_matches_model_sdpa():
    """The kernel must agree with the model's einsum attention math."""
    from repro.models.attention import _sdpa, causal_mask

    rng = np.random.default_rng(0)
    b, s, nq, nkv, hd = 2, 128, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, nkv, hd)), jnp.float32)
    model_out = _sdpa(q, k, v, causal_mask(s, s))
    kern_out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(kern_out), np.asarray(model_out), rtol=2e-5, atol=2e-5
    )


# ---------------------------------------------------------------------------
# dram timing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dram", ["default", "ddr3", "hbm", "hitgraph"])
@pytest.mark.parametrize("n,block", [(200, 64), (1024, 256), (3000, 512)])
def test_dram_timing_kernel_matches_scan(dram, n, block):
    cfg = dram_config(dram)
    rng = np.random.default_rng(n + block)
    # mix of sequential and random lines (both locality regimes)
    seq = np.arange(n // 2, dtype=np.int64)
    rand = rng.integers(0, 1 << 20, size=n - n // 2)
    lines = np.concatenate([seq, rand])
    tr = Trace(lines, np.zeros(n, dtype=bool))
    out_kernel = simulate_trace(tr, cfg, use_pallas=True, block=block, interpret=True)

    bank, row = decode(tr.lines, cfg)
    t = cfg.timing_cycles()
    ref = np.asarray(
        dram_timing_ref(bank, row, nbanks=cfg.nbanks, tCL=t["tCL"],
                        tRCD=t["tRCD"], tRP=t["tRP"], tRC=t["tRC"],
                        tBL=t["tBL"], lookahead=16 * t["tBL"])
    )
    assert out_kernel["cycles"] == ref[0]
    assert out_kernel["hits"] == ref[1]
    assert out_kernel["misses"] == ref[2]
    assert out_kernel["conflicts"] == ref[3]


@pytest.mark.parametrize("dram", ["default", "hbm"])
def test_dram_timing_kernel_batch_matches_single(dram):
    """The batched kernel (one lane per trace, one dispatch for all)
    must agree with per-trace kernel calls and the batched scan oracle."""
    cfg = dram_config(dram)
    rng = np.random.default_rng(42)
    traces = [
        Trace(np.arange(300, dtype=np.int64), np.zeros(300, dtype=bool)),
        Trace(rng.integers(0, 1 << 20, size=1000), np.zeros(1000, dtype=bool)),
        Trace.empty(),
        Trace(rng.integers(0, 1 << 12, size=77), np.zeros(77, dtype=bool)),
    ]
    block = 256
    batch = simulate_trace_batch(traces, cfg, use_pallas=True, block=block,
                                 interpret=True)
    for tr, out in zip(traces, batch):
        single = simulate_trace(tr, cfg, use_pallas=True, block=block,
                                interpret=True)
        assert out == single

    # batched oracle agrees with the batched kernel layout-for-layout
    L = 1024
    bank = np.full((len(traces), L), -1, dtype=np.int32)
    row = np.zeros((len(traces), L), dtype=np.int32)
    for i, tr in enumerate(traces):
        if tr.n:
            bank[i, : tr.n], row[i, : tr.n] = decode(tr.lines, cfg)
    t = cfg.timing_cycles()
    ref = np.asarray(dram_timing_ref_batch(
        bank, row, nbanks=cfg.nbanks, tCL=t["tCL"], tRCD=t["tRCD"],
        tRP=t["tRP"], tRC=t["tRC"], tBL=t["tBL"], lookahead=16 * t["tBL"]))
    for i, tr in enumerate(traces):
        if tr.n:
            assert batch[i]["cycles"] == ref[i, 0]
            assert batch[i]["hits"] == ref[i, 1]
            assert batch[i]["misses"] == ref[i, 2]
            assert batch[i]["conflicts"] == ref[i, 3]


def _lane_batch(cfg, B, L, seed):
    """[B, L] bank/row arrays of mixed locality with ragged lanes: for B >=
    3 lane 1 is empty (all padding) and lane 2 holds one request."""
    rng = np.random.default_rng(seed)
    lines = np.where(rng.random((B, L)) < 0.6,
                     np.arange(L)[None, :] + rng.integers(0, 1 << 20, (B, 1)),
                     rng.integers(0, 1 << 22, (B, L)))
    bank, row = decode(lines.ravel(), cfg)
    bank = bank.reshape(B, L).astype(np.int32)
    row = row.reshape(B, L).astype(np.int32)
    lens = rng.integers(0, L + 1, B)
    lens[0] = L
    if B >= 3:
        lens[1], lens[2] = 0, 1
    bank[np.arange(L)[None, :] >= lens[:, None]] = -1
    return bank, row


def _statics(cfg):
    t = cfg.timing_cycles()
    return dict(nbanks=cfg.nbanks, tCL=t["tCL"], tRCD=t["tRCD"], tRP=t["tRP"],
                tRC=t["tRC"], tBL=t["tBL"], lookahead=16 * t["tBL"],
                page_open=cfg.page_open)


@pytest.mark.parametrize("B", [1, 3, 16])
@pytest.mark.parametrize("policy", ["open", "closed"])
@pytest.mark.parametrize("dram", ["default", "ddr3", "hbm", "hitgraph"])
def test_dram_timing_lanes_matches_vmapped_scan(dram, policy, B):
    """The lane-major kernel (interpret mode) equals the vmapped scan field
    for field: every preset (HBM as its pseudo-channel view), both page
    policies, ragged, empty and one-request lanes."""
    from repro.kernels.dram_timing.dram_timing import dram_timing_lanes

    cfg = dram_config(dram, page_policy=policy).pseudo_channel_view()
    statics = _statics(cfg)
    bank, row = _lane_batch(cfg, B, 256, seed=B)
    got = np.asarray(dram_timing_lanes(bank, row, block=64, interpret=True,
                                       **statics))
    want = np.asarray(dram_timing_ref_batch(bank, row, **statics))
    np.testing.assert_array_equal(got, want.T)


def test_dram_timing_lanes_splits_past_128_lanes():
    """A batch wider than one lane group takes a grid step per group."""
    from repro.kernels.dram_timing.dram_timing import dram_timing_lanes

    cfg = dram_config("default")
    statics = _statics(cfg)
    bank, row = _lane_batch(cfg, 130, 64, seed=130)
    got = np.asarray(dram_timing_lanes(bank, row, block=32, interpret=True,
                                       **statics))
    want = np.asarray(dram_timing_ref_batch(bank, row, **statics))
    assert got.shape == (4, 130)
    np.testing.assert_array_equal(got, want.T)


@pytest.mark.parametrize("L,block", [(200, 64), (256, 100)])
def test_dram_timing_lanes_refuses_ragged_blocks(L, block):
    """L must be a multiple of the block, and the block of the 8-row tile."""
    from repro.kernels.dram_timing.dram_timing import dram_timing_lanes

    bank = np.full((2, L), -1, np.int32)
    with pytest.raises(ValueError, match="multiple of"):
        dram_timing_lanes(bank, bank, block=block, interpret=True,
                          **_statics(dram_config("default")))


@pytest.mark.parametrize("dram", ["default", "hbm"])
def test_simulate_batch_through_kernel_matches_sequential(dram, monkeypatch):
    """``simulate_batch`` with its dispatch routed through the kernel (as
    lowered for a TPU, here interpreted) reports what the per-trace scan
    reports, including a bucket L the kernel pads to its block."""
    from repro.core import engine

    def kernel(bank, row, nbanks, tCL, tRCD, tRP, tRC, tBL, lookahead,
               page_open):
        return engine._kernel_batch(
            bank, row, nbanks=nbanks, tCL=tCL, tRCD=tRCD, tRP=tRP, tRC=tRC,
            tBL=tBL, lookahead=lookahead, page_open=page_open, interpret=True)

    cfg = dram_config(dram).pseudo_channel_view()
    rng = np.random.default_rng(7)
    traces = [Trace(rng.integers(0, 1 << 20, size=n), np.zeros(n, dtype=bool))
              for n in (1, 250, 300, 700)] + [Trace.empty()]
    traces.append(Trace(np.arange(900, dtype=np.int64),
                        np.zeros(900, dtype=bool)))
    # buckets 512 and 1024 are padded to 768 and 1152 with no-op requests
    monkeypatch.setattr(engine, "KERNEL_BLOCK", 384)
    monkeypatch.setattr(engine, "_scan_engine_batch", kernel)
    batched = engine.simulate_batch(traces, cfg)
    monkeypatch.undo()
    assert batched == engine.simulate_sequential(traces, cfg)


def test_scan_engine_batch_lowers_scan_on_cpu():
    """Lowered for the CPU the batched engine is the scan: no kernel."""
    from repro.core.engine import _scan_engine_batch

    req = jax.ShapeDtypeStruct((4, 256), jnp.int32)
    text = _scan_engine_batch.lower(
        req, req, **_statics(dram_config("default"))).compile().as_text()
    assert "tpu_custom_call" not in text
    assert "while" in text


# ---------------------------------------------------------------------------
# spmv
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n,m", [(64, 256), (300, 1200), (1000, 3000)])
def test_spmv_kernel_matches_ref(n, m, seed):
    g = uniform_random(n, m, seed=seed).with_weights()
    rng = np.random.default_rng(seed)
    x = rng.normal(size=g.n).astype(np.float32)
    y_kernel = spmv(g, x, use_pallas=True, interpret=True, block_rows=64)
    w = g.weights
    y_ref = np.asarray(
        spmv_coo_ref(jnp.asarray(g.src), jnp.asarray(g.dst), jnp.asarray(w),
                     jnp.asarray(x), g.n)
    )
    np.testing.assert_allclose(y_kernel, y_ref, rtol=1e-5, atol=1e-5)


def test_spmv_rmat_graph():
    g = rmat(8, edge_factor=8, seed=3).with_weights()
    x = np.random.default_rng(3).normal(size=g.n).astype(np.float32)
    y_kernel = spmv(g, x, use_pallas=True, interpret=True, block_rows=64)
    y_ref = np.asarray(
        spmv_coo_ref(jnp.asarray(g.src), jnp.asarray(g.dst),
                     jnp.asarray(g.weights), jnp.asarray(x), g.n)
    )
    np.testing.assert_allclose(y_kernel, y_ref, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# edge update (min-propagation relaxation)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("problem", ["bfs", "wcc", "sssp"])
@pytest.mark.parametrize("block", [256, 1024])
def test_edge_update_kernel_matches_ref(problem, block):
    g = uniform_random(200, 800, seed=7)
    if problem == "sssp":
        g = g.with_weights()
    rng = np.random.default_rng(7)
    values = np.where(rng.random(g.n) < 0.3, rng.random(g.n) * 10, np.inf).astype(
        np.float32
    )
    out = relax_step(g, values, problem, use_pallas=True, block=block, interpret=True)
    if problem == "bfs":
        delta = np.ones(g.m, dtype=np.float32)
    elif problem == "wcc":
        delta = np.zeros(g.m, dtype=np.float32)
    else:
        delta = g.weights
    acc = np.asarray(
        edge_update_ref(jnp.asarray(g.src), jnp.asarray(g.dst),
                        jnp.asarray(delta), jnp.asarray(values), g.n)
    )
    ref = np.minimum(values, acc)
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)


def _scatter_min_oracle(src, dst, delta, values, n, mask=None):
    """Numpy oracle with the kernel's saturation contract: min is exact, so
    the comparison is bit-equality, not allclose."""
    top = np.asarray(sentinel_max(values.dtype))
    acc = np.full(n, top, dtype=values.dtype)
    keep = src >= 0
    if mask is not None:
        keep &= mask
    sv = values[np.maximum(src, 0)]
    keep &= sv != top  # saturated sources stay saturated (int overflow)
    np.minimum.at(acc, dst[keep], (sv + delta.astype(values.dtype))[keep])
    return acc


# 64-bit dtypes need jax_enable_x64 (off in this deployment — jnp would
# silently truncate the sentinel to 32 bits and the test would lie)
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_scatter_min_dtype_sentinel(dtype):
    """Integer dtypes must saturate unreached sources at the dtype max
    instead of overflowing on + delta; floats use +inf."""
    n, m = 50, 400
    rng = np.random.default_rng(11)
    src = rng.integers(0, n, size=m).astype(np.int32)
    dst = rng.integers(0, n, size=m).astype(np.int32)
    delta = rng.integers(1, 5, size=m)
    top = np.asarray(sentinel_max(dtype))
    values = np.where(rng.random(n) < 0.5,
                      rng.integers(0, 100, size=n), top).astype(dtype)
    out = np.asarray(scatter_min(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(delta, dtype=dtype),
        jnp.asarray(values), use_pallas=None, interpret=None))
    ref = _scatter_min_oracle(src, dst, delta.astype(dtype), values, n)
    np.testing.assert_array_equal(out, ref)
    assert not np.any(out < 0) if np.issubdtype(np.dtype(dtype), np.integer) \
        else True  # overflow would wrap negative


def test_scatter_min_padding_edges_are_noops():
    """src == -1 padding edges (the semexec block-padding convention) and
    masked-out edges contribute nothing, wherever their dst points."""
    n = 16
    values = np.arange(n, dtype=np.float32)
    src = np.array([0, -1, 3, -1], dtype=np.int32)
    dst = np.array([5, 0, 5, 7], dtype=np.int32)
    delta = np.ones(4, dtype=np.float32)
    out = np.asarray(scatter_min(jnp.asarray(src), jnp.asarray(dst),
                                 jnp.asarray(delta), jnp.asarray(values)))
    assert out[5] == 1.0  # min(0+1, 3+1)
    assert out[0] == np.inf and out[7] == np.inf  # padding did not land
    # an explicit mask drops a live edge the same way
    mask = np.array([False, True, True, True])
    out2 = np.asarray(scatter_min(jnp.asarray(src), jnp.asarray(dst),
                                  jnp.asarray(delta), jnp.asarray(values),
                                  mask=jnp.asarray(mask)))
    assert out2[5] == 4.0


def test_scatter_min_empty_frontier_and_isolated_vertices():
    """All edges masked (empty frontier) -> all-sentinel accumulator;
    vertices with no in-edges always hold the sentinel."""
    n, m = 12, 30
    rng = np.random.default_rng(5)
    src = rng.integers(0, n // 2, size=m).astype(np.int32)
    dst = rng.integers(0, n // 2, size=m).astype(np.int32)
    delta = rng.random(m).astype(np.float32)
    values = rng.random(n).astype(np.float32)
    empty = np.asarray(scatter_min(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(delta),
        jnp.asarray(values), mask=jnp.zeros(m, dtype=bool)))
    assert np.all(np.isinf(empty))
    out = np.asarray(scatter_min(jnp.asarray(src), jnp.asarray(dst),
                                 jnp.asarray(delta), jnp.asarray(values)))
    assert np.all(np.isinf(out[n // 2:]))  # isolated upper half
    ref = _scatter_min_oracle(src, dst, delta, values, n)
    np.testing.assert_array_equal(out, ref)


def test_scatter_min_zero_edges():
    """m == 0 (a partition with no edges) must not trip the Pallas grid."""
    values = np.array([1.0, np.inf], dtype=np.float32)
    out = np.asarray(scatter_min(
        jnp.zeros(0, dtype=jnp.int32), jnp.zeros(0, dtype=jnp.int32),
        jnp.zeros(0, dtype=jnp.float32), jnp.asarray(values)))
    assert np.all(np.isinf(out))


def test_spmv_edges_padding_and_isolated():
    """Zero-weight padding edges routed to vertex 0 (the semexec layout
    convention) leave the result untouched; rows with no edges stay 0."""
    n, m = 20, 60
    rng = np.random.default_rng(9)
    src = rng.integers(0, n, size=m).astype(np.int32)
    dst = rng.integers(0, n // 2, size=m).astype(np.int32)
    w = rng.random(m).astype(np.float32)
    x = rng.random(n).astype(np.float32)
    y = np.asarray(spmv_edges(jnp.asarray(src), jnp.asarray(dst),
                              jnp.asarray(w), jnp.asarray(x), n))
    pad = 17
    srcp = np.concatenate([src, np.zeros(pad, dtype=np.int32)])
    dstp = np.concatenate([dst, np.zeros(pad, dtype=np.int32)])
    wp = np.concatenate([w, np.zeros(pad, dtype=np.float32)])
    yp = np.asarray(spmv_edges(jnp.asarray(srcp), jnp.asarray(dstp),
                               jnp.asarray(wp), jnp.asarray(x), n))
    np.testing.assert_array_equal(y, yp)
    assert np.all(y[n // 2:] == 0.0)  # no in-edges -> empty sum


@pytest.mark.parametrize("use_pallas,interpret,want", [
    (None, None, (True, False)),
    (True, None, (True, False)),
    (False, None, (False, False)),
    (None, False, (True, False)),
    (None, True, (True, True)),  # only an explicit request interprets
])
def test_resolve_pallas_never_interprets_on_tpu(monkeypatch, use_pallas,
                                                interpret, want):
    from repro.kernels import _platform

    monkeypatch.setattr(_platform, "on_tpu", lambda: True)
    assert _platform.resolve_pallas(use_pallas, interpret) == want
