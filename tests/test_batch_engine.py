"""Batched DRAM timing engine: TraceBatch packing, batched == sequential
report identity across accelerators x memory technologies, dispatch
accounting, engine-selection policy, and the unified bw_utilization
denominator."""
import dataclasses

import numpy as np
import pytest

from repro.configs.graphsim import default_config
from repro.core.accelerators import ACCELERATORS
from repro.core.accelerators.base import PhasedTrace, simulate_phased
from repro.core.dram import dram_config
from repro.core.engine import (
    SCAN_CUTOFF,
    TimingReport,
    TraceBatch,
    dispatch_stats,
    reset_dispatch_stats,
    select_engine,
    simulate_batch,
    simulate_channel_fast,
    simulate_channel_scan,
    simulate_dram,
    simulate_many,
)
from repro.core.trace import Trace
from repro.graph.problems import PROBLEMS

INT_FIELDS = ("cycles", "hits", "misses", "conflicts", "bytes_total",
              "bytes_read", "bytes_written", "requests", "channels_used")
FLOAT_FIELDS = ("time_ns", "bw_utilization")


def assert_reports_identical(a: TimingReport, b: TimingReport, ctx=""):
    for f in INT_FIELDS:
        assert getattr(a, f) == getattr(b, f), f"{ctx}: {f}"
    for f in FLOAT_FIELDS:
        av, bv = getattr(a, f), getattr(b, f)
        assert av == pytest.approx(bv, rel=1e-9, abs=1e-9), f"{ctx}: {f}"


def _random_traces(seed, sizes, spread=1 << 18, write_frac=0.3):
    rng = np.random.default_rng(seed)
    return [
        Trace(rng.integers(0, spread, size=n), rng.random(n) < write_frac)
        for n in sizes
    ]


# ---- select_engine ---------------------------------------------------------


def test_select_engine_policy():
    assert select_engine(10) == "scan"
    assert select_engine(SCAN_CUTOFF) == "scan"
    assert select_engine(SCAN_CUTOFF + 1) == "fast"
    assert select_engine(10, "fast") == "fast"
    assert select_engine(10**9, "scan") == "scan"
    assert select_engine(10, "auto", scan_cutoff=5) == "fast"
    with pytest.raises(ValueError, match="unknown engine"):
        select_engine(10, "warp")


# ---- TraceBatch packing ----------------------------------------------------


def test_trace_batch_pow2_bucketing():
    cfg = dram_config("default")
    traces = _random_traces(0, [5, 300, 700])
    batch = TraceBatch.from_traces(traces, cfg)
    assert batch.bucket_len == 1024  # pow2 of longest (700), min 256
    assert batch.bank.shape == (4, 1024)  # batch axis padded 3 -> 4
    assert batch.size == 3
    assert batch.lengths.tolist() == [5, 300, 700]
    # padding slots are engine no-ops (bank == -1); pad rows entirely so
    for i, t in enumerate(traces):
        assert (batch.bank[i, t.n:] == -1).all()
    assert (batch.bank[3] == -1).all()


def test_trace_batch_handles_empty_traces():
    cfg = dram_config("default")
    traces = [Trace.empty(), _random_traces(1, [100])[0], Trace.empty()]
    batch = TraceBatch.from_traces(traces, cfg)
    assert batch.size == 3
    assert (batch.bank[0] == -1).all() and (batch.bank[2] == -1).all()
    reports = simulate_batch(traces, cfg)
    assert reports[0] == TimingReport.zero()
    assert reports[2] == TimingReport.zero()
    assert reports[1] == simulate_channel_scan(traces[1], cfg)


# ---- batched == sequential on synthetic traces -----------------------------


@pytest.mark.parametrize("dram", ["default", "ddr3", "hbm", "hitgraph"])
def test_simulate_batch_matches_per_trace_scan(dram):
    cfg = dram_config(dram)
    traces = _random_traces(7, [1, 37, 256, 300, 999, 0, 2048, 513])
    batched = simulate_batch(traces, cfg)
    for tr, rb in zip(traces, batched):
        assert_reports_identical(rb, simulate_channel_scan(tr, cfg)
                                 if tr.n else TimingReport.zero(), dram)


def test_simulate_batch_fast_engine_matches_per_trace():
    cfg = dram_config("default")
    traces = _random_traces(11, [400, 1200, 64, 999])
    batched = simulate_batch(traces, cfg, engine="fast")
    for tr, rb in zip(traces, batched):
        assert rb == simulate_channel_fast(tr, cfg)  # bit-identical


def test_simulate_batch_auto_mixes_engines():
    cfg = dram_config("default")
    traces = _random_traces(13, [100, 3000, 500])
    batched = simulate_batch(traces, cfg, scan_cutoff=1000)
    assert batched[0] == simulate_channel_scan(traces[0], cfg)
    assert batched[1] == simulate_channel_fast(traces[1], cfg)
    assert batched[2] == simulate_channel_scan(traces[2], cfg)


def test_simulate_many_groups_across_configs():
    ddr4, hbm = dram_config("default"), dram_config("hbm")
    traces = _random_traces(17, [150, 400, 700, 280])
    items = [(tr, ddr4 if i % 2 == 0 else hbm, "auto", SCAN_CUTOFF)
             for i, tr in enumerate(traces)]
    reset_dispatch_stats()
    reports = simulate_many(items)
    grouped = dispatch_stats()
    for (tr, cfg, _, _), r in zip(items, reports):
        assert_reports_identical(r, simulate_channel_scan(tr, cfg))
    # 2 timing configs x at most 2 length buckets >= dispatches, and far
    # fewer than one per trace once batches grow
    assert grouped["dispatches"] <= 4
    assert grouped["traces"] == len(traces)


def test_batched_dispatch_reduction():
    cfg = dram_config("default")
    traces = _random_traces(19, [300] * 16)  # one shared length bucket
    reset_dispatch_stats()
    seq = [simulate_channel_scan(t, cfg) for t in traces]
    n_seq = dispatch_stats()["dispatches"]
    reset_dispatch_stats()
    bat = simulate_batch(traces, cfg)
    n_bat = dispatch_stats()["dispatches"]
    assert seq == bat
    assert n_seq == 16
    assert n_bat == 1
    assert n_seq >= 5 * n_bat  # the acceptance-criterion floor


def test_dispatch_stats_count_scan_steps(monkeypatch):
    """``scan_steps`` is the sum of the padded length L of every device
    dispatch: the sequential steps the scan walks, whatever its lanes
    hold (also when the batch cap splits a bucket)."""
    from repro.core import engine

    cfg = dram_config("default")
    traces = _random_traces(23, [100, 200, 300, 700, 0, 2000, 150])
    reset_dispatch_stats()
    simulate_batch(traces, cfg)
    # buckets 256 (3 traces), 512, 1024, 2048: one dispatch each
    assert dispatch_stats()["dispatches"] == 4
    assert dispatch_stats()["scan_steps"] == 256 + 512 + 1024 + 2048
    monkeypatch.setattr(engine, "MAX_BATCH_ELEMS", 512)
    reset_dispatch_stats()
    simulate_batch(traces, cfg)
    # now 2 lanes a dispatch at L = 256 and one past it: 2 + 1 + 1 + 1
    assert dispatch_stats()["dispatches"] == 5
    assert dispatch_stats()["scan_steps"] == 2 * 256 + 512 + 1024 + 2048
    reset_dispatch_stats()
    simulate_channel_scan(traces[0], cfg)
    assert dispatch_stats()["scan_steps"] == 256
    reset_dispatch_stats()
    assert dispatch_stats()["scan_steps"] == 0


# ---- batched == sequential through the accelerator timing stack -----------


@pytest.fixture(scope="module", params=list(ACCELERATORS))
def accel_pending(request, small_rmat):
    """One semantic execution per accelerator (shared across DRAM params):
    the PhasedTrace is timing-independent."""
    name = request.param
    accel = ACCELERATORS[name](default_config(name))
    root = int(np.argmax(small_rmat.degrees_out))
    pending = accel.prepare(small_rmat, PROBLEMS["bfs"], root=root)
    return name, pending


@pytest.mark.parametrize("dram", ["default", "ddr3", "hbm"])
def test_phased_batched_identical_to_sequential(accel_pending, dram):
    """Acceptance criterion: the batched path produces identical
    TimingReports (ints exact, floats to 1e-9) to the sequential scan path
    for every accelerator x {ddr4, ddr3, hbm}."""
    name, pending = accel_pending
    cfg = dram_config(dram)
    batched = simulate_phased(pending.pt, cfg, pending.config, batched=True)
    sequential = simulate_phased(pending.pt, cfg, pending.config, batched=False)
    assert_reports_identical(batched, sequential, f"{name}/{dram}")
    assert batched.time_ns > 0


def test_finalize_with_external_reports_matches_run(small_rmat):
    """PendingRun.finalize(reports) — the sweep batch-mode path — equals
    the plain accelerator run."""
    accel = ACCELERATORS["accugraph"](default_config("accugraph"))
    root = int(np.argmax(small_rmat.degrees_out))
    rep_direct = accel.run(small_rmat, PROBLEMS["bfs"], root=root)
    pending = accel.prepare(small_rmat, PROBLEMS["bfs"], root=root)
    reports = simulate_batch(pending.traces(), pending.dram,
                             engine=pending.config.engine,
                             scan_cutoff=pending.config.scan_cutoff)
    rep_batch = pending.finalize(reports)
    assert rep_direct.timing == rep_batch.timing
    assert rep_direct.iterations == rep_batch.iterations


# ---- bw_utilization denominator regression (satellite) ---------------------


def test_bw_utilization_denominator_unified():
    """simulate_dram and simulate_phased must use the same denominator:
    actual channels used, not the device channel count or the trace-list
    length."""
    cfg = dram_config("thundergp")  # 4-channel device
    traces = _random_traces(23, [500, 400])  # only 2 channels carry traffic
    dram_rep = simulate_dram(traces, cfg)
    pt = PhasedTrace()
    pt.add_phase(list(traces))
    phased_rep = simulate_phased(pt, cfg, default_config("thundergp"))
    assert dram_rep.channels_used == 2
    assert phased_rep.channels_used == 2
    # one phase: same busy window, same traffic -> same utilization
    assert dram_rep.bw_utilization == pytest.approx(
        phased_rep.bw_utilization, rel=1e-9)
    # the old phased denominator (cfg.channels == 4) would halve it
    assert phased_rep.bw_utilization == pytest.approx(
        phased_rep.bytes_total / (phased_rep.time_ns * cfg.bw_per_channel * 2),
        rel=1e-9)


def test_simulate_dram_ignores_empty_channels_in_denominator():
    cfg = dram_config("thundergp")
    (tr,) = _random_traces(29, [600])
    with_empty = simulate_dram([tr, Trace.empty(), Trace.empty()], cfg)
    alone = simulate_dram([tr], cfg)
    assert with_empty.channels_used == 1
    assert with_empty.bw_utilization == pytest.approx(alone.bw_utilization,
                                                      rel=1e-9)


def test_simulate_dram_batched_flag_identical():
    cfg = dram_config("hitgraph")
    traces = _random_traces(31, [200, 800, 450, 120])
    assert_reports_identical(simulate_dram(traces, cfg, batched=True),
                             simulate_dram(traces, cfg, batched=False))
