"""Device-resident semantic execution (repro.core.semexec).

The device engine's contract against the numpy oracle:

- request streams byte-identical (trace_stream_hash), iteration counts equal,
- min-problem values bit-identical (f32 min is exact and order-independent),
- acc-problem values allclose (segment_sum associates differently than
  np.add.at),
- a requested "device" engine on an unsupported accelerator/problem pair
  falls back to numpy with a one-time warning and the layout records the
  engine that actually ran.

The contract holds under every layout axis: vertex reordering, the
PE/channel count and the interval scale.
"""
from __future__ import annotations

import warnings
from functools import partial

import numpy as np
import pytest

from repro.configs.graphsim import default_config
from repro.core import semexec
from repro.core.accelerators import ACCELERATORS
from repro.core.dram import dram_config
from repro.core.engine import TraceBatch
from repro.core.trace import emit_bank_row_device, trace_stream_hash
from repro.graph.generators import GraphSpec
from repro.graph.problems import PROBLEMS

COMBOS = [(a, p) for a, probs in sorted(semexec.SUPPORTED.items())
          for p in sorted(probs)]


@pytest.fixture(scope="module")
def tiny_graph():
    return GraphSpec("tiny", "uniform", 256, 1024, True, 1, 0).build()


def _prepare(accel: str, g, problem_name: str, engine: str, **layout):
    """Prepare at a small layout; ``layout`` overrides AccelConfig fields
    (reorder, n_pes, interval_scale) after the defaults below."""
    cfg = default_config(accel)
    import dataclasses
    cfg = dataclasses.replace(cfg, interval_size=64, n_pes=2, semexec=engine)
    cfg = dataclasses.replace(cfg, **layout)
    return ACCELERATORS[accel](cfg).prepare(g, PROBLEMS[problem_name],
                                            root=g.degrees_out.argmax())


@pytest.fixture(scope="module")
def kron_graph():
    """Graph500's undirected Kronecker graph, with its own float32 weights
    in [0, 1) (the least over parallel edges)."""
    return GraphSpec("kron", "rmat", 256, 4096, False, 16, 0).build()


# the partitioning axes architects sweep, one at a time off the default
LAYOUTS = [dict(reorder="degree"), dict(reorder="random"),
           dict(reorder="bfs"), dict(n_pes=1), dict(n_pes=4),
           dict(interval_scale=2)]

# every pair on the uniform graph (integer weights where weighted) and on
# the weighted Kronecker graph, then every pair on the Kronecker graph
# under each layout; the default-layout cases keep their ids
GRAPH_COMBOS = ([pytest.param(a, p, "uniform", {}, id=f"{a}-{p}")
                 for a, p in COMBOS]
                + [pytest.param(a, p, "kronecker", {}, id=f"{a}-{p}-kronecker")
                   for a, p in COMBOS]
                + [pytest.param(a, p, "kronecker", lay,
                                id=f"{a}-{p}-kronecker-{k}{v}")
                   for lay in LAYOUTS for (k, v) in lay.items()
                   for a, p in COMBOS])


@pytest.mark.parametrize("accel,prob,graph,layout", GRAPH_COMBOS)
def test_device_matches_numpy(accel, prob, graph, layout, tiny_graph,
                              kron_graph):
    if graph == "kronecker":
        g = kron_graph
    else:
        g = (tiny_graph.with_weights() if PROBLEMS[prob].needs_weights
             else tiny_graph)
    host = _prepare(accel, g, prob, "numpy", **layout)
    dev = _prepare(accel, g, prob, "device", **layout)
    assert host.layout["engine"] == "numpy"
    assert dev.layout["engine"] == "device"
    assert host.iterations == dev.iterations
    assert trace_stream_hash(host.traces()) == trace_stream_hash(dev.traces())
    if PROBLEMS[prob].kind == "min":
        np.testing.assert_array_equal(host.values, dev.values)
    else:
        np.testing.assert_allclose(host.values, dev.values,
                                   rtol=1e-5, atol=1e-6)


def test_unsupported_pair_falls_back_with_warning():
    # accugraph has no weighted problems at all, so sssp can never gain a
    # device path; the resolver must warn once and fall back
    semexec._FALLBACK_WARNED.clear()
    with pytest.warns(UserWarning, match="falling back"):
        assert semexec.resolve_engine("accugraph", "sssp", "device") == "numpy"
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second request: silent
        assert semexec.resolve_engine("accugraph", "sssp", "device") == "numpy"


def test_supported_pair_resolves_device():
    for accel, prob in COMBOS:
        assert semexec.resolve_engine(accel, prob, "device") == "device"
        assert semexec.resolve_engine(accel, prob, "numpy") == "numpy"


def test_bad_engine_rejected():
    with pytest.raises(ValueError):
        semexec.validate_engine("cuda")
    with pytest.raises(ValueError):
        import dataclasses
        dataclasses.replace(default_config("hitgraph"), semexec="cuda")


def test_semexec_excluded_from_semantic_key():
    """The requested engine must not split the semantics cache: device and
    numpy produce the same traces, and a fallen-back "device" request must
    share the numpy entry."""
    import dataclasses
    cfg_n = default_config("hitgraph")
    cfg_d = dataclasses.replace(cfg_n, semexec="device")
    assert cfg_n.semantic_key() == cfg_d.semantic_key()


@pytest.mark.parametrize("mapping", ["row", "bank", "bank_xor"])
def test_emit_bank_row_device_matches_trace_batch(mapping, tiny_graph):
    """The fused device decode must agree bit-for-bit with the host
    TraceBatch packing for every address-mapping scheme."""
    from repro.core.dram import AddressMapping

    pend = _prepare("hitgraph", tiny_graph, "bfs", "numpy")
    traces = pend.traces()
    cfg = dram_config("default", mapping=AddressMapping(mapping))
    ref = TraceBatch.from_traces(traces, cfg, pad_batch=False)
    bank, row, lengths = emit_bank_row_device(traces, cfg)
    assert bank.shape == ref.bank.shape and row.shape == ref.row.shape
    np.testing.assert_array_equal(np.asarray(bank), ref.bank)
    np.testing.assert_array_equal(np.asarray(row), ref.row)
    np.testing.assert_array_equal(lengths, ref.lengths)


STEP_PROGRAMS = ("_hitgraph_min_step", "_jacobi_min_step", "_acc_step",
                 "_gs_min_step", "_gs_acc_step", "_fg_min_step")


def _gathers(jaxpr) -> tuple[int, int]:
    """Index elements the gathers of ``jaxpr`` (and of the jaxprs it
    calls) read, and its scatters."""
    gathered = scatters = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather":
            gathered += int(np.prod(eqn.invars[1].aval.shape[:-1]))
        scatters += name.startswith("scatter")
        for sub in eqn.params.values():
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                g, s = _gathers(sub)
                gathered, scatters = gathered + g, scatters + s
    return gathered, scatters


@pytest.mark.parametrize("accel", sorted(semexec.SUPPORTED))
def test_device_steps_reduce_through_plans(accel, monkeypatch):
    """Every backend runs one device program: each step reduces through
    its table-order reduce plans (no Pallas kernel, no scatter), gathers
    no more index elements than its layout counts in
    ``semexec_gathered``, and the simulated result equals the numpy
    engine's."""
    import jax

    g = GraphSpec("plans", "uniform", 300, 1500, True, 3, 0).build()
    kinds, calls = [], []
    real = semexec.apply_reduce_plan

    def counting(plan, cand, kind):
        kinds.append(kind)
        return real(plan, cand, kind)

    def recording(step):
        def call(*args, **kwargs):
            calls.append((step, args, kwargs))
            return step(*args, **kwargs)
        return call

    monkeypatch.setattr(semexec, "apply_reduce_plan", counting)
    for name in STEP_PROGRAMS:
        monkeypatch.setattr(semexec, name, recording(getattr(semexec, name)))
    for prob in ("bfs", "pr"):
        host = _prepare(accel, g, prob, "numpy")
        kinds.clear()
        calls.clear()
        jax.clear_caches()  # retrace the steps, so their bodies run here
        before = semexec.step_stats()["semexec_gathered"]
        dev = _prepare(accel, g, prob, "device")
        assert dev.layout["engine"] == "device"
        assert kinds and set(kinds) <= {"min", "sum", "max"}
        assert ("min" in kinds) == (PROBLEMS[prob].kind == "min")
        traced = [_gathers(jax.make_jaxpr(partial(step, **kw))(*args).jaxpr)
                  for step, args, kw in calls]
        assert calls and all(s == 0 for _, s in traced)
        assert (sum(n for n, _ in traced)
                == semexec.step_stats()["semexec_gathered"] - before)
        rep_h, rep_d = host.finalize(), dev.finalize()
        assert rep_d.timing == rep_h.timing
        assert rep_d.iterations == rep_h.iterations
        assert rep_d.runtime_s == rep_h.runtime_s


@pytest.mark.parametrize("accel", ["hitgraph", "thundergp"])
def test_step_counter_counts_each_device_step(accel, kron_graph):
    """``semexec_steps`` counts the jitted steps dispatched: one an
    iteration for HitGraph's and ThunderGP's SSSP, none on the numpy
    engine or when the semantics cache serves the execution."""
    from repro.core.hostcache import SEMANTICS

    SEMANTICS.clear()
    g = kron_graph
    before = semexec.step_stats()["semexec_steps"]
    host = _prepare(accel, g, "sssp", "numpy")
    assert semexec.step_stats()["semexec_steps"] == before
    dev = _prepare(accel, g, "sssp", "device")
    assert semexec.step_stats()["semexec_steps"] == before + dev.iterations
    assert dev.iterations == host.iterations > 1
    _prepare(accel, g, "sssp", "device")  # a semantics-cache hit
    assert semexec.step_stats()["semexec_steps"] == before + dev.iterations


@pytest.mark.parametrize("accel", sorted(semexec.SUPPORTED))
def test_gathered_counter_advances_by_layout_count(accel, kron_graph):
    """``semexec_gathered`` grows at each dispatched step by the layout's
    static count, and not at all on the numpy engine or when the semantics
    cache serves the execution.  With one interval every model's step
    gathers the same: the source values of the destination plan's slots
    and the plan's gather over the vertices."""
    from repro.core.hostcache import SEMANTICS

    SEMANTICS.clear()
    g = kron_graph
    _, slots = semexec.build_reduce_plan(g.dst, g.n)
    per_step = sum(s.size for s in slots) + g.n

    def counters():
        st = semexec.step_stats()
        return st["semexec_steps"], st["semexec_gathered"]

    steps0, gathered0 = counters()
    _prepare(accel, g, "bfs", "numpy", interval_size=4096)
    assert counters() == (steps0, gathered0)
    _prepare(accel, g, "bfs", "device", interval_size=4096)
    steps1, gathered1 = counters()
    assert steps1 > steps0
    assert gathered1 - gathered0 == (steps1 - steps0) * per_step
    _prepare(accel, g, "bfs", "device", interval_size=4096)  # a cache hit
    assert counters() == (steps1, gathered1)
