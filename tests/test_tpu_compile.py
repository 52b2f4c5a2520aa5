"""Compile the main path's device programs for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, which ships with jaxlib,
compiles for a topology that is described and not attached, and refuses
what the chip would refuse (unsupported gathers in Pallas kernels, tiling
violations, programs that do not fit).  Shapes are real: the batched
DRAM-timing engine, which lowers to its Pallas kernel for the chip, at its
``MAX_BATCH_ELEMS`` shapes, and every semexec
device step with the argument shapes of the ``lj`` graph's layouts, taken
from the accelerators' own layout builders.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shapes(tree, sharding):
    """Arrays -> ShapeDtypeStructs on the described chip; other leaves
    (static Python values) pass through."""
    def leaf(x):
        if isinstance(x, (jax.Array, np.ndarray, np.generic)):
            return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                        sharding=sharding)
        return x
    return jax.tree.map(leaf, tree)


@pytest.mark.parametrize("page_policy", ["open", "closed"])
def test_scan_engine_batch_compiles(one_chip, page_policy):
    from repro.core.dram import dram_config
    from repro.core.engine import MAX_BATCH_ELEMS, _scan_engine_batch

    cfg = dram_config("default", page_policy=page_policy)
    t = cfg.timing_cycles()
    B, L = 64, MAX_BATCH_ELEMS // 64
    req = jax.ShapeDtypeStruct((B, L), jnp.int32, sharding=one_chip)
    compiled = _scan_engine_batch.lower(
        req, req, cfg.nbanks, t["tCL"], t["tRCD"], t["tRP"], t["tRC"],
        t["tBL"], lookahead=16 * t["tBL"], page_open=cfg.page_open,
    ).compile()
    assert compiled.memory_analysis() is not None
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel


# (DRAM preset, page policy, HBM pseudo-channels): DDR4's 16 banks both
# ways, and the 8-bank pseudo-channel view the HBM rows are timed on
MEMORIES = [("default", "open", False), ("default", "closed", False),
            ("hbm", "closed", True)]


@pytest.mark.parametrize("dram,page_policy,pseudo_channels", MEMORIES)
def test_scan_kernel_compiles_at_largest_warm_shape(one_chip, dram,
                                                    page_policy,
                                                    pseudo_channels):
    """The largest shape the benchmark's warm-up compiles (L = 262,144 at
    ``MAX_BATCH_ELEMS // L`` lanes) lowers to the kernel and fits."""
    from repro.core.dram import dram_config
    from repro.core.engine import MAX_BATCH_ELEMS, _scan_engine_batch

    cfg = dram_config(dram, page_policy=page_policy,
                      pseudo_channels=pseudo_channels).pseudo_channel_view()
    t = cfg.timing_cycles()
    L = 262144
    req = jax.ShapeDtypeStruct((MAX_BATCH_ELEMS // L, L), jnp.int32,
                               sharding=one_chip)
    compiled = _scan_engine_batch.lower(
        req, req, cfg.nbanks, t["tCL"], t["tRCD"], t["tRP"], t["tRC"],
        t["tBL"], lookahead=16 * t["tBL"], page_open=cfg.page_open,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


class _Captured(Exception):
    pass


@pytest.fixture(scope="module")
def lj_graph():
    from repro.graph.generators import PAPER_GRAPHS

    return PAPER_GRAPHS["lj"].build()


# (device step, accelerator, problem that reaches it first)
STEPS = [
    ("_hitgraph_min_step", "hitgraph", "bfs"),
    ("_jacobi_min_step", "thundergp", "bfs"),
    ("_acc_step", "hitgraph", "pr"),
    ("_fg_min_step", "foregraph", "bfs"),
    ("_gs_min_step", "accugraph", "bfs"),
    ("_gs_acc_step", "accugraph", "pr"),
]


@pytest.mark.parametrize("step,accel,problem", STEPS)
def test_semexec_step_compiles(one_chip, lj_graph, monkeypatch, step, accel,
                               problem):
    """Build the real lj layout through the accelerator, capture the
    arguments of the step's first call, and compile that step for the
    chip from their shapes."""
    from repro.configs.graphsim import default_config
    from repro.core import semexec
    from repro.core.accelerators import ACCELERATORS
    from repro.graph.generators import PAPER_GRAPHS
    from repro.graph.problems import PROBLEMS

    jitted = getattr(semexec, step)
    seen = {}

    def capture(*args, **kwargs):
        seen.update(args=args, kwargs=kwargs)
        raise _Captured

    monkeypatch.setattr(semexec, step, capture)
    cfg = dataclasses.replace(default_config(accel), semexec="device")
    with pytest.raises(_Captured):
        ACCELERATORS[accel](cfg).prepare(lj_graph, PROBLEMS[problem],
                                         root=PAPER_GRAPHS["lj"].root)
    args = _shapes(seen["args"], one_chip)
    compiled = jitted.lower(*args, **seen["kwargs"]).compile()
    assert "tpu_custom_call" not in compiled.as_text()  # no Pallas kernel
