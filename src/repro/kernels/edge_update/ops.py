"""Public ops: min-propagation scatter over edges.

``scatter_min`` is the array-level primitive (jnp in/out, safe to call from
inside an outer ``jax.jit``); ``relax_step`` is the Graph-level convenience
wrapper kept for the workload benches.  Neither is on the simulator's path:
the semexec device engine reduces through reduce plans
(``repro.core.semexec``), because the TPU compiler refuses this kernel's
1-D gather ("Only 2D gather is supported").
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.graph.structure import Graph
from repro.kernels._platform import resolve_pallas
from repro.kernels.edge_update.edge_update import edge_update_pallas
from repro.kernels.edge_update.ref import edge_update_ref

# VMEM holds the full value + accumulator vectors in the Pallas kernel;
# past this vertex count fall back to the XLA segment-min reference.
PALLAS_MAX_VERTICES = 1 << 20


def scatter_min(
    src: jnp.ndarray,  # (m,) int32, -1 marks masked/padding edges
    dst: jnp.ndarray,  # (m,) int32, in [0, n) (use 0 for masked edges)
    delta: jnp.ndarray,  # (m,) values.dtype
    values: jnp.ndarray,  # (n,)
    *,
    mask: jnp.ndarray | None = None,  # (m,) bool, False drops the edge
    use_pallas: bool | None = None,
    block: int = 1024,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """acc[d] = min over edges of values[src] + delta; returns acc (n,).

    Empty segments hold the dtype's sentinel max (+inf for floats).  The
    Pallas kernel is taken when resolved on AND the static shapes fit its
    constraints (edge count a block multiple, value vector VMEM-sized);
    otherwise the XLA segment-min reference — same result either way.
    """
    use_pallas, interpret = resolve_pallas(use_pallas, interpret)
    n = values.shape[0]
    if mask is not None:
        src = jnp.where(mask, src, -1)
    if use_pallas and src.shape[0] % block == 0 and src.shape[0] > 0 \
            and n <= PALLAS_MAX_VERTICES:
        return edge_update_pallas(src, dst, delta, values,
                                  block=block, interpret=interpret)
    return edge_update_ref(src, dst, delta, values, n)


def relax_step(
    g: Graph,
    values: np.ndarray,
    problem: str = "bfs",
    *,
    use_pallas: bool | None = None,
    block: int = 1024,
    interpret: bool | None = None,
) -> np.ndarray:
    """new_values = min(values, segment_min_dst(values[src] + delta))."""
    v = jnp.asarray(values)
    if problem == "bfs":
        delta = np.ones(g.m, dtype=v.dtype)
    elif problem == "wcc":
        delta = np.zeros(g.m, dtype=v.dtype)
    elif problem == "sssp":
        assert g.weights is not None
        delta = g.weights.astype(v.dtype)
    else:
        raise ValueError(problem)
    use_pallas, interpret = resolve_pallas(use_pallas, interpret)
    if use_pallas:
        pad = (-g.m) % block
        src = np.concatenate([g.src, np.full(pad, -1, dtype=np.int32)])
        dst = np.concatenate([g.dst, np.zeros(pad, dtype=np.int32)])
        dl = np.concatenate([delta, np.zeros(pad, dtype=delta.dtype)])
        acc = scatter_min(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(dl),
                          v, use_pallas=True, block=block, interpret=interpret)
    else:
        acc = scatter_min(jnp.asarray(g.src), jnp.asarray(g.dst),
                          jnp.asarray(delta), v,
                          use_pallas=False, interpret=interpret)
    return np.asarray(jnp.minimum(v, acc))
