"""Public SpMV ops.

``spmv_edges`` is the array-level primitive (jnp in/out, safe to embed in an
outer ``jax.jit``); ``spmv`` is the Graph-level wrapper kept for the
workload benches.  Neither is on the simulator's path: the semexec device
engine accumulates through reduce plans (``repro.core.semexec``), because
the TPU compiler refuses this kernel's 1-D gather.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.graph.structure import Graph
from repro.kernels._platform import resolve_pallas
from repro.kernels.spmv.ref import spmv_coo_ref, spmv_ell_ref, to_ell
from repro.kernels.spmv.spmv import spmv_ell_pallas


def spmv_edges(
    src: jnp.ndarray,  # (m,) int32
    dst: jnp.ndarray,  # (m,) int32, in [0, n)
    w: jnp.ndarray,  # (m,) f32 effective edge weights
    x: jnp.ndarray,  # (n,) f32
    n: int,
    *,
    ell: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    use_pallas: bool | None = None,
    block_rows: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """y[d] = sum over edges of w * x[src]; returns y (n,).

    When a precomputed ELL layout ``(idx, val)`` (see ``to_ell``) is passed
    and the Pallas path is resolved on, the blocked ELL kernel runs;
    otherwise the XLA segment-sum reference over the COO arrays.
    """
    use_pallas, interpret = resolve_pallas(use_pallas, interpret)
    if use_pallas and ell is not None:
        idx, val = ell
        y = spmv_ell_pallas(idx, val, x, block_rows=block_rows,
                            interpret=interpret)
        return y[:n]
    return spmv_coo_ref(src, dst, w, x, n)


def spmv(
    g: Graph,
    x: np.ndarray,
    *,
    use_pallas: bool | None = None,
    block_rows: int = 256,
    interpret: bool | None = None,
) -> np.ndarray:
    """y = A @ x with A[dst, src] = weight (1.0 if unweighted)."""
    use_pallas, interpret = resolve_pallas(use_pallas, interpret)
    x = jnp.asarray(x, dtype=jnp.float32)
    w = g.weights if g.weights is not None else np.ones(g.m, dtype=np.float32)
    ell = None
    if use_pallas:
        ell = to_ell(g.src, g.dst, g.weights, g.n, block_rows=block_rows)
        ell = (jnp.asarray(ell[0]), jnp.asarray(ell[1]))
    y = spmv_edges(jnp.asarray(g.src), jnp.asarray(g.dst), jnp.asarray(w), x,
                   g.n, ell=ell, use_pallas=use_pallas,
                   block_rows=block_rows, interpret=interpret)
    return np.asarray(y)
