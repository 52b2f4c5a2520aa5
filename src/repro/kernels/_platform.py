"""Shared backend-selection policy for the Pallas kernels.

Every kernel package (``spmv``, ``edge_update``, ``dram_timing``) exposes an
ops-level entry point with two knobs:

- ``use_pallas``: take the Pallas kernel instead of the jnp reference.
- ``interpret``: run the Pallas kernel in interpreter mode (no TPU needed).

Historically each ops module resolved the ``None`` defaults on its own; the
logic now lives here so every kernel picks the same policy and CPU CI
exercises the Pallas path automatically:

- On a TPU backend the Pallas kernel is compiled (``interpret=False``);
  only a caller's explicit ``interpret=True`` interprets it there.
- Anywhere else (CPU CI, laptops) the Pallas kernel still runs, via
  ``interpret=True`` — same program, interpreted — so tier-1 covers it.
- Passing ``interpret=True`` explicitly also opts into the Pallas path,
  matching the kernels' historical ``use_pallas or interpret`` behaviour.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_pallas(use_pallas: bool | None,
                   interpret: bool | None) -> tuple[bool, bool]:
    """Resolve the (use_pallas, interpret) pair for a kernel call.

    ``use_pallas=None`` means "kernel on TPU, kernel-in-interpreter
    elsewhere"; ``interpret=None`` means "compile on TPU, interpret
    elsewhere".  Explicit values are always honoured.
    """
    tpu = on_tpu()
    if interpret is None:
        interpret = not tpu
    if use_pallas is None:
        use_pallas = tpu or bool(interpret)
    return bool(use_pallas), bool(interpret)
