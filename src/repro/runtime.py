"""Process-level JAX set-up for the processes that time on a device.

Three rules, one place:

- **Compile cache.**  :func:`enable_compile_cache` keeps JAX's persistent
  compilation cache where ``JAX_COMPILATION_CACHE_DIR`` says when that is
  set (JAX reads the variable itself; nothing else is set), and otherwise
  at :data:`DEFAULT_CACHE_DIR`, a fixed path next to the package.  The
  path is part of the cache key, so it never holds a temp name, a PID or
  a time.  Every process that compiles calls it before its first compile:
  the serve seats (``repro.serve.worker.init_worker``) and ``run_sweep``
  (in-process and in its pool workers).
- **One device seat per chip.**  A TPU belongs to one process at a time.
  :func:`check_device_seats` refuses to start more than one seat when
  ``JAX_PLATFORMS`` asks for the TPU; run such hosts with ``--workers 1``.
- **No hidden CPU.**  :func:`device_info` is what a seat reports of the
  backend it came up on (platform, device kind, device count).  Under
  ``JAX_PLATFORMS=tpu`` JAX itself raises when the chip cannot be opened,
  so a seat fails loudly instead of timing on the host.

Nothing here initialises a backend except :func:`device_info`; the
scheduler process and its clients import this module freely.
"""
from __future__ import annotations

import os

import jax

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def wants_tpu() -> bool:
    """True when ``JAX_PLATFORMS`` names the TPU."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    return "tpu" in (p.strip() for p in platforms.split(","))


def check_device_seats(seats: int) -> None:
    """Refuse more than one device seat on a TPU host (ValueError)."""
    if seats > 1 and wants_tpu():
        raise ValueError(
            f"{seats} worker seats under JAX_PLATFORMS=tpu: a chip belongs "
            f"to one process, so run one seat per chip (--workers 1)")


def device_info() -> dict:
    """Platform, device kind and device count of this process's default
    backend (initialises it)."""
    devices = jax.devices()
    return dict(platform=devices[0].platform, kind=devices[0].device_kind,
                count=len(devices))
