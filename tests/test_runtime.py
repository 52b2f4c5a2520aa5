"""repro.runtime: compile-cache placement, one device seat per chip, and a
scheduler process that never opens a JAX backend."""
from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

from repro import runtime

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture
def cache_config():
    """Restore JAX's compile-cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_follows_env(monkeypatch, tmp_path, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: the helper sets nothing else
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = runtime.enable_compile_cache()
    assert runtime.enable_compile_cache() == first == runtime.DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == first
    # inside the checkout, next to src/: no temp name, PID or time in it
    root = os.path.dirname(first)
    assert os.path.isdir(os.path.join(root, "src", "repro"))
    assert os.path.basename(first) == ".jax_cache"


@pytest.mark.parametrize("platforms,seats,ok", [
    ("tpu", 1, True),
    ("tpu", 2, False),
    ("tpu,cpu", 4, False),
    ("cpu", 4, True),
    ("", 4, True),
])
def test_check_device_seats(monkeypatch, platforms, seats, ok):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    if ok:
        runtime.check_device_seats(seats)
    else:
        with pytest.raises(ValueError, match="one seat per chip"):
            runtime.check_device_seats(seats)


def test_serve_cli_refuses_two_seats_on_a_chip(monkeypatch, capsys, tmp_path):
    from repro.serve.__main__ import main

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    rc = main(["--port", "0", "--workers", "2", "--cache", str(tmp_path),
               "--quiet"])
    assert rc == 2
    assert "one seat per chip" in capsys.readouterr().err


def test_run_sweep_refuses_two_seats_on_a_chip(monkeypatch):
    from repro.graph.generators import GraphSpec
    from repro.sweep import SweepSpec, run_sweep

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    spec = SweepSpec(name="t", accelerators=("accugraph",),
                     graphs=(GraphSpec("tiny", "uniform", 256, 1024, True,
                                       1, 0),),
                     problems=("bfs",), drams=("default",))
    with pytest.raises(ValueError, match="one seat per chip"):
        run_sweep(spec, workers=2)


SCHEDULER_ONLY = r"""
from concurrent.futures import Future
import time
from jax._src import xla_bridge
from repro.graph.generators import GraphSpec
from repro.serve import SweepScheduler
from repro.sweep import SweepSpec

class NoPool:
    size = 1
    def submit(self, fn, scenarios, *args):
        fut = Future()
        fut.set_result(dict(records=[dict(status="error", error="x",
                                          wall_s=0.0)] * len(scenarios),
                            hostcache={}))
        return fut
    def shutdown(self, **kw):
        pass

sched = SweepScheduler(None, pool_factory=NoPool)
spec = SweepSpec(name="t",
                 accelerators=("accugraph", "foregraph", "hitgraph",
                               "thundergp"),
                 graphs=(GraphSpec("tiny", "uniform", 256, 1024, True, 1, 0),),
                 problems=("bfs", "pr"), drams=("default", "hbm"),
                 engines=("numpy", "device"))
job = sched.submit(spec)
deadline = time.time() + 30
while sched.stats()["jobs"]["completed"] < 1 and time.time() < deadline:
    time.sleep(0.05)
sched.close()
assert sched.stats()["jobs"]["completed"] == 1
print("backends", xla_bridge.backends_are_initialized())
"""


def test_scheduler_process_never_opens_a_backend():
    """The scheduler plans, dedups and streams without a JAX backend: on a
    chip host its process must not hold the chip its seat needs."""
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", SCHEDULER_ONLY], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "backends False" in out.stdout
