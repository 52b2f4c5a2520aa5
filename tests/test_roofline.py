"""Validation of the loop-aware HLO analyzer against hand-countable
programs (the roofline instrument must itself be verified)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import roofline_terms
from repro.roofline.hlo import analyze_hlo, shape_bytes, top_ops


def test_shape_bytes():
    assert shape_bytes("f32[128,128]{1,0}") == 128 * 128 * 4
    assert shape_bytes("bf16[2,3]") == 12
    assert shape_bytes("(f32[4], bf16[8])") == 16 + 16
    assert shape_bytes("(s32[], /*index=5*/f32[2,2]{1,0})") == 4 + 16


def test_scan_flops_scaled_by_trip_count():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=8)
        return out

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(f).lower(x, x).compile()
    a = analyze_hlo(c.as_text())
    want = 8 * 2 * 128 ** 3
    assert abs(a["flops"] - want) / want < 0.01
    assert a["n_loops"] == 1 and a["loops"][0]["trip"] == 8


def test_single_matmul_flops_exact():
    x = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 32), jnp.float32)
    c = jax.jit(lambda a, b: a @ b).lower(x, w).compile()
    a = analyze_hlo(c.as_text())
    assert a["flops"] == 2 * 64 * 32 * 256


def test_collectives_counted_in_subprocess():
    """Sharded contraction -> all-reduce of the (64, 32) f32 output."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.roofline.hlo import analyze_hlo
        mesh = jax.make_mesh((8,), ("m",))
        xs = NamedSharding(mesh, P(None, "m"))
        ws = NamedSharding(mesh, P("m", None))
        out_s = NamedSharding(mesh, P(None, None))
        c = jax.jit(lambda a, b: a @ b, in_shardings=(xs, ws),
                    out_shardings=out_s).lower(
            jax.ShapeDtypeStruct((64, 256), jnp.float32),
            jax.ShapeDtypeStruct((256, 32), jnp.float32)).compile()
        a = analyze_hlo(c.as_text())
        assert a["flops"] == 2 * 64 * 32 * 32, a["flops"]
        assert a["collective_bytes"] == 64 * 32 * 4, a["collective_bytes"]
        assert a["collectives_by_op"].get("all-reduce") == 64 * 32 * 4
        print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_dus_counts_update_not_buffer():
    def f(buf, upd):
        return jax.lax.dynamic_update_slice(buf, upd, (0, 0))

    buf = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    upd = jax.ShapeDtypeStruct((1, 1024), jnp.float32)
    c = jax.jit(f, donate_argnums=(0,)).lower(buf, upd).compile()
    a = analyze_hlo(c.as_text())
    # traffic: params read once (buf + upd) + ~update-sized write, NOT a
    # full-buffer rewrite
    assert a["bytes"] < 1024 * 1024 * 4 * 1.5, a


def test_top_ops_orders_by_value():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=4)
        return out

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c = jax.jit(f).lower(x, x).compile()
    rows = top_ops(c.as_text(), k=5, by="flops")
    assert rows and rows[0]["op"] == "dot"
    assert rows[0]["mult"] == 4


def test_roofline_terms_dominance():
    t = roofline_terms(197e12, 0.0, 0.0)  # 1 second of pure compute
    assert t["dominant"] == "compute" and abs(t["compute_s"] - 1.0) < 1e-9
    t = roofline_terms(0.0, 819e9, 50e9 * 2)
    assert t["dominant"] == "collective"
    assert abs(t["memory_s"] - 1.0) < 1e-9 and abs(t["collective_s"] - 2.0) < 1e-9

