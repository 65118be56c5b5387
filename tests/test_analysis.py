"""Roofline analyzer: trip-count-aware HLO cost parsing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo_cost import HloModule, analyze_text


def _compiled(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_scan_flops_multiplied_by_trip_count():
    def body(c, _):
        return c @ c, None

    for n in (1, 10, 37):
        def f(x, n=n):
            y, _ = jax.lax.scan(body, x, None, length=n)
            return y

        c = _compiled(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
        cost = analyze_text(c.as_text())
        expect = n * 2 * 128 ** 3
        assert abs(cost.flops - expect) / expect < 1e-6, (n, cost.flops)


def test_nested_scan_flops():
    def inner(c, _):
        return c @ c, None

    def outer(c, _):
        y, _ = jax.lax.scan(inner, c, None, length=5)
        return y, None

    def f(x):
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    c = _compiled(f, jax.ShapeDtypeStruct((64, 64), jnp.float32))
    cost = analyze_text(c.as_text())
    expect = 3 * 5 * 2 * 64 ** 3
    assert abs(cost.flops - expect) / expect < 1e-6


def test_dot_general_batched_flops():
    def f(a, b):
        return jax.lax.dot_general(
            a, b, (((2,), (1,)), ((0,), (0,))))   # batched matmul

    a = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 16, 24), jnp.float32)
    cost = analyze_text(_compiled(f, a, b).as_text())
    expect = 2 * 4 * 32 * 16 * 24
    assert abs(cost.flops - expect) / expect < 1e-6


def test_collective_bytes_parsed():
    """An explicitly-sharded psum program must show all-reduce wire bytes."""
    import subprocess
    import sys
    import os
    script = r"""
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.analysis.hlo_cost import analyze_text
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("x",))
def f(a):
    return jax.lax.with_sharding_constraint(
        a.sum(axis=0, keepdims=True), NamedSharding(mesh, P()))
c = jax.jit(f, in_shardings=NamedSharding(mesh, P("x")),
            out_shardings=NamedSharding(mesh, P())).lower(
    jax.ShapeDtypeStruct((8, 1024), jnp.float32)).compile()
cost = analyze_text(c.as_text())
assert cost.coll_bytes > 0, c.as_text()[:4000]
assert "all-reduce" in cost.coll_by_kind or "all-gather" in cost.coll_by_kind
print("PASS")
"""
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu"}
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", script, src],
                       capture_output=True, text=True, env=env, timeout=300)
    assert "PASS" in r.stdout, r.stdout + r.stderr


def test_bytes_counts_boundaries_not_fused_internals():
    def f(x):
        return jnp.tanh(x) * 2 + 1     # one fused elementwise chain

    c = _compiled(f, jax.ShapeDtypeStruct((1024, 1024), jnp.float32))
    cost = analyze_text(c.as_text())
    nbytes = 1024 * 1024 * 4
    # in + out (+ small slack for copies); must NOT count 3 intermediates
    assert cost.bytes <= 4 * nbytes, cost.bytes
    assert cost.bytes >= 1.5 * nbytes


def test_slice_aware_scan_residuals():
    """A scan that saves per-step residuals must charge the slice, not the
    whole stacked buffer, per step."""
    def body(c, _):
        y = c @ c
        return y, y     # stacks (n, 256, 256) residuals

    def f(x):
        y, res = jax.lax.scan(body, x, None, length=100)
        return y, res

    c = _compiled(f, jax.ShapeDtypeStruct((256, 256), jnp.float32))
    cost = analyze_text(c.as_text())
    step = 256 * 256 * 4
    # stacked buffer is 100 steps; whole-buffer-per-step would be ~100×100
    # slices; correct accounting is O(100) slices + matmul traffic
    assert cost.bytes < 100 * step * 20, cost.bytes
