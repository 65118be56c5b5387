"""Mesh-sharded Batched SpMM regression tests (DESIGN.md §6).

The mesh tests run in an 8-device subprocess (XLA locks the host device
count at first init — same pattern as tests/test_distributed.py); the
pure-shape tests (per-shard workload resolution, padding) run in-process.
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str, n_dev: int = 8, timeout: int = 600):
    env = {**os.environ,
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_dev}",
           "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "-c", script, SRC],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


_HEADER = r"""
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from repro.core.formats import random_batch
from repro.distributed.spmm import resolve_sharded_impl, sharded_batched_spmm
from repro.kernels.ops import batched_spmm
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
"""


def test_sharded_matches_single_device_fwd_and_grad():
    """Forward and both gradients match the single-device batched_spmm
    bit-for-bit on an 8-way mesh, for impl="ref" and impl="auto"."""
    script = _HEADER + r"""
a, m_pad = random_batch(rng, batch=16, dim=24, nnz_per_row=3)
b = jnp.asarray(rng.standard_normal((16, m_pad, 32)), jnp.float32)
for impl in ("ref", "auto"):
    ref = batched_spmm(a, b, impl=impl, k_pad=8)
    got = sharded_batched_spmm(a, b, mesh=mesh, impl=impl, k_pad=8)
    assert float(jnp.max(jnp.abs(ref - got))) == 0.0, impl

    def loss(f):
        return lambda v, bb: jnp.sum(f(a.with_values(v), bb) ** 2)

    f_ref = lambda aa, bb: batched_spmm(aa, bb, impl=impl, k_pad=8)
    f_sh = lambda aa, bb: sharded_batched_spmm(aa, bb, mesh=mesh, impl=impl,
                                               k_pad=8)
    gr = jax.grad(loss(f_ref), argnums=(0, 1))(a.values, b)
    gs = jax.grad(loss(f_sh), argnums=(0, 1))(a.values, b)
    assert float(jnp.max(jnp.abs(gr[0] - gs[0]))) == 0.0, impl   # dValues
    assert float(jnp.max(jnp.abs(gr[1] - gs[1]))) == 0.0, impl   # dB
    # under jit XLA may re-fuse the gather-dot: tight allclose, not bitwise
    gj = jax.jit(jax.grad(loss(f_sh), argnums=(0, 1)))(a.values, b)
    np.testing.assert_allclose(gr[0], gj[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(gr[1], gj[1], rtol=2e-5, atol=2e-5)
print("PASS")
"""
    r = _run(script)
    assert "PASS" in r.stdout, r.stdout + "\n" + r.stderr


def test_sharded_batch_not_divisible_by_devices():
    """batch=13 on 8 devices: padded with zero-nnz samples (§IV-C padding
    invariant), output sliced back, fwd + grads still match."""
    script = _HEADER + r"""
a, m_pad = random_batch(rng, batch=13, dim=20, nnz_per_row=3)
b = jnp.asarray(rng.standard_normal((13, m_pad, 16)), jnp.float32)
for impl in ("ref", "auto"):
    ref = batched_spmm(a, b, impl=impl, k_pad=8)
    got = sharded_batched_spmm(a, b, mesh=mesh, impl=impl, k_pad=8)
    assert got.shape == ref.shape
    assert float(jnp.max(jnp.abs(ref - got))) == 0.0, impl

    def loss(f):
        return lambda v, bb: jnp.sum(f(a.with_values(v), bb) ** 2)

    f_ref = lambda aa, bb: batched_spmm(aa, bb, impl=impl, k_pad=8)
    f_sh = lambda aa, bb: sharded_batched_spmm(aa, bb, mesh=mesh, impl=impl,
                                               k_pad=8)
    gr = jax.grad(loss(f_ref), argnums=(0, 1))(a.values, b)
    gs = jax.grad(loss(f_sh), argnums=(0, 1))(a.values, b)
    assert gs[0].shape == gr[0].shape and gs[1].shape == gr[1].shape
    assert float(jnp.max(jnp.abs(gr[0] - gs[0]))) == 0.0, impl
    assert float(jnp.max(jnp.abs(gr[1] - gs[1]))) == 0.0, impl
print("PASS")
"""
    r = _run(script)
    assert "PASS" in r.stdout, r.stdout + "\n" + r.stderr


def test_forward_output_stays_batch_sharded():
    """No forward all-gather: the jitted sharded output carries a
    batch-sharded NamedSharding over the data axis."""
    script = _HEADER + r"""
a, m_pad = random_batch(rng, batch=16, dim=24, nnz_per_row=3)
b = jnp.asarray(rng.standard_normal((16, m_pad, 32)), jnp.float32)
out = jax.jit(lambda v, bb: sharded_batched_spmm(
    a.with_values(v), bb, mesh=mesh))(a.values, b)
spec = out.sharding.spec
assert tuple(spec)[:1] == ("data",), spec
print("PASS")
"""
    r = _run(script)
    assert "PASS" in r.stdout, r.stdout + "\n" + r.stderr


def test_graph_serve_engine_mesh_wave_matches_single_device():
    """GraphServeEngine(mesh=...): one wave spans all devices and the logits
    match the single-device engine."""
    script = _HEADER + r"""
from repro.core.gcn import GCNConfig, init_gcn
from repro.serving.engine import GraphRequest, GraphServeEngine
cfg = GCNConfig(n_features=8, channels=2, conv_widths=(16,), n_tasks=4)
params = init_gcn(jax.random.key(0), cfg)
def make():
    reqs = []
    r2 = np.random.default_rng(7)
    for i in range(10):
        m = int(r2.integers(5, 12)); e = int(r2.integers(4, 10))
        reqs.append(GraphRequest(
            rows=[r2.integers(0, m, e).astype(np.int32)
                  for _ in range(cfg.channels)],
            cols=[r2.integers(0, m, e).astype(np.int32)
                  for _ in range(cfg.channels)],
            features=r2.standard_normal((m, cfg.n_features)).astype(
                np.float32),
            n_nodes=m))
    return reqs
single = GraphServeEngine(params, cfg, batch=16, m_pad=16, nnz_pad=16)
meshed = GraphServeEngine(params, cfg, batch=16, m_pad=16, nnz_pad=16,
                          mesh=mesh)
r1, r2_ = single.run(make()), meshed.run(make())
assert all(r.done for r in r2_)
d = max(float(np.max(np.abs(a.logits - b.logits))) for a, b in zip(r1, r2_))
assert d < 1e-5, d
print("PASS")
"""
    r = _run(script)
    assert "PASS" in r.stdout, r.stdout + "\n" + r.stderr


def test_scheduler_mesh_waves_match_single_device():
    """The continuous-batching Scheduler with mesh= spans every wave across
    the 8-device mesh (per-tier engines inherit the mesh) and its logits
    match the single-device scheduler's."""
    script = _HEADER + r"""
from repro.core.gcn import GCNConfig, init_gcn
from repro.data.graphs import GraphDatasetSpec, generate
from repro.scheduler import Scheduler, TierPolicy, VirtualClock
from repro.serving.engine import GraphRequest
spec = GraphDatasetSpec.tox21_like(n_samples=12, n_features=8, channels=2,
                                   size_dist="skewed", seed=3)
data = generate(spec)
cfg = GCNConfig(n_features=8, channels=2, conv_widths=(16,), n_tasks=4)
params = init_gcn(jax.random.key(0), cfg)
policy = TierPolicy.from_requests(
    [(s.n_nodes, max(len(r) for r in s.rows)) for s in data],
    levels=2, batch=8)
def make():
    return [GraphRequest(rows=s.rows, cols=s.cols, features=s.features,
                         n_nodes=s.n_nodes) for s in data]
single = Scheduler(params, cfg, tiers=policy, clock=VirtualClock())
meshed = Scheduler(params, cfg, tiers=policy, clock=VirtualClock(),
                   mesh=mesh)
r1, r2 = single.serve(make()), meshed.serve(make())
assert all(r.done for r in r2)
assert meshed.metrics.compile_count == single.metrics.compile_count
d = max(float(np.max(np.abs(a.logits - b.logits))) for a, b in zip(r1, r2))
assert d < 1e-5, d
print("PASS")
"""
    r = _run(script)
    assert "PASS" in r.stdout, r.stdout + "\n" + r.stderr


def test_gcn_trainer_mesh_gradients_match_single_device():
    """GCNTrainer(mesh=...): the data-parallel step's loss and gradients
    match the single-device step (the grad all-reduce is GSPMD's, inserted
    from the sharded-batch/replicated-params layout)."""
    script = _HEADER + r"""
from repro.core.gcn import GCNConfig, gcn_loss, init_gcn
cfg = GCNConfig(n_features=8, channels=2, conv_widths=(16,), n_tasks=4)
a0, m_pad = random_batch(rng, batch=16, dim=12, nnz_per_row=2)
adj = [a0] * cfg.channels
x = jnp.asarray(rng.standard_normal((16, m_pad, cfg.n_features)), jnp.float32)
n_nodes = jnp.asarray(a0.n_rows)
labels = jnp.asarray(
    rng.integers(0, 2, (16, cfg.n_tasks)).astype(np.float32))
params = init_gcn(jax.random.key(0), cfg)
vg = lambda mk: jax.jit(jax.value_and_grad(
    lambda p: gcn_loss(p, cfg, adj, x, n_nodes, labels, mesh=mk)[0]))
(l1, g1), (l2, g2) = vg(None)(params), vg(mesh)(params)
assert abs(float(l1) - float(l2)) < 1e-5, (l1, l2)
for ga, gb in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
    assert float(jnp.max(jnp.abs(ga - gb))) < 1e-5
print("PASS")
"""
    r = _run(script)
    assert "PASS" in r.stdout, r.stdout + "\n" + r.stderr


def test_sharded_fused_graph_conv_matches_local():
    """Per-shard fused megakernel dispatch (DESIGN.md §7): fwd + all four
    grads match the local fused layer on an 8-way mesh, including a batch
    that is not divisible by the device count (zero-nnz padding)."""
    script = _HEADER + r"""
from repro.core.graph_conv import init_graph_conv, stack_channels
from repro.distributed.spmm import sharded_fused_graph_conv
from repro.kernels.fused_graph_conv import fused_graph_conv
for batch in (16, 13):
    adj = []
    for _ in range(3):
        a, m_pad = random_batch(rng, batch=batch, dim=(8, 24),
                                nnz_per_row=(1, 3))
        adj.append(a)
    m_pad = 24
    x = jnp.asarray(rng.standard_normal((batch, m_pad, 10)), jnp.float32)
    params = init_graph_conv(jax.random.key(0), 10, 16, 3)
    rids, cids, vals, nnz = stack_channels(adj)
    args = (vals, x, params["w"], params["b"])

    def loc(v, xx, ww, bb):
        return fused_graph_conv(rids, cids, v, nnz, xx, ww, bb)

    def sh(v, xx, ww, bb):
        return sharded_fused_graph_conv(rids, cids, v, nnz, xx, ww, bb,
                                        mesh=mesh)

    ref, got = loc(*args), sh(*args)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    gl = jax.grad(lambda *a: jnp.sum(jnp.tanh(loc(*a))),
                  argnums=(0, 1, 2, 3))(*args)
    gs = jax.grad(lambda *a: jnp.sum(jnp.tanh(sh(*a))),
                  argnums=(0, 1, 2, 3))(*args)
    for name, a1, a2 in zip(("dvals", "dx", "dw", "db"), gl, gs):
        np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                                   rtol=2e-5, atol=2e-5, err_msg=name)
print("PASS")
"""
    r = _run(script)
    assert "PASS" in r.stdout, r.stdout + "\n" + r.stderr


# ---- in-process, shape-only checks -----------------------------------------

def test_workload_shard_view():
    from repro.autotune import Workload

    w = Workload(batch=13, m_pad=56, nnz_pad=256, k_pad=4, n_b=64)
    assert w.shard(8).batch == 2          # ceil(13 / 8)
    assert w.shard(1) == w
    assert w.shard(8).m_pad == w.m_pad and w.shard(8).n_b == w.n_b


def test_pad_batch_zero_nnz_and_slice():
    import numpy as np

    import jax.numpy as jnp
    from repro.core.formats import random_batch
    from repro.distributed.spmm import pad_batch

    rng = np.random.default_rng(0)
    a, m_pad = random_batch(rng, batch=5, dim=8, nnz_per_row=2)
    b = jnp.ones((5, m_pad, 4), jnp.float32)
    a2, b2, pad = pad_batch(a, b, 4)
    assert pad == 3 and b2.shape[0] == 8 and a2.values.shape[0] == 8
    assert float(jnp.sum(a2.values[5:])) == 0.0
    assert int(jnp.sum(a2.nnz[5:])) == 0
    a3, b3, pad3 = pad_batch(a, b, 5)
    assert pad3 == 0 and b3 is b
