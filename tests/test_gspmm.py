"""g-SpMM message-passing regression tests (DESIGN.md §11).

The (op × reduce × edge-kind) generalized-SpMM matrix against the pure-jnp
oracle across every g-SpMM-capable impl; segment_softmax; the GAT / R-GCN
layers against per-head/per-relation dense references; workload resolution
and the ELL-guard class gating; and mesh-sharded parity (subprocess, same
pattern as tests/test_sharded_spmm.py)."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oracle import (
    GSPMM_EDGE_KINDS,
    GSPMM_MATRIX,
    check_gspmm_forward,
    check_gspmm_grads,
    gspmm_cases,
)
from repro.autotune import GSPMM_IMPLS, Workload, supports_gspmm
from repro.core import coo_from_lists, random_batch
from repro.core.spmm import GSPMM_OPS, GSPMM_REDUCES, batched_gspmm
from repro.kernels.segment_softmax import segment_softmax


# ---------------------------------------------------------------------------
# the full matrix: every capable impl × every (op, reduce) × both edge kinds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op,reduce", GSPMM_MATRIX)
@pytest.mark.parametrize("impl", GSPMM_IMPLS)
def test_gspmm_forward_vs_oracle(impl, op, reduce):
    for edges in GSPMM_EDGE_KINDS:
        check_gspmm_forward(impl, op, reduce, edges)


@pytest.mark.parametrize("op,reduce", GSPMM_MATRIX)
@pytest.mark.parametrize("impl", GSPMM_IMPLS)
def test_gspmm_grads_vs_oracle(impl, op, reduce):
    for edges in GSPMM_EDGE_KINDS:
        check_gspmm_grads(impl, op, reduce, edges)


@pytest.mark.parametrize("reduce", ["max", "mean"])
@pytest.mark.parametrize("impl", GSPMM_IMPLS)
def test_gspmm_zero_nnz_identity(impl, reduce):
    """Regression (ISSUE 7): a zero-nnz sample must emit the 0.0 identity —
    not the NEG_INF max sentinel, not a 0/0 NaN from the mean normalizer —
    for EVERY concrete impl, with finite (zero) gradients."""
    empty = (np.zeros(0, np.int32), np.zeros(0, np.int32),
             np.zeros(0, np.float32))
    coo = coo_from_lists([empty, empty], [16, 16])
    b = jnp.asarray(np.random.default_rng(3).normal(size=(2, 16, 8)),
                    jnp.float32)
    out = batched_gspmm(coo, b, op="mul", reduce=reduce, impl=impl, k_pad=4)
    np.testing.assert_array_equal(np.asarray(out), 0.0)

    g = jax.grad(lambda v, bb: jnp.sum(batched_gspmm(
        coo.with_values(v) if hasattr(coo, "with_values")
        else dataclasses.replace(coo, values=v),
        bb, op="mul", reduce=reduce, impl=impl, k_pad=4) ** 2),
        argnums=(0, 1))(coo.values, b)
    for leaf in g:
        arr = np.asarray(leaf)
        assert np.isfinite(arr).all()
        np.testing.assert_array_equal(arr, 0.0)


def test_gspmm_validates_op_reduce_and_impl():
    rng = np.random.default_rng(0)
    a, m_pad = random_batch(rng, batch=2, dim=8, nnz_per_row=2)
    b = jnp.ones((2, m_pad, 4), jnp.float32)
    with pytest.raises(ValueError, match="unknown g-SpMM op"):
        batched_gspmm(a, b, op="div")
    with pytest.raises(ValueError, match="unknown g-SpMM reduce"):
        batched_gspmm(a, b, reduce="min")
    # a reduced-precision variant cannot carry a non-default corner …
    with pytest.raises(ValueError, match="cannot run g-SpMM"):
        batched_gspmm(a, b, op="add", impl="csr_bf16")
    # … but the (mul, sum, scalar) corner IS plain batched SpMM and
    # delegates to the full registry, precision variants included
    out = batched_gspmm(a, b, op="mul", reduce="sum", impl="csr_bf16")
    assert out.shape == (2, m_pad, 4)


def test_gspmm_matrix_covers_all_corners():
    assert set(GSPMM_MATRIX) == {
        (op, red) for op in GSPMM_OPS for red in GSPMM_REDUCES}
    assert len(GSPMM_MATRIX) == 9


# ---------------------------------------------------------------------------
# segment_softmax
# ---------------------------------------------------------------------------

def _softmax_case():
    rng = np.random.default_rng(7)
    coo, m_pad = random_batch(rng, batch=3, dim=(8, 16), nnz_per_row=(1, 4))
    scores = jnp.asarray(rng.normal(size=coo.row_ids.shape), jnp.float32)
    return coo, m_pad, scores


def _softmax_ref(scores, row_ids, nnz, m_pad):
    """Pure-jnp per-row softmax (one-hot matmul formulation) — independent
    of the kernel's NEG_INF/clip machinery, fully autodiffable."""
    valid = jnp.arange(scores.shape[1])[None, :] < nnz[:, None]
    onehot = jax.nn.one_hot(row_ids, m_pad, dtype=jnp.float32)
    onehot = onehot * valid[..., None]
    e = jnp.exp(scores) * valid                    # small scores: no overflow
    denom = jnp.einsum("bnm,bn->bm", onehot, e)
    gath = jnp.einsum("bnm,bm->bn", onehot, denom)
    # invalid slots gather a 0 denominator; substitute 1.0 (not a tiny
    # epsilon — its square underflows f32 in the quotient backward → 0/0)
    return e / jnp.where(gath > 0, gath, 1.0)


def test_segment_softmax_rows_sum_to_one():
    coo, m_pad, scores = _softmax_case()
    alpha = segment_softmax(scores, coo.row_ids, nnz=coo.nnz, m_pad=m_pad)
    want = _softmax_ref(scores, coo.row_ids, coo.nnz, m_pad)
    np.testing.assert_allclose(np.asarray(alpha), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # per-destination-row mass: exactly 1 for rows with edges, 0 otherwise
    onehot = jax.nn.one_hot(coo.row_ids, m_pad, dtype=jnp.float32)
    valid = (jnp.arange(scores.shape[1])[None, :]
             < coo.nnz[:, None]).astype(jnp.float32)
    mass = jnp.einsum("bnm,bn->bm", onehot * valid[..., None], alpha)
    deg = jnp.einsum("bnm,bn->bm", onehot, valid)
    np.testing.assert_allclose(np.asarray(mass),
                               np.asarray((deg > 0).astype(jnp.float32)),
                               atol=1e-5)


def test_segment_softmax_grads_match_autodiff_ref():
    coo, m_pad, scores = _softmax_case()
    g = jax.grad(lambda s: jnp.sum(jnp.tanh(segment_softmax(
        s, coo.row_ids, nnz=coo.nnz, m_pad=m_pad))))(scores)
    g_ref = jax.grad(lambda s: jnp.sum(jnp.tanh(
        _softmax_ref(s, coo.row_ids, coo.nnz, m_pad))))(scores)
    valid = np.asarray(
        jnp.arange(scores.shape[1])[None, :] < coo.nnz[:, None], np.float32)
    np.testing.assert_allclose(np.asarray(g) * valid,
                               np.asarray(g_ref) * valid,
                               atol=1e-5, rtol=1e-5)
    assert np.isfinite(np.asarray(g)).all()


def test_segment_softmax_zero_nnz_finite():
    """All-empty batch: zero attention, zero (finite) gradient — the
    zero-degree rows of a GAT wave must not NaN the step."""
    empty = (np.zeros(0, np.int32), np.zeros(0, np.int32),
             np.zeros(0, np.float32))
    coo = coo_from_lists([empty, empty], [8, 8])
    scores = jnp.asarray(np.random.default_rng(1).normal(
        size=coo.row_ids.shape), jnp.float32)
    alpha = segment_softmax(scores, coo.row_ids, nnz=coo.nnz, m_pad=8)
    np.testing.assert_array_equal(np.asarray(alpha), 0.0)
    g = jax.grad(lambda s: jnp.sum(segment_softmax(
        s, coo.row_ids, nnz=coo.nnz, m_pad=8) ** 2))(scores)
    np.testing.assert_array_equal(np.asarray(g), 0.0)


# ---------------------------------------------------------------------------
# GAT / R-GCN layers vs dense per-head / per-relation references
# ---------------------------------------------------------------------------

def _layer_geometry():
    rng = np.random.default_rng(5)
    coo, m_pad = random_batch(rng, batch=3, dim=(10, 16), nnz_per_row=(1, 4))
    x = jnp.asarray(rng.normal(size=(3, m_pad, 10)), jnp.float32)
    return coo, m_pad, x


def test_gat_layer_vs_dense_reference():
    from repro.models.gnn import gat_layer, init_gat_layer

    coo, m_pad, x = _layer_geometry()
    heads, n_out = 2, 8
    d_head = n_out // heads
    p = init_gat_layer(jax.random.PRNGKey(2), x.shape[-1], n_out, heads)
    out = gat_layer(p, coo, x, impl="ref")

    ref_out = np.zeros((x.shape[0], m_pad, n_out), np.float32)
    for b in range(x.shape[0]):
        nz = int(coo.nnz[b])
        rid = np.asarray(coo.row_ids[b][:nz])
        cid = np.asarray(coo.col_ids[b][:nz])
        for h_i in range(heads):
            hb = np.asarray(x[b]) @ np.asarray(p["w"][h_i])
            logit = (hb @ np.asarray(p["a_src"][h_i]))[cid] \
                + (hb @ np.asarray(p["a_dst"][h_i]))[rid]
            logit = np.where(logit >= 0, logit, 0.2 * logit)
            for r in range(m_pad):
                sel = rid == r
                if not sel.any():
                    continue
                e = np.exp(logit[sel] - logit[sel].max())
                alpha = e / e.sum()
                ref_out[b, r, h_i * d_head:(h_i + 1) * d_head] = (
                    alpha[:, None] * hb[cid[sel]]).sum(0)
    np.testing.assert_allclose(np.asarray(out), ref_out + np.asarray(p["b"]),
                               atol=1e-4, rtol=1e-4)

    g = jax.grad(lambda pp: jnp.sum(gat_layer(pp, coo, x, impl="ref") ** 2))(p)
    assert all(bool(jnp.isfinite(v).all())
               for v in jax.tree_util.tree_leaves(g))


def test_rgcn_layer_vs_dense_reference():
    from repro.models.gnn import init_rgcn_layer, rgcn_layer

    coo, m_pad, x = _layer_geometry()
    rng = np.random.default_rng(21)
    adjs = [coo, random_batch(rng, batch=3, dim=m_pad, nnz_per_row=2)[0]]
    n_out = 8
    p = init_rgcn_layer(jax.random.PRNGKey(3), x.shape[-1], n_out, len(adjs))
    out = rgcn_layer(p, adjs, x, impl="ref")

    ref_out = np.zeros((x.shape[0], m_pad, n_out), np.float32)
    for b in range(x.shape[0]):
        for r_i, a in enumerate(adjs):
            nz = int(a.nnz[b])
            rid = np.asarray(a.row_ids[b][:nz])
            cid = np.asarray(a.col_ids[b][:nz])
            hb = np.asarray(x[b]) @ np.asarray(p["w_rel"][r_i])
            for row in range(m_pad):
                sel = rid == row
                if sel.any():
                    ref_out[b, row] += hb[cid[sel]].mean(0)
    want = (ref_out + np.asarray(x) @ np.asarray(p["w_self"])
            + np.asarray(p["b"]))
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4, rtol=1e-4)

    g = jax.grad(
        lambda pp: jnp.sum(rgcn_layer(pp, adjs, x, impl="ref") ** 2))(p)
    assert all(bool(jnp.isfinite(v).all())
               for v in jax.tree_util.tree_leaves(g))


def test_grouped_matmul_vjp_vs_dense():
    """grouped_matmul's custom VJP (pallas_call has no autodiff rule) vs
    autodiff of the per-row dense gather formulation — both operands."""
    from repro.kernels.grouped_matmul import grouped_matmul

    x = jax.random.normal(jax.random.PRNGKey(0), (20, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 6, 5))
    sizes = jnp.asarray([7, 9, 4], jnp.int32)
    rg = np.repeat([0, 1, 2], [7, 9, 4])

    def f(x, w):
        return jnp.sum(jnp.sin(grouped_matmul(x, w, sizes, tm=8)))

    def f_ref(x, w):
        return jnp.sum(jnp.sin(jnp.einsum("mk,mkn->mn", x, w[rg])))

    g = jax.grad(f, argnums=(0, 1))(x, w)
    g_ref = jax.grad(f_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(g_ref[0]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(g_ref[1]),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# workload resolution + config plumbing
# ---------------------------------------------------------------------------

def test_workload_gspmm_keys_and_capability():
    base = dict(batch=4, m_pad=64, nnz_pad=32, k_pad=8, n_b=48, itemsize=4)
    assert not Workload(**base).is_gspmm
    assert Workload(**base, reduce="max").is_gspmm
    assert Workload(**base, op="copy_lhs").is_gspmm
    assert Workload(**base, d_e=48).is_gspmm
    key = Workload(**base, d_e=48, reduce="max", op="copy_lhs").key()
    assert key.endswith("_e48_rmax_ocopy_lhs")
    assert "_r" not in Workload(**base).key()

    for impl in GSPMM_IMPLS:
        assert supports_gspmm(impl)
    for impl in ("dense", "pallas_gemm", "csr_bf16", "pallas_ell_i8",
                 "fused", "auto"):
        assert not supports_gspmm(impl)


def test_resolve_gspmm_impl_stays_in_capable_set():
    from repro.core.spmm import resolve_gspmm_impl

    rng = np.random.default_rng(0)
    a, m_pad = random_batch(rng, batch=4, dim=24, nnz_per_row=3)
    b = jnp.ones((4, m_pad, 16), jnp.float32)
    for op, reduce in GSPMM_MATRIX:
        d = resolve_gspmm_impl(a, b, op=op, reduce=reduce, k_pad=8)
        if (op, reduce) == ("mul", "sum"):
            # scalar edges: that corner IS plain batched SpMM — the full
            # registry (dense, precision variants) stays in play
            continue
        assert d.impl in GSPMM_IMPLS, (op, reduce, d.impl)
        assert all(i in GSPMM_IMPLS for i, _ in d.scores)


def test_message_passing_matches_batched_gspmm():
    from repro.core.message_passing import (
        message_passing,
        resolve_message_passing_impl,
    )

    rng = np.random.default_rng(9)
    a, m_pad = random_batch(rng, batch=2, dim=12, nnz_per_row=2)
    x = jnp.asarray(rng.normal(size=(2, m_pad, 6)), jnp.float32)
    want = batched_gspmm(a, x, op="copy_lhs", reduce="max", impl="csr")
    got = message_passing(a, x, op="copy_lhs", reduce="max", impl="csr")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    d = resolve_message_passing_impl(a, x, op="copy_lhs", reduce="max")
    assert d.impl in GSPMM_IMPLS


def test_resolve_conv_impls_layer_kinds():
    from repro.core.gcn import GCNConfig, resolve_conv_impls

    geom = dict(batch=8, m_pad=64, nnz_pad=256)
    for layer in ("gcn", "gat", "rgcn"):
        cfg = GCNConfig.tox21(layer=layer, interpret=True)
        ds = resolve_conv_impls(cfg, **geom)
        assert len(ds) == len(cfg.conv_widths)
        forced = resolve_conv_impls(
            GCNConfig.tox21(layer=layer, impl="csr", interpret=True), **geom)
        assert all(d.impl == "csr" and d.source == "forced" for d in forced)
    gat = resolve_conv_impls(
        GCNConfig.tox21(layer="gat", interpret=True), **geom)
    assert all(d.impl in GSPMM_IMPLS for d in gat)


def test_gcn_config_rejects_bad_layer_kinds():
    from repro.core.gcn import GCNConfig, apply_gcn, init_gcn

    with pytest.raises(ValueError, match="unknown layer kind"):
        init_gcn(jax.random.PRNGKey(0), GCNConfig.tox21(layer="sage"))
    cfg = GCNConfig.tox21(layer="gat", batched=False, interpret=True)
    params = init_gcn(jax.random.PRNGKey(0), GCNConfig.tox21(layer="gat"))
    rng = np.random.default_rng(0)
    adj, m_pad = random_batch(rng, batch=2, dim=8, nnz_per_row=2)
    x = jnp.ones((2, m_pad, cfg.n_features), jnp.float32)
    with pytest.raises(ValueError, match="requires batched=True"):
        apply_gcn(params, cfg, [adj] * cfg.channels, x,
                  jnp.asarray([4, 4], jnp.int32))


def test_ell_guard_ors_over_every_layer_decision(monkeypatch):
    """The engine's ELL degree guard must trip when ANY conv layer's
    decision lands in the ELL class — including reduced-precision ELL
    variants — not just the first layer's (regression: ISSUE 7 satellite)."""
    import repro.core.gcn as gcn_mod
    from repro.core.gcn import GCNConfig, init_gcn
    from repro.serving.engine import GraphServeEngine

    cfg = GCNConfig.tox21(interpret=True)
    params = init_gcn(jax.random.PRNGKey(0), cfg)

    def fake_resolver(mixed_impls):
        def resolve(cfg, batch, m_pad, nnz_pad, *, itemsize=4, mesh=None):
            from repro.autotune import Workload, forced_decision

            w = Workload(batch=batch, m_pad=m_pad, nnz_pad=nnz_pad,
                         k_pad=cfg.k_pad, n_b=64, itemsize=itemsize)
            return tuple(forced_decision(w, i) for i in mixed_impls)
        return resolve

    # deep layer resolves to a reduced-precision ELL variant → guard on
    monkeypatch.setattr(gcn_mod, "resolve_conv_impls",
                        fake_resolver(["csr", "ell_bf16"]))
    eng = GraphServeEngine(params, cfg, batch=4)
    assert eng._ell_degree_guard
    # no layer in the ELL class → guard off
    monkeypatch.setattr(gcn_mod, "resolve_conv_impls",
                        fake_resolver(["csr", "pallas_coo"]))
    eng = GraphServeEngine(params, cfg, batch=4)
    assert not eng._ell_degree_guard
    # forced concrete ELL impl bypasses the resolver entirely → guard on
    eng = GraphServeEngine(
        params, dataclasses.replace(cfg, impl="pallas_ell_bf16"), batch=4)
    assert eng._ell_degree_guard


def test_ops_docstring_lists_every_impl():
    """The impl table in kernels/ops.py is GENERATED from IMPLS (ISSUE 7
    satellite: the hand-written list had drifted) — every registry entry
    must appear in the rendered module docstring."""
    from repro.core.spmm import IMPLS
    from repro.kernels import ops

    for impl in IMPLS:
        assert f"'{impl}'" in ops.__doc__, impl


# ---------------------------------------------------------------------------
# end-to-end: GAT trains via GCNTrainer and serves via the engine/scheduler
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layer", ["gat", "rgcn"])
def test_layer_trains_and_serves(layer, tmp_path):
    from repro.core.gcn import GCNConfig
    from repro.data.graphs import GraphDatasetSpec, batches, generate
    from repro.serving import GraphRequest, GraphServeEngine
    from repro.training import GCNTrainer, TrainerConfig

    spec = GraphDatasetSpec.tox21_like(n_samples=16)
    data = generate(spec)
    cfg = GCNConfig.tox21(layer=layer, interpret=True)
    trainer = GCNTrainer(cfg, tcfg=TrainerConfig(
        checkpoint_dir=str(tmp_path), checkpoint_every=1000))
    params, _, metrics = trainer.fit(
        lambda e: batches(data, spec, 8, seed=e), epochs=1)
    assert np.isfinite(metrics["loss"])

    reqs = [GraphRequest(rows=s.rows, cols=s.cols, features=s.features,
                         n_nodes=s.n_nodes) for s in data[:3]]
    out = GraphServeEngine(params, cfg, batch=4).run(reqs)
    assert all(r.done and r.logits.shape == (cfg.n_tasks,) for r in out)


def test_gat_serves_via_scheduler_auto_per_tier():
    """A GAT model rides the continuous-batching scheduler: every request
    completes, and each geometry tier's program records an ``impl="auto"``
    decision resolved against THAT tier's g-SpMM workload."""
    from repro.core.gcn import GCNConfig, init_gcn
    from repro.data.graphs import GraphDatasetSpec, generate
    from repro.scheduler import Scheduler, TierPolicy, VirtualClock
    from repro.serving import GraphRequest

    spec = GraphDatasetSpec.tox21_like(
        n_samples=12, n_features=8, channels=2, size_dist="skewed", seed=1)
    data = generate(spec)
    cfg = GCNConfig(n_features=8, channels=2, conv_widths=(8,), n_tasks=3,
                    layer="gat", heads=2, interpret=True)
    params = init_gcn(jax.random.PRNGKey(0), cfg)
    policy = TierPolicy.from_requests(
        [(s.n_nodes, max(len(r) for r in s.rows)) for s in data],
        levels=2, batch=4)
    sched = Scheduler(params, cfg, tiers=policy, clock=VirtualClock())
    out = sched.serve([GraphRequest(rows=s.rows, cols=s.cols,
                                    features=s.features, n_nodes=s.n_nodes)
                       for s in data])
    assert all(r.done and not r.failed for r in out)
    assert all(r.logits.shape == (cfg.n_tasks,) for r in out)
    decisions = sched.programs.decisions()
    assert decisions
    assert all(d.impl in GSPMM_IMPLS for d in decisions.values())


# ---------------------------------------------------------------------------
# mesh-sharded parity (8-device subprocess, as in test_sharded_spmm.py)
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_sharded_gspmm_matches_local():
    script = r"""
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from repro.core.formats import random_batch
from repro.distributed.spmm import sharded_batched_gspmm
from repro.kernels.ops import batched_gspmm
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
a, m_pad = random_batch(rng, batch=12, dim=24, nnz_per_row=3)  # 12 % 8 != 0
b = jnp.asarray(rng.standard_normal((12, m_pad, 16)), jnp.float32)
for op, red in (("mul", "max"), ("copy_lhs", "mean"), ("add", "sum")):
    ref = batched_gspmm(a, b, op=op, reduce=red, impl="csr", k_pad=8)
    got = sharded_batched_gspmm(a, b, op=op, reduce=red, mesh=mesh,
                                impl="csr", k_pad=8)
    assert float(jnp.max(jnp.abs(ref - got))) < 1e-5, (op, red)

    def loss(f):
        return lambda v, bb: jnp.sum(jnp.tanh(f(a.with_values(v), bb)))

    f_ref = lambda aa, bb: batched_gspmm(aa, bb, op=op, reduce=red,
                                         impl="csr", k_pad=8)
    f_sh = lambda aa, bb: sharded_batched_gspmm(aa, bb, op=op, reduce=red,
                                                mesh=mesh, impl="csr",
                                                k_pad=8)
    gr = jax.grad(loss(f_ref), argnums=(0, 1))(a.values, b)
    gs = jax.grad(loss(f_sh), argnums=(0, 1))(a.values, b)
    assert float(jnp.max(jnp.abs(gr[0] - gs[0]))) < 1e-5, (op, red)
    assert float(jnp.max(jnp.abs(gr[1] - gs[1]))) < 1e-5, (op, red)
print("SHARDED-GSPMM-OK")
"""
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", script, SRC],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "SHARDED-GSPMM-OK" in r.stdout
