"""Degree-binned hybrid SpMM regression tests (DESIGN.md §12).

Covers the hybrid dispatch contract end to end:

- ``plan_hybrid`` static properties (bins tile m_pad SUBLANES-aligned, the
  degenerate ``d_pad = 0`` guard, tau validation);
- degenerate-input guards: all-empty-row batches and rows whose density sits
  EXACTLY at the hub threshold (``deg == dmin`` classifies dense — the ``>=``
  comparison is load-bearing);
- the row-permutation round trip: permute → SpMM → inverse-permute is
  bitwise-stable on outputs and matches the unpermuted gradients for EVERY
  concrete impl in the registry, including zero-nnz and single-long-row
  matrices;
- the cost model's skew pricing: the CSR branch is monotone in the measured
  ``max_deg`` (the serialization bound the kernel actually pays), the hybrid
  branch amortizes only when ``max_deg`` clears ``dmin``, and the workload
  key grows a ``_md`` suffix only when the knob is set (cache back-compat);
- the fused fold-in: ``fused_hybrid`` with residual + ReLU epilogue, and
  mesh-sharded parity.
"""
import dataclasses
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oracle import CONCRETE_SPMM_IMPLS, tols_for
from repro.autotune.cost_model import Workload, estimate, rank, tpu_refusal
from repro.autotune.selector import KINDS
from repro.core.batching import HYBRID_TAU, SUBLANES, plan_hybrid
from repro.core.formats import (
    coo_from_lists,
    random_powerlaw_batch,
    row_degrees,
)
from repro.core.spmm import batched_spmm
from repro.kernels.batched_spmm_hybrid import hybrid_operands


# ---------------------------------------------------------------------------
# plan_hybrid statics
# ---------------------------------------------------------------------------

def test_plan_hybrid_static_properties():
    hp = plan_hybrid(batch=4, m_pad=64, n_b=32, nnz_pad=512)
    assert hp.dmin == math.ceil(HYBRID_TAU * 64)
    assert hp.d_pad % SUBLANES == 0 and 0 < hp.d_pad <= 64
    # bins tile [0, m_pad) exactly, SUBLANES-aligned, in order
    assert hp.bins[0][0] == 0 and hp.bins[-1][1] == 64
    for (_, e), (s2, _) in zip(hp.bins, hp.bins[1:]):
        assert e == s2
    for s, e in hp.bins:
        assert s % SUBLANES == 0 and s < e


def test_plan_hybrid_degenerate_dpad_zero():
    """nnz_pad below dmin: NO row can reach hub density, so the planner must
    not size a dense tile group at all (satellite: never emit an empty MXU
    tile group)."""
    hp = plan_hybrid(batch=2, m_pad=64, n_b=32, nnz_pad=8)
    assert hp.dmin == 16 and hp.d_pad == 0


def test_plan_hybrid_tau_validation():
    for tau in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError):
            plan_hybrid(batch=1, m_pad=8, n_b=8, nnz_pad=8, tau=tau)


# ---------------------------------------------------------------------------
# degenerate-input guards (fails-pre-fix regressions)
# ---------------------------------------------------------------------------

def _empty_sample():
    return (np.zeros(0, np.int32), np.zeros(0, np.int32),
            np.zeros(0, np.float32))


def test_hybrid_dpad_zero_path_matches_ref():
    """m_pad = 64 with an 8-slot budget → dmin = 16 > nnz_pad: the d_pad = 0
    plan must route cleanly through both hybrid siblings (no zero-size MXU
    tile group in the kernel)."""
    rng = np.random.default_rng(3)
    tri = [(np.asarray([0, 1, 2], np.int32), np.asarray([5, 6, 7], np.int32),
            rng.normal(size=3).astype(np.float32)) for _ in range(2)]
    coo = coo_from_lists(tri, [64, 64])
    assert coo.nnz_pad < plan_hybrid(batch=2, m_pad=64, n_b=16,
                                     nnz_pad=coo.nnz_pad).dmin
    b = jnp.asarray(rng.normal(size=(2, 64, 16)), jnp.float32)
    want = np.asarray(batched_spmm(coo, b, impl="ref"))
    for impl in ("hybrid", "pallas_hybrid"):
        got = np.asarray(batched_spmm(coo, b, impl=impl, k_pad=4))
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5,
                                   err_msg=impl)


def test_hybrid_all_empty_rows_matches_ref():
    """Every row empty: degrees are all zero, n_dense must be 0 and both
    siblings must return exact zeros (no garbage from the slab scatter)."""
    coo = coo_from_lists([_empty_sample()] * 3, [24, 24, 24])
    b = jnp.asarray(np.random.default_rng(4).normal(size=(3, 24, 16)),
                    jnp.float32)
    for impl in ("hybrid", "pallas_hybrid"):
        got = np.asarray(batched_spmm(coo, b, impl=impl, k_pad=1))
        assert not got.any(), impl


def test_hybrid_exact_threshold_row_classifies_dense():
    """A row whose degree sits EXACTLY at dmin is a hub (>= comparison):
    its sparse trip count must be zeroed and its nnz must land in the slab.
    Pre-fix (a strict > comparison) the row stays in the slot loop and the
    tile group sized for it is empty."""
    m_pad = 32
    hp = plan_hybrid(batch=1, m_pad=m_pad, n_b=16, nnz_pad=16)
    dmin = hp.dmin                      # 8 at tau = 0.25
    rows = np.concatenate([np.zeros(dmin, np.int32),
                           np.asarray([3, 9], np.int32)])
    cols = np.concatenate([np.arange(dmin, dtype=np.int32),
                           np.asarray([1, 2], np.int32)])
    vals = np.ones(rows.size, np.float32)
    coo = coo_from_lists([(rows, cols, vals)], [m_pad], nnz_pad=16)
    (rank_, start_s, rlen_sparse, rowmax_bins, cid_f, val_f,
     slab) = hybrid_operands(coo.row_ids, coo.col_ids, coo.values, coo.nnz,
                             m_pad, hp)
    deg = np.asarray(row_degrees(coo, m_pad))[0]
    assert deg[0] == dmin
    # row 0 sorts to position 0; as a hub its sparse trip count is zero...
    assert int(np.asarray(rlen_sparse)[0, 0]) == 0
    # ...and ALL of its nnz live in slab row 0 (unit values → sum == dmin)
    assert float(np.asarray(slab)[0, 0].sum()) == float(dmin)
    # the light rows keep their slots in the sparse remainder
    assert int(np.asarray(rlen_sparse)[0].sum()) == 2
    # and the forward stays exact
    b = jnp.asarray(np.random.default_rng(5).normal(size=(1, m_pad, 16)),
                    jnp.float32)
    want = np.asarray(batched_spmm(coo, b, impl="ref"))
    for impl in ("hybrid", "pallas_hybrid"):
        np.testing.assert_allclose(
            np.asarray(batched_spmm(coo, b, impl=impl, k_pad=dmin)), want,
            atol=1e-5, rtol=1e-5, err_msg=impl)


# ---------------------------------------------------------------------------
# row-permutation round trip (every concrete impl)
# ---------------------------------------------------------------------------

def _mixed_batch():
    """Uniform, skewed-with-hub, zero-nnz and single-long-row samples in ONE
    batch — the corners the permutation must survive."""
    rng = np.random.default_rng(6)
    m = 16
    uni_r = np.repeat(np.arange(m, dtype=np.int32), 2)
    uni_c = np.asarray(rng.integers(0, m, uni_r.size), np.int32)
    skew_r = np.concatenate([np.full(8, 2, np.int32),
                             np.asarray([0, 5, 11], np.int32)])
    skew_c = np.asarray(rng.integers(0, m, skew_r.size), np.int32)
    long_r = np.full(m, 7, np.int32)        # ONE row holding every nnz
    long_c = np.arange(m, dtype=np.int32)
    tri = [
        (uni_r, uni_c, rng.normal(size=uni_r.size).astype(np.float32)),
        (skew_r, skew_c, rng.normal(size=skew_r.size).astype(np.float32)),
        _empty_sample(),
        (long_r, long_c, rng.normal(size=m).astype(np.float32)),
    ]
    coo = coo_from_lists(tri, [m] * 4)
    b = jnp.asarray(rng.normal(size=(4, m, 24)), jnp.float32)
    return coo, m, b


@pytest.mark.parametrize("impl", CONCRETE_SPMM_IMPLS)
def test_row_permutation_round_trip(impl):
    """Relabel rows by a random per-sample permutation, run the impl, and
    inverse-permute the output: BITWISE equal to the unpermuted output (the
    per-row accumulation sequence is label-independent), and the gradients
    match the unpermuted ones at the impl's policy tolerance."""
    coo, m_pad, b = _mixed_batch()
    k_pad = m_pad                       # single-long-row needs the full bound
    rng = np.random.default_rng(7)
    pi = np.stack([rng.permutation(m_pad) for _ in range(coo.batch)])
    pi_j = jnp.asarray(pi, jnp.int32)
    coo_p = dataclasses.replace(
        coo, row_ids=jnp.take_along_axis(pi_j, coo.row_ids, axis=1))

    out = np.asarray(batched_spmm(coo, b, impl=impl, k_pad=k_pad))
    out_p = np.asarray(batched_spmm(coo_p, b, impl=impl, k_pad=k_pad))
    recover = np.take_along_axis(out_p, pi[:, :, None], axis=1)
    np.testing.assert_array_equal(recover, out, err_msg=impl)

    # gradients: same loss expressed through the permuted layout must give
    # the same dValues/dB as the unpermuted call
    t = jnp.asarray(np.random.default_rng(8).normal(size=out.shape),
                    jnp.float32)

    def loss(values, bb, a, weight):
        c = batched_spmm(dataclasses.replace(a, values=values), bb,
                         impl=impl, k_pad=k_pad)
        return jnp.sum(c.astype(jnp.float32) * weight)

    t_p = jnp.take_along_axis(
        t, jnp.argsort(pi_j, axis=1)[:, :, None], axis=1)
    g = jax.grad(loss, argnums=(0, 1))(coo.values, b, coo, t)
    g_p = jax.grad(loss, argnums=(0, 1))(coo_p.values, b, coo_p, t_p)
    atol, rtol = tols_for(impl)
    np.testing.assert_allclose(np.asarray(g_p[0]), np.asarray(g[0]),
                               atol=atol, rtol=rtol,
                               err_msg=f"{impl} dvalues")
    np.testing.assert_allclose(np.asarray(g_p[1]), np.asarray(g[1]),
                               atol=atol, rtol=rtol, err_msg=f"{impl} db")


# ---------------------------------------------------------------------------
# cost model: max_deg pricing + amortization rule
# ---------------------------------------------------------------------------

_SKEW_W = Workload(batch=100, m_pad=256, nnz_pad=2048, k_pad=None, n_b=256)


def test_workload_key_max_deg_suffix():
    w = Workload(batch=4, m_pad=64, nnz_pad=256, k_pad=8, n_b=32)
    assert "_md" not in w.key()         # legacy cache keys unchanged
    assert dataclasses.replace(w, max_deg=48).key() == w.key() + "_md48"


def test_csr_cost_prices_max_degree():
    """Satellite regression: the CSR kernel's slot loop serializes on the
    per-matrix MAX row degree, so its estimate must be strictly increasing
    in ``max_deg`` (pre-fix it only priced flat-nnz traffic and was flat)."""
    es = [estimate(dataclasses.replace(_SKEW_W, max_deg=md), "pallas_csr")
          for md in (2, 64, 128, 248)]
    assert es == sorted(es) and len(set(es)) == len(es), es
    # unset max_deg keeps the legacy flat estimate, well below the skew price
    assert 0.0 < estimate(_SKEW_W, "pallas_csr") < es[-1]


def test_hybrid_amortizes_only_under_skew():
    """The amortization rule (DESIGN.md §12): hybrid's permutation + slab
    overhead only pays when the measured max degree clears dmin — uniform
    degrees keep the CSR class ahead, hub degrees flip the order."""
    lo = dataclasses.replace(_SKEW_W, max_deg=4)
    hi = dataclasses.replace(_SKEW_W, max_deg=248)
    assert estimate(lo, "pallas_csr") < estimate(lo, "pallas_hybrid")
    assert estimate(hi, "pallas_hybrid") < estimate(hi, "pallas_csr")
    # the hybrid bound is dmin-1 BY CONSTRUCTION: its estimate is flat in
    # max_deg once above dmin, while csr keeps climbing
    mid = dataclasses.replace(_SKEW_W, max_deg=128)
    assert estimate(mid, "pallas_hybrid") == estimate(hi, "pallas_hybrid")
    # without skew evidence the hybrid class must never win the ranking
    assert KINDS[rank(_SKEW_W, allow_pallas=True)[0][0]] != "hybrid"


def test_hybrid_kinds_registered():
    assert KINDS["hybrid"] == KINDS["pallas_hybrid"] == "hybrid"
    assert KINDS["pallas_hybrid_bf16"] == "hybrid"
    assert KINDS["fused_hybrid"] == "fused"
    # the XLA sibling ranks on both ladders; the Pallas kernel is on
    # neither: interpret mode never ranks Pallas, and Mosaic refuses
    # pallas_hybrid for the TPU (cost_model.TPU_REFUSED)
    ranked = [i for i, _ in rank(_SKEW_W, allow_pallas=True)]
    assert "hybrid" in ranked and "pallas_hybrid" not in ranked
    assert tpu_refusal("pallas_hybrid") and tpu_refusal("pallas_hybrid_bf16")
    assert "pallas_hybrid" not in [
        i for i, _ in rank(_SKEW_W, allow_pallas=False)]


# ---------------------------------------------------------------------------
# powerlaw generator (the bench's skewed-degree geometry family)
# ---------------------------------------------------------------------------

def test_powerlaw_batch_is_skewed():
    rng = np.random.default_rng(9)
    coo, m_pad = random_powerlaw_batch(rng, batch=4, dim=64, avg_deg=4)
    deg = np.asarray(row_degrees(coo, m_pad))
    valid = deg[np.asarray(coo.n_rows)[:, None]
                > np.arange(m_pad)[None, :]]
    assert deg.max() >= 4 * max(1.0, valid.mean())   # hubs well above mean
    hp = plan_hybrid(batch=4, m_pad=m_pad, n_b=64, nnz_pad=coo.nnz_pad)
    assert deg.max() >= hp.dmin         # the hybrid split actually engages


# ---------------------------------------------------------------------------
# fused fold-in: epilogue corners + mesh-sharded parity
# ---------------------------------------------------------------------------

def test_fused_hybrid_residual_relu_matches_fused():
    """The inverse permutation must land BEFORE the residual/ReLU epilogue —
    a permuted residual add would silently mix rows."""
    from repro.core.graph_conv import init_graph_conv, stack_channels
    from repro.kernels.fused_graph_conv import fused_graph_conv

    coo, m_pad, _ = _mixed_batch()
    rng = np.random.default_rng(10)
    rids, cids, vals, nnz = stack_channels([coo, coo])
    x = jnp.asarray(rng.normal(size=(coo.batch, m_pad, 12)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(coo.batch, m_pad, 20)), jnp.float32)
    params = init_graph_conv(jax.random.key(10), 12, 20, 2)
    outs = {}
    for impl in ("fused", "fused_hybrid"):
        outs[impl] = np.asarray(fused_graph_conv(
            rids, cids, vals, nnz, x, params["w"], params["b"],
            epilogue="relu", residual=res, impl=impl))
    np.testing.assert_allclose(outs["fused_hybrid"], outs["fused"],
                               atol=1e-5, rtol=1e-5)


def test_fused_hybrid_sharded_parity():
    """Mesh-sharded fused_hybrid == local fused_hybrid, forward and dX, on a
    2-device host mesh (subprocess: XLA locks the device count at init)."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = r"""
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.core.formats import random_powerlaw_batch
from repro.core.graph_conv import graph_conv_batched, init_graph_conv
rng = np.random.default_rng(1)
coo, m_pad = random_powerlaw_batch(rng, batch=5, dim=24, avg_deg=4)
adj = [coo, coo]
x = jnp.asarray(rng.normal(size=(5, m_pad, 8)), jnp.float32)
params = init_graph_conv(jax.random.PRNGKey(1), 8, 16, 2)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2,), ("data",))
def run(mesh=None):
    def loss(xx):
        return jnp.sum(jnp.sin(graph_conv_batched(
            params, adj, xx, impl="fused_hybrid", epilogue="relu",
            mesh=mesh)))
    return loss(x), jax.grad(loss)(x)
y0, g0 = run()
y1, g1 = run(mesh)
assert float(jnp.abs(y1 - y0).max()) == 0.0, "fwd"
assert float(jnp.abs(g1 - g0).max()) == 0.0, "grad"
print("OK")
"""
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", script, src],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0 and "OK" in r.stdout, r.stdout + r.stderr
