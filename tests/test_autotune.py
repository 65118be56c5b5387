"""Adaptive dispatch (impl="auto") — selector regimes, oracle equivalence,
tuning cache persistence (DESIGN.md §5)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.autotune import (
    Decision,
    TuningCache,
    Workload,
    autotune,
    estimate,
    measure_workload,
    rank,
    select_impl,
)
from repro.core import coo_to_dense, random_batch
from repro.core.spmm import IMPLS, batched_spmm, resolve_impl


# ---------------------------------------------------------------------------
# Selector: the paper's three regimes must pick three different impl classes
# ---------------------------------------------------------------------------

SMALL_DENSE = Workload(batch=100, m_pad=56, nnz_pad=512, k_pad=16, n_b=64)
LARGE_M = Workload(batch=4, m_pad=10000, nnz_pad=40000, k_pad=4, n_b=64)
COL_PANELED = Workload(batch=100, m_pad=2048, nnz_pad=8192, k_pad=4, n_b=512)


def test_selector_small_dense_picks_gemm_class():
    """Small dense-ish matrices: densify + batched GEMM (the paper's §V-A
    gemmBatched observation)."""
    d = select_impl(SMALL_DENSE)
    assert d.kind == "gemm", d
    assert d.case == 1
    assert d.impl in ("dense", "pallas_gemm")


def test_selector_large_m_forces_case3_fallback():
    """m_pad > LARGE_M: planner case 3, per-sample fallback, no batching."""
    d = select_impl(LARGE_M)
    assert d.case == 3
    assert d.impl == "ref"
    assert d.source == "forced"


def test_selector_column_paneled_picks_ell_class():
    """Case 2 (n_b split into column panels), sparse rows: the row-split ELL
    kernel — the paper's headline batched SpMM."""
    d = select_impl(COL_PANELED)
    assert d.kind == "ell", d
    assert d.case == 2
    assert d.plan.p > 1


def test_three_regimes_are_three_different_classes():
    kinds = {select_impl(w).kind for w in (SMALL_DENSE, LARGE_M, COL_PANELED)}
    assert len(kinds) == 3, kinds


def test_allow_pallas_switches_backend_not_class():
    """interpret=True (CPU) must not pick Pallas impls, but the kernel CLASS
    decision is backend-independent."""
    for w in (SMALL_DENSE, COL_PANELED):
        d_tpu = select_impl(w, allow_pallas=True)
        d_cpu = select_impl(w, allow_pallas=False)
        assert d_tpu.kind == d_cpu.kind
        assert not d_cpu.impl.startswith("pallas")


def test_selector_skewed_degree_picks_csr_class():
    """avg degree (nnz_pad/m_pad) well below k_pad: the CSR row-split —
    flat nnz traffic, rpt-bounded loop — beats ELL's padded m_pad·k_pad
    slots (GE-SpMM's skewed-degree case, DESIGN.md §9)."""
    w = Workload(batch=100, m_pad=2048, nnz_pad=8192, k_pad=8, n_b=512)
    # the model still prices the Pallas row-split under the padded ELL
    # kernel — that is the layout argument...
    assert estimate(w, "pallas_csr") < estimate(w, "pallas_ell")
    # ...but Mosaic refuses pallas_csr for the TPU (cost_model.TPU_REFUSED),
    # so neither posture can pick it. The XLA csr fallback is a segment-sum —
    # same scatter traffic as ref plus the rpt arrays — so both postures
    # keep the scatter/csr class, and neither pays ELL's padded slots
    for allow_pallas in (True, False):
        d = select_impl(w, allow_pallas=allow_pallas)
        assert d.kind in ("csr", "scatter"), d
        assert "pallas_csr" not in {i for i, _ in d.scores}


def test_csr_runnable_without_k_pad():
    """CSR has no per-row bound, so unlike the ELL class it stays a
    candidate when k_pad is unknown."""
    w = Workload(batch=100, m_pad=2048, nnz_pad=8192, k_pad=None, n_b=512)
    impls = {i for i, _ in rank(w)}
    assert {"csr"} <= impls
    assert not impls & {"ell", "pallas_ell"}


def test_no_k_pad_excludes_ell_class():
    w = Workload(batch=100, m_pad=2048, nnz_pad=8192, k_pad=None, n_b=512)
    d = select_impl(w)
    assert d.kind != "ell"
    assert all(i not in ("ell", "pallas_ell") for i, _ in d.scores)


def test_rank_is_complete_and_sorted():
    scored = rank(SMALL_DENSE, allow_pallas=True)
    ts = [t for _, t in scored]
    assert ts == sorted(ts)
    assert {i for i, _ in scored} <= set(IMPLS)
    assert "loop" in {i for i, _ in scored}   # baseline is ranked, never inf


# ---------------------------------------------------------------------------
# impl="auto" end-to-end: numerics match the ref oracle in every regime
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch,dim,nnz,n_b", [
    (8, 20, 2, 16),      # small sparse (quickstart-like)
    (6, 40, 8, 64),      # small dense-ish
    (4, 60, 2, 200),     # wider n_b
])
def test_auto_matches_dense_oracle(batch, dim, nnz, n_b):
    rng = np.random.default_rng(batch + dim)
    coo, m_pad = random_batch(rng, batch=batch, dim=dim, nnz_per_row=nnz)
    b = jnp.asarray(rng.normal(size=(batch, m_pad, n_b)), jnp.float32)
    want = np.asarray(jnp.einsum("bij,bjk->bik", coo_to_dense(coo, m_pad), b))
    got = np.asarray(batched_spmm(coo, b, impl="auto", k_pad=nnz + 2))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_auto_is_default_and_jit_safe():
    rng = np.random.default_rng(0)
    coo, m_pad = random_batch(rng, batch=4, dim=16, nnz_per_row=2)
    b = jnp.asarray(rng.normal(size=(4, m_pad, 8)), jnp.float32)
    fn = jax.jit(functools.partial(batched_spmm, k_pad=4))   # impl defaults
    got = np.asarray(fn(coo, b))
    want = np.asarray(batched_spmm(coo, b, impl="ref"))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_auto_differentiable():
    rng = np.random.default_rng(3)
    coo, m_pad = random_batch(rng, batch=3, dim=12, nnz_per_row=2)
    b = jnp.asarray(rng.normal(size=(3, m_pad, 8)), jnp.float32)

    def loss(values, bb, impl):
        return jnp.sum(batched_spmm(coo.with_values(values), bb,
                                    impl=impl, k_pad=4) ** 2)

    g_auto = jax.grad(loss, argnums=(0, 1))(coo.values, b, "auto")
    g_ref = jax.grad(loss, argnums=(0, 1))(coo.values, b, "ref")
    for ga, gr in zip(g_auto, g_ref):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gr),
                                   atol=1e-4, rtol=1e-4)


def test_resolve_impl_exposes_decision():
    rng = np.random.default_rng(1)
    coo, m_pad = random_batch(rng, batch=4, dim=16, nnz_per_row=2)
    b = jnp.asarray(rng.normal(size=(4, m_pad, 8)), jnp.float32)
    d = resolve_impl(coo, b, k_pad=4)
    assert isinstance(d, Decision)
    assert d.impl in IMPLS and d.impl != "auto"
    assert d.reason
    pinned = resolve_impl(coo, b, impl="dense", k_pad=4)
    assert pinned.impl == "dense" and pinned.source == "forced"


# ---------------------------------------------------------------------------
# Planner case boundaries drive the expected impl class
# ---------------------------------------------------------------------------

def test_case_boundaries():
    # case 1: one panel, tiny working set
    w1 = Workload(batch=10, m_pad=64, nnz_pad=256, k_pad=8, n_b=64)
    d1 = select_impl(w1)
    assert d1.case == 1 and d1.plan.p == 1
    # case 2: same rows, wide n_b → panels. Avg degree (nnz_pad/m_pad = 4)
    # is half of k_pad=8, so the model prices the CSR row-split — flat nnz
    # traffic — under ELL, which pays the padded m_pad·k_pad slots
    # (GE-SpMM's skewed-degree case, DESIGN.md §9). The Pallas CSR kernel is
    # refused for the TPU (cost_model.TPU_REFUSED), so the compiled ladder
    # lands elsewhere — but never on ELL.
    w2 = Workload(batch=10, m_pad=2048, nnz_pad=8192, k_pad=8, n_b=4096)
    d2 = select_impl(w2)
    assert d2.case == 2 and d2.plan.p > 1
    assert estimate(w2, "pallas_csr") < estimate(w2, "pallas_ell")
    assert d2.kind != "ell"
    # case 3: over the LARGE_M threshold
    w3 = Workload(batch=2, m_pad=8200, nnz_pad=16400, k_pad=8, n_b=64)
    d3 = select_impl(w3)
    assert d3.case == 3 and d3.impl == "ref"


# ---------------------------------------------------------------------------
# Tuning cache: persistence + measured override
# ---------------------------------------------------------------------------

def test_cache_roundtrip(tmp_path):
    path = str(tmp_path / "tune.json")
    cache = TuningCache(path)
    w = Workload(batch=4, m_pad=16, nnz_pad=64, k_pad=4, n_b=8)
    best = cache.put(w.key(), {"ref": 2e-4, "ell": 1e-4, "dense": 3e-4},
                     interpret=True)
    assert best == "ell"
    reloaded = TuningCache(path)
    assert reloaded.best(w.key()) == "ell"
    assert reloaded.times(w.key())["dense"] == pytest.approx(3e-4)


def test_cache_merge_on_save_unions_writers(tmp_path):
    """Regression (ISSUE 10): two caches sharing one path used to be
    last-write-wins — the second save silently dropped the first writer's
    records. save() now re-reads the file and unions: disk-only keys
    survive, shared keys merge their times at per-impl min with ``best``
    recomputed."""
    path = str(tmp_path / "tune.json")
    a = TuningCache(path)
    b = TuningCache(path)       # opened before a writes anything
    a.put("k_a", {"ref": 2e-4, "ell": 3e-4}, interpret=True)
    b.put("k_b", {"dense": 1e-4}, interpret=True)   # pre-fix: clobbered k_a
    merged = TuningCache(path)
    assert set(merged.records) == {"k_a", "k_b"}
    assert merged.best("k_a") == "ref" and merged.best("k_b") == "dense"
    # shared key: per-impl min, best recomputed from the merged map
    c = TuningCache(path)
    c.records.pop("k_b")        # this writer never measured k_b
    c.put("k_a", {"ref": 5e-4, "dense": 0.5e-4}, interpret=True)
    final = TuningCache(path)
    assert set(final.records) == {"k_a", "k_b"}     # k_b still survives
    assert final.times("k_a") == pytest.approx(
        {"ref": 2e-4, "ell": 3e-4, "dense": 0.5e-4})
    assert final.best("k_a") == "dense"
    # the merged view is also what the saving process sees afterwards
    assert c.best("k_a") == "dense"


def test_cache_overrides_model_selection(tmp_path):
    cache = TuningCache(str(tmp_path / "tune.json"))
    w = SMALL_DENSE
    assert select_impl(w, cache=cache).source == "model"
    cache.put(w.key(), {"ref": 1e-6, "dense": 9e-4}, interpret=True)
    d = select_impl(w, cache=cache)
    assert d.source == "cache" and d.impl == "ref"


def test_cache_ignores_unrunnable_measured_winner(tmp_path):
    cache = TuningCache(str(tmp_path / "tune.json"))
    w = SMALL_DENSE
    cache.put(w.key(), {"pallas_gemm": 1e-6}, interpret=True)
    # pallas not allowed on CPU → measured winner not runnable → model wins
    d = select_impl(w, allow_pallas=False, cache=cache)
    assert d.source == "model"


def test_autotune_measures_and_caches(tmp_path):
    cache = TuningCache(str(tmp_path / "tune.json"))
    w = Workload(batch=4, m_pad=16, nnz_pad=64, k_pad=4, n_b=8)
    best = autotune(w, cache=cache, impls=("ref", "ell"), interpret=True)
    assert best in ("ref", "ell")
    times = cache.times(w.key())
    assert set(times) == {"ref", "ell"}
    assert all(t > 0 for t in times.values())
    # memoized: second call returns without measuring (same record object)
    assert autotune(w, cache=cache) == best


def test_measured_cache_selects_csr_for_fig8_geometry(tmp_path, monkeypatch):
    """Acceptance (ISSUE 5): ``impl="auto"`` can select a CSR impl for a
    Fig. 8 geometry via the MEASURED tuning cache, end-to-end through the
    ``$REPRO_TUNE_CACHE`` default-cache path — and the selected impl matches
    the oracle."""
    from repro.autotune import cache as cache_mod

    rng = np.random.default_rng(0)
    coo, m_pad = random_batch(rng, batch=20, dim=20, nnz_per_row=2)  # fig8
    b = jnp.asarray(rng.normal(size=(20, m_pad, 64)), jnp.float32)
    w = Workload(batch=20, m_pad=m_pad, nnz_pad=coo.nnz_pad, k_pad=4, n_b=64)

    path = str(tmp_path / "tune.json")
    cache = TuningCache(path)
    # the user-side refresh workflow: measure the CSR class on this exact
    # workload key and persist the record
    times = measure_workload(w, ("csr",), interpret=True, warmup=1, iters=2)
    assert set(times) == {"csr"} and times["csr"] > 0
    cache.put(w.key(), times, interpret=True)

    monkeypatch.setenv(cache_mod.ENV_VAR, path)
    cache_mod._cache_for.cache_clear()    # the default cache memoizes by path
    try:
        d = resolve_impl(coo, b, impl="auto", k_pad=4)
        assert d.impl == "csr" and d.source == "cache", d
        got = np.asarray(batched_spmm(coo, b, impl=d.impl, k_pad=4))
        want = np.asarray(batched_spmm(coo, b, impl="ref"))
        np.testing.assert_allclose(got, want, atol=1e-4)
    finally:
        cache_mod._cache_for.cache_clear()


def test_measure_workload_returns_sane_times():
    w = Workload(batch=2, m_pad=16, nnz_pad=32, k_pad=4, n_b=8)
    times = measure_workload(w, ("ref", "dense"), interpret=True,
                             warmup=1, iters=2)
    assert set(times) == {"ref", "dense"}
    assert all(0 < t < 60 for t in times.values())


def test_measure_workload_back_to_back_calls(monkeypatch):
    """``calls`` times that many back-to-back calls per timing, blocks once,
    and reports the time per call."""
    import jax

    w = Workload(batch=2, m_pad=16, nnz_pad=32, k_pad=4, n_b=8)
    blocks = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocks.append(1) or real(x))
    times = measure_workload(w, ("ref",), interpret=True, warmup=1, iters=3,
                             calls=4)
    assert len(blocks) == 1 + 3          # warm-up, then once per timing
    assert 0 < times["ref"] < 60


def test_measure_workload_raises_on_failing_candidate():
    """A candidate that cannot compile or run must not vanish from the
    record in silence: measuring it raises, naming the impl."""
    w = Workload(batch=2, m_pad=16, nnz_pad=32, k_pad=None, n_b=8)
    with pytest.raises(RuntimeError, match="'pallas_ell'"):
        measure_workload(w, ("ref", "pallas_ell"), interpret=True,
                         warmup=1, iters=1)


# ---------------------------------------------------------------------------
# Consumers route through impl="auto" by default
# ---------------------------------------------------------------------------

def test_gcn_config_defaults_to_auto():
    from repro.core.gcn import GCNConfig

    assert GCNConfig().impl == "auto"
    assert GCNConfig.tox21().impl == "auto"


def test_trainer_and_serving_consume_auto(tmp_path):
    """GCNTrainer trains and GraphServeEngine serves with the default
    (adaptive) impl — the whole consumer path exercises the dispatcher."""
    from repro.core.gcn import GCNConfig
    from repro.data.graphs import GraphDatasetSpec, batches, generate
    from repro.serving import GraphRequest, GraphServeEngine
    from repro.training import GCNTrainer, TrainerConfig

    spec = GraphDatasetSpec.tox21_like(n_samples=32)
    data = generate(spec)
    cfg = GCNConfig.tox21()
    trainer = GCNTrainer(cfg, tcfg=TrainerConfig(
        checkpoint_dir=str(tmp_path), checkpoint_every=1000))
    params, _, metrics = trainer.fit(
        lambda e: batches(data, spec, 16, seed=e), epochs=1)
    assert np.isfinite(metrics["loss"])

    reqs = [GraphRequest(rows=s.rows, cols=s.cols, features=s.features,
                         n_nodes=s.n_nodes) for s in data[:3]]
    out = GraphServeEngine(params, cfg, batch=4).run(reqs)
    assert all(r.done and r.logits.shape == (cfg.n_tasks,) for r in out)
