"""Fused graph-conv megakernel (DESIGN.md §7): oracle equivalence vs the
``impl="ref"`` layer across channel counts, skewed-nnz batches, gradients
through values/X/W/b, the epilogue, skew-aware packing, and the autotuner
registration of ``impl="fused"``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batching, random_batch
from repro.core.batching import CHUNK, chunk_counts, plan_fused_graph_conv
from repro.core.formats import coo_from_lists
from repro.core.graph_conv import (
    graph_conv_batched,
    graph_conv_nonbatched,
    init_graph_conv,
    resolve_graph_conv_impl,
    stack_channels,
)
from repro.kernels.fused_graph_conv import fused_graph_conv, runtime_chunks


def _layer_case(seed, batch, dim, nnz, channels, n_in, n_out):
    rng = np.random.default_rng(seed)
    adj, m_pads = [], []
    for _ in range(channels):
        coo, mp = random_batch(rng, batch=batch, dim=dim, nnz_per_row=nnz)
        adj.append(coo)
        m_pads.append(mp)
    m_pad = max(m_pads)
    x = jnp.asarray(rng.normal(size=(batch, m_pad, n_in)), jnp.float32)
    params = init_graph_conv(jax.random.key(seed), n_in, n_out, channels)
    return params, adj, x


def _skewed_case(seed, channels=3, n_in=24, n_out=48):
    """One giant graph in a batch of tiny ones — the padded-nnz waste case."""
    rng = np.random.default_rng(seed)
    n_nodes = [40, 6, 8, 5]
    adj = []
    for _ in range(channels):
        triples = []
        for n in n_nodes:
            k = (n * 8) if n > 20 else 2        # heavy skew: 320 vs 2 nnz
            r = rng.integers(0, n, k).astype(np.int32)
            c = rng.integers(0, n, k).astype(np.int32)
            triples.append((r, c, rng.normal(size=k).astype(np.float32)))
        adj.append(coo_from_lists(triples, n_nodes))
    m_pad = -(-max(n_nodes) // 8) * 8
    x = jnp.asarray(rng.normal(size=(len(n_nodes), m_pad, n_in)), jnp.float32)
    params = init_graph_conv(jax.random.key(seed), n_in, n_out, channels)
    return params, adj, x


# ---------------------------------------------------------------------------
# Forward: fused == ref oracle across channel counts and shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("channels", [1, 2, 4])
@pytest.mark.parametrize("batch,dim,nnz,n_in,n_out", [
    (4, 24, 2, 16, 32),             # tiny
    (6, (10, 50), (1, 4), 62, 64),  # ChemGCN regime (mixed sizes)
    (2, 48, 3, 30, 260),            # non-multiple-of-128 n_out (panel path)
])
def test_fused_matches_ref(channels, batch, dim, nnz, n_in, n_out):
    params, adj, x = _layer_case(0, batch, dim, nnz, channels, n_in, n_out)
    want = graph_conv_batched(params, adj, x, impl="ref")
    got = graph_conv_batched(params, adj, x, impl="fused")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * max(n_in, 16), rtol=1e-5)


def test_fused_matches_ref_on_skewed_batch():
    params, adj, x = _skewed_case(1)
    want = graph_conv_batched(params, adj, x, impl="ref")
    got = graph_conv_batched(params, adj, x, impl="fused")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-5)
    # the skew-aware loop bound really differs per sample
    _, _, _, nnz = stack_channels(adj)
    chunks = np.asarray(runtime_chunks(nnz))
    assert chunks.min() < chunks.max()          # skew is visible to the kernel


def test_fused_zero_nnz_samples_are_inert():
    """§IV-C padding invariant under skew-aware packing: a zero-nnz sample
    runs ZERO chunks and still writes its (zero) output."""
    params, adj, x = _layer_case(2, 4, 16, 2, 2, 8, 16)
    adj = [dataclasses.replace(
        a, values=a.values.at[0].set(0.0), nnz=a.nnz.at[0].set(0))
        for a in adj]
    _, _, _, nnz = stack_channels(adj)
    assert int(runtime_chunks(nnz)[0].sum()) == 0
    want = graph_conv_batched(params, adj, x, impl="ref")
    got = graph_conv_batched(params, adj, x, impl="fused")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# Gradients: jax.grad through values / X / W / b matches the ref layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ["uniform", "skewed"])
def test_fused_grads_match_ref(case):
    if case == "uniform":
        params, adj, x = _layer_case(3, 4, (10, 30), (1, 4), 2, 12, 24)
    else:
        params, adj, x = _skewed_case(3)
    rids, cids, vals, nnz = stack_channels(adj)

    def loss_fused(vals, x, w, b):
        y = fused_graph_conv(rids, cids, vals, nnz, x, w, b)
        return jnp.sum(jnp.tanh(y))

    def loss_ref(vals, x, w, b):
        adj2 = [dataclasses.replace(a, values=vals[:, ch])
                for ch, a in enumerate(adj)]
        y = graph_conv_batched({"w": w, "b": b}, adj2, x, impl="ref")
        return jnp.sum(jnp.tanh(y))

    args = (vals, x, params["w"], params["b"])
    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(*args)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(*args)
    for name, gf, gr in zip(("dvalues", "dx", "dw", "db"), g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


def test_fused_epilogue_and_residual():
    params, adj, x = _layer_case(4, 3, 20, 2, 2, 10, 20)
    rids, cids, vals, nnz = stack_channels(adj)
    res = jnp.asarray(np.random.default_rng(4).normal(
        size=(x.shape[0], x.shape[1], 20)), jnp.float32)
    base = graph_conv_batched(params, adj, x, impl="ref")
    got = fused_graph_conv(rids, cids, vals, nnz, x, params["w"], params["b"],
                           epilogue="relu", residual=res)
    np.testing.assert_allclose(np.asarray(got),
                               np.maximum(np.asarray(base + res), 0.0),
                               atol=1e-5)
    # residual is differentiable too: d(relu(y+r))/dr == relu mask
    g = jax.grad(lambda r: jnp.sum(fused_graph_conv(
        rids, cids, vals, nnz, x, params["w"], params["b"],
        epilogue="relu", residual=r)))(res)
    np.testing.assert_allclose(np.asarray(g),
                               (np.asarray(base + res) > 0).astype(np.float32),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# Shared oracle harness (tests/oracle.py, ISSUE 6): the fused layer class —
# f32 megakernel AND its bf16 variant — over the same uniform/skewed/zero-nnz
# matrix as every SpMM impl, at per-policy tolerance.
# ---------------------------------------------------------------------------

from oracle import LAYER_IMPLS, check_layer_forward, check_layer_grads  # noqa: E402


@pytest.mark.parametrize("impl", LAYER_IMPLS)
def test_layer_matrix_forward_matches_ref(impl):
    check_layer_forward(impl)


@pytest.mark.parametrize("impl", LAYER_IMPLS)
def test_layer_matrix_grads_match_ref(impl):
    check_layer_grads(impl)


def test_fused_bf16_registered_and_ranked():
    """fused_bf16 is a first-class layer candidate: admitted by rank_layer
    under a reduced dtype policy, absent at f32, and resolvable end-to-end
    through graph_conv_batched(impl='auto', precision='bf16')."""
    from repro.autotune import KINDS, Workload, rank_layer

    assert KINDS["fused_bf16"] == KINDS["fused"] == "fused"
    w = Workload(batch=100, m_pad=56, nnz_pad=512, k_pad=8, n_b=64,
                 channels=4, n_in=62, nnz_avg=128, dtype="bf16")
    cands = [i for i, _ in rank_layer(w, allow_pallas=True)]
    assert "fused_bf16" in cands
    wf = dataclasses.replace(w, dtype="f32")
    assert "fused_bf16" not in [i for i, _ in rank_layer(wf,
                                                         allow_pallas=True)]


# ---------------------------------------------------------------------------
# Skew-aware packing plan
# ---------------------------------------------------------------------------

def test_plan_fused_sample_chunks():
    nnz = [5, 300, 129, 0]
    plan = plan_fused_graph_conv(batch=4, m_pad=64, n_in=32, n_out=64,
                                 channels=2, nnz_pad=512,
                                 nnz_per_sample=nnz)
    assert plan.sample_chunks == chunk_counts(nnz) == (1, 3, 2, 0)
    assert plan.max_chunks == 3
    # skew-oblivious bound would be ceil(512/128) = 4 chunks for EVERY sample
    assert all(c <= -(-512 // CHUNK) for c in plan.sample_chunks)
    # per-(sample × channel) rows: the SUM of ceils the channel loop runs
    # (ceils do not commute with the channel sum: [1,1,1,1] → 4, not 1)
    assert chunk_counts([[1, 1, 1, 1], [300, 0, 5, 0]]) == (4, 4)
    per_ch = np.array([[5, 0], [200, 100], [129, 0], [0, 0]])
    assert chunk_counts(per_ch) == (1, 3, 2, 0)
    # runtime (trace-safe) counts agree with the static audit
    rt = np.asarray(runtime_chunks(jnp.asarray(per_ch))).sum(1)
    assert tuple(rt) == chunk_counts(per_ch)


def test_plan_fused_panels_wide_output():
    plan = plan_fused_graph_conv(batch=8, m_pad=2048, n_in=64, n_out=4096,
                                 channels=4, nnz_pad=8192)
    assert plan.case == 2 and plan.p > 1
    assert plan.n_block % batching.LANES == 0
    assert plan.bytes_per_step <= batching.VMEM_TILE_BUDGET * 1.01
    # the X panel + indices are fixed costs the column split cannot shrink:
    # with a large n_in the plan bottoms out at one-lane-tile panels
    floor = plan_fused_graph_conv(batch=8, m_pad=1024, n_in=512, n_out=4096,
                                  channels=4, nnz_pad=8192)
    assert floor.n_block == batching.LANES and floor.case == 2
    # ... and an X panel that alone overflows the scoped VMEM is not offered
    huge = plan_fused_graph_conv(batch=8, m_pad=2048, n_in=2048, n_out=4096,
                                 channels=4, nnz_pad=8192)
    assert huge.case == 3


def test_fused_rejects_case3():
    plan = plan_fused_graph_conv(batch=2, m_pad=10000, n_in=8, n_out=8,
                                 channels=1, nnz_pad=64)
    assert plan.case == 3
    z = jnp.zeros((2, 1, 64), jnp.int32)
    with pytest.raises(ValueError, match="case 3"):
        fused_graph_conv(z, z, z.astype(jnp.float32),
                         jnp.zeros((2, 1), jnp.int32),
                         jnp.zeros((2, 10000, 8), jnp.float32),
                         jnp.zeros((1, 8, 8), jnp.float32),
                         jnp.zeros((1, 8), jnp.float32))


# ---------------------------------------------------------------------------
# Autotuner registration: impl="fused" is selectable
# ---------------------------------------------------------------------------

def test_autotuner_selects_fused_for_gcn_layer():
    from repro.autotune import Workload, rank_layer, select_graph_conv_impl

    # a layer of larger graphs over a skewed batch: nnz_pad is the batch
    # max, the MEAN nnz (skew knob) is what the fused kernel's per-sample
    # loop actually pays
    w = Workload(batch=100, m_pad=256, nnz_pad=4096, k_pad=8, n_b=64,
                 channels=4, n_in=62, nnz_avg=128)
    d = select_graph_conv_impl(w, allow_pallas=True)
    assert d.impl == "fused" and d.kind == "fused" and d.source == "model"
    assert ("fused" in {i for i, _ in d.scores})
    # CPU/interpret posture: the Pallas megakernel is never a candidate
    d_cpu = select_graph_conv_impl(w, allow_pallas=False)
    assert d_cpu.impl != "fused"
    assert all(i != "fused" for i, _ in rank_layer(w, allow_pallas=False))


def test_autotuner_fused_skew_awareness_lowers_cost():
    from repro.autotune import Workload, estimate_layer

    dense_w = Workload(batch=64, m_pad=56, nnz_pad=1024, k_pad=8, n_b=64,
                       channels=4, n_in=62)
    skewed = dataclasses.replace(dense_w, nnz_avg=128)   # mean ≪ padded max
    assert estimate_layer(skewed, "fused") < estimate_layer(dense_w, "fused")


def test_fused_workload_key_distinct_and_backcompat():
    from repro.autotune import Workload

    w = Workload(batch=4, m_pad=16, nnz_pad=64, k_pad=4, n_b=8)
    assert w.key() == "b4_m16_nnz64_k4_n8_i4"     # unchanged for plain SpMM
    wl = dataclasses.replace(w, channels=4, n_in=62)
    assert wl.key() != w.key() and "_c4_" in wl.key()


def test_forced_layer_decision_reports_layer_plan():
    """A pinned layer impl must audit the plan the layer actually runs —
    the fused kernel's own plan, not a bare per-channel SpMM plan."""
    from repro.autotune import Workload, forced_decision

    w = Workload(batch=32, m_pad=56, nnz_pad=512, k_pad=8, n_b=64,
                 channels=4, n_in=62)
    d = forced_decision(w, "fused")
    want = plan_fused_graph_conv(batch=32, m_pad=56, n_in=62, n_out=64,
                                 channels=4, nnz_pad=512)
    assert d.plan == want
    # stacked fallback impls audit the (channels·batch) stacked plan
    d_ref = forced_decision(w, "ref")
    assert d_ref.plan.batch == 32 * 4


def test_layer_workload_autotune_measures_the_layer(tmp_path):
    """A channels-aware Workload in the tuning cache must be measured as the
    LAYER it keys (graph_conv_batched per candidate), and the measured
    winner must then drive select_graph_conv_impl."""
    from repro.autotune import (TuningCache, Workload, autotune,
                                select_graph_conv_impl)

    cache = TuningCache(str(tmp_path / "tune.json"))
    w = Workload(batch=4, m_pad=16, nnz_pad=64, k_pad=4, n_b=8,
                 channels=2, n_in=6)
    best = autotune(w, cache=cache, impls=("ref", "dense"), interpret=True)
    assert best in ("ref", "dense")
    assert set(cache.times(w.key())) == {"ref", "dense"}
    d = select_graph_conv_impl(w, allow_pallas=False, cache=cache)
    assert d.source == "cache" and d.impl == best


def test_batched_spmm_rejects_fused():
    from repro.core.spmm import IMPLS, batched_spmm

    assert "fused" in IMPLS
    rng = np.random.default_rng(0)
    coo, m_pad = random_batch(rng, batch=2, dim=8, nnz_per_row=1)
    b = jnp.zeros((2, m_pad, 4), jnp.float32)
    with pytest.raises(ValueError, match="graph_conv"):
        batched_spmm(coo, b, impl="fused")


def test_graph_conv_auto_resolves_layer_workload():
    params, adj, x = _layer_case(5, 4, 20, 2, 2, 12, 16)
    d = resolve_graph_conv_impl(adj, x, 16, interpret=True)
    assert d.impl != "fused"          # interpret posture → no Pallas
    d_tpu = resolve_graph_conv_impl(adj, x, 16, interpret=False)
    assert d_tpu.impl in ("fused", "pallas_coo", "pallas_ell", "pallas_gemm",
                          "ref", "ell", "dense", "loop")
    want = graph_conv_batched(params, adj, x, impl="ref")
    got = graph_conv_batched(params, adj, x, impl="auto")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


# ---------------------------------------------------------------------------
# Stacked fallback (one (channels·batch) SpMM) and the whole-model path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "dense", "pallas_coo"])
def test_stacked_fallback_matches_nonbatched(impl):
    params, adj, x = _layer_case(6, 5, (8, 30), (1, 3), 3, 14, 28)
    want = graph_conv_nonbatched(params, adj, x)
    got = graph_conv_batched(params, adj, x, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-5, err_msg=impl)


def test_stacked_fallback_mixed_channel_nnz_pad():
    """Channels with different nnz_pad stack fine (zero-padded to the max)."""
    rng = np.random.default_rng(7)
    n_nodes = [10, 12]
    t1 = [(np.array([0, 1], np.int32), np.array([1, 0], np.int32),
           np.ones(2, np.float32)) for _ in n_nodes]
    t2 = [(np.arange(9, dtype=np.int32), np.arange(9, dtype=np.int32),
           np.ones(9, np.float32)) for _ in n_nodes]
    adj = [coo_from_lists(t1, n_nodes), coo_from_lists(t2, n_nodes)]
    assert adj[0].nnz_pad != adj[1].nnz_pad
    m_pad = 16
    x = jnp.asarray(rng.normal(size=(2, m_pad, 6)), jnp.float32)
    params = init_graph_conv(jax.random.key(7), 6, 12, 2)
    want = graph_conv_nonbatched(params, adj, x)
    for impl in ("ref", "fused"):
        got = graph_conv_batched(params, adj, x, impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, err_msg=impl)


def test_gcn_trains_with_fused_impl(tmp_path):
    """End-to-end: GCNTrainer with cfg.impl='fused' — the megakernel's VJP
    carries a real training step."""
    from repro.core.gcn import GCNConfig
    from repro.data.graphs import GraphDatasetSpec, batches, generate
    from repro.training import GCNTrainer, TrainerConfig

    spec = GraphDatasetSpec.tox21_like(n_samples=16)
    data = generate(spec)
    cfg = GCNConfig.tox21(impl="fused")
    trainer = GCNTrainer(cfg, tcfg=TrainerConfig(
        checkpoint_dir=str(tmp_path), checkpoint_every=1000))
    batch = next(iter(batches(data, spec, 8)))
    d = trainer.layer_decision(batch)
    assert d.impl == "fused" and d.source == "forced"
    params, _, metrics = trainer.fit(
        lambda e: batches(data, spec, 8, seed=e), epochs=1)
    assert np.isfinite(metrics["loss"])

    # identical logits as the ref-impl model with identical params
    from repro.core.gcn import apply_gcn
    b = next(iter(batches(data, spec, 8)))
    lf = apply_gcn(params, cfg, b["adj"], b["x"], b["n_nodes"])
    lr = apply_gcn(params, dataclasses.replace(cfg, impl="ref"),
                   b["adj"], b["x"], b["n_nodes"])
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lr), atol=1e-4)


def test_graph_serve_engine_reports_layer_decision():
    from repro.core.gcn import GCNConfig, init_gcn
    from repro.serving import GraphServeEngine

    cfg = GCNConfig.tox21(impl="fused")
    params = init_gcn(jax.random.key(0), cfg)
    eng = GraphServeEngine(params, cfg, batch=4, m_pad=16, nnz_pad=64)
    d = eng.layer_decision()
    assert d.impl == "fused" and d.source == "forced"


# ---------------------------------------------------------------------------
# default_interpret resolver (REPRO_INTERPRET)
# ---------------------------------------------------------------------------

def test_default_interpret_resolver(monkeypatch):
    from repro.kernels import default_interpret, resolve_interpret

    monkeypatch.delenv("REPRO_INTERPRET", raising=False)
    assert default_interpret() is True          # CPU backend → interpret
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    assert default_interpret() is False
    assert resolve_interpret(None) is False
    monkeypatch.setenv("REPRO_INTERPRET", "true")
    assert default_interpret() is True
    assert resolve_interpret(False) is False    # explicit beats env
    monkeypatch.setenv("REPRO_INTERPRET", "maybe")
    with pytest.raises(ValueError, match="REPRO_INTERPRET"):
        default_interpret()


def test_require_compiled_refuses_cpu_and_interpret(monkeypatch):
    """The compiled-posture guard: a non-TPU backend raises, and on a TPU
    REPRO_INTERPRET=1 or an explicit interpret=True raises too — while
    default_interpret()'s CPU behaviour stays as it was."""
    import jax

    from repro.kernels import default_interpret, require_compiled

    monkeypatch.delenv("REPRO_INTERPRET", raising=False)
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        require_compiled()
    assert default_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    require_compiled()                          # TPU, compiled: passes
    require_compiled(False)
    with pytest.raises(RuntimeError, match="interpret=True"):
        require_compiled(True)
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="REPRO_INTERPRET=1"):
        require_compiled()


def test_compile_cache_default_dir_is_fixed_and_ignored():
    """Without $JAX_COMPILATION_CACHE_DIR the entry points cache compiles in
    one fixed directory inside the checkout, which git ignores."""
    import pathlib

    from repro import compile_cache

    root = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.DEFAULT_DIR == root / ".jax_cache"
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
