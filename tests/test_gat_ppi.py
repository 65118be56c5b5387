"""GAT in its PPI configuration (``GCNConfig.ppi_gat``) against the plain
dense reference ``chipbench/reference_gat.py``, at a small size: per-node
logits, loss, gradients and three Adam steps through ``GCNTrainer.fit``;
the layer's pieces (zero-degree rows, head mean, skip projection, the
scalar-attention SpMM against the vector-edge form it replaced); and the
``impl="auto"`` picks on both the tox21 and the PPI shapes."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from chipbench import reference_gat  # noqa: E402
from repro.autotune import Workload, gat_attention_path  # noqa: E402
from repro.autotune.cost_model import estimate  # noqa: E402
from repro.core.formats import BatchedCOO  # noqa: E402
from repro.core.gcn import (  # noqa: E402
    GCNConfig,
    apply_gcn,
    gcn_loss,
    init_gcn,
    resolve_conv_impls,
)
from repro.data.graphs import (  # noqa: E402
    GraphSample,
    PPISpec,
    batches,
    ppi_like,
)
from repro.models.gnn import gat_layer, init_gat_layer  # noqa: E402
from repro.observability import REGISTRY  # noqa: E402

SEED = 2 ** 31 + 77
# 2 graphs of at most 40 nodes, heads (2, 2, 3) of width 8; 10 directed
# edges per node keep nnz_pad above m_pad · k_pad, so ELL stays out
SPEC = PPISpec(n_graphs=2, total_nodes=60, min_nodes=20, max_nodes=40,
               avg_degree=10, n_features=6, n_labels=8)
CFG = GCNConfig.ppi_gat(n_features=6, conv_widths=(16, 16, 24), n_tasks=8,
                        heads=(2, 2, 3))
GCN = {"n_features": 6, "conv_widths": [16, 16, 24], "heads": [2, 2, 3],
       "skip": [1]}
OPT = {"lr": 0.005, "b1": 0.9, "b2": 0.999, "eps": 1e-8}


def _batch(graphs):
    return next(batches(graphs, SPEC.dataset_spec(), len(graphs), seed=0))


def _dense(graphs, m_pad):
    return reference_gat.dense_batch(graphs, m_pad, SPEC.n_features,
                                     SPEC.n_labels)


def _leaves(tree):
    return {jax.tree_util.keystr(p): np.asarray(v) for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def graphs():
    return ppi_like(SPEC)


def test_init_matches_reference_by_path():
    got = _leaves(init_gcn(jax.random.key(SEED), CFG))
    want = _leaves(reference_gat.init_params(SEED, GCN))
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_logits_loss_and_grads_match_reference(graphs):
    b = _batch(graphs)
    m_pad = b["x"].shape[1]
    adj, x, mask, labels = _dense(graphs, m_pad)
    params = init_gcn(jax.random.key(SEED), CFG)
    got = apply_gcn(params, CFG, b["adj"], b["x"], b["n_nodes"])
    want = jax.vmap(functools.partial(reference_gat.forward,
                                      precision="highest"),
                    in_axes=(None, 0, 0, 0))(params, adj, x, mask)
    assert got.shape == (2, m_pad, CFG.n_tasks)
    np.testing.assert_allclose(np.asarray(got) * mask, np.asarray(want) * mask,
                               rtol=2e-5, atol=2e-5)

    loss, grads = jax.value_and_grad(
        lambda p: gcn_loss(p, CFG, b["adj"], b["x"], b["n_nodes"],
                           b["labels"])[0])(params)
    ref_loss, ref_grads = jax.value_and_grad(functools.partial(
        reference_gat.loss_fn, precision="highest"))(params, adj, x, mask,
                                                     labels)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    got_g, want_g = _leaves(grads), _leaves(ref_grads)
    assert got_g.keys() == want_g.keys()
    for k in want_g:
        np.testing.assert_allclose(got_g[k], want_g[k], rtol=1e-4,
                                   atol=1e-6 * np.abs(want_g[k]).max(),
                                   err_msg=k)


def test_three_adam_steps_through_fit(graphs, tmp_path):
    from repro.optim import AdamConfig
    from repro.training import GCNTrainer, TrainerConfig

    stream = list(batches(graphs, SPEC.dataset_spec(), 2, seed=0,
                          epochs=3))
    m_pad = stream[0]["x"].shape[1]
    trainer = GCNTrainer(CFG, opt=AdamConfig(**OPT),
                         tcfg=TrainerConfig(checkpoint_dir=str(tmp_path),
                                            seed=SEED, log_every=1))
    losses = []
    params, _, _ = trainer.fit(lambda e: [stream[e]], epochs=3,
                               on_metrics=lambda _, r: losses.append(
                                   r["loss"]))
    order = [np.random.default_rng((0, e)).permutation(2) for e in range(3)]
    ref = reference_gat.train(SEED, GCN, OPT, [
        _dense([graphs[i] for i in o], m_pad) for o in order],
        precision="highest")
    np.testing.assert_allclose(losses[1:], ref["losses"][1:], rtol=1e-5)
    got, want, start = (_leaves(t) for t in (params, ref["params"],
                                             ref["params0"]))
    for k in want:
        moved = np.linalg.norm(want[k] - start[k])
        assert np.linalg.norm(got[k] - want[k]) <= 1e-3 * moved, k


def _ring_with_isolated_node():
    """One 12-node graph: a ring with self loops on nodes 0..10; node 11
    has no edge at all, not even a self loop."""
    rng = np.random.default_rng(3)
    n = 12
    ring = np.arange(11)
    rows = np.concatenate([ring, (ring + 1) % 11, ring]).astype(np.int32)
    cols = np.concatenate([(ring + 1) % 11, ring, ring]).astype(np.int32)
    feats = rng.standard_normal((n, SPEC.n_features)).astype(np.float32)
    labels = (rng.random((n, SPEC.n_labels)) < 0.3).astype(np.float32)
    return GraphSample([rows], [cols], n, feats, labels)


def test_zero_degree_row_outputs_the_bias_with_finite_grads():
    g = _ring_with_isolated_node()
    b = _batch([g])
    adj, x, mask, labels = _dense([g], b["x"].shape[1])
    params = init_gcn(jax.random.key(SEED), CFG)
    params["convs"][2]["b"] = jnp.linspace(-1.0, 1.0, 24)
    got = apply_gcn(params, CFG, b["adj"], b["x"], b["n_nodes"])
    want = reference_gat.forward(params, adj[0], x[0], mask[0],
                                 precision="highest")
    np.testing.assert_allclose(np.asarray(got[0, :12]),
                               np.asarray(want[:12]), rtol=2e-5, atol=2e-5)
    b_mean = np.asarray(params["convs"][2]["b"]).reshape(3, 8).mean(0)
    np.testing.assert_allclose(np.asarray(got[0, 11]), b_mean, atol=1e-6)
    grads = jax.grad(lambda p: gcn_loss(p, CFG, b["adj"], b["x"],
                                        b["n_nodes"], b["labels"])[0])(params)
    assert all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(grads))


def _layer_inputs(n_in=6, skip=False, heads=2, n_out=16):
    g = ppi_like(SPEC)
    b = _batch(g)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        b["x"].shape[:2] + (n_in,)), jnp.float32)
    p = init_gat_layer(jax.random.key(5), n_in, n_out, heads, skip=skip)
    p["b"] = jnp.linspace(-0.5, 0.5, n_out)
    return p, b["adj"][0], x


def test_head_mean_is_the_mean_of_the_concatenated_heads():
    p, adj, x = _layer_inputs(heads=3, n_out=24)
    cat = gat_layer(p, adj, x, impl="ref")
    mean = gat_layer(p, adj, x, impl="ref", mean_heads=True)
    assert mean.shape == x.shape[:2] + (8,)
    np.testing.assert_allclose(
        np.asarray(mean), np.asarray(cat).reshape(*x.shape[:2], 3, 8)
        .mean(2), rtol=1e-6, atol=1e-6)


def test_skip_projection_adds_the_projected_input():
    p, adj, x = _layer_inputs(skip=True)
    plain = {k: v for k, v in p.items() if k != "w_skip"}
    got = gat_layer(p, adj, x, impl="ref")
    want = gat_layer(plain, adj, x, impl="ref") + x @ p["w_skip"]
    assert p["w_skip"].shape == (6, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _vector_edge_gat(params, adj, x):
    """The aggregation ``gat_layer`` had before: each head's attention
    repeated over the head width as a vector edge feature of a ``(mul,
    sum)`` g-SpMM."""
    from repro.core.message_passing import message_passing
    from repro.kernels.segment_softmax import segment_softmax

    heads, _, d = params["w"].shape
    batch, m_pad, _ = x.shape
    nnz_pad = adj.row_ids.shape[1]
    h = jnp.einsum("bmn,hnf->hbmf", x, params["w"])
    s_src = jnp.einsum("hbmf,hf->hbm", h, params["a_src"])
    s_dst = jnp.einsum("hbmf,hf->hbm", h, params["a_dst"])
    gather = jax.vmap(jax.vmap(lambda s, ids: s[ids]))
    logits = jax.nn.leaky_relu(
        gather(s_src, jnp.broadcast_to(adj.col_ids, (heads, batch, nnz_pad)))
        + gather(s_dst, jnp.broadcast_to(adj.row_ids,
                                         (heads, batch, nnz_pad))), 0.2)
    alpha = segment_softmax(logits.transpose(1, 2, 0), adj.row_ids,
                            nnz=adj.nnz, m_pad=m_pad)

    def flat(t):
        return jnp.broadcast_to(t, (heads,) + t.shape).reshape(
            (heads * batch,) + t.shape[1:])

    e_vec = jnp.repeat(alpha.transpose(2, 0, 1).reshape(
        heads * batch, nnz_pad)[..., None], d, axis=-1)
    a_flat = BatchedCOO(flat(adj.row_ids), flat(adj.col_ids), e_vec,
                        flat(adj.nnz), flat(adj.n_rows))
    out = message_passing(a_flat, h.reshape(heads * batch, m_pad, d),
                          op="mul", reduce="sum", impl="ref")
    return (out.reshape(heads, batch, m_pad, d).transpose(1, 2, 0, 3)
            .reshape(batch, m_pad, heads * d) + params["b"])


@pytest.mark.parametrize("impl", ["ref", "csr", "dense"])
def test_scalar_attention_spmm_equals_vector_edge_form(impl):
    p, adj, x = _layer_inputs()
    got = gat_layer(p, adj, x, impl=impl)
    want = _vector_edge_gat(p, adj, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    dy = jnp.asarray(np.random.default_rng(2).standard_normal(got.shape),
                     jnp.float32)
    g_got = jax.grad(lambda q: jnp.sum(gat_layer(q, adj, x, impl=impl)
                                       * dy))(p)
    g_want = jax.grad(lambda q: jnp.sum(_vector_edge_gat(q, adj, x) * dy))(p)
    for k in p:
        np.testing.assert_allclose(np.asarray(g_got[k]),
                                   np.asarray(g_want[k]), rtol=1e-4,
                                   atol=1e-5, err_msg=k)


# ``impl="auto"`` on tox21's shapes, as before this configuration came in:
# (batch, m_pad, nnz_pad) -> the two layers' picks, for the compiled (TPU)
# and the interpret posture
TOX21_PICKS = {
    (100, 56, 96): ("dense", "dense"),       # the training batch
    (32, 16, 40): ("ref", "ref"),            # the serving tiers
    (32, 32, 64): ("dense", "dense"),
    (32, 56, 96): ("dense", "dense"),
}


@pytest.mark.parametrize("interpret", [False, True])
@pytest.mark.parametrize("geom", sorted(TOX21_PICKS))
def test_auto_keeps_every_tox21_pick(geom, interpret):
    cfg = GCNConfig.tox21(interpret=interpret)
    assert tuple(d.impl for d in resolve_conv_impls(cfg, *geom)) \
        == TOX21_PICKS[geom]


# ``impl="auto"`` at PPI's padded shapes (2 graphs, m_pad 3,480, nnz_pad
# 103,704): ELL cannot hold the edges (m_pad · k_pad < nnz_pad) and the
# Pallas kernels' blocks exceed VMEM, so the model ranks the XLA paths; it
# puts ``dense`` ahead of ``ref`` at the two 1,024-wide layers, where the
# chip's layer probe measured ``dense`` ahead too
PPI_PICKS = ("dense", "dense", "ref")


@pytest.mark.parametrize("interpret", [False, True])
def test_auto_picks_on_ppi_layers_keep_ell_and_pallas_out(interpret):
    cfg = GCNConfig.ppi_gat(interpret=interpret)
    decisions = resolve_conv_impls(cfg, 2, 3480, 103704)
    assert tuple(d.impl for d in decisions) == PPI_PICKS
    for heads, width in zip(cfg.heads, cfg.conv_widths):
        w = Workload(batch=2 * heads, m_pad=3480, nnz_pad=103704,
                     k_pad=cfg.k_pad, n_b=width // heads, dtype="f32")
        for impl in ("ell", "pallas_ell", "pallas_gemm", "pallas_coo"):
            assert estimate(w, impl) == float("inf"), impl


# --- the dense-block attention path (``impl="auto"``, no mesh) -------------

def _layer_count(path):
    return REGISTRY.counter("gat_attention_layers_total").value(path=path)


def _awkward_batch():
    """3 graphs, m_pad 24, nnz_pad 80: graph 0 repeats an edge, graph 1
    has no edge at all, rows 5 and 21 have no incoming edge, and every
    padded slot holds ids the softmax must ignore."""
    rng = np.random.default_rng(11)
    batch, m_pad, nnz_pad = 3, 24, 80
    nnz = np.array([60, 0, 45], np.int32)
    rows = rng.integers(0, m_pad, (batch, nnz_pad)).astype(np.int32)
    cols = rng.integers(0, m_pad, (batch, nnz_pad)).astype(np.int32)
    for b in range(batch):
        r = rng.integers(0, 20, nnz[b])
        rows[b, :nnz[b]] = np.where(r == 5, 6, r)
        cols[b, :nnz[b]] = rng.integers(0, 22, nnz[b])
    rows[0, 1], cols[0, 1] = rows[0, 0], cols[0, 0]
    adj = BatchedCOO(jnp.asarray(rows), jnp.asarray(cols),
                     jnp.ones((batch, nnz_pad), jnp.float32),
                     jnp.asarray(nnz), jnp.full((batch,), m_pad, jnp.int32))
    x = jnp.asarray(rng.standard_normal((batch, m_pad, 7)), jnp.float32)
    return adj, x


@pytest.mark.parametrize("heads, skip, mean_heads", [
    (1, False, False), (4, True, False), (6, False, True), (4, True, True)])
def test_dense_blocks_equal_the_edge_path(heads, skip, mean_heads):
    adj, x = _awkward_batch()
    p = init_gat_layer(jax.random.key(heads), 7, 6 * heads, heads, skip=skip)
    p["b"] = jnp.linspace(-1.0, 1.0, 6 * heads)

    def layer(impl):
        return lambda q, x: gat_layer(q, adj, x, impl=impl,
                                      mean_heads=mean_heads)

    before = _layer_count("dense_blocks")
    got = layer("auto")(p, x)
    assert _layer_count("dense_blocks") == before + 1
    want = layer("ref")(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # no incoming edge (row 5, every row of graph 1): the bias and skip
    base = jnp.broadcast_to(p["b"], got.shape[:2] + p["b"].shape) + (
        x @ p["w_skip"] if skip else 0.0)
    if mean_heads:
        base = base.reshape(*x.shape[:2], heads, 6).mean(2)
    np.testing.assert_allclose(np.asarray(got)[:, 5], np.asarray(base)[:, 5],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got)[1], np.asarray(base)[1],
                               rtol=1e-6, atol=1e-6)
    dy = jnp.asarray(np.random.default_rng(3).standard_normal(got.shape),
                     jnp.float32)
    g_got, g_want = (jax.grad(lambda q, x: jnp.sum(layer(i)(q, x) * dy),
                              argnums=(0, 1))(p, x) for i in ("auto", "ref"))
    assert all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(g_got))
    for k in p:
        np.testing.assert_allclose(np.asarray(g_got[0][k]),
                                   np.asarray(g_want[0][k]), rtol=1e-4,
                                   atol=1e-5, err_msg=k)
    np.testing.assert_allclose(np.asarray(g_got[1]), np.asarray(g_want[1]),
                               rtol=1e-4, atol=1e-5)


def test_a_dst_gradient_is_zero_where_no_logit_crosses_the_kink():
    """With every logit on LeakyReLU's positive side the softmax cancels
    ``s_dst`` outright, so ``a_dst``'s gradient is 0: the dense path gives
    exactly 0, not the residue of a row sum."""
    adj, _ = _awkward_batch()
    x = jnp.abs(jnp.asarray(np.random.default_rng(4).standard_normal(
        (3, 24, 7)), jnp.float32))
    p = init_gat_layer(jax.random.key(9), 7, 16, 2)
    p = dict(p, w=jnp.abs(p["w"]), a_src=jnp.abs(p["a_src"]),
             a_dst=jnp.abs(p["a_dst"]))
    dy = jnp.asarray(np.random.default_rng(5).standard_normal((3, 24, 16)),
                     jnp.float32)
    g = jax.grad(lambda q: jnp.sum(gat_layer(q, adj, x) * dy))(p)
    assert not np.asarray(g["a_dst"]).any()
    assert np.asarray(g["a_src"]).any()


def test_ppi_layers_take_dense_blocks():
    # the CPU reports no capacity, so the cap is the v5e's 16 GiB / 16
    for heads in GCNConfig.ppi_gat().heads:
        assert gat_attention_path(heads, 2, 3480) == "dense_blocks"


def test_blocks_above_a_sixteenth_of_hbm_take_the_edges():
    assert gat_attention_path(4, 2, 40_000) == "edges"


def test_the_device_reported_capacity_sets_the_cap(monkeypatch):
    class Device:
        def memory_stats(self):
            return {"bytes_limit": 8 * 2 ** 30}

    monkeypatch.setattr(jax, "devices", lambda *a: [Device()])
    # against 8 GiB / 16 = 537 MB
    assert gat_attention_path(4, 2, 3480) == "dense_blocks"   # 388 MB
    assert gat_attention_path(6, 2, 3480) == "edges"          # 581 MB


@pytest.mark.parametrize("impl", ["ref", "csr", "dense"])
def test_an_explicit_impl_keeps_the_edge_path(impl):
    assert gat_attention_path(4, 2, 64, impl=impl) == "edges"
    p, adj, x = _layer_inputs()
    before = _layer_count("edges")
    gat_layer(p, adj, x, impl=impl)
    assert _layer_count("edges") == before + 1


def test_a_mesh_keeps_the_edge_path():
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    assert gat_attention_path(4, 2, 64, mesh=mesh) == "edges"
    p, adj, x = _layer_inputs()
    before = _layer_count("edges")
    got = gat_layer(p, adj, x, mesh=mesh)
    assert _layer_count("edges") == before + 1
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(gat_layer(p, adj, x)),
                               rtol=1e-5, atol=1e-5)


def test_the_counter_counts_traced_layers_not_calls(graphs):
    b = _batch(graphs)
    params = init_gcn(jax.random.key(SEED), CFG)
    step = jax.jit(jax.grad(lambda p, x: gcn_loss(
        p, CFG, b["adj"], x, b["n_nodes"], b["labels"])[0]))
    before = (_layer_count("dense_blocks"), _layer_count("edges"))
    for _ in range(3):
        step(params, b["x"])
    assert (_layer_count("dense_blocks"), _layer_count("edges")) \
        == (before[0] + len(CFG.conv_widths), before[1])


def test_the_dense_block_step_gathers_and_scatters_no_edge():
    """The whole training gradient under ``auto`` keeps one scatter per
    layer (the multiplicity block) and no gather; the edge path keeps the
    per-edge gathers and the softmax's scatters."""
    b = _batch(ppi_like(SPEC))
    params = init_gcn(jax.random.key(SEED), CFG)

    def primitives(cfg):
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: gcn_loss(
            p, cfg, b["adj"], b["x"], b["n_nodes"], b["labels"])[0]))(params)
        names = []

        def walk(j):
            for e in j.eqns:
                names.append(e.primitive.name)
                for v in e.params.values():
                    if isinstance(v, jax.extend.core.ClosedJaxpr):
                        walk(v.jaxpr)
                    elif isinstance(v, jax.extend.core.Jaxpr):
                        walk(v)

        walk(jaxpr.jaxpr)
        return {n: names.count(n) for n in set(names)
                if "gather" in n or "scatter" in n}

    assert primitives(CFG) == {"scatter-add": len(CFG.conv_widths)}
    edges = primitives(GCNConfig.ppi_gat(
        n_features=6, conv_widths=(16, 16, 24), n_tasks=8, heads=(2, 2, 3),
        impl="ref"))
    assert edges["gather"] > 0 and edges["scatter-max"] == 3
