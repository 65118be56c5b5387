"""The pieces of the ``ppi_gat.train`` cell on the CPU: the GAT reference
against a per-edge loop, ``work_gat``'s counts on a graph sized by hand,
and a rehearsal of its traffic kind at a tiny size, where the control and
the half-batch fault are judged not correct."""
import json
import time

import numpy as np
import pytest

from chipbench import reference_gat, work_gat
from chipbench import run as harness
from conftest import ROOT

BENCH = harness.load_json(ROOT / "BENCHMARK.json")
# ppi_gat.train cut to a test's size: 4 graphs of 20-40 nodes, heads
# (2, 2, 3) of width 8 (the last layer's 8 are the labels)
TINY_GCN = {"n_features": 8, "conv_widths": [16, 16, 24], "heads": [2, 2, 3],
            "n_tasks": 8}
TINY_PPI = {"total_nodes": 120, "min_nodes": 20, "max_nodes": 40,
            "avg_degree": 10, "n_features": 8, "n_labels": 8}
SEED = 2 ** 31 + 45


def test_dense_attention_matches_a_per_edge_loop():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    n, k, d = 7, 2, 3
    adj = (rng.random((n, n)) < 0.4).astype(np.float32)
    adj[3] = 0.0                                     # a row with no edge
    h = rng.standard_normal((k, n, d)).astype(np.float32)
    a_src, a_dst = (rng.standard_normal((k, d)).astype(np.float32)
                    for _ in range(2))
    got = np.asarray(reference_gat.attention(
        jnp.asarray(h), jnp.asarray(a_src), jnp.asarray(a_dst),
        jnp.asarray(adj), "highest")[0])
    want = np.zeros((k, n, d), np.float32)
    for head in range(k):
        for i in range(n):
            js = np.flatnonzero(adj[i])
            if not len(js):
                continue
            e = np.array([h[head, i] @ a_dst[head] + h[head, j] @ a_src[head]
                          for j in js])
            e = np.where(e > 0, e, 0.2 * e)
            w = np.exp(e - e.max())
            w /= w.sum()
            want[head, i] = sum(wj * h[head, j] for wj, j in zip(w, js))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert not got[:, 3].any()


def test_work_counts_on_a_hand_sized_graph():
    # 3 nodes, 5 edges, 2 inputs, 2 heads of 3
    assert work_gat.layer_flops(3, 5, 2, 2, 3, False, False) == 272
    assert work_gat.layer_bytes(3, 5, 2, 2, 3, False, False) == 416
    assert work_gat.layer_flops(3, 5, 2, 2, 3, True, True) == 362
    assert work_gat.layer_bytes(3, 5, 2, 2, 3, True, True) == 428
    gcn = {"n_features": 2, "conv_widths": [6, 6], "heads": [2, 2],
           "skip": []}
    assert work_gat.layers(gcn) == [(2, 2, 3, False, False),
                                    (6, 2, 3, False, True)]
    assert work_gat.train_flops(3, 5, gcn) == 3 * (272 + 434)
    assert work_gat.layer_train([(3, 5), (3, 5)], (2, 2, 3, False, False)) \
        == (6 * 272, 6 * 416)


def test_kink_changes_admit_either_slope_at_near_kink_logits(monkeypatch):
    import jax

    from chipbench import compare
    from chipbench.drivers import train_nodes

    cell = harness.resolve_cell(BENCH, "ppi_gat.train")
    gcn = dict(cell.config["gcn"], **TINY_GCN)
    graphs, _ = train_nodes.dataset(
        dict(cell.config, ppi=dict(cell.config["ppi"], **TINY_PPI)),
        dict(cell.spec, graphs=4))
    batch = reference_gat.dense_batch(graphs[:2], 40, gcn["n_features"],
                                      gcn["n_tasks"])

    def train():
        return reference_gat.train(SEED, gcn, cell.config["optimizer"],
                                   [batch], precision="highest", kinks=True)

    ref = train()
    grad1 = jax.grad(reference_gat.loss_fn)(
        reference_gat.init_params(SEED, gcn), *batch, precision="highest")
    assert compare.diff_gap(ref["grad1"], grad1)[0] < 1e-6
    # no logit of this batch lies within float32 rounding of the kink
    assert ref["kink_changes"] == []
    grad1 = ref["grad1"]
    assert [compare.diff_gap(g, grad1)[0] for g in
            reference_gat.admissible(grad1, [])] == [0.0]
    # planted: admit the nearest logits of the batch, far from rounding
    monkeypatch.setattr(reference_gat, "KINK_TOL", 0.05)
    ref = train()
    changes = ref["kink_changes"]
    assert len(changes) == reference_gat.KINK_MAX
    assert compare.diff_gap(ref["grad1"], grad1)[0] == 0.0
    assert min(compare.diff_gap(jax.tree.map(np.add, grad1, c), grad1)[0]
               for c in changes) > 1e-7
    # a gradient that took the other slope at two of them reads as one of
    # the admissible gradients, and far from the reference's own
    got = jax.tree.map(lambda g, a, b: g + a + b, grad1, changes[0],
                       changes[3])
    admitted = list(reference_gat.admissible(grad1, changes))
    assert len(admitted) == 2 ** reference_gat.KINK_MAX
    assert min(compare.diff_gap(got, g)[0] for g in admitted) < 1e-9
    assert compare.diff_gap(got, grad1)[0] > 1e-6


@pytest.fixture
def tiny_ppi(no_compile_cache):
    cell = harness.resolve_cell(BENCH, "ppi_gat.train")
    cell.config = dict(cell.config,
                       gcn=dict(cell.config["gcn"], **TINY_GCN),
                       ppi=dict(cell.config["ppi"], **TINY_PPI))
    cell.spec = dict(cell.spec, graphs=4)
    return cell


def _run(cell, extra=()):
    import jax

    return harness.run_cell(cell, SEED, 1.0, False,
                            t_process=time.monotonic(),
                            devices=jax.devices()[:cell.chips], extra=extra)


def test_train_nodes_rehearses_and_its_control_fails(tiny_ppi, capsys):
    result = _run(tiny_ppi, extra=("control", "half_batch"))
    capsys.readouterr()
    harness.emit(result)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {k: v["unit"] for k, v in line["metrics"].items()} == {
        m["name"]: m["unit"] for m in tiny_ppi.end_to_end}
    assert set(line["checks"]) == set(tiny_ppi.spec["limits"])
    limits = tiny_ppi.spec["limits"]
    assert harness.judge(result["_readings"]["program"], limits)[1]
    assert not harness.judge(result["_readings"]["control"], limits)[1]
    assert not harness.judge(result["_readings"]["half_batch"], limits)[1]
