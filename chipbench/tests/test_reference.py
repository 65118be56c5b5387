"""The plain reference against the program's own XLA path (``impl="ref"``),
on a small seeded batch, and its lower-precision controls against the
cells' limits."""
import dataclasses

import jax
import numpy as np
import pytest

from chipbench import compare, reference, traffic
from conftest import ROOT

SEED = 2 ** 31 + 17


def _setup(name):
    from chipbench import run as harness

    cell = harness.resolve_cell(harness.load_json(ROOT / "BENCHMARK.json"),
                                name)
    spec = dict(cell.spec, molecules=16)
    pool = traffic.molecule_pool(cell.config, spec)
    return cell, pool


def _program_loss_and_grads(cell, pool, params):
    from repro.core.formats import BatchedCOO
    from repro.core.gcn import gcn_loss
    from repro.data.graphs import batches

    g = dict(cell.config["gcn"], conv_widths=tuple(
        cell.config["gcn"]["conv_widths"]), impl="ref")
    from repro.core.gcn import GCNConfig

    cfg = GCNConfig(**g)
    spec = traffic.dataset_spec(cell.config, dict(cell.spec, molecules=16))
    (b,) = list(batches(pool, spec, len(pool), seed=SEED))
    ids = traffic.epoch_batches(SEED, len(pool), len(pool))[0]

    def loss(p):
        adj = [BatchedCOO(a.row_ids, a.col_ids, a.values, a.nnz, a.n_rows)
               for a in b["adj"]]
        return gcn_loss(p, cfg, adj, b["x"], b["n_nodes"], b["labels"])[0]

    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(loss)(params)
    return float(value), grads, [pool[i] for i in ids]


@pytest.mark.parametrize("name", ["tox21.train"])
def test_reference_matches_program_ref_path(name):
    from repro.core.gcn import init_gcn

    cell, pool = _setup(name)
    g = cell.config["gcn"]
    params = reference.init_params(SEED, g)
    from chipbench import run as harness

    run = harness.Run(cell=cell, seed=SEED, seconds=1, trace=False,
                      peak=None)
    program_params = init_gcn(jax.random.key(SEED), run.gcn_config())
    assert jax.tree.all(jax.tree.map(np.array_equal, params,
                                     program_params))

    loss, grads, mols = _program_loss_and_grads(cell, pool, params)
    n_max = cell.config["molecules"]["max_nodes"]
    adj, x, mask = reference.dense_batch(mols, n_max, g["channels"],
                                         g["n_features"])
    labels = np.stack([m.label for m in mols])
    vg = jax.value_and_grad(lambda p: reference.loss_fn(
        p, adj, x, mask, labels, task=g["task"], precision="highest"))
    ref_loss, ref_grads = vg(params)
    got = {"losses": [loss], "grad1": grads, "dparams": grads}
    want = {"losses": [float(ref_loss)], "grad1": ref_grads,
            "dparams": ref_grads}
    r = compare.train_readings(got, want)
    limits = cell.spec["limits"]
    assert r["loss1"] <= limits["loss1"] / 3
    assert r["grad1"] <= limits["grad1"] / 3


def test_bf16_forward_falls_outside_the_limits():
    cell, pool = _setup("tox21.train")
    g = cell.config["gcn"]
    n_max = cell.config["molecules"]["max_nodes"]
    mols = pool[:16]
    f32 = reference.serve_logits(SEED, g, mols, n_max, precision="highest",
                                 block=16)
    bf16 = reference.serve_logits(SEED, g, mols, n_max, precision="bf16",
                                  block=16)
    serve_limit = _setup("tox21.serve.poisson")[0].spec["limits"]
    assert compare.logit_readings(bf16, f32)["logit_gap"] \
        > serve_limit["logit_gap"]

    adj, x, mask = reference.dense_batch(mols, n_max, g["channels"],
                                         g["n_features"])
    labels = np.stack([m.label for m in mols])
    params = reference.init_params(SEED, g)
    losses = [float(reference.loss_fn(params, adj, x, mask, labels,
                                      task=g["task"], precision=p))
              for p in ("highest", "bf16")]
    assert abs(losses[1] - losses[0]) / losses[0] \
        > cell.spec["limits"]["loss1"]


def test_dense_batch_counts_duplicate_edges():
    @dataclasses.dataclass
    class Mol:
        rows: list
        cols: list
        n_nodes: int
        features: np.ndarray

    m = Mol(rows=[np.array([0, 1, 1]), np.array([0])],
            cols=[np.array([0, 0, 0]), np.array([1])], n_nodes=2,
            features=np.eye(2, 3, dtype=np.float32))
    adj, x, mask = reference.dense_batch([m], 3, 2, 3)
    assert adj[0, 0].tolist() == [[1, 0, 0], [2, 0, 0], [0, 0, 0]]
    assert adj[0, 1].tolist() == [[0, 1, 0], [0, 0, 0], [0, 0, 0]]
    assert mask[0, :, 0].tolist() == [1, 1, 0]
