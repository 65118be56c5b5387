"""Quickstart: train a small ChemGCN with Batched SpMM in ~1 minute on CPU.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.core.formats import BatchedCOO
from repro.core.gcn import GCNConfig, gcn_loss, init_gcn
from repro.data.graphs import GraphDatasetSpec, batches, generate
from repro.optim import AdamConfig, adam_init, adam_update


def main():
    spec = GraphDatasetSpec.tox21_like(n_samples=256)
    data = generate(spec)
    cfg = GCNConfig.tox21(impl="auto")         # adaptive dispatch (DESIGN.md
                                               # §5); pin e.g. impl="pallas_ell"
                                               # to override
    params = init_gcn(jax.random.key(0), cfg)
    opt, state = AdamConfig(lr=3e-3), None
    state = adam_init(params)

    @jax.jit
    def step(params, state, adj_arrays, x, n_nodes, labels):
        adj = [BatchedCOO(*a) for a in adj_arrays]
        (loss, acc), grads = jax.value_and_grad(
            lambda p: gcn_loss(p, cfg, adj, x, n_nodes, labels),
            has_aux=True)(params)
        params, state = adam_update(opt, params, grads, state)
        return params, state, loss, acc

    for epoch in range(5):
        for b in batches(data, spec, 32, seed=epoch):
            adj_arrays = [(a.row_ids, a.col_ids, a.values, a.nnz, a.n_rows)
                          for a in b["adj"]]
            params, state, loss, acc = step(
                params, state, adj_arrays, b["x"], b["n_nodes"], b["labels"])
        print(f"epoch {epoch}: loss {float(loss):.4f} acc {float(acc):.3f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
