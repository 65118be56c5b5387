"""Reproduce the paper's core comparison end-to-end: ChemGCN with the
batched (Fig. 7) vs non-batched (Fig. 6) graph-convolution execution —
identical losses, different wall time.

    PYTHONPATH=src python examples/chemgcn_batched_vs_nonbatched.py
"""
import dataclasses
import time

import jax

from repro.core.formats import BatchedCOO
from repro.core.gcn import GCNConfig, gcn_loss, init_gcn
from repro.data.graphs import GraphDatasetSpec, batches, generate
from repro.optim import AdamConfig, adam_init, adam_update


def train(cfg, spec, data, epochs=2):
    params = init_gcn(jax.random.key(0), cfg)
    opt = AdamConfig(lr=3e-3)
    state = adam_init(params)

    @jax.jit
    def step(params, state, adj_arrays, x, n_nodes, labels):
        adj = [BatchedCOO(*a) for a in adj_arrays]
        (loss, acc), grads = jax.value_and_grad(
            lambda p: gcn_loss(p, cfg, adj, x, n_nodes, labels),
            has_aux=True)(params)
        params, state = adam_update(opt, params, grads, state)
        return params, state, loss, acc

    t0, losses = time.perf_counter(), []
    for epoch in range(epochs):
        for b in batches(data, spec, 50, seed=epoch):
            adj_arrays = [(a.row_ids, a.col_ids, a.values, a.nnz, a.n_rows)
                          for a in b["adj"]]
            params, state, loss, acc = step(
                params, state, adj_arrays, b["x"], b["n_nodes"], b["labels"])
        losses.append(float(loss))
    jax.block_until_ready(loss)
    return time.perf_counter() - t0, losses


def infer_times(cfg, spec, data):
    """Batched single-op inference vs TF-style per-sample dispatch."""
    from repro.core.gcn import apply_gcn

    params = init_gcn(jax.random.key(0), cfg)
    b = next(batches(data, spec, 50))
    adj_arrays = [(a.row_ids, a.col_ids, a.values, a.nnz, a.n_rows)
                  for a in b["adj"]]

    @jax.jit
    def fwd(params, adj_arrays, x, n_nodes):
        adj = [BatchedCOO(*a) for a in adj_arrays]
        return apply_gcn(params, cfg, adj, x, n_nodes)

    jax.block_until_ready(fwd(params, adj_arrays, b["x"], b["n_nodes"]))
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(fwd(params, adj_arrays, b["x"], b["n_nodes"]))
    t_batched = (time.perf_counter() - t0) / 5

    def slice_sample(i):
        return ([tuple(x[i:i + 1] for x in a) for a in adj_arrays],
                b["x"][i:i + 1], b["n_nodes"][i:i + 1])

    jax.block_until_ready(fwd(params, *slice_sample(0)))
    t0 = time.perf_counter()
    for _ in range(2):
        for i in range(50):     # one dispatch per sample (TF-style)
            out = fwd(params, *slice_sample(i))
        jax.block_until_ready(out)
    t_dispatch = (time.perf_counter() - t0) / 2
    return t_batched, t_dispatch


def main():
    spec = GraphDatasetSpec.tox21_like(n_samples=300)
    data = generate(spec)
    base = GCNConfig.tox21(impl="ref")
    t_b, l_b = train(base, spec, data)
    t_n, l_n = train(dataclasses.replace(base, batched=False), spec, data)
    print(f"batched    (Fig.7): {t_b:6.2f}s  losses={[round(x,4) for x in l_b]}")
    print(f"nonbatched (Fig.6): {t_n:6.2f}s  losses={[round(x,4) for x in l_n]}")
    print(f"train speedup vs in-graph sequential: {t_n / t_b:.2f}x")
    print("(XLA whole-program compilation already amortizes launches that "
          "TF dispatched per-op; the TF-style baseline is per-sample "
          "dispatch:)")
    ti_b, ti_d = infer_times(base, spec, data)
    print(f"inference batched one-op:      {ti_b*1e3:8.1f} ms/minibatch")
    print(f"inference per-sample dispatch: {ti_d*1e3:8.1f} ms/minibatch")
    print(f"speedup: {ti_d / ti_b:.2f}x  (paper: 1.37x infer end-to-end, "
          "~10x SpMM-only, on P100)")
    assert all(abs(a - b) < 1e-2 for a, b in zip(l_b, l_n)), \
        "batched execution changed numerics!"


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
