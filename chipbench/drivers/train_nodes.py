"""Node-level training traffic: ``GCNTrainer.fit`` over an epoch of
batches of PPI-shaped graphs, cycled until the window ends.

Cell parameters: ``graphs`` and ``generator_seed`` (the fixed dataset,
``repro.data.graphs.ppi_like`` at the configuration's ``ppi`` sizes) and
``batch`` (graphs per step). Every batch is padded to the dataset's
largest graph and edge count, so one step program serves the epoch.

Set-up, window and check follow ``train.py``: set-up builds the epoch's
batches with the program's own batch builder (shuffled by ``--seed``) and
drives one ``GCNTrainer`` through three steps with ``fit``; the window is
``train.window`` (a third ``fit``, resumed after step 3); the check
compares the three set-up steps with ``reference_gat.py``, admitting
either LeakyReLU slope at the attention logits that sit within float32
rounding of the kink (``reference_gat.train``'s ``kinks``). One PPI graph
counts as one molecule in ``mol_per_s``. The window also reports
``pad_waste``: the share of the edge slots it stepped on that padding
held, from the trainer's ``train_edges_total`` and
``train_edge_slots_total`` counters.
"""
from __future__ import annotations

import functools
import tempfile
import time

import numpy as np

from chipbench.drivers import train


def gcn_config(gcn: dict):
    """The ``GCNConfig`` of a configuration's ``gcn`` (lists as tuples)."""
    from repro.core.gcn import GCNConfig

    return GCNConfig(**{k: tuple(v) if isinstance(v, list) else v
                        for k, v in gcn.items()})


def dataset(config: dict, cell: dict):
    """The cell's graphs and the ``GraphDatasetSpec`` that batches them."""
    from repro.data.graphs import PPISpec, ppi_like

    spec = PPISpec(n_graphs=cell["graphs"], seed=cell["generator_seed"],
                   **config["ppi"])
    return ppi_like(spec), spec.dataset_spec()


def _edge_counts() -> tuple[float, float]:
    from repro.observability import default_registry

    reg = default_registry()
    return (reg.counter("train_edges_total").total(),
            reg.counter("train_edge_slots_total").total())


def setup(run):
    import jax

    from chipbench import traffic, work_gat
    from repro.core.gcn import resolve_conv_impls
    from repro.data.graphs import batches
    from repro.optim import AdamConfig
    from repro.training import GCNTrainer, TrainerConfig

    cell, config = run.cell.spec, run.cell.config
    cfg = gcn_config(config["gcn"])
    t0 = time.monotonic()
    graphs, spec = dataset(config, cell)
    stream = list(batches(graphs, spec, cell["batch"], seed=run.seed))
    ids = traffic.epoch_batches(run.seed, len(graphs), cell["batch"])
    for b, i in zip(stream, ids, strict=True):
        if not np.array_equal(np.asarray(b["n_nodes"]),
                              [graphs[j].n_nodes for j in i]):
            raise RuntimeError("the program's batch order is not the one "
                               "the benchmark derives from the seed")
    x = stream[0]["x"]
    nnz_pad = stream[0]["adj"][0].nnz_pad
    run.log(f"data: {len(graphs)} graphs of {sum(g.n_nodes for g in graphs)}"
            f" nodes, {len(stream)} batches of {cell['batch']} (m_pad "
            f"{x.shape[1]}, nnz_pad {nnz_pad}) in {time.monotonic() - t0:.2f}"
            " s")
    for i, d in enumerate(resolve_conv_impls(cfg, x.shape[0], x.shape[1],
                                             nnz_pad)):
        run.log(f"layer {i}: auto resolves to {d.impl} [{d.source}]: "
                f"{d.reason}")

    ckpt = tempfile.TemporaryDirectory(prefix="chipbench-ckpt-")
    trainer = GCNTrainer(cfg, opt=AdamConfig(**config["optimizer"]),
                         tcfg=TrainerConfig(checkpoint_dir=ckpt.name,
                                            checkpoint_every=10 ** 9,
                                            seed=run.seed))
    params0 = train._host(trainer.init_state()[0])
    order = traffic.batch_cycle(run.seed, len(stream))
    first = [next(order) for _ in range(train.SETUP_STEPS)]
    _, state1, rec = trainer.fit([stream[first[0]]])
    losses = [rec["loss"]]
    grad1 = jax.tree.map(lambda m: m / (1 - trainer.opt.b1),
                         train._host(state1["m"]))
    params3, _, _ = trainer.fit(
        lambda e: [stream[first[e]]], epochs=train.SETUP_STEPS,
        on_metrics=lambda step, r: losses.append(r["loss"]))
    flops = float(np.mean([work_gat.train_flops(
        g.n_nodes, len(g.rows[0]), config["gcn"]) for g in graphs]))
    state = {"trainer": trainer, "stream": stream, "order": order,
             "first": first, "ckpt": ckpt, "pool": graphs, "ids": ids,
             "program": {"losses": losses, "grad1": grad1,
                         "dparams": jax.tree.map(
                             np.subtract, train._host(params3), params0)},
             "flops_per_mol": flops}
    if run.trace:
        state["probe"] = _build_probe(run, cfg, stream[first[0]], params3)
    return state


def window(state, run):
    edges0, slots0 = _edge_counts()
    win = train.window(state, run)
    edges, slots = (b - a for a, b in zip((edges0, slots0), _edge_counts()))
    win["counters"]["pad_waste"] = (slots - edges) / slots if slots else None
    return win


def _build_probe(run, cfg, batch, params):
    """One jitted forward+backward of each GAT layer over a real batch,
    under the stable name ``chipbench_gat<i>``, compiled here."""
    import jax
    import jax.numpy as jnp

    from chipbench import work, work_gat
    from repro.core.formats import BatchedCOO
    from repro.models.gnn import gat_layer

    a = batch["adj"][0]
    adj = (a.row_ids, a.col_ids, a.values, a.nnz, a.n_rows)
    n_nodes = np.asarray(batch["n_nodes"])
    graphs = list(zip(n_nodes.tolist(), np.asarray(a.nnz).tolist()))
    m_pad = batch["x"].shape[1]
    mask = (np.arange(m_pad)[None, :, None] < n_nodes[:, None, None])
    calls, layers = [], []
    specs = work_gat.layers(run.cell.config["gcn"])
    for i, (conv, spec) in enumerate(zip(params["convs"], specs)):
        n_in, heads, d, _, mean = spec

        def layer(p, adj_arrays, x, dy, mean=mean):
            coo = BatchedCOO(*adj_arrays)
            y, vjp = jax.vjp(lambda p, x: gat_layer(
                p, coo, x, impl=cfg.impl, k_pad=cfg.k_pad,
                interpret=cfg.interpret, mean_heads=mean), p, x)
            return y, vjp(dy)

        name = f"chipbench_gat{i}"
        layer.__name__ = layer.__qualname__ = name
        x = jnp.asarray(batch["x"]) if i == 0 else jnp.asarray(
            mask * np.random.default_rng(i).standard_normal(
                (len(n_nodes), m_pad, n_in)), jnp.float32)
        dy = jnp.asarray(mask * np.ones((1, 1, d if mean else heads * d)),
                         jnp.float32)
        fn = jax.jit(layer)
        args = (conv, adj, x, dy)
        jax.block_until_ready(fn(*args))
        calls.append((fn, args))
        flops, nbytes = (train.PROBE_CALLS * v
                         for v in work_gat.layer_train(graphs, spec))
        least, bound = work.least_time(flops, nbytes, run.peak) \
            if run.peak else (None, None)
        run.log(f"probe {name}: {train.PROBE_CALLS} calls, {flops} useful "
                f"FLOPs, {nbytes} useful bytes; least time {least!r} s "
                f"({bound} bound)")
        layers.append({"program": f"jit_{name}", "flops": flops,
                       "bytes": nbytes})
    return {"calls": calls, "counters": {
        "programs": [p["program"] for p in layers], "probe": layers}}


probe = train.probe


def check(state, run, extra=()):
    import jax

    from chipbench import compare, reference_gat

    config = run.cell.config
    gcn = config["gcn"]
    program = state["program"]
    graphs, ids = state["pool"], state["ids"]
    n_max = max(g.n_nodes for g in graphs)
    batches = [reference_gat.dense_batch(
        [graphs[j] for j in ids[pos]], n_max, gcn["n_features"],
        gcn["n_tasks"]) for pos in state["first"]]
    state.clear()                   # free the program before the reference
    jax.clear_caches()
    ref = functools.partial(reference_gat.train, run.seed, gcn,
                            config["optimizer"], batches)

    def as_readings(r):
        return {"losses": r["losses"], "grad1": r["grad1"],
                "dparams": jax.tree.map(np.subtract, r["params"],
                                        r["params0"])}

    highest = ref(precision="highest", kinks=True)
    want, changes = as_readings(highest), highest["kink_changes"]

    def readings(got):
        # against the reference whose first gradient takes, at each logit
        # within rounding of LeakyReLU's kink, the side nearest ``got``'s
        grad1 = min(reference_gat.admissible(want["grad1"], changes),
                    key=lambda g: compare.diff_gap(got["grad1"], g)[0])
        return compare.train_readings(got, dict(want, grad1=grad1))

    out = {"program": readings(program)}
    if "control" in extra:
        out["control"] = readings(as_readings(ref(precision="high")))
    if "half_batch" in extra:
        out["half_batch"] = readings(
            as_readings(ref(precision="highest", keep_fraction=0.5)))
    run.log(f"check: {len(changes)} attention logit(s) within "
            f"{reference_gat.KINK_TOL} of the kink, either slope admitted")
    return out
