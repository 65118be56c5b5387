"""Training traffic: ``GCNTrainer.fit`` over one epoch of batches, cycled
until the window ends.

Cell parameters: ``molecules``, ``size_dist``, ``generator_seed`` (the
fixed pool) and ``batch``.

Set-up builds the epoch's batches on the device with the program's own
batch builder (shuffled by ``--seed``), builds one ``GCNTrainer`` from the
seed and drives it through its first three steps with ``fit`` (the first
compiles the step): one step, then two more resumed from its checkpoint,
so the state after step 1 and after step 3 can be read. The window is a
third ``fit`` on the same trainer, resumed after step 3, over batches in
an order drawn from the seed; it ends when ``fit``'s ``on_metrics`` fires
after the last step's loss reached the host, before the final checkpoint.

The check compares the three set-up steps with the reference: each step's
loss, the first gradient as Adam holds it after step 1 (``m / (1 - b1)``),
and each leaf's change over the three steps.
"""
from __future__ import annotations

import functools
import tempfile
import time

import numpy as np

SETUP_STEPS = 3
PROBE_CALLS = 20


def _host(tree):
    import jax

    return jax.tree.map(np.asarray, jax.device_get(tree))


def setup(run):
    import jax

    from chipbench import traffic, work
    from repro.core.gcn import resolve_conv_impls
    from repro.data.graphs import batches
    from repro.optim import AdamConfig
    from repro.training import GCNTrainer, TrainerConfig

    cell, config = run.cell.spec, run.cell.config
    cfg = run.gcn_config()
    t0 = time.monotonic()
    spec = traffic.dataset_spec(config, cell)
    pool = traffic.molecule_pool(config, cell)
    stream = list(batches(pool, spec, cell["batch"], seed=run.seed))
    ids = traffic.epoch_batches(run.seed, len(pool), cell["batch"])
    for b, i in zip(stream, ids, strict=True):
        if not np.array_equal(np.asarray(b["n_nodes"]),
                              [pool[j].n_nodes for j in i]):
            raise RuntimeError("the program's batch order is not the one "
                               "the benchmark derives from the seed")
    x = stream[0]["x"]
    nnz_pad = max(a.nnz_pad for a in stream[0]["adj"])
    run.log(f"data: {len(pool)} molecules, {len(stream)} batches of "
            f"{cell['batch']} (m_pad {x.shape[1]}, nnz_pad {nnz_pad}) in "
            f"{time.monotonic() - t0:.2f} s")
    for i, d in enumerate(resolve_conv_impls(cfg, x.shape[0], x.shape[1],
                                             nnz_pad)):
        run.log(f"layer {i}: auto resolves to {d.impl} [{d.source}]")

    ckpt = tempfile.TemporaryDirectory(prefix="chipbench-ckpt-")
    trainer = GCNTrainer(cfg, opt=AdamConfig(**config["optimizer"]),
                         tcfg=TrainerConfig(checkpoint_dir=ckpt.name,
                                            checkpoint_every=10 ** 9,
                                            seed=run.seed))
    params0 = _host(trainer.init_state()[0])
    order = traffic.batch_cycle(run.seed, len(stream))
    first = [next(order) for _ in range(SETUP_STEPS)]
    # step 1: compiles; its state gives the first gradient
    _, state1, rec = trainer.fit([stream[first[0]]])
    losses = [rec["loss"]]
    grad1 = jax.tree.map(lambda m: m / (1 - trainer.opt.b1),
                         _host(state1["m"]))
    # steps 2 and 3, resumed from step 1's checkpoint, one per epoch so
    # on_metrics reports each loss
    params3, _, _ = trainer.fit(
        lambda e: [stream[first[e]]], epochs=SETUP_STEPS,
        on_metrics=lambda step, r: losses.append(r["loss"]))
    flops = float(np.mean([work.train_flops(m, config["gcn"])
                           for m in pool]))
    state = {"trainer": trainer, "stream": stream, "order": order,
             "first": first, "ckpt": ckpt, "pool": pool, "ids": ids,
             "program": {"losses": losses, "grad1": grad1,
                         "dparams": jax.tree.map(
                             np.subtract, _host(params3), params0)},
             "flops_per_mol": flops}
    if run.trace:
        state["probe"] = _build_probe(run, cfg, stream[first[0]], params3)
    return state


def window(state, run):
    trainer, stream = state["trainer"], state["stream"]
    rec: dict = {"steps": 0}

    def epoch(_):
        yield from (stream[i] for i in state["first"])   # fast-forwarded
        run.open_window()
        rec["start"] = t = time.monotonic()
        end = t + run.seconds
        while True:
            rec["steps"] += 1
            yield stream[next(state["order"])]
            if time.monotonic() >= end:
                return

    def done(_, r):
        rec["end"] = time.monotonic()
        run.close_window()
        rec["loss"] = r["loss"]

    trainer.fit(epoch, epochs=1, on_metrics=done)
    batch = run.cell.spec["batch"]
    mol_per_s = rec["steps"] * batch / (rec["end"] - rec["start"])
    run.log(f"window: {rec['steps']} steps of {batch} in "
            f"{rec['end'] - rec['start']:.4f} s; last loss {rec['loss']!r}")
    return {"t_start": rec["start"], "t_end": rec["end"],
            "attempted": rec["steps"],
            "failed": 0 if np.isfinite(rec["loss"]) else rec["steps"],
            "metrics": {"mol_per_s": mol_per_s},
            "counters": {"mol_per_s": mol_per_s,
                         "train_flops_per_mol": state["flops_per_mol"],
                         **state.get("probe", {}).get("counters", {})}}


def _build_probe(run, cfg, batch, params):
    """One jitted forward+backward of each conv layer over a real batch,
    under the stable name ``chipbench_conv<i>``, compiled here."""
    import jax
    import jax.numpy as jnp

    from chipbench import work
    from repro.core.formats import BatchedCOO
    from repro.core.graph_conv import graph_conv_batched

    adj = [(a.row_ids, a.col_ids, a.values, a.nnz, a.n_rows)
           for a in batch["adj"]]
    n_nodes = np.asarray(batch["n_nodes"])
    nnz = np.stack([np.asarray(a.nnz) for a in batch["adj"]], axis=1)
    m_pad = batch["x"].shape[1]
    mask = (np.arange(m_pad)[None, :, None] < n_nodes[:, None, None])
    calls, layers = [], []
    n_in = cfg.n_features
    for i, (conv, n_out) in enumerate(zip(params["convs"],
                                          cfg.conv_widths)):
        def layer(p, adj_arrays, x, dy):
            coo = [BatchedCOO(*a) for a in adj_arrays]
            y, vjp = jax.vjp(lambda p, x: graph_conv_batched(
                p, coo, x, impl=cfg.impl, k_pad=cfg.k_pad,
                interpret=cfg.interpret, precision=cfg.precision), p, x)
            return y, vjp(dy)

        name = f"chipbench_conv{i}"
        layer.__name__ = layer.__qualname__ = name
        x = jnp.asarray(batch["x"]) if i == 0 else jnp.asarray(
            mask * np.random.default_rng(i).standard_normal(
                (len(n_nodes), m_pad, n_in)), jnp.float32)
        dy = jnp.asarray(mask * np.ones((1, 1, n_out)), jnp.float32)
        fn = jax.jit(layer)
        args = (conv, adj, x, dy)
        jax.block_until_ready(fn(*args))
        calls.append((fn, args))
        flops = PROBE_CALLS * work.conv_train_flops(
            [(int(n), [int(z) for z in row]) for n, row in
             zip(n_nodes, nnz)], n_in, n_out)
        nbytes = PROBE_CALLS * work.conv_train_bytes(
            int(n_nodes.sum()), int(nnz.sum()), cfg.channels, n_in, n_out)
        least, bound = work.least_time(flops, nbytes, run.peak) \
            if run.peak else (None, None)
        run.log(f"probe {name}: {PROBE_CALLS} calls, {flops} useful FLOPs,"
                f" {nbytes} useful bytes; least time {least!r} s ({bound} "
                "bound)")
        layers.append({"program": f"jit_{name}", "flops": flops,
                       "bytes": nbytes})
        n_in = n_out
    return {"calls": calls, "counters": {
        "programs": [p["program"] for p in layers], "probe": layers}}


def probe(state, run):
    import jax

    for fn, args in state["probe"]["calls"]:
        out = None
        for _ in range(PROBE_CALLS):
            out = fn(*args)
        jax.block_until_ready(out)


def check(state, run, extra=()):
    import jax

    from chipbench import compare, reference

    config = run.cell.config
    program = state["program"]
    pool, ids = state["pool"], state["ids"]
    n_max = config["molecules"]["max_nodes"]
    g = config["gcn"]
    batches = []
    for pos in state["first"]:
        mols = [pool[j] for j in ids[pos]]
        adj, x, mask = reference.dense_batch(mols, n_max, g["channels"],
                                             g["n_features"])
        labels = np.stack([m.label for m in mols])
        batches.append((adj, x, mask, labels))
    state.clear()                   # free the program before the reference
    jax.clear_caches()
    ref = functools.partial(reference.train, run.seed, g,
                            config["optimizer"], batches)

    def as_readings(r):
        return {"losses": r["losses"], "grad1": r["grad1"],
                "dparams": jax.tree.map(np.subtract, r["params"],
                                        r["params0"])}

    want = as_readings(ref(precision="highest"))
    out = {"program": compare.train_readings(program, want)}
    if "control" in extra:
        out["control"] = compare.train_readings(
            as_readings(ref(precision="high")), want)
    if "half_batch" in extra:
        out["half_batch"] = compare.train_readings(
            as_readings(ref(precision="highest", keep_fraction=0.5)), want)
    return out
