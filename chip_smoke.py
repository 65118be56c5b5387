#!/usr/bin/env python3
"""Bring-up smoke run of the ChemGCN train and serve path on the TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # four chips: the mesh paths only

One chip, in one process (any failing phase exits non-zero):

1. device — JAX must report a TPU and the Pallas kernels must run compiled
   (``$REPRO_INTERPRET=1`` or ``GCNConfig.interpret=True`` raise);
2. train — every compiled candidate of each conv layer is timed on the
   chip (``autotune.measure_workload``) and recorded in a tuning cache, so
   ``impl="auto"`` resolves from measurement; each layer's decision must be
   a compiled Pallas impl. Then ``GCNTrainer(GCNConfig.tox21()).fit`` runs
   20 steps at batch 100 over a seeded tox21-shaped dataset of 7,831
   molecules (Tox21's compound count). The loss must be finite and fall; on
   one batch, loss and gradients under ``impl="auto"`` must match
   ``impl="ref"`` within the f32 tolerance of ``tests/oracle.py``;
3. serve — a ``Scheduler`` on ``RealClock`` serves 256 skewed-size requests
   with the trained params (``bn_mode="sample"``) after a warm-up: every
   request done, none failed, one compiled program per tier used, logits
   matching a ``GraphServeEngine`` built with ``impl="ref"``. Tiers resolve
   ``impl="auto"`` from the cost model.

``--chips 4`` runs only ``GCNTrainer(mesh=)`` data-parallel training and
``Scheduler(mesh=)`` waves on a 4-device ``("data",)`` mesh, each against
its single-device counterpart.

Matmuls run at ``highest`` precision so that ``impl="ref"`` is an f32
reference on the TPU too. Printed times are smoke timings, not metrics. The
last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import itertools
import json
import os
import pathlib
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
TOX21_COMPOUNDS = 7831      # compounds in Tox21 (paper §V-B)
SEED = 0                    # data and weights are generated from it
PALLAS_BASES = ("fused", "fused_hybrid", "pallas_coo", "pallas_gemm")


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str):
    log(f"== {name}")
    t0 = time.perf_counter()
    try:
        yield
    except BaseException:
        print(f"chip_smoke: phase {name!r} FAILED", file=sys.stderr,
              flush=True)
        raise
    log(f"== {name}: passed in {time.perf_counter() - t0:.1f} s")


def bootstrap() -> None:
    """Put the checkout's ``src`` (and ``tests``, for the oracle tolerance)
    on the path; refuse to run outside a checkout."""
    if not (HERE / "src" / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {HERE / 'src'}"
                         "; run it from a checkout of the repository")
    sys.path[:0] = [str(HERE / "src"), str(HERE / "tests")]


def f32_tol() -> tuple[float, float]:
    from oracle import TOLS

    return TOLS["f32"]


def assert_close(name: str, got, want, tol) -> float:
    import jax
    import numpy as np

    atol, rtol = tol
    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        if not np.all(np.isfinite(g)):
            raise AssertionError(f"{name}: non-finite values")
        np.testing.assert_allclose(g, w, atol=atol, rtol=rtol, err_msg=name)
        worst = max(worst, float(np.max(np.abs(g - w), initial=0.0)))
    log(f"{name}: max abs difference {worst!r} (atol {atol}, rtol {rtol})")
    return worst


def is_pallas(impl: str) -> bool:
    from repro.autotune import precision_of

    return precision_of(impl)[0] in PALLAS_BASES


def device_phase(chips: int, cfg) -> None:
    import jax

    from repro.kernels import require_compiled

    devs = jax.devices()
    log(f"devices: {len(devs)} x {devs[0].platform} ({devs[0].device_kind})")
    require_compiled(cfg.interpret)     # a TPU backend, kernels compiled
    if len(devs) < chips:
        raise RuntimeError(f"--chips {chips} but JAX sees {len(devs)}")


def adj_arrays(b):
    return [(a.row_ids, a.col_ids, a.values, a.nnz, a.n_rows)
            for a in b["adj"]]


def loss_and_grads(cfg, mesh=None):
    import jax

    from repro.core.formats import BatchedCOO
    from repro.core.gcn import gcn_loss

    def loss(p, arrays, x, n, y):
        adj = [BatchedCOO(*a) for a in arrays]
        return gcn_loss(p, cfg, adj, x, n, y, mesh=mesh)[0]

    return jax.jit(jax.value_and_grad(loss))


def tox21_stream(n_samples: int, batch: int, steps: int, seed: int):
    from repro.data.graphs import GraphDatasetSpec, batches, generate

    t0 = time.perf_counter()
    spec = GraphDatasetSpec.tox21_like(n_samples=n_samples, seed=seed)
    data = generate(spec)
    stream = list(itertools.islice(batches(data, spec, batch, seed=seed),
                                   steps))
    b = stream[0]
    log(f"data: {n_samples} molecules, {len(stream)} batches of {batch} "
        f"(m_pad {b['x'].shape[1]}, nnz_pad {b['adj'][0].nnz_pad}) in "
        f"{time.perf_counter() - t0:.2f} s (set-up)")
    return stream


def tune_layers(cfg, batch: int, m_pad: int, nnz_pad: int) -> None:
    """Time every compiled candidate of each conv layer's workload on the
    chip and record the times in the process tuning cache
    (``$REPRO_TUNE_CACHE``), from which ``impl="auto"`` then resolves."""
    from repro.autotune import default_cache, measure_workload
    from repro.core.gcn import resolve_conv_impls

    cache = default_cache()
    for i, d in enumerate(resolve_conv_impls(cfg, batch, m_pad, nnz_pad)):
        t0 = time.perf_counter()
        times = measure_workload(d.workload, interpret=False, iters=5,
                                 calls=20)
        cache.put(d.workload.key(), times, interpret=False)
        ranked = ", ".join(f"{k} {v * 1e6:.0f} us" for k, v in
                           sorted(times.items(), key=lambda kv: kv[1]))
        log(f"layer {i} forward wall per call, 20 calls back to back "
            f"(smoke timings, not metrics; "
            f"{time.perf_counter() - t0:.1f} s with compiles): {ranked}")


def train_phase(cfg, stream):
    """Tune, then train; return the trained params."""
    import numpy as np

    from repro.core.gcn import resolve_conv_impls
    from repro.training import GCNTrainer, TrainerConfig

    probe = stream[0]
    batch, m_pad = probe["x"].shape[:2]
    nnz_pad = max(a.nnz_pad for a in probe["adj"])
    tune_layers(cfg, batch, m_pad, nnz_pad)
    for i, d in enumerate(resolve_conv_impls(cfg, batch, m_pad, nnz_pad)):
        log(f"layer {i} decision: {d.impl} [{d.source}] {d.reason}")
        if not is_pallas(d.impl):
            raise AssertionError(f"layer {i} runs {d.impl!r}, not a compiled "
                                 "Pallas impl")
    vg = loss_and_grads(cfg)
    args = (adj_arrays(probe), probe["x"], probe["n_nodes"], probe["labels"])
    with tempfile.TemporaryDirectory() as ckpt:
        trainer = GCNTrainer(cfg, tcfg=TrainerConfig(
            checkpoint_dir=ckpt, checkpoint_every=10 ** 9,
            log_every=len(stream)), telemetry=False)
        params0, _ = trainer.init_state()
        loss0 = float(vg(params0, *args)[0])
        t0 = time.perf_counter()
        params, _, metrics = trainer.fit(stream, epochs=1)
        wall = time.perf_counter() - t0
    loss1 = float(vg(params, *args)[0])
    log(f"fit: {len(stream)} steps in {wall:.2f} s wall, first-step compile "
        f"included (smoke timing, not a metric)")
    log(f"loss on batch 0: {loss0!r} before, {loss1!r} after; last step "
        f"loss {metrics['loss']!r}")
    if not (np.isfinite(loss1) and np.isfinite(metrics["loss"])):
        raise AssertionError("non-finite training loss")
    if not loss1 < loss0:
        raise AssertionError(f"loss did not fall: {loss0} -> {loss1}")

    # parity on one batch: the compiled auto path against the XLA oracle
    ref = loss_and_grads(dataclasses.replace(cfg, impl="ref"))
    assert_close("train loss+grads", vg(params, *args), ref(params, *args),
                 f32_tol())
    return params


def serve_requests(n: int, seed: int):
    from repro.data.graphs import GraphDatasetSpec, generate
    from repro.serving import GraphRequest

    spec = GraphDatasetSpec.tox21_like(n_samples=n, seed=seed,
                                       size_dist="skewed")
    data = generate(spec)

    def make():
        return [GraphRequest(rows=s.rows, cols=s.cols, features=s.features,
                             n_nodes=s.n_nodes) for s in data]

    geoms = [(s.n_nodes, max(len(r) for r in s.rows)) for s in data]
    return make, geoms


def run_scheduler(params, cfg, policy, requests, *, mesh=None):
    """Warm up, serve, check the per-tier program invariant; return the
    scheduler (its ``completed`` list carries each request's tier)."""
    from repro.scheduler import RealClock, Scheduler

    sched = Scheduler(params, cfg, tiers=policy, clock=RealClock(),
                      mesh=mesh)
    t0 = time.perf_counter()
    n_prog = sched.warmup(requests)
    log(f"warm-up: {n_prog} tier programs compiled in "
        f"{time.perf_counter() - t0:.2f} s")
    for key, d in sched.programs.decisions().items():
        log(f"tier {key} decision: {d.impl} [{d.source}]")
    t0 = time.perf_counter()
    sched.serve(requests)
    wall = time.perf_counter() - t0
    bad = [r for r in requests if not r.done or r.failed]
    if bad:
        raise AssertionError(f"{len(bad)} requests not served: {bad[0].error}")
    # one program per tier the traffic maps to (warm-up compiled each
    # once); a request topped up into a bigger tier's wave adds none
    used = {policy.assign(r) for r in requests}
    if sched.programs.compile_count != len(used) or not {
            p.served_tier for p in sched.completed} <= used:
        raise AssertionError(f"{sched.programs.compile_count} programs for "
                             f"{len(used)} tiers used")
    sizes = sched.programs.jit_cache_sizes()
    if any(v != 1 for v in sizes.values()):
        raise AssertionError(f"a tier compiled more than once: {sizes}")
    s = sched.metrics.summary()
    log(f"served {len(requests)} requests in {s['waves']} waves, "
        f"{len(used)} tier programs, in {wall:.3f} s wall; latency p50 "
        f"{s['latency_p50_s']!r} s, p99 {s['latency_p99_s']!r} s (smoke "
        "timings, not metrics)")
    return sched


def serve_phase(params, cfg, *, n_requests: int, wave: int,
                seed: int) -> None:
    import numpy as np

    from repro.scheduler import TierPolicy
    from repro.serving import GraphServeEngine

    make, geoms = serve_requests(n_requests, seed)
    policy = TierPolicy.from_requests(geoms, levels=3, batch=wave)
    requests = make()
    sched = run_scheduler(params, cfg, policy, requests)

    # reference: each request re-scored by an impl="ref" engine of the tier
    # that served it (bn_mode="sample" makes a request's logits independent
    # of its wave-mates)
    cfg_ref = dataclasses.replace(cfg, impl="ref", bn_mode="sample")
    by_tier: dict = {}
    for p in sched.completed:
        by_tier.setdefault(p.served_tier, []).append(p.request)
    got, want = [], []
    for tier, reqs in sorted(by_tier.items()):
        eng = GraphServeEngine(params, cfg_ref, batch=tier.batch,
                               m_pad=tier.m_pad, nnz_pad=tier.nnz_pad)
        twins = [dataclasses.replace(r, logits=None, done=False)
                 for r in reqs]
        eng.run(twins)
        got += [r.logits for r in reqs]
        want += [r.logits for r in twins]
    assert_close("served logits", np.stack(got), np.stack(want), f32_tol())


def mesh_phase(cfg, stream, *, n_requests: int, wave: int,
               seed: int) -> None:
    """GCNTrainer(mesh=) and Scheduler(mesh=) on a 4-device data mesh,
    each against its single-device counterpart."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.distributed import sharding as shrules
    from repro.launch.mesh import make_mesh
    from repro.scheduler import TierPolicy
    from repro.training import GCNTrainer, TrainerConfig

    n = len(jax.devices())
    mesh = make_mesh((n,), ("data",))
    if sorted(d.id for d in mesh.devices.flat) != sorted(
            d.id for d in jax.devices()):
        raise AssertionError(f"mesh {mesh.devices} does not hold every "
                             "device")
    log(f"mesh: {mesh.shape} over devices "
        f"{[d.id for d in mesh.devices.flat]}")
    tol = f32_tol()

    with tempfile.TemporaryDirectory() as ckpt:
        trainer = GCNTrainer(cfg, tcfg=TrainerConfig(
            checkpoint_dir=ckpt, checkpoint_every=10 ** 9), mesh=mesh,
            telemetry=False)
        t0 = time.perf_counter()
        params, _, metrics = trainer.fit(stream, epochs=1)
        log(f"mesh fit: {len(stream)} steps in "
            f"{time.perf_counter() - t0:.2f} s "
            f"wall (smoke timing, not a metric); loss {metrics['loss']!r}")
        if not np.isfinite(metrics["loss"]):
            raise AssertionError("non-finite mesh training loss")
    b0 = stream[0]
    d = trainer.layer_decision(b0)
    log(f"mesh layer 0 decision (per shard): {d.impl} [{d.source}]")
    args = (adj_arrays(b0), b0["x"], b0["n_nodes"], b0["labels"])
    placed = jax.tree.map(lambda leaf: jax.device_put(leaf, NamedSharding(
        mesh, shrules.batch_specs(leaf, mesh))), args)
    one = jax.device_get(params)
    assert_close("mesh vs one-device loss+grads",
                 loss_and_grads(cfg, mesh)(params, *placed),
                 loss_and_grads(cfg)(one, *args), tol)

    make, geoms = serve_requests(n_requests, seed)
    policy = TierPolicy.from_requests(geoms, levels=3, batch=wave)
    single, meshed = make(), make()
    run_scheduler(one, cfg, policy, single)
    run_scheduler(one, cfg, policy, meshed, mesh=mesh)
    assert_close("mesh vs one-device served logits",
                 np.stack([r.logits for r in meshed]),
                 np.stack([r.logits for r in single]), tol)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh training and serving paths")
    args = ap.parse_args()
    bootstrap()

    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.core.gcn import GCNConfig

    cfg = GCNConfig.tox21()
    with phase("device"):
        device_phase(args.chips, cfg)
    cache_dir = enable_compile_cache()
    warm = os.path.isdir(cache_dir) and any(os.scandir(cache_dir))
    log(f"compile cache: {cache_dir} ({'warm' if warm else 'cold'})")
    jax.config.update("jax_default_matmul_precision", "highest")

    batch, steps, n_requests, wave = 100, 20, 256, 32
    if args.chips == 4:
        with phase("mesh train+serve"):
            stream = tox21_stream(TOX21_COMPOUNDS, batch, 3, SEED)
            mesh_phase(cfg, stream, n_requests=n_requests, wave=wave,
                       seed=SEED + 1)
    else:
        with tempfile.TemporaryDirectory() as tune_dir:
            # the training layers' measured times; nothing else reads it
            os.environ["REPRO_TUNE_CACHE"] = os.path.join(tune_dir,
                                                          "tune.json")
            with phase("train"):
                stream = tox21_stream(TOX21_COMPOUNDS, batch, steps, SEED)
                params = train_phase(cfg, stream)
            with phase("serve"):
                serve_phase(params, cfg, n_requests=n_requests, wave=wave,
                            seed=SEED + 1)

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"peak_bytes_in_use: {stats['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
