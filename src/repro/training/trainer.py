"""Fault-tolerant trainers: the LM ``Trainer`` and the paper-side
``GCNTrainer`` (ChemGCN over Batched SpMM, §IV-D/§V-B).

``GCNTrainer`` routes every graph-convolution through
``batched_spmm(impl=cfg.impl)`` — ``"auto"`` by default, so the adaptive
dispatcher (DESIGN.md §5) picks the kernel per workload shape instead of the
trainer hard-coding one.

LM ``Trainer`` responsibilities:
- builds the pjit train step from ``distributed.steps`` against any mesh
  (elastic: restart on a different mesh shape re-lowers automatically);
- checkpoint/restart: atomic periodic checkpoints + resume-from-latest; a
  SIGTERM triggers one final checkpoint before exit (preemption-safe);
- straggler posture: the input pipeline is pull-based (any iterator), steps
  are dispatched asynchronously (JAX async dispatch) and the loss is only
  synced every ``log_every`` steps, so a slow host does not serialize the
  whole fleet on every step; checkpoint writes happen off the critical path
  (device→host copy only at save steps).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os
import signal
import time
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import ModelConfig
from repro.core.formats import BatchedCOO, validate_ell_k_pad
from repro.core.gcn import GCNConfig, gcn_loss, gcn_node_loss, init_gcn
from repro.distributed.compression import ef_init
from repro.distributed.steps import build_train_step
from repro.models import lm
from repro.optim import AdamConfig, adam_init, adam_update


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    total_steps: int = 100
    checkpoint_every: int = 50
    log_every: int = 10
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    microbatches: int = 1
    remat: bool = False
    compress_grads: bool = False
    zero1: bool = True
    seed: int = 0


class Trainer:
    def __init__(self, cfg: ModelConfig, mesh, opt: AdamConfig,
                 tcfg: TrainerConfig):
        self.cfg, self.mesh, self.opt, self.tcfg = cfg, mesh, opt, tcfg
        self.manager = CheckpointManager(tcfg.checkpoint_dir, keep=tcfg.keep)
        self._jit_builder, self.p_specs, self.o_specs = build_train_step(
            cfg, mesh, opt, microbatches=tcfg.microbatches, remat=tcfg.remat,
            compress_grads=tcfg.compress_grads, zero1=tcfg.zero1,
            donate=True)
        self._step_fn = None
        self._interrupted = False

    # -- state ---------------------------------------------------------

    def init_state(self):
        params = lm.init_params(jax.random.key(self.tcfg.seed), self.cfg)
        opt_state = adam_init(params)
        if self.tcfg.compress_grads:
            opt_state["ef_err"] = ef_init(params)
        return params, opt_state

    def restore_or_init(self):
        params, opt_state = self.init_state()
        latest = self.manager.latest_step()
        if latest is not None:
            params, opt_state = self.manager.restore(
                latest, (params, opt_state))
            return params, opt_state, latest
        return params, opt_state, 0

    # -- loop ----------------------------------------------------------

    def _on_sigterm(self, *_):
        self._interrupted = True

    def fit(self, data_iter: Iterator[dict],
            on_metrics: Callable[[int, dict], None] | None = None):
        tcfg = self.tcfg
        params, opt_state, start = self.restore_or_init()
        old_handler = signal.signal(signal.SIGTERM, self._on_sigterm)
        log_path = os.path.join(tcfg.checkpoint_dir, "metrics.jsonl")
        step = start
        try:
            with self.mesh:
                for step in range(start, tcfg.total_steps):
                    batch = next(data_iter)
                    if self._step_fn is None:
                        shapes = jax.tree.map(
                            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            batch)
                        self._step_fn = self._jit_builder(shapes)
                    params, opt_state, metrics = self._step_fn(
                        params, opt_state, batch)
                    if (step + 1) % tcfg.log_every == 0 or \
                            step + 1 == tcfg.total_steps:
                        loss = float(metrics["loss"])   # sync point
                        rec = {"step": step + 1, "loss": loss,
                               "time": time.time()}
                        with open(log_path, "a") as f:
                            f.write(json.dumps(rec) + "\n")
                        if on_metrics:
                            on_metrics(step + 1, rec)
                    if (step + 1) % tcfg.checkpoint_every == 0:
                        self.manager.save(step + 1, (params, opt_state))
                    if self._interrupted:
                        break
        finally:
            signal.signal(signal.SIGTERM, old_handler)
        if self._interrupted:
            # preemption: final durable checkpoint before exiting
            self.manager.save(step + 1, (params, opt_state))
        return params, opt_state


class _FlatCarry:
    """Layout of ``GCNTrainer``'s training carry: the params and Adam's
    ``m`` and ``v``, each ravelled in the params tree's leaf order, as the
    three rows of one ``(3, n_params)`` float32 array, plus Adam's int32
    ``step``. ``pack`` and ``unpack`` trace under ``jit`` and round-trip
    every value bit for bit (a narrower float param widens to float32 and
    back)."""

    def __init__(self, params_like):
        leaves, self.treedef = jax.tree.flatten(params_like)
        self.shapes = [leaf.shape for leaf in leaves]
        self.dtypes = [leaf.dtype for leaf in leaves]
        self.bounds = np.cumsum([0] + [int(np.prod(s)) for s in self.shapes])

    def pack(self, params, state):
        rows = [jnp.concatenate([jnp.ravel(leaf).astype(jnp.float32)
                                 for leaf in jax.tree.leaves(tree)])
                for tree in (params, state["m"], state["v"])]
        return jnp.stack(rows), state["step"]

    def unpack(self, carry):
        flat, step = carry

        def tree(row, dtypes):
            return self.treedef.unflatten([
                flat[row, lo:hi].reshape(shape).astype(dtype)
                for lo, hi, shape, dtype in zip(
                    self.bounds[:-1], self.bounds[1:], self.shapes, dtypes)])

        f32 = [jnp.float32] * len(self.shapes)
        return tree(0, self.dtypes), {"m": tree(1, f32), "v": tree(2, f32),
                                      "step": step}


class _FlatStep:
    """``GCNTrainer._step``: calling it runs the jitted step over the flat
    carry (the program named ``step``, carry donated); ``lower`` takes the
    pytrees ``init_state`` returns and packs them first, so it lowers the
    program ``fit`` runs."""

    def __init__(self, jitted, pack):
        self.jitted, self._pack = jitted, pack

    def __call__(self, carry, adj_arrays, x, n_nodes, labels):
        return self.jitted(carry, adj_arrays, x, n_nodes, labels)

    def lower(self, params, state, adj_arrays, x, n_nodes, labels):
        return self.jitted.lower(self._pack(params, state), adj_arrays, x,
                                 n_nodes, labels)


def _read_metrics(metrics) -> tuple[float, float, float]:
    """Loss, accuracy and gradient norm from the step's ``(3,)`` metrics
    vector, in one device→host copy (a sync); NaNs before any step."""
    if metrics is None:
        return (float("nan"),) * 3
    return tuple(metrics.tolist())


class GCNTrainer:
    """Trainer for the paper's target application: ChemGCN over Batched SpMM.

    One jitted step per batch shape; adjacency pytrees are flattened to plain
    arrays at the jit boundary (the quickstart/test idiom) so retracing is
    shape-keyed only. The SpMM implementation comes from ``cfg.impl`` —
    ``"auto"`` by default, resolved per workload by ``repro.autotune``.

    Calling convention (DESIGN.md §4): inside ``fit`` the training state is
    one flat carry, ``(flat, step)`` — params, Adam ``m`` and ``v`` ravelled
    in the params tree's leaf order as the rows of one ``(3, n_params)``
    float32 array, and Adam's int32 step — packed once after
    ``restore_or_init`` and donated to every step, which unravels it, runs
    ``gcn_loss`` → ``value_and_grad`` → ``adam_update`` on pytrees, ravels
    the result back into the donated buffers and returns loss, accuracy and
    gradient norm as one ``(3,)`` vector. So the hot call takes the carry's
    2 buffers and the batch's arrays, returns 3 buffers and allocates no
    fresh state (``train_step_buffers``: 25 in, 3 out on tox21). The
    carry is unpacked to ``(params, state)`` pytrees only for a checkpoint,
    the final save and ``fit``'s return, so checkpoints and return values
    keep their structure. ``_step.lower(params, state, adj_arrays, x,
    n_nodes, labels)`` takes ``init_state``'s pytrees, packs them and lowers
    the program ``fit`` runs (named ``step``).

    ``mesh=`` turns the step data-parallel (DESIGN.md §6): every graph
    convolution's Batched SpMM runs mesh-sharded over the ``"data"`` axis
    (per-shard ``impl="auto"`` resolution), batch leaves are placed
    batch-sharded on the mesh, params/optimizer state stay replicated, and
    the gradient all-reduce over the mesh is inserted by GSPMD from exactly
    that sharded-batch/replicated-params layout.

    Telemetry (DESIGN.md §13): each iteration of ``fit`` runs in a
    ``train/iter`` span holding ``train/batch`` (fetch, ELL guard,
    placement), ``train/step`` (the jitted step's enqueue), ``train/sync``
    (the ``log_every`` and epoch-end host syncs) and ``train/checkpoint``;
    steps count on ``registry`` (the process default unless one is passed),
    and so do the real edges and padded edge slots of each batch that
    carries them as host integers (``train_edges_total``,
    ``train_edge_slots_total``; ``data.graphs.batches`` does),
    ``train_step_buffers{dir="in"|"out"}`` holds the argument and result
    buffer counts of the dispatched step (set once per batch shape), and
    loss/accuracy/grad-norm gauges and graphs-throughput sync on the
    ``tcfg.log_every`` cadence — the per-step path never forces a device
    sync (JAX async dispatch stays pipelined). ``telemetry=False`` opts the
    instance out entirely.
    """

    def __init__(self, cfg: GCNConfig, opt: AdamConfig | None = None,
                 tcfg: TrainerConfig | None = None, *, mesh=None,
                 registry=None, telemetry: bool = True):
        from repro.observability import default_registry

        self.cfg = cfg
        self.opt = opt or AdamConfig(lr=3e-3)
        self.tcfg = tcfg or TrainerConfig()
        self.mesh = mesh
        self.manager = CheckpointManager(self.tcfg.checkpoint_dir,
                                         keep=self.tcfg.keep)
        self.telemetry = telemetry
        self.registry = registry if registry is not None else \
            default_registry()
        self._m_steps = self.registry.counter(
            "train_steps_total", "training steps executed")
        self._m_loss = self.registry.gauge("train_loss", "last synced loss")
        self._m_acc = self.registry.gauge(
            "train_accuracy", "last synced accuracy")
        self._m_gnorm = self.registry.gauge(
            "train_grad_norm", "last synced global gradient L2 norm")
        self._m_tput = self.registry.gauge(
            "train_graphs_per_s", "graphs/s over the last log window")
        self._m_buffers = self.registry.gauge(
            "train_step_buffers",
            "argument (dir=in) and result (dir=out) buffers of the "
            "dispatched training step")
        self._m_edges = self.registry.counter(
            "train_edges_total", "real edges of the batches stepped on")
        self._m_edge_slots = self.registry.counter(
            "train_edge_slots_total",
            "padded edge slots of the batches stepped on")

        carry = _FlatCarry(jax.eval_shape(
            lambda: init_gcn(jax.random.key(0), cfg)))
        self._pack = jax.jit(carry.pack)
        self._unpack = jax.jit(carry.unpack)

        @functools.partial(jax.jit, donate_argnums=0)
        def step(flat_carry, adj_arrays, x, n_nodes, labels):
            params, state = carry.unpack(flat_carry)
            adj = [BatchedCOO(*a) for a in adj_arrays]
            (loss, acc), grads = jax.value_and_grad(
                lambda p: gcn_loss(p, self.cfg, adj, x, n_nodes, labels,
                                   mesh=mesh),
                has_aux=True)(params)
            gnorm = jnp.sqrt(sum(
                jnp.vdot(g, g).real for g in jax.tree.leaves(grads)))
            params, state = adam_update(self.opt, params, grads, state)
            new_carry = carry.pack(params, state)
            if mesh is not None:    # replicated in, replicated out
                new_carry = jax.lax.with_sharding_constraint(
                    new_carry, jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec()))
            return new_carry, jnp.stack([loss, acc, gnorm])

        self._step = _FlatStep(step, self._pack_carry)

        @functools.partial(jax.jit, static_argnames=("m_pads", "impls"))
        def sampled_step(params, state, adj_arrays, x, labels, *, m_pads,
                         impls):
            adjs = [BatchedCOO(*a) for a in adj_arrays]
            (loss, acc), grads = jax.value_and_grad(
                lambda p: gcn_node_loss(p, self.cfg, adjs, x, labels,
                                        m_pads=m_pads, impls=impls),
                has_aux=True)(params)
            gnorm = jax.numpy.sqrt(sum(
                jax.numpy.vdot(g, g).real
                for g in jax.tree.leaves(grads)))
            params, state = adam_update(self.opt, params, grads, state)
            return params, state, loss, acc, gnorm

        self._sampled_step = sampled_step
        self._block_impl_memo: dict[tuple, tuple] = {}

    def block_decisions(self, batch) -> tuple:
        """Per-layer autotune decisions for one sampled minibatch
        (``repro.autotune.Decision`` each) — the block-aware workload:
        ``block`` = the layer's padded dst-row count, ``max_deg`` = the
        sampled in-degree skew rounded up to a power of two (so the memo and
        tuning-cache keys stay bounded), ``k_pad=None`` (no global ELL bound
        exists for a sampled block). Memoized per (geometry, skew) key; the
        jitted step receives the resolved impl names as static args."""
        from repro import autotune
        from repro.kernels import resolve_interpret

        blocks = batch.blocks
        m_pads = tuple(b.m_pad for b in blocks)
        n_seed = len(batch.labels)
        # static per-layer dst-row bound: the next block's padded src count
        # (dst rows ARE its src prefix); the last layer's is the seed count
        dst_pads = tuple(
            min(m_pads[i], m_pads[i + 1]) if i + 1 < len(blocks)
            else min(m_pads[i], -(-n_seed // 8) * 8)
            for i in range(len(blocks)))
        max_degs = tuple(
            1 << max(b.max_deg, 1).bit_length() for b in blocks)
        key = (m_pads, tuple(b.nnz_pad for b in blocks), dst_pads, max_degs)
        if key not in self._block_impl_memo:
            interpret = resolve_interpret(self.cfg.interpret)
            decisions = []
            for i, b in enumerate(blocks):
                w = autotune.Workload(
                    batch=1, m_pad=b.m_pad, nnz_pad=b.nnz_pad, k_pad=None,
                    n_b=self.cfg.conv_widths[i],
                    itemsize=batch.x.dtype.itemsize,
                    max_deg=max_degs[i], block=dst_pads[i])
                if self.cfg.impl != "auto":
                    decisions.append(autotune.forced_decision(
                        w, self.cfg.impl))
                else:
                    decisions.append(autotune.select_impl(
                        w, allow_pallas=not interpret,
                        cache=autotune.default_cache()))
            self._block_impl_memo[key] = tuple(decisions)
        return self._block_impl_memo[key]

    def fit_sampled(self, loader, *, epochs: int = 1, prefetch: bool = True,
                    on_metrics: Callable[[int, dict], None] | None = None):
        """Giant-graph training over a sampled-minibatch stream
        (DESIGN.md §14): same step/checkpoint/telemetry machinery as ``fit``
        on ``repro.sampling.SampledNodeLoader`` batches.

        Per minibatch: the per-layer block decisions resolve host-side
        (:meth:`block_decisions` — block-aware ``Workload``, memoized per
        geometry) and the jitted node-classification step runs with the
        blocks' ``(m_pads, impls)`` as static args, so the compile count is
        bounded by the loader's bucket ladder, not the epoch length. The
        distinct program count is exported as the ``train_sampled_programs``
        gauge next to the usual loss/accuracy/step-time series.

        Resume follows ``fit``'s contract: restore-latest, then fast-forward
        ``start`` batches — the loader's ``(seed, epoch, batch)``-addressable
        sampling makes the replayed stream bitwise identical. ``prefetch``
        wraps each epoch in the one-deep double buffer so the next
        minibatch's sample+gather overlaps the current step."""
        if self.mesh is not None:
            raise ValueError("fit_sampled is single-host for now: sampled "
                             "blocks have batch=1, so there is no batch "
                             "axis to shard over a mesh")
        from repro.observability import TRACER, enabled

        params, state, start = self.restore_or_init()
        loss = acc = gnorm = float("nan")
        labels_kw = {"layer": self.cfg.layer, "impl": self.cfg.impl}
        log_every = max(self.tcfg.log_every, 1)
        win_t0, win_nodes = time.perf_counter(), 0
        m_programs = self.registry.gauge(
            "train_sampled_programs",
            "distinct compiled sampled-step programs (bucket-bounded)")
        programs: set[tuple] = set()
        step = seen = 0
        for epoch in range(epochs):
            batches = loader.epoch(epoch)
            if prefetch:
                from repro.sampling import Prefetcher

                batches = Prefetcher(batches, registry=self.registry)
            for b in batches:
                seen += 1
                if seen <= start:
                    continue    # already trained before the restart
                decisions = self.block_decisions(b)
                impls = tuple(d.impl for d in decisions)
                m_pads = tuple(bl.m_pad for bl in b.blocks)
                adj_arrays = [(bl.adj.row_ids, bl.adj.col_ids,
                               bl.adj.values, bl.adj.nnz, bl.adj.n_rows)
                              for bl in b.blocks]
                programs.add((m_pads,
                              tuple(bl.nnz_pad for bl in b.blocks), impls))
                if self.telemetry:
                    with TRACER.span("train/sampled_step", cat="train",
                                     args={"step": seen, **labels_kw}
                                     if enabled() else None):
                        params, state, loss, acc, gnorm = self._sampled_step(
                            params, state, adj_arrays, b.x, b.labels,
                            m_pads=m_pads, impls=impls)
                    self._m_steps.inc(**labels_kw)
                    m_programs.set(len(programs), **labels_kw)
                    win_nodes += len(b.labels)
                    if seen % log_every == 0:
                        # the ONLY per-window device sync (same posture
                        # as fit)
                        self._m_loss.set(float(loss), **labels_kw)
                        self._m_acc.set(float(acc), **labels_kw)
                        self._m_gnorm.set(float(gnorm), **labels_kw)
                        now = time.perf_counter()
                        if now > win_t0:
                            self._m_tput.set(win_nodes / (now - win_t0),
                                             **labels_kw)
                        win_t0, win_nodes = now, 0
                else:
                    params, state, loss, acc, gnorm = self._sampled_step(
                        params, state, adj_arrays, b.x, b.labels,
                        m_pads=m_pads, impls=impls)
                step = seen
                if step % max(self.tcfg.checkpoint_every, 1) == 0:
                    self.manager.save(step, (params, state))
            if step > start:
                rec = {"epoch": epoch + 1, "loss": float(loss),
                       "acc": float(acc), "grad_norm": float(gnorm),
                       "programs": len(programs), "time": time.time()}
                if self.telemetry:
                    self._m_loss.set(float(loss), **labels_kw)
                    self._m_acc.set(float(acc), **labels_kw)
                    self._m_gnorm.set(float(gnorm), **labels_kw)
                if on_metrics:
                    on_metrics(epoch + 1, rec)
        if step > start:
            self.manager.save(step, (params, state))
        return params, state, {"loss": float(loss), "acc": float(acc),
                               "grad_norm": float(gnorm),
                               "programs": len(programs)}

    def layer_decision(self, batch: dict):
        """The adaptive layer decision (``repro.autotune.Decision``) for one
        training batch's first conv layer — fused megakernel vs stacked SpMM
        (DESIGN.md §5/§7) — resolved exactly as the jitted step will resolve
        it (per-shard workload when the trainer is mesh-parallel). Audit /
        logging only; the step itself resolves at trace time."""
        from repro.core.graph_conv import resolve_graph_conv_impl

        if self.cfg.layer != "gcn":
            from repro.core.gcn import resolve_conv_impls

            adj, x = batch["adj"], batch["x"]
            return resolve_conv_impls(
                self.cfg, x.shape[0], x.shape[1], adj[0].row_ids.shape[1],
                mesh=self.mesh)[0]
        return resolve_graph_conv_impl(
            batch["adj"], batch["x"], self.cfg.conv_widths[0],
            impl=self.cfg.impl, k_pad=self.cfg.k_pad,
            interpret=self.cfg.interpret, mesh=self.mesh,
            precision=self.cfg.precision)

    def _replicate(self, tree):
        if self.mesh is None:
            return tree
        repl = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec())
        return jax.device_put(tree, repl)

    def _pack_carry(self, params, state):
        """The flat carry of ``(params, state)``, replicated on the mesh."""
        return self._replicate(self._pack(params, state))

    def init_state(self):
        params = init_gcn(jax.random.key(self.tcfg.seed), self.cfg)
        state = adam_init(params)
        return self._replicate(params), self._replicate(state)

    def restore_or_init(self):
        """Resume-from-latest (the LM ``Trainer`` pattern): restore the
        newest checkpoint's (params, opt-state) and its step counter, or
        fresh-init at step 0 when the directory holds none. ``fit`` calls
        this — NOT ``init_state`` — so a restarted trainer continues where
        the killed one checkpointed instead of silently restarting at step
        0 and overwriting prior saves."""
        params, state = self.init_state()
        latest = self.manager.latest_step()
        if latest is not None:
            params, state = self.manager.restore(latest, (params, state))
            return self._replicate(params), self._replicate(state), latest
        return params, state, 0

    def _place_batch(self, tree):
        """Batch-shard every batch-leading leaf on the mesh's data axis (the
        computation then follows the data: SpMMs run per-shard, GSPMD
        all-reduces the grads)."""
        if self.mesh is None:
            return tree
        from repro.distributed import sharding as shrules

        def one(x):
            spec = shrules.batch_specs(x, self.mesh)
            return jax.device_put(
                x, jax.sharding.NamedSharding(self.mesh, spec))

        return jax.tree.map(one, tree)

    def _guard_ell(self, b: dict, memo: dict, candidates: tuple) -> None:
        """Raise when this batch would reach an ELL impl with a row degree
        above ``cfg.k_pad`` (see ``fit``). ``memo`` caches, per batch shape,
        whether any conv layer resolves to one of ``candidates``."""
        from repro.core.gcn import resolve_conv_impls

        key = (b["x"].shape[0], b["x"].shape[1],
               max(a.nnz_pad for a in b["adj"]))
        if key not in memo:
            memo[key] = (
                self.cfg.impl in candidates
                or any(d.impl in candidates
                       for d in resolve_conv_impls(
                           self.cfg, *key, itemsize=b["x"].dtype.itemsize,
                           mesh=self.mesh)))
        if memo[key]:
            for a in b["adj"]:
                validate_ell_k_pad(a, b["x"].shape[1], self.cfg.k_pad)

    def fit(self, batch_iter: Iterator[dict] | Callable, *, epochs: int = 1,
            on_metrics: Callable[[int, dict], None] | None = None):
        """``batch_iter``: a callable returning one epoch's batch iterator
        (e.g. ``lambda e: data.batches(...)``), or an iterable. A one-shot
        iterator/generator is materialized once so every epoch sees the
        full data (a generator would silently exhaust after epoch 1).
        Checkpoints every ``checkpoint_every`` *steps* (the LM Trainer
        convention) plus a final save.

        Resume: the latest checkpoint in ``tcfg.checkpoint_dir`` is restored
        (``restore_or_init``) and the first ``start`` batches of the stream
        are fast-forwarded, so a save→kill→restart sequence continues the
        same deterministic trajectory instead of re-initializing at step 0
        and overwriting the saved state.

        Spans (class docstring): one ``train/iter`` per batch taken, and one
        more per epoch for the fetch that finds the epoch's end.

        The state is the flat carry (class docstring) from the pack after
        ``restore_or_init`` to the unpack for each checkpoint and the
        return: ``fit`` returns fresh ``(params, state, rec)`` pytrees, of
        ``init_state``'s structure."""
        params, state, start = self.restore_or_init()
        carry = self._pack_carry(params, state)
        if not callable(batch_iter):
            data = (batch_iter if isinstance(batch_iter, (list, tuple))
                    else list(batch_iter))
            batch_iter = lambda epoch: data  # noqa: E731
        # The jitted step can never data-branch, so the ELL silent-drop
        # guard (ISSUE 5) lives HERE, at the last concrete boundary: when
        # any conv layer's impl resolves to an ELL path for this batch's
        # shapes, an undersized k_pad fails fast instead of silently
        # zeroing edges in coo_to_ell. The impl resolution is shape-keyed
        # and memoized; the DATA check (a bincount per sample) runs on
        # every batch — it is data-dependent, so no object/shape memo can
        # soundly skip it, and it is trivial next to a training step.
        # Class membership via precision_of so reduced-precision ELL
        # variants (ell_bf16, pallas_ell_i8, …) trip the guard too.
        from repro.autotune import precision_of
        from repro.core.spmm import IMPLS

        ell_candidates = tuple(
            i for i in IMPLS if precision_of(i)[0] in ("ell", "pallas_ell"))
        maybe_ell = (self.cfg.k_pad is not None
                     and self.cfg.impl in ("auto",) + ell_candidates)
        from repro.observability import TRACER, enabled

        def span(name, args=None):
            return TRACER.span(name, cat="train", args=args) \
                if self.telemetry else contextlib.nullcontext()

        ell_by_shape: dict[tuple, bool] = {}
        step_shapes: set[tuple] = set()
        step = seen = 0
        metrics = None
        labels = {"layer": self.cfg.layer, "impl": self.cfg.impl}
        log_every = max(self.tcfg.log_every, 1)
        win_t0, win_graphs = time.perf_counter(), 0
        for epoch in range(epochs):
            batches = iter(batch_iter(epoch))
            while True:
                with span("train/iter"):
                    with span("train/batch"):
                        b = next(batches, None)
                        if b is None:
                            break
                        seen += 1
                        if seen <= start:
                            continue    # already trained before the restart
                        if maybe_ell:
                            self._guard_ell(b, ell_by_shape, ell_candidates)
                        adj_arrays = [(a.row_ids, a.col_ids, a.values, a.nnz,
                                       a.n_rows) for a in b["adj"]]
                        adj_arrays, x, n_nodes, y = self._place_batch(
                            (adj_arrays, b["x"], b["n_nodes"], b["labels"]))
                    with span("train/step", {"step": seen, **labels}
                              if enabled() else None):
                        carry, metrics = self._step(
                            carry, adj_arrays, x, n_nodes, y)
                    if self.telemetry:
                        self._m_steps.inc(**labels)
                        if "edges" in b:    # host ints of the batch builder
                            self._m_edges.inc(b["edges"], **labels)
                            self._m_edge_slots.inc(b["edge_slots"], **labels)
                        shape = (x.shape, tuple(a[0].shape
                                                for a in adj_arrays))
                        if shape not in step_shapes:
                            step_shapes.add(shape)
                            self._m_buffers.set(len(jax.tree.leaves(
                                (carry, adj_arrays, x, n_nodes, y))),
                                dir="in")
                            self._m_buffers.set(len(jax.tree.leaves(
                                (carry, metrics))), dir="out")
                        win_graphs += b["x"].shape[0]
                        if seen % log_every == 0:
                            # the ONLY per-window device sync (mirrors the LM
                            # Trainer's log_every posture)
                            with span("train/sync"):
                                loss, acc, gnorm = _read_metrics(metrics)
                                self._m_loss.set(loss, **labels)
                                self._m_acc.set(acc, **labels)
                                self._m_gnorm.set(gnorm, **labels)
                            now = time.perf_counter()
                            if now > win_t0:
                                self._m_tput.set(
                                    win_graphs / (now - win_t0), **labels)
                            win_t0, win_graphs = now, 0
                    step = seen
                    if step % max(self.tcfg.checkpoint_every, 1) == 0:
                        with span("train/checkpoint"):
                            self.manager.save(step, self._unpack(carry))
            if step > start:    # an epoch fully fast-forwarded on resume
                with span("train/sync"):
                    loss, acc, gnorm = _read_metrics(metrics)
                    rec = {"epoch": epoch + 1, "loss": loss, "acc": acc,
                           "grad_norm": gnorm, "time": time.time()}
                if self.telemetry:
                    self._m_loss.set(rec["loss"], **labels)
                    self._m_acc.set(rec["acc"], **labels)
                    self._m_gnorm.set(rec["grad_norm"], **labels)
                if on_metrics:
                    on_metrics(epoch + 1, rec)
        params, state = self._unpack(carry)
        if step > start:
            self.manager.save(step, (params, state))
        loss, acc, gnorm = _read_metrics(metrics)
        return params, state, {"loss": loss, "acc": acc, "grad_norm": gnorm}
