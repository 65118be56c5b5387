"""Batched serving engines (wave-scheduled, slot-masked).

Two engines share the wave philosophy:

- ``ServeEngine``      — LM decode waves (one compiled decode step per token);
- ``GraphServeEngine`` — ChemGCN inference waves: a queue of single-molecule
  scoring requests becomes ONE batched forward pass per wave, every graph
  convolution running as one Batched SpMM with ``impl="auto"`` (adaptive
  dispatch, DESIGN.md §5) instead of one dispatch per molecule — the paper's
  launch-amortization argument applied to online inference.

The Batched-SpMM philosophy applied to serving: a batch of small independent
jobs becomes ONE compiled decode step per token, never one dispatch per
request. Requests are served in waves of ``batch`` slots:

- prompts in a wave are left-padded to a common length and prefilled in
  lockstep through the shared decode step (one compiled program total — the
  decode cell of the dry-run);
- finished sequences are masked (their sampled tokens discarded) so one long
  request cannot stall completed ones' results — and the wave ends as soon as
  EVERY slot is done, at which point the next wave refills all slots;
- sampling is greedy or temperature-categorical.

A production multi-host engine would add per-slot position vectors for true
continuous batching; the step function and caches already support restarting
a slot, so that is a scheduler change, not a model change.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.formats import coo_from_lists
from repro.core.gcn import GCNConfig, apply_gcn
from repro.models import lm


@dataclasses.dataclass
class Request:
    prompt: list[int]
    max_new_tokens: int = 16
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False          # served to completion (max_new_tokens reached)
    truncated: bool = False     # cut off by the engine's max_len window


def _serve_in_waves(engine, requests: list) -> list:
    """Shared wave scheduler: slice the queue into ``engine.batch``-slot
    waves, run each through ``engine._run_wave``."""
    queue = list(requests)
    while queue:
        wave, queue = queue[:engine.batch], queue[engine.batch:]
        engine._run_wave(wave)
    return requests


class ServeEngine:
    def __init__(self, params, cfg: ModelConfig, *, batch: int = 4,
                 max_len: int = 128, temperature: float = 0.0, seed: int = 0):
        self.params, self.cfg = params, cfg
        self.batch, self.max_len = batch, max_len
        self.temperature = temperature
        self.key = jax.random.key(seed)
        self._decode = jax.jit(
            lambda p, t, c, pos: lm.decode_step(p, cfg, t, c, pos))

    def _sample(self, logits):
        if self.temperature <= 0:
            return jnp.argmax(logits, axis=-1)
        self.key, sub = jax.random.split(self.key)
        return jax.random.categorical(sub, logits / self.temperature)

    def _run_wave(self, wave: list[Request]) -> None:
        n = len(wave)
        maxp = max(len(r.prompt) for r in wave)
        toks = np.zeros((self.batch, maxp), np.int32)
        for s, r in enumerate(wave):
            toks[s, maxp - len(r.prompt):] = r.prompt    # left padding
        caches = lm.init_decode_state(self.cfg, self.batch, self.max_len)
        # lockstep prefill through the decode step (positions shared)
        last = None
        for i in range(maxp):
            last, caches = self._decode(
                self.params, jnp.asarray(toks[:, i:i + 1]), caches,
                jnp.asarray(i, jnp.int32))
        pos = maxp
        cur = np.asarray(self._sample(last[:, 0, :]))
        active = np.array([True] * n + [False] * (self.batch - n))
        for s, r in enumerate(wave):
            if r.max_new_tokens <= 0:       # zero-budget: served, no tokens
                r.done = True
                active[s] = False
                continue
            r.out.append(int(cur[s]))
            if r.max_new_tokens <= 1:
                r.done = True
                active[s] = False
        while active.any() and pos < self.max_len - 1:
            logits, caches = self._decode(
                self.params, jnp.asarray(cur.reshape(-1, 1)), caches,
                jnp.asarray(pos, jnp.int32))
            cur = np.asarray(self._sample(logits[:, 0, :]))
            pos += 1
            for s, r in enumerate(wave):
                if not active[s]:
                    continue
                r.out.append(int(cur[s]))
                if len(r.out) >= r.max_new_tokens:
                    r.done = True
                    active[s] = False
        # slots still active here hit the max_len window, not their token
        # budget: record the truncation honestly instead of claiming done.
        for s, r in enumerate(wave):
            if active[s]:
                r.truncated = True
                active[s] = False

    def run(self, requests: list[Request]) -> list[Request]:
        return _serve_in_waves(self, requests)


@dataclasses.dataclass
class GraphRequest:
    """One molecule to score: per-channel COO triples + node features.

    ``failed``/``error`` record a per-request rejection (oversize for the
    wave geometry, no admissible bucket, …) — a failed request never kills
    its wave; the other slots are served normally.
    """

    rows: list[np.ndarray]          # one (e,) int array per channel
    cols: list[np.ndarray]
    features: np.ndarray            # (n_nodes, n_features)
    n_nodes: int
    logits: np.ndarray | None = None
    done: bool = False
    failed: bool = False
    error: str | None = None

    @property
    def max_nnz(self) -> int:
        """Largest per-channel edge count — with ``n_nodes`` the request's
        geometry, which the scheduler buckets on (DESIGN.md §8)."""
        return max((len(r) for r in self.rows), default=0)


@dataclasses.dataclass(frozen=True)
class GraphWaveReport:
    """What one executed wave actually carried vs. what its geometry paid
    for — the per-wave record behind the scheduler's padding-waste metric."""

    slots: int                      # wave batch slots (engine.batch)
    n_requests: int                 # real requests placed in the wave
    n_failed: int                   # of those, rejected by validation
    real_nodes: int                 # Σ n_nodes over served requests
    real_nnz: int                   # Σ over served requests and channels
    node_capacity: int              # slots * m_pad
    nnz_capacity: int               # slots * channels * nnz_pad


class GraphServeEngine:
    """Wave-scheduled batched GCN inference.

    Requests are padded to fixed wave geometry (``batch`` slots, ``m_pad``
    node rows) so every wave hits the SAME jitted program — one compilation
    total, and per (conv layer × wave) either ONE fused megakernel op
    (``impl="fused"``/auto-selected, DESIGN.md §7) or one stacked
    (channels·batch) Batched SpMM. Empty slots carry zero-nnz adjacencies
    and contribute nothing (the padding invariant of §IV-C) — under the
    fused kernel's skew-aware packing they cost zero nnz chunks too, so a
    part-full final wave does not pay for its empty slots. The layer impl
    per workload shape is chosen by ``cfg.impl`` — ``"auto"`` resolves via
    repro.autotune at trace time; :meth:`layer_decision` exposes the choice.
    """

    def __init__(self, params, cfg: GCNConfig, *, batch: int = 32,
                 m_pad: int = 56, nnz_pad: int = 256, mesh=None,
                 precision: str | None = None):
        if precision is not None:
            # Serving's dtype-policy override (DESIGN.md §10): training keeps
            # the config's f32, an engine may opt its waves into bf16 without
            # touching the shared GCNConfig.
            cfg = dataclasses.replace(cfg, precision=precision)
        self.params, self.cfg = params, cfg
        self.batch, self.m_pad, self.nnz_pad = batch, m_pad, nnz_pad
        self.mesh = mesh
        if mesh is not None:
            params = jax.device_put(params, jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))
            self.params = params
        self._apply = jax.jit(
            lambda adj_arrays, x, n_nodes: apply_gcn(
                params, cfg, self._rebuild(adj_arrays), x, n_nodes,
                mesh=mesh))
        # Degree guard posture (see _validate): only an ELL-class layer impl
        # silently drops > k_pad nnz/row, so resolve what this engine's
        # geometry will actually run — EVERY conv layer, since each layer
        # re-resolves "auto" against its own n_in/n_out workload.
        impls = {cfg.impl}
        if cfg.impl == "auto" and cfg.k_pad is not None:
            from repro.core.gcn import resolve_conv_impls

            impls = {d.impl for d in resolve_conv_impls(
                cfg, batch, m_pad, nnz_pad, mesh=mesh)}
        from repro.autotune import precision_of

        self._ell_degree_guard = (
            self.cfg.k_pad is not None
            and any(i != "auto"
                    and precision_of(i)[0] in ("ell", "pallas_ell")
                    for i in impls))

    @staticmethod
    def _rebuild(adj_arrays):
        from repro.core.formats import BatchedCOO
        return [BatchedCOO(*a) for a in adj_arrays]

    def layer_decision(self):
        """The adaptive layer decision for this engine's (fixed) wave
        geometry — fused megakernel vs stacked SpMM for ``layer="gcn"``, the
        g-SpMM workload for ``"gat"``/``"rgcn"`` (DESIGN.md §11) — for the
        first conv layer. Audit/ops visibility; the jitted apply resolves
        identically."""
        from repro.core.formats import BatchedCOO
        from repro.core.graph_conv import resolve_graph_conv_impl

        if self.cfg.layer != "gcn":
            from repro.core.gcn import resolve_conv_impls

            return resolve_conv_impls(self.cfg, self.batch, self.m_pad,
                                      self.nnz_pad, mesh=self.mesh)[0]
        z2 = jnp.zeros((self.batch, self.nnz_pad), jnp.int32)
        adj = [BatchedCOO(z2, z2, z2.astype(jnp.float32),
                          jnp.zeros((self.batch,), jnp.int32),
                          jnp.full((self.batch,), self.m_pad, jnp.int32))
               for _ in range(self.cfg.channels)]
        x = jnp.zeros((self.batch, self.m_pad, self.cfg.n_features),
                      jnp.float32)
        return resolve_graph_conv_impl(
            adj, x, self.cfg.conv_widths[0], impl=self.cfg.impl,
            k_pad=self.cfg.k_pad, interpret=self.cfg.interpret,
            mesh=self.mesh, precision=self.cfg.precision)

    def _validate(self, s: int, r: GraphRequest) -> str | None:
        """Reason this request cannot ride this engine's wave geometry, or
        None when it fits. Never raises: an oversize request is a per-slot
        failure, not a wave-killer — the scheduler routes it to a bigger
        bucket or rejects it cleanly (DESIGN.md §8)."""
        if r.n_nodes > self.m_pad:
            return (f"request {s}: n_nodes={r.n_nodes} exceeds wave "
                    f"m_pad={self.m_pad}; needs a bigger geometry tier")
        # channel-count defects first: zip would silently truncate, letting
        # an unvalidated channel reach the degree guard / wave assembly
        if len(r.rows) != self.cfg.channels or len(r.cols) != self.cfg.channels:
            return (f"request {s}: {len(r.rows)} row / {len(r.cols)} col "
                    f"channels, engine expects {self.cfg.channels}")
        for ch, (rows, cols) in enumerate(zip(r.rows, r.cols)):
            if len(rows) > self.nnz_pad:
                return (f"request {s}, channel {ch}: {len(rows)} edges "
                        f"exceed wave nnz_pad={self.nnz_pad}")
            if len(rows) != len(cols):
                return (f"request {s}, channel {ch}: {len(rows)} row ids vs "
                        f"{len(cols)} col ids")
            if len(rows):
                rr, cc = np.asarray(rows), np.asarray(cols)
                # malformed ids must soft-fail like every other defect —
                # never raise (a negative id would blow up np.bincount
                # below, and a huge one would corrupt the wave's scatter)
                if (int(rr.min()) < 0 or int(cc.min()) < 0
                        or int(rr.max()) >= r.n_nodes
                        or int(cc.max()) >= r.n_nodes):
                    return (f"request {s}, channel {ch}: edge ids outside "
                            f"[0, n_nodes={r.n_nodes})")
        if self._ell_degree_guard:
            # ELL silent-drop guard (ISSUE 5) at the concrete boundary: the
            # jitted apply cannot data-branch, so a request whose row degree
            # exceeds cfg.k_pad would get edges silently zeroed by
            # coo_to_ell — soft-fail it instead. Active only when this
            # engine's layer impl actually resolves to the ELL class.
            for ch, rows in enumerate(r.rows):
                if len(rows):
                    deg = int(np.bincount(np.asarray(rows, np.int64)).max())
                    if deg > self.cfg.k_pad:
                        return (f"request {s}, channel {ch}: max row degree "
                                f"{deg} exceeds cfg.k_pad={self.cfg.k_pad} "
                                "(an ELL impl would silently drop edges)")
        return None

    def run_wave(self, wave: list[GraphRequest]) -> GraphWaveReport:
        """Execute ONE wave (≤ ``batch`` requests) through the shared jitted
        program and return the wave's fill/padding accounting. This is the
        per-wave executor the continuous-batching ``repro.scheduler`` drives;
        ``run()`` keeps the legacy fixed-slicing loop on top of it.

        The whole wave runs inside a ``serve/wave`` span (DESIGN.md §13)
        tagged with the wave geometry; any kernel-dispatch spans fired at
        trace time (telemetry on, first wave per geometry) nest inside it."""
        from repro.observability import TRACER

        n = len(wave)
        if n > self.batch:
            raise ValueError(f"wave of {n} requests > {self.batch} slots")
        with TRACER.span("serve/wave", cat="serve", args={
                "n_requests": n, "slots": self.batch, "m_pad": self.m_pad,
                "nnz_pad": self.nnz_pad, "channels": self.cfg.channels,
                "layer": self.cfg.layer, "impl": self.cfg.impl}):
            return self._run_wave_inner(wave)

    def _run_wave_inner(self, wave: list[GraphRequest]) -> GraphWaveReport:
        n = len(wave)
        channels = self.cfg.channels
        n_feat = self.cfg.n_features
        x = np.zeros((self.batch, self.m_pad, n_feat), np.float32)
        n_nodes = np.zeros((self.batch,), np.int32)
        triples_by_ch = [[] for _ in range(channels)]
        served: list[tuple[int, GraphRequest]] = []
        n_failed = real_nodes = real_nnz = 0
        for s in range(self.batch):
            r = wave[s] if s < n else None
            if r is not None:
                err = self._validate(s, r)
                if err is None:
                    served.append((s, r))
                    x[s, :r.n_nodes] = r.features
                    n_nodes[s] = r.n_nodes
                    real_nodes += r.n_nodes
                    for ch in range(channels):
                        rows = np.asarray(r.rows[ch], np.int32)
                        cols = np.asarray(r.cols[ch], np.int32)
                        real_nnz += len(rows)
                        triples_by_ch[ch].append(
                            (rows, cols, np.ones(len(rows), np.float32)))
                    continue
                r.failed, r.error, r.done = True, err, False
                n_failed += 1
            # empty or failed slot: zero-nnz adjacency
            for ch in range(channels):
                z = np.zeros(0, np.int32)
                triples_by_ch[ch].append((z, z, np.zeros(0, np.float32)))
        adj = [coo_from_lists(t, n_rows=list(n_nodes),
                              nnz_pad=self.nnz_pad)
               for t in triples_by_ch]
        adj_arrays = [(a.row_ids, a.col_ids, a.values, a.nnz, a.n_rows)
                      for a in adj]
        x, n_nodes = jnp.asarray(x), jnp.asarray(n_nodes)
        if self.mesh is not None:
            # one wave spans every device: batch-shard the wave operands so
            # each shard_map'd SpMM (and the dense ops GSPMD partitions
            # around it) runs on its slice of the slots
            from repro.distributed import sharding as shrules

            def place(leaf):
                return jax.device_put(leaf, jax.sharding.NamedSharding(
                    self.mesh, shrules.batch_specs(leaf, self.mesh)))

            adj_arrays, x, n_nodes = jax.tree.map(
                place, (adj_arrays, x, n_nodes))
        logits = np.asarray(self._apply(adj_arrays, x, n_nodes))
        for s, r in served:
            r.logits = logits[s]
            r.done = True
        return GraphWaveReport(
            slots=self.batch, n_requests=n, n_failed=n_failed,
            real_nodes=real_nodes, real_nnz=real_nnz,
            node_capacity=self.batch * self.m_pad,
            nnz_capacity=self.batch * channels * self.nnz_pad)

    # _serve_in_waves drives waves through the same public executor
    _run_wave = run_wave

    def compiled_programs(self) -> int:
        """Entries in this engine's jit cache — 1 is the one-program-per-
        geometry invariant the scheduler's program cache relies on. The
        count comes from JAX's private ``_cache_size`` introspection helper
        (this method is the ONE place that dependency lives)."""
        return self._apply._cache_size()

    def run(self, requests: list[GraphRequest]) -> list[GraphRequest]:
        return _serve_in_waves(self, requests)
