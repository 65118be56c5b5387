"""ChemGCN — the paper's target application (§IV-D, §V-B).

Architecture per the paper: a stack of graph-convolution layers, batch
normalization after each layer, ReLU, a masked sum readout over nodes, and a
dense prediction head. Two task heads match the evaluation datasets:

- Tox21: 12 independent binary toxicity tasks (sigmoid + BCE);
- Reaction100: 100-way reaction classification (softmax + CE).

``GCNConfig.ppi_gat()`` is the node-level counterpart: GAT on PPI-shaped
protein graphs (arXiv:1710.10903 §3.3), ELU and no batch norm, a skip
projection across the middle layer, and the last layer's head mean as each
node's 121 label logits under a per-node sigmoid cross-entropy.

The model is pure-functional (init/apply), with ``batched=True`` selecting the
Fig. 7 execution and ``batched=False`` the Fig. 6 baseline — identical
numerics, different op structure. Conv layer ``i`` is traced under
``jax.named_scope(f"conv{i}")``, so its ops (and, in a gradient, their
transposes) carry the layer in their HLO metadata.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.formats import BatchedCOO
from repro.core.graph_conv import (
    graph_conv_batched,
    graph_conv_nonbatched,
    init_graph_conv,
)


@dataclasses.dataclass(frozen=True)
class GCNConfig:
    n_features: int = 62          # input atom-feature width
    channels: int = 4             # bond-type adjacency channels
    conv_widths: tuple[int, ...] = (64, 64)   # Tox21: two layers of 64
    n_tasks: int = 12             # Tox21: 12 binary tasks
    task: str = "multitask_binary"  # graph level: "multitask_binary" |
                                  # "multiclass" (masked sum readout, then
                                  # a dense head); node level:
                                  # "node_multilabel" (the last conv layer's
                                  # head mean IS each node's logits: no
                                  # readout, no head; its width / heads
                                  # must equal n_tasks)
    layer: str = "gcn"            # conv layer kind (DESIGN.md §11):
                                  # "gcn"  — channel-summed graph conv
                                  #          (paper eq. (2));
                                  # "gat"  — multi-head attention over the
                                  #          first adjacency channel's
                                  #          connectivity (models/gnn.py);
                                  # "rgcn" — adjacency channels as relations
                                  #          with per-relation weights
    heads: int | tuple[int, ...] = 4  # attention heads (layer="gat" only):
                                  # one for every layer, or one per layer;
                                  # each conv width must divide by its
                                  # layer's heads (concatenated heads, or
                                  # averaged in a node task's last layer)
    activation: str = "relu"      # after every hidden layer: "relu" | "elu"
    skip: tuple[int, ...] = ()    # gat layers that add a learned projection
                                  # of their input before the activation
    impl: str = "auto"            # layer implementation (repro.core.spmm.IMPLS
                                  # incl. the "fused" megakernel; "auto" =
                                  # adaptive dispatch, DESIGN.md §5/§7)
    k_pad: int = 8                # max nnz/row for the ELL path
    batched: bool = True          # Fig. 7 (True) vs Fig. 6 (False)
    precision: str = "f32"        # layer dtype policy under impl="auto"
                                  # ("f32"|"bf16"|"i8", DESIGN.md §10);
                                  # training keeps f32, serving may opt
                                  # into bf16 via GraphServeEngine
    interpret: bool | None = None  # None → repro.kernels.default_interpret()
                                   # ($REPRO_INTERPRET, auto-False on TPU)
    bn_mode: str = "batch"        # "batch": stats over the whole wave (the
                                  # paper's TF training graph); "sample":
                                  # per-graph stats over its own real nodes —
                                  # wave-composition-INVARIANT, required for
                                  # continuous-batching serving where the set
                                  # of co-batched requests is a scheduling
                                  # accident (DESIGN.md §8); "none": no
                                  # batch norm (and no bn params)

    @staticmethod
    def tox21(**kw) -> "GCNConfig":
        return GCNConfig(conv_widths=(64, 64), n_tasks=12,
                         task="multitask_binary", **kw)

    @staticmethod
    def reaction100(**kw) -> "GCNConfig":
        # three conv layers, width 512 (paper §V-B)
        return GCNConfig(conv_widths=(512, 512, 512), n_tasks=100,
                         task="multiclass", **kw)

    @staticmethod
    def ppi_gat(**kw) -> "GCNConfig":
        """GAT on PPI (arXiv:1710.10903 §3.3): three attention layers, 4
        heads of 256 concatenated twice with ELU and a skip projection
        across the middle layer, then 6 heads of 121 averaged into each
        node's 121 label logits; no batch norm. ``kw`` overrides any
        field (a test's smaller widths)."""
        return dataclasses.replace(
            GCNConfig(n_features=50, channels=1,
                      conv_widths=(1024, 1024, 726), n_tasks=121,
                      task="node_multilabel", layer="gat", heads=(4, 4, 6),
                      activation="elu", skip=(1,), bn_mode="none"), **kw)

    @property
    def node_task(self) -> bool:
        return self.task == "node_multilabel"

    def layer_heads(self, i: int) -> int:
        """Attention heads of conv layer ``i``."""
        return self.heads[i] if isinstance(self.heads, tuple) else self.heads


def _init_conv(key, cfg: GCNConfig, i: int, n_in: int, n_out: int):
    """Conv layer ``i``'s params for ``cfg.layer`` (DESIGN.md §11)."""
    if cfg.skip and cfg.layer != "gat":
        raise ValueError("skip projections are a gat-layer option; "
                         f"layer={cfg.layer!r}")
    if cfg.layer == "gcn":
        return init_graph_conv(key, n_in, n_out, cfg.channels)
    from repro.models.gnn import init_gat_layer, init_rgcn_layer

    if cfg.layer == "gat":
        return init_gat_layer(key, n_in, n_out, cfg.layer_heads(i),
                              skip=i in cfg.skip)
    if cfg.layer == "rgcn":
        return init_rgcn_layer(key, n_in, n_out, cfg.channels)
    raise ValueError(f"unknown layer kind {cfg.layer!r}: expected 'gcn', "
                     "'gat' or 'rgcn'")


def _n_hidden(cfg: GCNConfig) -> int:
    """Conv layers followed by batch norm and the activation: all of them,
    but a node task's last layer, whose output is the logits."""
    return len(cfg.conv_widths) - cfg.node_task


def init_gcn(key, cfg: GCNConfig):
    """``{"convs", "bns", "head"}``; a config without batch norm has no
    ``bns`` and a node task no ``head``."""
    keys = jax.random.split(key, len(cfg.conv_widths) + 1)
    params = {"convs": [], "bns": []}
    n_in = cfg.n_features
    for i, w in enumerate(cfg.conv_widths):
        params["convs"].append(_init_conv(keys[i], cfg, i, n_in, w))
        if i < _n_hidden(cfg):
            params["bns"].append({
                "scale": jnp.ones((w,), jnp.float32),
                "bias": jnp.zeros((w,), jnp.float32),
            })
        n_in = w
    if cfg.bn_mode == "none":
        del params["bns"]
    if cfg.node_task:
        return params
    scale = 1.0 / jnp.sqrt(n_in)
    params["head"] = {
        "w": jax.random.uniform(keys[-1], (n_in, cfg.n_tasks), jnp.float32,
                                -scale, scale),
        "b": jnp.zeros((cfg.n_tasks,), jnp.float32),
    }
    return params


def resolve_conv_impls(cfg: GCNConfig, batch: int, m_pad: int, nnz_pad: int,
                       *, itemsize: int = 4, mesh=None):
    """The resolved layer impl for EVERY conv layer of the stack, one
    :class:`repro.autotune.Decision` per ``cfg.conv_widths`` entry.

    ``apply_gcn`` re-resolves ``impl="auto"`` per layer (each layer's
    workload differs in n_in/n_out), so a guard or audit that looks only at
    the first layer can miss a deeper layer landing in a different kernel
    class — consumers that gate on "could an ELL impl run?" must OR over
    this whole tuple. ``itemsize`` must match the features the runtime will
    actually carry (the Workload key embeds it, and the tuning cache is
    keyed per itemsize) — default 4 for the f32 GCN stack. Pure shape work:
    safe to call host-side per geometry.

    ``cfg.layer`` selects the workload shape (DESIGN.md §11): ``"gcn"``
    resolves the graph-conv LAYER workload (fused megakernel vs stacked
    SpMM); ``"gat"`` resolves the edge path's attention aggregation, a
    plain SpMM with the attention weights as scalar edge values over the
    head-flattened batch (the full ladder) — the layer runs it only where
    ``autotune.gat_attention_path`` does not pick dense blocks, so there
    the decision names the fallback; ``"rgcn"`` the ``(copy_lhs, mean)`` g-SpMM over
    the relation-flattened batch (the g-SpMM-capable candidate subset)."""
    from repro import autotune
    from repro.kernels import resolve_interpret

    interpret = resolve_interpret(cfg.interpret)
    decisions = []
    n_in = cfg.n_features
    dtype = (autotune.precision_of(cfg.impl)[1] if cfg.impl != "auto"
             else cfg.precision)
    for i, n_out in enumerate(cfg.conv_widths):
        if cfg.layer == "gat":
            heads = cfg.layer_heads(i)
            w = autotune.Workload(
                batch=batch * heads, m_pad=m_pad, nnz_pad=nnz_pad,
                k_pad=cfg.k_pad, n_b=n_out // heads, itemsize=itemsize,
                dtype=dtype)
        elif cfg.layer == "rgcn":
            w = autotune.Workload(
                batch=batch * cfg.channels, m_pad=m_pad, nnz_pad=nnz_pad,
                k_pad=cfg.k_pad, n_b=n_out, itemsize=itemsize,
                dtype=dtype, op="copy_lhs", reduce="mean")
        else:
            w = autotune.Workload(
                batch=batch, m_pad=m_pad, nnz_pad=nnz_pad, k_pad=cfg.k_pad,
                n_b=n_out, itemsize=itemsize, channels=cfg.channels,
                n_in=n_in, dtype=dtype)
        if mesh is not None:
            from repro.distributed.spmm import shard_count

            w = w.shard(shard_count(mesh, "data"))
        if cfg.impl != "auto":
            decisions.append(autotune.forced_decision(w, cfg.impl))
        elif cfg.layer == "gcn":
            decisions.append(autotune.select_graph_conv_impl(
                w, allow_pallas=not interpret,
                cache=autotune.default_cache()))
        else:
            decisions.append(autotune.select_impl(
                w, allow_pallas=not interpret,
                cache=autotune.default_cache()))
        n_in = n_out
    return tuple(decisions)


def _batch_norm(p, x, mask, mode: str = "batch"):
    """Masked batch-norm: padded nodes excluded from the statistics (the
    paper's TF graph normalizes over real nodes only).

    ``mode="batch"`` reduces over (batch, nodes) — training semantics, but the
    output of one graph then depends on which OTHER graphs share its wave.
    ``mode="sample"`` reduces over each graph's own nodes only, so a request's
    logits are identical whether it is scored alone or inside any wave — the
    invariant the continuous-batching scheduler relies on (DESIGN.md §8).
    """
    if mode not in ("batch", "sample"):
        # a typo silently falling into "batch" would void the scheduler's
        # wave-composition-invariance guarantee — fail at trace time instead
        raise ValueError(f"unknown bn_mode {mode!r}: expected 'batch' or "
                         "'sample'")
    if mode == "sample":
        denom = jnp.maximum(jnp.sum(mask, axis=(1, 2), keepdims=True), 1.0)
        mean = jnp.sum(x * mask, axis=1, keepdims=True) / denom
        var = jnp.sum(((x - mean) * mask) ** 2, axis=1, keepdims=True) / denom
    else:
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        mean = jnp.sum(x * mask, axis=(0, 1)) / denom
        var = jnp.sum(((x - mean) * mask) ** 2, axis=(0, 1)) / denom
    xn = (x - mean) * jax.lax.rsqrt(var + 1e-5)
    return xn * p["scale"] + p["bias"]


def _conv(conv_p, cfg: GCNConfig, adj, h, mesh, *, mean_heads=False):
    """One conv layer of kind ``cfg.layer`` (DESIGN.md §11)."""
    if cfg.layer == "gat":
        from repro.models.gnn import gat_layer

        return gat_layer(conv_p, adj[0], h, impl=cfg.impl, k_pad=cfg.k_pad,
                         interpret=cfg.interpret, mesh=mesh,
                         mean_heads=mean_heads)
    if cfg.layer == "rgcn":
        from repro.models.gnn import rgcn_layer

        return rgcn_layer(conv_p, adj, h, impl=cfg.impl, k_pad=cfg.k_pad,
                          interpret=cfg.interpret, mesh=mesh)
    if cfg.batched:
        return graph_conv_batched(conv_p, adj, h, impl=cfg.impl,
                                  k_pad=cfg.k_pad, interpret=cfg.interpret,
                                  mesh=mesh, precision=cfg.precision)
    return graph_conv_nonbatched(conv_p, adj, h)


def apply_gcn(
    params,
    cfg: GCNConfig,
    adj: Sequence[BatchedCOO],
    x: jax.Array,                # (batch, m_pad, n_features)
    n_nodes: jax.Array,          # (batch,) true node counts
    *,
    mesh=None,                   # shard every SpMM's batch axis (DESIGN.md §6)
) -> jax.Array:
    mask = (
        jnp.arange(x.shape[1])[None, :, None] < n_nodes[:, None, None]
    ).astype(x.dtype)
    if cfg.layer != "gcn" and not cfg.batched:
        # GAT/R-GCN only exist on the batched g-SpMM stack — there is no
        # Fig. 6 per-sample baseline for them
        raise ValueError(f"layer={cfg.layer!r} requires batched=True")
    act = {"relu": jax.nn.relu, "elu": jax.nn.elu}[cfg.activation]
    h = x
    for i, conv_p in enumerate(params["convs"]):
        last = i == _n_hidden(cfg)
        with jax.named_scope(f"conv{i}"):
            h = _conv(conv_p, cfg, adj, h, mesh, mean_heads=last)
        if last:                        # a node task's per-node logits
            return h
        if cfg.bn_mode != "none":
            h = _batch_norm(params["bns"][i], h * mask, mask, cfg.bn_mode)
        h = act(h) * mask
    readout = jnp.sum(h, axis=1)                          # masked sum readout
    return readout @ params["head"]["w"] + params["head"]["b"]


def apply_gcn_blocks(
    params,
    cfg: GCNConfig,
    adjs: Sequence[BatchedCOO],  # one per conv layer, input-side first
    x: jax.Array,                # (m_pads[0], n_features) input-layer src rows
    *,
    m_pads: tuple[int, ...],     # static per-layer square dims (bucket rungs)
    impls: tuple[str, ...] | None = None,  # static per-layer resolved impls
) -> jax.Array:
    """Forward over one sampled minibatch's layered blocks (DESIGN.md §14).

    ``adjs[i]`` is layer ``i``'s bipartite block in the square
    ``(m_pads[i], m_pads[i])`` embedding (``core.csc.Block.adj``): its first
    ``n_dst_i`` output rows are — by the dst-prefix convention — exactly
    layer ``i+1``'s src prefix, so chaining is a static slice/pad to
    ``m_pads[i+1]`` plus a mask from the traced ``adj.n_rows``. All shapes
    here are static (the loader's bucket rungs): one compile per distinct
    ``(m_pads, impls, nnz_pads)``, bounded by the ladder product.

    ``impls`` carries the trainer's per-layer block-aware autotune decision
    (``Workload(block=..., max_deg=...)``) — ``None`` falls back to
    ``cfg.impl`` for every layer. Returns per-node logits
    ``(m_pads[-1], n_tasks)``; rows past the seed count are padding.
    """
    if len(adjs) != len(params["convs"]):
        raise ValueError(f"{len(adjs)} blocks for "
                         f"{len(params['convs'])} conv layers")
    if cfg.layer != "gcn":
        raise ValueError("sampled-block forward currently supports "
                         f"layer='gcn' only, got {cfg.layer!r}")
    if impls is None:
        impls = (cfg.impl,) * len(adjs)
    h = x[None]                               # (1, m_pads[0], n_features)
    for i, (conv_p, bn_p) in enumerate(zip(params["convs"], params["bns"])):
        adj = adjs[i]
        # real dst rows of THIS layer (traced — part of the block's pytree)
        mask = (
            jnp.arange(h.shape[1])[None, :, None] < adj.n_rows[0]
        ).astype(h.dtype)
        with jax.named_scope(f"conv{i}"):
            h = graph_conv_batched(conv_p, [adj], h, impl=impls[i],
                                   k_pad=cfg.k_pad, interpret=cfg.interpret,
                                   precision=cfg.precision)
        h = _batch_norm(bn_p, h * mask, mask, cfg.bn_mode)
        h = jax.nn.relu(h) * mask
        if i + 1 < len(adjs):
            # dst rows ARE the next block's src prefix (same local ids)
            m_next = m_pads[i + 1]
            if m_next <= h.shape[1]:
                h = h[:, :m_next]
            else:
                h = jnp.pad(h, ((0, 0), (0, m_next - h.shape[1]), (0, 0)))
    # node-level head: no readout — one logit row per dst node
    return h[0] @ params["head"]["w"] + params["head"]["b"]


def gcn_node_loss(params, cfg: GCNConfig, adjs, x, labels, *,
                  m_pads: tuple[int, ...],
                  impls: tuple[str, ...] | None = None):
    """Node-classification loss over the seed rows of a sampled minibatch:
    softmax CE on the first ``len(labels)`` rows of the block forward (the
    seed prefix of the last block — padding rows never touch the loss)."""
    logits = apply_gcn_blocks(params, cfg, adjs, x, m_pads=m_pads,
                              impls=impls)[:labels.shape[0]]
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, acc


def gcn_loss(params, cfg: GCNConfig, adj, x, n_nodes, labels, *, mesh=None):
    logits = apply_gcn(params, cfg, adj, x, n_nodes, mesh=mesh)
    if cfg.node_task:
        # labels: (batch, m_pad, n_tasks) in {0, 1}; the mean sigmoid
        # cross-entropy over real nodes × labels
        z = logits
        real = (jnp.arange(z.shape[1])[None, :] < n_nodes[:, None])
        real = real.astype(z.dtype)[..., None]
        count = jnp.maximum(jnp.sum(real), 1.0) * z.shape[-1]
        per = jnp.maximum(z, 0) - z * labels + jnp.log1p(jnp.exp(-jnp.abs(z)))
        loss = jnp.sum(per * real) / count
        hit = ((z > 0).astype(jnp.float32) == labels).astype(jnp.float32)
        acc = jnp.sum(hit * real) / count
    elif cfg.task == "multitask_binary":
        # labels: (batch, n_tasks) in {0, 1}
        z = logits
        loss = jnp.maximum(z, 0) - z * labels + jnp.log1p(jnp.exp(-jnp.abs(z)))
        loss = jnp.mean(loss)
        pred = (z > 0).astype(jnp.float32)
        acc = jnp.mean((pred == labels).astype(jnp.float32))
    else:
        # labels: (batch,) int class ids
        logp = jax.nn.log_softmax(logits, axis=-1)
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, acc
