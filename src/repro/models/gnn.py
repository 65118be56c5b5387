"""GNN layer zoo on top of the g-SpMM message-passing primitive
(DESIGN.md §11).

Both layers keep the paper's batched execution discipline — a handful of
batched device ops per layer for the WHOLE mini-batch, never a per-sample or
per-head loop:

- ``gat_layer``  (Graph Attention, arXiv:1710.10903): the per-head feature
  transform is one einsum; the attention then takes one of two paths,
  chosen by ``repro.autotune.gat_attention_path``. Under ``impl="auto"``
  with no mesh, where one f32 ``(heads·batch, m_pad, m_pad)`` block fits
  a sixteenth of the device's HBM, the logits, the masked row softmax and
  the aggregation run on dense per-(graph, head) blocks: elementwise work
  and ONE batched matmul, no per-edge gather or scatter. Otherwise the
  per-edge path: logits by two gathers over node-level projections, the
  softmax over each destination row's incoming edges by
  :func:`repro.kernels.segment_softmax.segment_softmax`, and the
  attention-weighted aggregation of EVERY head as ONE ``batched_spmm``
  with the head axis flattened into the batch axis — each head's
  attention weights are its scalar edge values. An optional skip
  projection and a mean over the heads (a node task's output layer)
  complete the PPI configuration of the paper.
- ``rgcn_layer`` (Relational GCN, arXiv:1703.06103): the per-relation weight
  transforms run as ONE ragged :func:`repro.kernels.grouped_matmul` over
  relation-major tokens (the MoE idiom of DESIGN.md §4 — relations are the
  groups), and the degree-normalized neighborhood aggregation of every
  relation is ONE ``(copy_lhs, mean)`` g-SpMM over the relation-flattened
  batch.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.autotune import gat_attention_path
from repro.core.formats import BatchedCOO
from repro.core.graph_conv import flatten_channels
from repro.core.message_passing import message_passing
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.ops import batched_spmm
from repro.kernels.segment_softmax import NEG_INF, segment_softmax
from repro.observability import REGISTRY


def init_gat_layer(key, n_in: int, n_out: int, heads: int, *,
                   skip: bool = False):
    """Multi-head GAT parameters: per-head transform ``w`` to ``n_out //
    heads`` features, split attention vectors ``a_src``/``a_dst`` (the
    concatenation trick: a·[h_i ‖ h_j] = a_src·h_j + a_dst·h_i), an
    output bias over the concatenated heads, and with ``skip`` the skip
    projection ``w_skip`` of the layer's input to every head's width."""
    if n_out % heads:
        raise ValueError(f"n_out={n_out} not divisible by heads={heads}")
    d_head = n_out // heads
    keys = jax.random.split(key, 4 if skip else 3)
    scale = 1.0 / jnp.sqrt(n_in)
    params = {
        "w": jax.random.uniform(keys[0], (heads, n_in, d_head), jnp.float32,
                                -scale, scale),
        "a_src": jax.random.uniform(keys[1], (heads, d_head), jnp.float32,
                                    -scale, scale),
        "a_dst": jax.random.uniform(keys[2], (heads, d_head), jnp.float32,
                                    -scale, scale),
        "b": jnp.zeros((n_out,), jnp.float32),
    }
    if skip:
        params["w_skip"] = jax.random.uniform(
            keys[3], (n_in, n_out), jnp.float32, -scale, scale)
    return params


def _edge_counts(adj: BatchedCOO, m_pad: int) -> jax.Array:
    """``(batch, m_pad, m_pad)`` f32: how many valid edge slots join each
    (destination row, source column) pair, so a duplicated edge counts
    twice, as in :func:`segment_softmax`. Connectivity only: no gradient
    flows through it. One scatter per graph, shared by every head."""
    batch, nnz_pad = adj.row_ids.shape
    valid = (jnp.arange(nnz_pad)[None, :] < adj.nnz[:, None])
    graph = jnp.broadcast_to(jnp.arange(batch)[:, None], (batch, nnz_pad))
    counts = jnp.zeros((batch, m_pad, m_pad), jnp.float32).at[
        graph, jnp.clip(adj.row_ids, 0, m_pad - 1),
        jnp.clip(adj.col_ids, 0, m_pad - 1)].add(valid.astype(jnp.float32))
    return jax.lax.stop_gradient(counts)


def _attend_dense_blocks(h, s_src, s_dst, counts, negative_slope):
    """Every head's attention as dense ``(heads, batch, m_pad, m_pad)``
    blocks: logits for every node pair, a softmax per row over the pairs
    ``counts`` marks (each weighted by its multiplicity), and ONE batched
    matmul with ``h`` → ``(heads, batch, m_pad, d_head)``, normalised
    after the matmul so XLA fuses each block into its producer and
    consumer and never writes one to HBM.

    The logits are ``LeakyReLU(s_dst[i] + s_src[j])`` less the row's
    constant ``s_dst[i]``, which the softmax cancels, written as ``s_src[j]
    + (slope − 1)·min(pre, 0)``: ``s_dst`` then reaches the weights only
    through the kink, so its gradient is exactly 0 in a row whose logits
    all lie on one side, not a residue of the row term. That residue pairs
    the backward matmul's ``dO·hᵀ`` with the forward matmul's rounding,
    which at ``HIGHEST`` on the TPU swamps the small gradient of
    ``a_dst``. The row max is held constant under differentiation, and a
    zero-degree row gives 0 with finite gradients."""
    edge = counts > 0

    def logit(src, pre):
        # LeakyReLU(pre) - s_dst, with LeakyReLU's slope 1 at 0
        return src + (negative_slope - 1.0) * jnp.where(pre < 0, pre, 0.0)

    # the logits rise with s_src, so a row's max over its edges is the
    # logit of the row's largest source half
    src_max = jnp.max(jnp.where(edge, s_src[..., None, :], NEG_INF), axis=-1)
    top = jax.lax.stop_gradient(logit(src_max, s_dst + src_max))[..., None]
    src = s_src[..., None, :]
    e = logit(src, s_dst[..., :, None] + src)
    # mask BEFORE exp: off the edges e may exceed its row's max
    z = jnp.exp(jnp.where(edge, e - top, -jnp.inf)) * counts
    out = jnp.einsum("hbij,hbjd->hbid", z, h,
                     precision=jax.lax.Precision.HIGHEST)
    return out / jnp.maximum(jnp.sum(z, axis=-1, keepdims=True), 1e-30)


def gat_layer(
    params,
    adj: BatchedCOO,             # connectivity; edge values are ignored
    x: jax.Array,                # (batch, m_pad, n_in)
    *,
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
    mesh=None,
    negative_slope: float = 0.2,
    mean_heads: bool = False,
) -> jax.Array:
    """One multi-head graph-attention layer → ``(batch, m_pad, n_out)`` with
    the heads' outputs concatenated, or ``(batch, m_pad, n_out // heads)``
    averaged over the heads with ``mean_heads``.

    ``alpha = softmax_row(LeakyReLU(a_src·h[c] + a_dst·h[r]))`` per head
    over each destination row's incoming edges, then ``out[r] =
    Σ_edges alpha · h[c]``, by one of two paths
    (``autotune.gat_attention_path``):

    - ``"dense_blocks"``: with ``impl="auto"``, no ``mesh``, and an f32
      ``(heads·batch, m_pad, m_pad)`` block within a sixteenth of the
      device's HBM, the logits, softmax and aggregation run on dense
      per-(graph, head) blocks masked by ``_edge_counts``
      (``_attend_dense_blocks``);
    - ``"edges"``: otherwise, two per-edge gathers give the logits,
      :func:`segment_softmax` the weights, and ONE ``batched_spmm`` the
      aggregation of ALL heads (heads flatten into the batch axis,
      head-major, each head's ``alpha`` the scalar value of its edges), so
      ``impl`` resolves over the full SpMM ladder.

    Both give zero-degree rows all-zero attention, so the 0.0 output with
    finite (zero) gradients — no NaN from the empty softmax. With
    ``w_skip`` in ``params`` the projection ``x @ w_skip`` is added to the
    concatenated heads (before any mean and the caller's activation).
    Each trace counts the layer on ``gat_attention_layers_total{path}``.

    Traced under the name scopes ``gat/project`` and ``gat/attend``
    (dense blocks) or ``gat/logits``, ``gat/softmax`` and
    ``gat/aggregate`` (edges, the SpMM's ``spmm/<impl>`` inside the
    last)."""
    heads, _, d_head = params["w"].shape
    batch, m_pad, _ = x.shape
    nnz_pad = adj.row_ids.shape[1]
    path = gat_attention_path(heads, batch, m_pad, impl=impl, mesh=mesh)
    REGISTRY.counter("gat_attention_layers_total",
                     "GAT layers traced, by attention path").inc(path=path)

    with jax.named_scope("gat/project"):
        h = jnp.einsum("bmn,hnf->hbmf", x, params["w"])  # (heads, b, m, d)
        # node-level halves of the edge logit
        s_src = jnp.einsum("hbmf,hf->hbm", h, params["a_src"])
        s_dst = jnp.einsum("hbmf,hf->hbm", h, params["a_dst"])
    if path == "dense_blocks":
        counts = _edge_counts(adj, m_pad)
        with jax.named_scope("gat/attend"):
            out = _attend_dense_blocks(h, s_src, s_dst, counts,
                                       negative_slope)
    else:
        with jax.named_scope("gat/logits"):
            gather = jax.vmap(jax.vmap(lambda s, ids: s[ids]))  # (heads, b)
            ids = (jnp.broadcast_to(adj.col_ids, (heads, batch, nnz_pad)),
                   jnp.broadcast_to(adj.row_ids, (heads, batch, nnz_pad)))
            logits = jax.nn.leaky_relu(
                gather(s_src, ids[0]) + gather(s_dst, ids[1]),
                negative_slope)
        with jax.named_scope("gat/softmax"):
            # per-row softmax, independent per head: (batch, nnz_pad, heads)
            alpha = segment_softmax(logits.transpose(1, 2, 0), adj.row_ids,
                                    nnz=adj.nnz, m_pad=m_pad)
        with jax.named_scope("gat/aggregate"):
            # ONE SpMM for all heads: heads flatten into the batch axis
            # (head-major, like graph_conv's flatten_channels) with each
            # head's alpha as its scalar edge values
            def flat(t):
                return jnp.broadcast_to(t, (heads,) + t.shape).reshape(
                    (heads * batch,) + t.shape[1:])

            a_flat = BatchedCOO(
                row_ids=flat(adj.row_ids), col_ids=flat(adj.col_ids),
                values=alpha.transpose(2, 0, 1).reshape(heads * batch,
                                                        nnz_pad),
                nnz=flat(adj.nnz), n_rows=flat(adj.n_rows))
            out = batched_spmm(a_flat,
                               h.reshape(heads * batch, m_pad, d_head),
                               impl=impl, k_pad=k_pad, interpret=interpret,
                               mesh=mesh).reshape(heads, batch, m_pad, d_head)
    out = (out.transpose(1, 2, 0, 3).reshape(batch, m_pad, heads * d_head)
           + params["b"])
    if "w_skip" in params:
        out = out + x @ params["w_skip"]
    if mean_heads:
        out = jnp.mean(out.reshape(batch, m_pad, heads, d_head), axis=2)
    return out


def init_rgcn_layer(key, n_in: int, n_out: int, relations: int):
    """R-GCN parameters: one weight per relation (stacked for the grouped
    matmul), a self-loop weight, and a bias."""
    k1, k2 = jax.random.split(key)
    scale = 1.0 / jnp.sqrt(n_in)
    return {
        "w_rel": jax.random.uniform(k1, (relations, n_in, n_out), jnp.float32,
                                    -scale, scale),
        "w_self": jax.random.uniform(k2, (n_in, n_out), jnp.float32,
                                     -scale, scale),
        "b": jnp.zeros((n_out,), jnp.float32),
    }


def rgcn_layer(
    params,
    adj: Sequence[BatchedCOO],   # one BatchedCOO per relation
    x: jax.Array,                # (batch, m_pad, n_in)
    *,
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
    mesh=None,
) -> jax.Array:
    """One R-GCN layer: ``out[i] = Σ_r mean_{j ∈ N_r(i)} (x[j] · W_r)
    + x[i] · W_self + b``.

    The per-relation transforms are ONE ragged grouped matmul over
    relation-major tokens (every graph's node block repeated per relation —
    equal group sizes, the capacity-style dispatch of DESIGN.md §4), and the
    per-relation mean aggregation is ONE ``(copy_lhs, mean)`` g-SpMM over
    the relation-flattened batch (``graph_conv.flatten_channels`` — the mean
    normalizer 1/|N_r(i)| is exactly the g-SpMM mean-reduce identity, with
    zero-degree rows contributing the 0.0 identity).
    """
    relations = len(adj)
    batch, m_pad, n_in = x.shape
    n_out = params["w_rel"].shape[-1]
    tokens = m_pad * batch

    # relation-major tokens: [all nodes under W_0 | all nodes under W_1 | …]
    xt = jnp.broadcast_to(x.reshape(1, tokens, n_in),
                          (relations, tokens, n_in)).reshape(-1, n_in)
    h = grouped_matmul(xt, params["w_rel"],
                       jnp.full((relations,), tokens, jnp.int32),
                       interpret=interpret)
    h = h.reshape(relations * batch, m_pad, n_out)

    a_flat = flatten_channels(adj)
    agg = message_passing(a_flat, h, op="copy_lhs", reduce="mean",
                          impl=impl, k_pad=k_pad, interpret=interpret,
                          mesh=mesh)
    y = jnp.sum(agg.reshape(relations, batch, m_pad, n_out), axis=0)
    return y + x @ params["w_self"] + params["b"]
