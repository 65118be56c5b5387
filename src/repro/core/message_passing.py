"""Message-passing layer API — the g-SpMM primitive as a layer building
block (DESIGN.md §11), beside ``repro.core.graph_conv``.

Graph convolution fixes the inner op to ``C[r] += val · B[c]`` (weighted-sum
aggregation). Message passing generalizes it to

    ``C[r] = reduce_{edges (r, c)} op(B[c], e)``

with a static ``(op, reduce)`` pair and edge values ``e`` that may be
scalars or per-edge feature vectors — the DGL g-SpMM shape
(arXiv:1909.01315) on the existing batched stack. The batched execution
story is unchanged: ONE device op per call for the whole mini-batch, kernels
shared with plain batched SpMM, ``impl="auto"`` resolved per workload by
``repro.autotune`` (the candidate ladder restricted to the g-SpMM-capable
subset), mesh sharding via ``repro.distributed.spmm``.

The model-zoo layers built on this primitive live in ``repro.models.gnn``:

- ``gat_layer``  — multi-head attention on one of two paths
  (``repro.autotune.gat_attention_path``): dense per-(graph, head)
  blocks where an f32 ``(heads·batch, m_pad, m_pad)`` block fits a
  sixteenth of HBM under ``impl="auto"`` with no mesh, else per-edge
  logits → :func:`repro.kernels.segment_softmax.segment_softmax` → one
  scalar-edge ``batched_spmm`` over the head-flattened batch;
- ``rgcn_layer`` — relation-batched weights via ``grouped_matmul`` + one
  ``(copy_lhs, mean)`` g-SpMM over the relation-flattened batch.
"""
from __future__ import annotations

import jax

from repro.core.formats import BatchedCOO
from repro.kernels.ops import batched_gspmm, resolve_gspmm_impl


def resolve_message_passing_impl(
    adj: BatchedCOO,
    x: jax.Array,
    *,
    op: str = "mul",
    reduce: str = "sum",
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
    mesh=None,
    mesh_axis: str = "data",
):
    """Resolve ``impl`` against one message-passing call's workload.

    Returns a :class:`repro.autotune.Decision`. With ``mesh=``, resolution
    runs against the per-shard workload — the shapes each device actually
    executes (DESIGN.md §6)."""
    if mesh is not None:
        from repro.distributed.spmm import resolve_sharded_gspmm_impl

        return resolve_sharded_gspmm_impl(
            adj, x, mesh, op=op, reduce=reduce, axis=mesh_axis, impl=impl,
            k_pad=k_pad, interpret=interpret)
    return resolve_gspmm_impl(adj, x, op=op, reduce=reduce, impl=impl,
                              k_pad=k_pad, interpret=interpret)


def message_passing(
    adj: BatchedCOO,
    x: jax.Array,                # (batch, m_pad, n_b) node features
    *,
    op: str = "mul",
    reduce: str = "sum",
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
    mesh=None,
    mesh_axis: str = "data",
) -> jax.Array:
    """One batched message-passing step: per sample,
    ``out[r] = reduce_{edges (r, c)} op(x[c], e)`` with ``e = adj.values``
    (scalar per edge, or a ``(batch, nnz_pad, d_e)`` feature vector with
    ``d_e`` equal to the feature width).

    Differentiable in ``adj.values`` and ``x``; zero-degree rows emit the
    0.0 identity with zero gradient for every reduce. ``(mul, sum)`` with
    scalar edges is exactly ``batched_spmm`` and delegates to it (full
    registry, precision variants)."""
    return batched_gspmm(adj, x, op=op, reduce=reduce, impl=impl,
                         k_pad=k_pad, interpret=interpret, mesh=mesh,
                         mesh_axis=mesh_axis)
