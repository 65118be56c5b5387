"""Graph convolution layer — paper Fig. 6 (non-batched) and Fig. 7 (batched).

Semantics (paper §II-A, eq. (2)): Y = Σ_ch A_ch · (X · W_ch + bias_ch), summed
over edge channels (bond types in ChemGCN). The execution strategies are
numerically identical and differ only in op structure:

- ``graph_conv_nonbatched``  Fig. 6: one op per (sample × channel) — the
  paper's O(channel·batchsize) launch baseline;
- ``graph_conv_batched``     Fig. 7 and beyond. The SpMM impl resolves per
  LAYER workload (``repro.autotune.select_graph_conv_impl``):

  * ``impl="fused"`` — ONE device op for the whole layer: the Pallas
    megakernel (``kernels/fused_graph_conv.py``, DESIGN.md §7) computes
    X·W_ch + b_ch on the MXU, consumes it in-VMEM with the one-hot-scatter
    SpMM, and accumulates the channel sum — no per-channel HBM
    intermediates, nnz loop bounded by each graph's REAL non-zeros;
  * any SpMM impl — the stacked fallback: the per-channel einsum and ALL
    channels' SpMMs are stacked into one ``(channels·batch)`` batched call
    (4·channels ops → 3 ops), then channel-summed.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.formats import BatchedCOO
from repro.core.spmm import batched_spmm
from repro.kernels import resolve_interpret
from repro.kernels.ref import spmm_coo_single


def init_graph_conv(key, n_in: int, n_out: int, channels: int):
    k1, _ = jax.random.split(key)
    scale = 1.0 / jnp.sqrt(n_in)
    return {
        "w": jax.random.uniform(k1, (channels, n_in, n_out), jnp.float32,
                                -scale, scale),
        "b": jnp.zeros((channels, n_out), jnp.float32),
    }


def stack_channels(adj: Sequence[BatchedCOO]):
    """Stack per-channel BatchedCOOs into channel-axis arrays for the fused
    kernel: (batch, channels, nnz_max) row/col/values + (batch, channels)
    true nnz. Channels with a smaller nnz_pad are zero-padded (value 0.0,
    index 0 — the §IV-C invariant)."""
    nnz_max = max(a.nnz_pad for a in adj)

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, nnz_max - x.shape[1])))

    rids = jnp.stack([pad(a.row_ids) for a in adj], axis=1)
    cids = jnp.stack([pad(a.col_ids) for a in adj], axis=1)
    vals = jnp.stack([pad(a.values) for a in adj], axis=1)
    nnz = jnp.stack([a.nnz for a in adj], axis=1)
    return rids, cids, vals, nnz


def flatten_channels(adj: Sequence[BatchedCOO]) -> BatchedCOO:
    """Concatenate the channel axis into the batch axis: one BatchedCOO of
    ``channels·batch`` samples (channel-major), for the stacked fallback's
    single ``(channels·batch)`` SpMM call."""
    rids, cids, vals, nnz = stack_channels(adj)
    batch, channels, nnz_pad = rids.shape

    def flat(t):
        return t.transpose(1, 0, 2).reshape(channels * batch, nnz_pad)

    n_rows = jnp.tile(adj[0].n_rows, channels)
    return BatchedCOO(row_ids=flat(rids), col_ids=flat(cids),
                      values=flat(vals),
                      nnz=nnz.transpose(1, 0).reshape(-1), n_rows=n_rows)


def resolve_graph_conv_impl(
    adj: Sequence[BatchedCOO],
    x: jax.Array,
    n_out: int,
    *,
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
    mesh=None,
    mesh_axis: str = "data",
    precision: str = "f32",
):
    """Resolve ``impl`` against the LAYER workload of one graph-conv call.

    Returns a :class:`repro.autotune.Decision`; candidates include the fused
    megakernel next to every SpMM impl (each priced as the stacked fallback
    layer), and — under a reduced ``precision`` policy — their bf16/i8
    variants (DESIGN.md §10). With ``mesh=``, resolution runs against the
    per-shard workload — the shapes each device actually executes
    (DESIGN.md §6).
    """
    from repro import autotune

    interpret = resolve_interpret(interpret)
    batch, m_pad, n_in = x.shape
    dtype = autotune.precision_of(impl)[1] if impl != "auto" else precision
    w = autotune.Workload(
        batch=batch, m_pad=m_pad, nnz_pad=max(a.nnz_pad for a in adj),
        k_pad=k_pad, n_b=n_out, itemsize=x.dtype.itemsize,
        channels=len(adj), n_in=n_in, dtype=dtype)
    if mesh is not None:
        from repro.distributed.spmm import shard_count

        w = w.shard(shard_count(mesh, mesh_axis))
    if impl != "auto":
        return autotune.forced_decision(w, impl)
    return autotune.select_graph_conv_impl(
        w, allow_pallas=not interpret, cache=autotune.default_cache())


def graph_conv_batched(
    params,
    adj: Sequence[BatchedCOO],   # one BatchedCOO per channel, batch-leading
    x: jax.Array,                # (batch, m_pad, n_in)
    *,
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
    mesh=None,
    epilogue: str = "none",
    precision: str = "f32",
) -> jax.Array:
    """Paper Fig. 7 and beyond: the whole mini-batch's layer in O(1) ops.

    ``impl="auto"`` resolves per layer workload (fused megakernel vs stacked
    SpMM classes); ``impl="fused"`` pins the megakernel; any SpMM impl pins
    the stacked fallback with that kernel. ``epilogue`` ("none"|"relu") is
    applied inside the fused kernel when it runs, as an XLA op otherwise —
    identical numerics either way.

    ``precision`` ("f32"|"bf16"|"i8") is the layer's dtype policy under
    ``impl="auto"`` (DESIGN.md §10); pinning a variant impl (e.g.
    ``"fused_bf16"``) applies its policy directly. The bf16 megakernel
    variant casts values/X/W/bias to bfloat16 before dispatch; the f32
    accumulator lives in the kernel and the output is cast back to X's
    dtype.

    ``mesh=`` shards the batch axis over the mesh's ``"data"`` axis
    (DESIGN.md §6): the fused megakernel dispatches per shard via
    ``distributed.spmm.sharded_fused_graph_conv``; the fallback's stacked
    SpMM runs through ``sharded_batched_spmm`` with the dense ops GSPMD
    partitions around it.
    """
    from repro.autotune.cost_model import precision_of

    interpret = resolve_interpret(interpret)
    channels = len(adj)
    n_out = params["w"].shape[-1]
    concrete = impl
    if impl == "auto":
        concrete = resolve_graph_conv_impl(
            adj, x, n_out, impl="auto", k_pad=k_pad, interpret=interpret,
            mesh=mesh, precision=precision).impl

    base, policy = precision_of(concrete)
    if base.startswith("fused"):
        rids, cids, vals, nnz = stack_channels(adj)
        xx, ww, bb = x, params["w"], params["b"]
        if policy == "bf16":
            # ids stay int32: the megakernel reads them as int32 chunk-rows
            vals = vals.astype(jnp.bfloat16)
            xx = xx.astype(jnp.bfloat16)
            ww = ww.astype(jnp.bfloat16)
            bb = bb.astype(jnp.bfloat16)
        if mesh is not None:
            from repro.distributed.spmm import sharded_fused_graph_conv

            y = sharded_fused_graph_conv(
                rids, cids, vals, nnz, xx, ww, bb,
                mesh=mesh, epilogue=epilogue, interpret=interpret,
                impl=concrete)
        else:
            from repro.kernels.fused_graph_conv import fused_graph_conv

            y = fused_graph_conv(rids, cids, vals, nnz, xx, ww, bb,
                                 epilogue=epilogue, interpret=interpret,
                                 impl=concrete)
        return y.astype(x.dtype) if policy != "f32" else y

    # Stacked fallback: ONE feature-transform einsum over all channels, ONE
    # (channels·batch) Batched SpMM, one channel-sum — 4·channels ops → 3.
    # On a mesh with impl="auto", keep "auto" so the sharded path re-resolves
    # against the per-shard stacked workload it actually runs (DESIGN.md §6);
    # otherwise pin the layer-resolved (or caller-pinned) impl. A variant
    # SpMM impl (e.g. "ell_bf16") applies its storage policy inside
    # batched_spmm.
    spmm_impl = "auto" if impl == "auto" and mesh is not None else concrete
    batch, m_pad = x.shape[0], x.shape[1]
    u = jnp.einsum("bmn,cnf->cbmf", x, params["w"]) \
        + params["b"][:, None, None, :]                 # MATMUL+ADD (one op)
    a_flat = flatten_channels(adj)
    c = batched_spmm(a_flat, u.reshape(channels * batch, m_pad, n_out),
                     impl=spmm_impl, k_pad=k_pad, interpret=interpret,
                     mesh=mesh, precision=precision)     # BATCHEDSPMM (one op)
    y = jnp.sum(c.reshape(channels, batch, m_pad, n_out), axis=0)  # SUM
    return jnp.maximum(y, 0.0) if epilogue == "relu" else y


def graph_conv_nonbatched(
    params,
    adj: Sequence[BatchedCOO],
    x: jax.Array,
) -> jax.Array:
    """Paper Fig. 6: the per-(sample × channel) loop, kept sequential with a
    scan over the batch so it reproduces the launch-per-sample structure that
    the paper measures as the baseline."""
    channels = len(adj)
    rids, cids, vals, _ = stack_channels(adj)       # (batch, ch, nnz_max)

    def per_sample(_, args):
        rid, cid, val, xb = args                     # one mini-batch sample
        m_pad = xb.shape[0]
        y = jnp.zeros((m_pad, params["w"].shape[-1]), xb.dtype)
        for ch in range(channels):
            u = xb @ params["w"][ch]                 # MATMUL (per sample)
            u = u + params["b"][ch]                  # ADD (per sample)
            y = y + spmm_coo_single(rid[ch], cid[ch], val[ch], u, m_pad)
        return None, y

    _, y = jax.lax.scan(per_sample, None, (rids, cids, vals, x))
    return y
