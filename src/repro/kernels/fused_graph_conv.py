"""Fused graph-conv megakernel with skew-aware nnz packing (DESIGN.md §7).

One Pallas grid step computes, for one (matrix × output-column panel), the
ENTIRE Fig. 7 layer ``Y = Σ_ch A_ch · (X·W_ch + b_ch)`` plus an optional
ReLU/residual epilogue:

- the feature transform ``X·W_ch + b_ch`` runs on the MXU and its product
  ``U_ch`` never leaves VMEM — the unfused path's per-channel
  ``(batch, m_pad, n_out)`` MatMul/Add intermediates, which each round-trip
  through HBM, disappear;
- ``U_ch`` is immediately consumed by the one-hot-scatter SpMM of
  ``batched_spmm_coo.py`` (atomics → MXU contraction, DESIGN.md §2);
- the channel sum accumulates in a single f32 VMEM accumulator, written to
  HBM exactly once per panel.

Device-op structure per layer: 4·channels ops (MatMul, Add, Batched SpMM,
channel-sum per edge channel) → ONE ``pallas_call`` — the paper's
O(channel·batchsize) → O(channel) launch reduction taken the rest of the way
to O(1), in the spirit of GE-SpMM/Accel-GCN's fused aggregation stage.

**Skew-aware nnz packing**: the per-channel non-zero loop is bounded by each
graph's REAL chunk count (``ceil(nnz[s, ch] / CHUNK)``, read from SMEM) rather
than the batch-max ``nnz_pad`` — on a skewed batch the padded slots the COO
kernel multiplies by 0.0 are simply never visited. The static, auditable side
of the same decision lives in ``BatchPlan.sample_chunks``
(``core/batching.plan_fused_graph_conv``).

The custom VJP recomputes ``U_ch`` (cheap: one einsum) instead of storing it,
runs dU = A_chᵀ·dZ as ONE channel-stacked batched SpMM, and reduces
dW/db/dX with dense contractions — so training through the fused layer keeps
the same batched-op structure as the forward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.batching import (
    CHUNK,
    BatchPlan,
    HybridPlan,
    plan_fused_graph_conv,
    plan_hybrid,
)
from repro.kernels import resolve_interpret

EPILOGUES = ("none", "relu")
HIGHEST = jax.lax.Precision.HIGHEST   # MXU products stay f32-exact


def _kernel(chunks_ref, rid_ref, cid_ref, val_ref, x_ref, w_ref, b_ref,
            *rest, channels: int, total_chunks: int, epilogue: str,
            has_residual: bool, d_pad: int = 0):
    rest = list(rest)
    if d_pad:           # hybrid dispatch (DESIGN.md §12): inverse-perm + slab
        rank_ref, slab_ref = rest[0], rest[1]
        rest = rest[2:]
    if has_residual:
        res_ref, c_ref = rest
    else:
        (c_ref,) = rest
    m_pad = c_ref.shape[1]
    s = pl.program_id(0)
    xx = x_ref[0].astype(jnp.float32)                     # (m_pad, n_in)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, m_pad), 1)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (m_pad, CHUNK), 0)
    acc = jnp.zeros(c_ref.shape[1:], jnp.float32)
    if d_pad:
        dacc = jnp.zeros((d_pad, c_ref.shape[2]), jnp.float32)

    def mm(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   precision=HIGHEST,
                                   preferred_element_type=jnp.float32)

    for ch in range(channels):    # static unroll; channels is small (bond types)
        # feature transform on the MXU — U_ch never leaves VMEM
        u = mm(xx, w_ref[ch].astype(jnp.float32)) \
            + b_ref[pl.ds(ch, 1), :].astype(jnp.float32)

        if d_pad:
            # hub rows: this channel's pre-gathered dense tiles contract
            # against U_ch on the MXU — no scatter loop for the heavy rows
            dacc = dacc + mm(slab_ref[0, ch].astype(jnp.float32), u)

        def body(i, a, u=u, ch=ch):
            # one chunk-row of the (chunks, CHUNK) index panels
            rid = rid_ref[0, ch, pl.ds(i, 1), :]              # (1, CHUNK)
            cid = cid_ref[0, ch, pl.ds(i, 1), :].reshape(CHUNK, 1)
            val = val_ref[0, ch, pl.ds(i, 1), :].reshape(CHUNK, 1)
            # gather U_ch[cid] and scatter-add into rid, both as one-hot
            # MXU contractions (DESIGN.md §2)
            g = mm((cid == col_iota).astype(jnp.float32), u) * val
            return a + mm((row_iota == rid).astype(jnp.float32), g)

        # skew-aware bound: this graph's real chunk count, not the batch max
        n_ch = jnp.minimum(chunks_ref[s * channels + ch], total_chunks)
        acc = jax.lax.fori_loop(0, n_ch, body, acc)

    if d_pad:
        # accumulator is in SORTED row order (hub rows first); merge the MXU
        # tiles, then the inverse permutation is fused into the epilogue so
        # outputs leave in the original row order (DESIGN.md §12):
        # out[r] = acc[rank[r]], a one-hot (transposed) MXU product
        head = acc[:d_pad] + dacc
        acc = head if d_pad == m_pad else jnp.concatenate([head, acc[d_pad:]])
        perm_t = (jax.lax.broadcasted_iota(jnp.int32, (m_pad, m_pad), 0)
                  == rank_ref[0]).astype(jnp.float32)     # [s, r]
        acc = jax.lax.dot_general(perm_t, acc, (((0,), (0,)), ((), ())),
                                  precision=HIGHEST,
                                  preferred_element_type=jnp.float32)

    if has_residual:
        acc = acc + res_ref[0].astype(jnp.float32)
    if epilogue == "relu":
        acc = jnp.maximum(acc, 0.0)
    c_ref[0] = acc.astype(c_ref.dtype)


@functools.partial(jax.jit, static_argnames=("plan", "epilogue", "interpret"))
def fused_forward(
    row_ids: jax.Array,     # (batch, channels, nnz_pad) int32
    col_ids: jax.Array,     # (batch, channels, nnz_pad) int32
    values: jax.Array,      # (batch, channels, nnz_pad)
    chunks: jax.Array,      # (batch, channels) int32 — real CHUNK counts
    x: jax.Array,           # (batch, m_pad, n_in)
    w: jax.Array,           # (channels, n_in, n_out)
    bias: jax.Array,        # (channels, n_out)
    residual: jax.Array | None = None,   # (batch, m_pad, n_out)
    rank: jax.Array | None = None,       # (batch, m_pad) int32 — hybrid inverse perm
    slab: jax.Array | None = None,       # (batch, channels, d_pad, m_pad) hub tiles
    *,
    plan: BatchPlan,
    epilogue: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Raw fused forward (no VJP) — shared by the local custom-VJP wrapper and
    the mesh-sharded per-shard dispatch (``distributed/spmm.py``).

    ``rank``/``slab`` (set together by :func:`fused_hybrid_forward`) switch on
    the hybrid dispatch: per-channel hub tiles are contracted on the MXU and
    the accumulator — built in degree-sorted row order — is inverse-permuted
    before the residual/ReLU epilogue.

    The per-(sample × channel) chunk counts are scalar-prefetched into SMEM.
    Ids and values enter as ``(chunks, CHUNK)`` panels per channel as
    int32/f32: Mosaic cannot load one packed 16-bit chunk-row at a dynamic
    offset, so the bf16 variant keeps bf16 storage for X/W/bias only."""
    interpret = resolve_interpret(interpret)
    assert (rank is None) == (slab is None), "rank/slab must be set together"
    d_pad = 0 if slab is None else slab.shape[2]
    if epilogue not in EPILOGUES:
        raise ValueError(f"epilogue={epilogue!r}; expected one of {EPILOGUES}")
    batch, channels, nnz_pad = row_ids.shape
    m_pad, n_in = x.shape[1], x.shape[2]
    n_out = w.shape[-1]
    assert plan.batch == batch and plan.m_pad == m_pad and plan.n_b == n_out, \
        (plan, row_ids.shape, x.shape, w.shape)

    if nnz_pad % CHUNK:
        pad = CHUNK - nnz_pad % CHUNK
        # padded rid points past the one-hot range so the slots are inert even
        # structurally; padded values are 0.0 anyway
        row_ids = jnp.pad(row_ids, ((0, 0), (0, 0), (0, pad)),
                          constant_values=m_pad)
        col_ids = jnp.pad(col_ids, ((0, 0), (0, 0), (0, pad)))
        values = jnp.pad(values, ((0, 0), (0, 0), (0, pad)))
        nnz_pad += pad
    total_chunks = nnz_pad // CHUNK

    def panel(t, dtype):
        return t.astype(dtype).reshape(batch, channels, total_chunks, CHUNK)

    n_block, p = plan.n_block, plan.p
    if n_out % n_block:
        padc = p * n_block - n_out
        w = jnp.pad(w, ((0, 0), (0, 0), (0, padc)))
        bias = jnp.pad(bias, ((0, 0), (0, padc)))
        if residual is not None:
            residual = jnp.pad(residual, ((0, 0), (0, 0), (0, padc)))

    idx_spec = pl.BlockSpec((1, channels, total_chunks, CHUNK),
                            lambda i, j, *_: (i, 0, 0, 0))
    in_specs = [
        idx_spec, idx_spec, idx_spec,
        pl.BlockSpec((1, m_pad, n_in), lambda i, j, *_: (i, 0, 0)),
        pl.BlockSpec((channels, n_in, n_block), lambda i, j, *_: (0, 0, j)),
        pl.BlockSpec((channels, n_block), lambda i, j, *_: (0, j)),
    ]
    operands = [chunks.astype(jnp.int32).reshape(-1),
                panel(row_ids, jnp.int32), panel(col_ids, jnp.int32),
                panel(values, jnp.float32), x, w, bias]
    if d_pad:
        in_specs.append(pl.BlockSpec((1, 1, m_pad),
                                     lambda i, j, *_: (i, 0, 0)))
        in_specs.append(pl.BlockSpec((1, channels, d_pad, m_pad),
                                     lambda i, j, *_: (i, 0, 0, 0)))
        operands += [rank.astype(jnp.int32).reshape(batch, 1, m_pad), slab]
    if residual is not None:
        in_specs.append(pl.BlockSpec((1, m_pad, n_block),
                                     lambda i, j, *_: (i, 0, j)))
        operands.append(residual)

    out = pl.pallas_call(
        functools.partial(
            _kernel, channels=channels, total_chunks=total_chunks,
            epilogue=epilogue, has_residual=residual is not None,
            d_pad=d_pad),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, p),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, m_pad, n_block),
                                   lambda i, j, *_: (i, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((batch, m_pad, p * n_block), x.dtype),
        interpret=interpret,
    )(*operands)
    return out[..., :n_out]


def runtime_chunks(nnz: jax.Array) -> jax.Array:
    """Trace-safe skew-aware chunk counts: ``ceil(nnz / CHUNK)`` per
    (sample × channel), from the BatchedCOO ``nnz`` metadata."""
    return ((nnz + CHUNK - 1) // CHUNK).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("plan", "hplan", "epilogue", "interpret"))
def fused_hybrid_forward(
    row_ids: jax.Array,     # (batch, channels, nnz_pad) int32
    col_ids: jax.Array,     # (batch, channels, nnz_pad) int32
    values: jax.Array,      # (batch, channels, nnz_pad)
    nnz: jax.Array,         # (batch, channels) int32 — true nnz per channel
    x: jax.Array,           # (batch, m_pad, n_in)
    w: jax.Array,           # (channels, n_in, n_out)
    bias: jax.Array,        # (channels, n_out)
    residual: jax.Array | None = None,
    *,
    plan: BatchPlan,
    hplan: HybridPlan,
    epilogue: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """The degree-binned hybrid dispatch folded into the fused megakernel
    (DESIGN.md §12): fully traced prep, one ``pallas_call``.

    Hub-ness is a property of the OUTPUT row across the whole layer, so rows
    are classified by their degree summed over edge channels. Hub rows' edges
    leave the one-hot scatter stream entirely — gathered into per-channel
    ``(d_pad, m_pad)`` dense tiles contracted on the MXU — and the surviving
    sparse slots are compacted to the front of each channel so the skew-aware
    chunk loop shrinks by exactly the work the MXU absorbed. Sparse slots
    target the degree-SORTED row position; the kernel merges the MXU head and
    inverse-permutes the accumulator before the epilogue, so outputs (and
    therefore cotangents) stay in original row order and the backward runs on
    the ORIGINAL arrays (``fused_bwd`` unchanged — exact by bilinearity).

    The layer-level padding semantics is the §IV-C VALUE invariant (padded
    slots carry value 0.0 and may sit ANYWHERE the visited chunks cover, not
    just past the ``nnz`` prefix — the channel streams are slot-permuted
    views), so slots are classified live by ``value != 0``, exactly the
    property that makes them inert in the plain kernel. Re-targeted hub and
    dead slots map to the ``m_pad`` row-id sentinel, structurally inert in
    the one-hot.
    """
    interpret = resolve_interpret(interpret)
    batch, channels, nnz_pad = row_ids.shape
    m_pad = x.shape[1]
    assert hplan.spmm.m_pad == m_pad, (hplan, x.shape)
    if hplan.d_pad == 0:
        # degenerate split (layer nnz budget below dmin): no row can be a
        # hub, so the MXU tile group would be empty — plain fused kernel
        return fused_forward(row_ids, col_ids, values, runtime_chunks(nnz),
                             x, w, bias, residual, plan=plan,
                             epilogue=epilogue, interpret=interpret)

    f32 = jnp.float32
    live = values != 0                                       # §IV-C: by value
    rid_c = jnp.clip(row_ids.astype(jnp.int32), 0, m_pad - 1)
    cid_c = jnp.clip(col_ids.astype(jnp.int32), 0, m_pad - 1)

    def sample_deg(rids_s, live_s):
        tgt = jnp.where(live_s, rids_s, m_pad).reshape(-1)
        return jnp.zeros((m_pad + 1,), jnp.int32).at[tgt].add(1)[:m_pad]

    deg = jax.vmap(sample_deg)(rid_c, live)                  # (batch, m_pad)
    perm = jnp.argsort(-deg, axis=1, stable=True)
    rank = jnp.argsort(perm, axis=1).astype(jnp.int32)       # inverse perm
    n_dense = jnp.minimum(
        jnp.sum((deg >= hplan.dmin).astype(jnp.int32), axis=1),
        hplan.d_pad).astype(jnp.int32)                       # (batch,)

    # sorted row position of every slot; hub slots are the ones landing in
    # the first n_dense sorted rows. Routing ignores liveness: dead slots
    # carry value 0.0, so wherever they land they contribute nothing.
    pos = jax.vmap(lambda r, i: r[i])(
        rank, rid_c.reshape(batch, -1)).reshape(rid_c.shape)
    is_hub = pos < n_dense[:, None, None]

    rid_m = jnp.where(is_hub, m_pad, pos)
    # compact live sparse slots to the front so runtime chunk counts shrink;
    # the tail (hub slots, dead slots) stays inert — by sentinel or by value
    live_sp = live & ~is_hub
    order = jnp.argsort(jnp.where(live_sp, 0, 1).astype(jnp.int32),
                        axis=2, stable=True)
    rid_s = jnp.take_along_axis(rid_m, order, axis=2)
    cid_s = jnp.take_along_axis(col_ids, order, axis=2)
    val_s = jnp.take_along_axis(values, order, axis=2)
    nnz_sparse = jnp.sum(live_sp.astype(jnp.int32), axis=2)

    def one_slab(pos_sc, hub_sc, cid_sc, val_sc):
        d = jnp.where(hub_sc, pos_sc, hplan.d_pad)
        return jnp.zeros((hplan.d_pad + 1, m_pad), f32).at[d, cid_sc].add(
            jnp.where(hub_sc, val_sc.astype(f32), 0.0))[:hplan.d_pad]

    slab = jax.vmap(jax.vmap(one_slab))(
        pos, is_hub, cid_c, values).astype(values.dtype)

    return fused_forward(rid_s, cid_s, val_s, runtime_chunks(nnz_sparse),
                         x, w, bias, residual, rank, slab, plan=plan,
                         epilogue=epilogue, interpret=interpret)


def fused_bwd(rids, cids, values, x, w, bias, y, dy, *,
              epilogue: str, interpret: bool, has_residual: bool,
              bwd_impl: str):
    """Backward through the fused layer, shared by the local custom VJP and
    the mesh-sharded per-shard backward.

    dZ = dY masked by the epilogue; dU_ch = A_chᵀ·dZ runs as ONE
    channel-stacked batched SpMM (indices swapped — free in COO, §IV-D);
    dValues is the batched gather-dot against the recomputed U_ch; dX/dW/db
    are dense contractions of dU. Returns (dvalues, dx, dw, db, dresidual).
    Like the unfused VJP, dValues is taken over every slot (padded slots
    carry value 0.0, so the linearization point is identical).
    """
    from repro.kernels.ops import _forward, dvalues

    batch, channels, nnz_pad = rids.shape
    m_pad = x.shape[1]
    n_out = w.shape[-1]
    f32 = jnp.float32
    dy = dy.astype(f32)
    dz = dy * (y > 0) if epilogue == "relu" else dy
    dres = dz if has_residual else None

    # channel-major stacking: one (channels·batch) batched call, not a loop
    def flat(t):
        return t.transpose(1, 0, 2).reshape(channels * batch, -1)

    rids_f, cids_f, vals_f = flat(rids), flat(cids), flat(values)
    dz_f = jnp.broadcast_to(
        dz[None], (channels, batch, m_pad, n_out)
    ).reshape(channels * batch, m_pad, n_out)
    nnz_f = jnp.full((channels * batch,), nnz_pad, jnp.int32)

    du_f = _forward(cids_f, rids_f, nnz_f, vals_f, dz_f,
                    impl=bwd_impl, k_pad=None, interpret=interpret)
    u = jnp.einsum("bmn,cnf->cbmf", x.astype(f32), w.astype(f32)) \
        + bias.astype(f32)[:, None, None, :]
    dvals_f = dvalues(rids_f, cids_f, dz_f,
                      u.reshape(channels * batch, m_pad, n_out))
    dvals = dvals_f.reshape(channels, batch, nnz_pad).transpose(1, 0, 2)
    du = du_f.astype(f32).reshape(channels, batch, m_pad, n_out)
    dx = jnp.einsum("cbmf,cnf->bmn", du, w.astype(f32))
    dw = jnp.einsum("bmn,cbmf->cnf", x.astype(f32), du)
    db = jnp.sum(du, axis=(1, 2))
    return (dvals.astype(values.dtype), dx.astype(x.dtype),
            dw.astype(w.dtype), db.astype(bias.dtype), dres)


def fused_graph_conv(
    row_ids: jax.Array,     # (batch, channels, nnz_pad) int32
    col_ids: jax.Array,     # (batch, channels, nnz_pad) int32
    values: jax.Array,      # (batch, channels, nnz_pad)
    nnz: jax.Array,         # (batch, channels) int32 — true nnz per channel
    x: jax.Array,           # (batch, m_pad, n_in)
    w: jax.Array,           # (channels, n_in, n_out)
    bias: jax.Array,        # (channels, n_out)
    *,
    plan: BatchPlan | None = None,
    epilogue: str = "none",
    residual: jax.Array | None = None,
    interpret: bool | None = None,
    impl: str = "fused",
) -> jax.Array:
    """Y = epilogue(Σ_ch A_ch·(X·W_ch + b_ch) [+ residual]) in ONE device op.

    Differentiable in ``values``, ``x``, ``w``, ``bias`` and ``residual``.
    ``plan=None`` builds the blocking plan from the call's static shapes
    (``core/batching.plan_fused_graph_conv``); pass a plan with
    ``sample_chunks`` when host-side nnz metadata is available so the
    packing decision is recorded statically too.
    """
    from repro.autotune.cost_model import precision_of
    from repro.kernels.ops import bwd_impl_for

    interpret = resolve_interpret(interpret)
    batch, channels, nnz_pad = row_ids.shape
    hybrid = precision_of(impl)[0] == "fused_hybrid"
    if plan is None:
        plan = plan_fused_graph_conv(
            batch=batch, m_pad=x.shape[1], n_in=x.shape[2], n_out=w.shape[-1],
            channels=channels, nnz_pad=nnz_pad, itemsize=x.dtype.itemsize,
            hybrid=hybrid)
    if plan.case == 3:
        raise ValueError(
            f"m_pad={plan.m_pad} is planner case 3 (> LARGE_M, or a working "
            "set past VMEM): the fused megakernel does not batch matrices "
            "this large — use the unfused graph_conv_batched fallback")
    chunks = runtime_chunks(nnz)
    bwd_impl = bwd_impl_for(impl) if not interpret else "ref"
    has_res = residual is not None
    rids, cids = row_ids, col_ids
    if hybrid:
        # hub-ness is judged on the layer's whole edge budget (all channels)
        hplan = plan_hybrid(batch=batch, m_pad=plan.m_pad, n_b=w.shape[-1],
                            nnz_pad=channels * nnz_pad,
                            itemsize=x.dtype.itemsize)

    @jax.custom_vjp
    def f(values, x, w, bias, residual):
        if hybrid:
            return fused_hybrid_forward(
                rids, cids, values, nnz, x, w, bias, residual, plan=plan,
                hplan=hplan, epilogue=epilogue, interpret=interpret)
        return fused_forward(rids, cids, values, chunks, x, w, bias, residual,
                             plan=plan, epilogue=epilogue, interpret=interpret)

    def fwd(values, x, w, bias, residual):
        y = f(values, x, w, bias, residual)
        return y, (values, x, w, bias, y)

    def bwd(res_, dy):
        values, xx, ww, bb, y = res_
        dvals, dx, dw, db, dres = fused_bwd(
            rids, cids, values, xx, ww, bb, y, dy, epilogue=epilogue,
            interpret=interpret, has_residual=has_res, bwd_impl=bwd_impl)
        return dvals, dx, dw, db, (dres if has_res else None)

    f.defvjp(fwd, bwd)
    return f(values, x, w, bias, residual)
