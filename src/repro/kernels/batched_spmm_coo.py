"""Batched COO/SparseTensor SpMM — the TPU adaptation of the paper's Batched
SWA-SpMM for SparseTensor (Fig. 3 + Fig. 5-(a)/(b)).

The GPU version splits work by *non-zero* and resolves output races with
``atomicAdd`` on shared memory. TPUs have no atomics; the adaptation
(DESIGN.md §2, "atomics → one-hot MXU scatter") is:

- non-zeros are processed in CHUNK-sized vector groups, one grid-resident
  ``(chunks, CHUNK)`` index panel per matrix, read a chunk-row at a time;
- the *gather* side (``B[cid]``) is a one-hot matrix product
  ``onehot(cid) @ B`` — a (CHUNK × m_pad) × (m_pad × n_block) contraction on
  the MXU (Mosaic has no sublane gather by a vector of indices);
- the *scatter-add* side (``C[rid] += …``) becomes a one-hot matrix product
  ``Pᵀ @ G`` where ``Pᵀ[r, i] = (rid[i] == r)`` — an (m_pad × CHUNK) ×
  (CHUNK × n_block) contraction that also runs on the MXU. Races disappear
  because the reduction is a dot-product, not a read-modify-write.

Accumulation across chunks happens in a VMEM-resident f32 accumulator — the
shared-memory-resident output of Fig. 5-(a) — and the column-panel grid
dimension reproduces the cache blocking of Fig. 5-(b).

g-SpMM generalization (DESIGN.md §11): a static ``(op, reduce)`` pair turns
``C[rid] += val · B[cid]`` into ``C[rid] = reduce(op(B[cid], e))``. The
``(mul, sum)`` corner keeps the unmasked legacy path (padding values 0.0
are neutral); every other corner takes the per-matrix true non-zero count
``nnz`` (scalar-prefetched into SMEM) and masks slots ``i ≥ nnz``
explicitly. ``sum`` stays a one-hot MXU contraction. ``max`` has no
dot-product form — it runs a one-hot *select*: each SUB-slot group of the
chunk broadcasts its messages against its one-hot row mask and folds with
``maximum`` (the (SUB, m_pad, n_block) intermediate bounds the VMEM cost of
a full-chunk broadcast).
``mean`` and the empty-row identity fix-up of ``max`` are applied by the
wrapper after the kernel (an XLA degree count — the kernel itself only
knows sum/max). Edge values may be scalars ``(batch, nnz_pad)`` or feature
vectors ``(batch, nnz_pad, d_e)`` with ``d_e == n_b``.

Compiled for the TPU, the ``max`` reduce is refused (its 3-D one-hot select
does not lower), so ``autotune`` keeps ``pallas_coo`` off the ``max``
ladder there (:data:`repro.autotune.cost_model.TPU_REFUSED`). Ids enter as
int32 and bf16 values are widened to f32 before the call: Mosaic cannot load
one packed 16-bit chunk-row at a dynamic offset.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.batching import CHUNK, BatchPlan
from repro.kernels import resolve_interpret

NEG_INF = -3.0e38   # finite stand-in for -inf (matches kernels/ref.py)
# one-hot-select group size for the max reduce: bounds the broadcast
# intermediate at (SUB, m_pad, n_block) f32 in VMEM per fold
_MAX_SUB = 8
HIGHEST = jax.lax.Precision.HIGHEST   # one-hot products stay exact in f32


def _kernel(*refs, m_pad: int, chunks: int, has_nnz: bool, op: str,
            reduce: str, vec: bool):
    refs = list(refs)
    nnz_ref = refs.pop(0) if has_nnz else None
    rid_ref, cid_ref, val_ref, b_ref, c_ref = refs
    bb = b_ref[0].astype(jnp.float32)                # (m_pad, n_block)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, m_pad), 1)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (m_pad, CHUNK), 0)
    n_valid = nnz_ref[pl.program_id(0)] if has_nnz else None

    def messages(i):
        rid = rid_ref[0, pl.ds(i, 1), :]             # (1, CHUNK) int32
        cid = cid_ref[0, pl.ds(i, 1), :].reshape(CHUNK, 1)
        # gather B[cid] as a one-hot MXU product
        g = jax.lax.dot_general(
            (cid == col_iota).astype(jnp.float32), bb,
            (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)      # (CHUNK, n_block)
        if op != "copy_lhs":
            if vec:
                val = val_ref[0, pl.ds(i * CHUNK, CHUNK), :]
            else:
                val = val_ref[0, pl.ds(i, 1), :].reshape(CHUNK, 1)
            val = val.astype(jnp.float32)
            g = g * val if op == "mul" else g + val
        valid = None
        if has_nnz:
            # explicit validity: the padding invariant (value 0.0 neutral)
            # only holds for (mul, sum)
            slot = i * CHUNK + jax.lax.broadcasted_iota(
                jnp.int32, (CHUNK, 1), 0)
            valid = slot < n_valid
        return rid, g, valid

    def body_sum(i, acc):
        rid, g, valid = messages(i)
        if valid is not None:
            g = jnp.where(valid, g, 0.0)
        p = (row_iota == rid).astype(jnp.float32)    # (m_pad, CHUNK)
        # scatter-add as MXU contraction: acc[r] += Σ_i p[r, i] * g[i]
        return acc + jax.lax.dot_general(
            p, g, (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)

    def body_max(i, acc):
        rid, g, valid = messages(i)
        g = jnp.where(valid, g, NEG_INF)
        p = rid.reshape(CHUNK, 1) == col_iota                # (CHUNK, m_pad)
        # one-hot select: no dot-product form for max, so fold SUB slots at
        # a time — candidate[s, r, :] is message s where it targets row r
        for s in range(0, CHUNK, _MAX_SUB):
            cand = jnp.where(p[s:s + _MAX_SUB, :, None],
                             g[s:s + _MAX_SUB, None, :], NEG_INF)
            acc = jnp.maximum(acc, jnp.max(cand, axis=0))
        return acc

    init = NEG_INF if reduce == "max" else 0.0
    acc = jax.lax.fori_loop(
        0, chunks, body_max if reduce == "max" else body_sum,
        jnp.full(c_ref.shape[1:], init, jnp.float32)
    )
    c_ref[0] = acc.astype(c_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("plan", "interpret", "op", "reduce"))
def batched_spmm_coo(
    row_ids: jax.Array,   # (batch, nnz_pad) int32
    col_ids: jax.Array,   # (batch, nnz_pad) int32
    values: jax.Array,    # (batch, nnz_pad[, d_e])
    b: jax.Array,         # (batch, m_pad, n_b)
    *,
    plan: BatchPlan,
    nnz: jax.Array | None = None,     # (batch,) true nnz; g-SpMM masking
    op: str = "mul",
    reduce: str = "sum",
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    batch, nnz_pad = row_ids.shape
    m_pad, n_b = b.shape[1], b.shape[2]
    assert plan.batch == batch and plan.m_pad == m_pad and plan.n_b == n_b, plan
    if (op, reduce) != ("mul", "sum"):
        assert nnz is not None, \
            f"({op}, {reduce}) needs the per-matrix true nnz for masking"
    vec = values.ndim == 3
    if vec:
        assert values.shape[-1] == n_b, \
            f"vector edge features need d_e == n_b, got {values.shape[-1]}"
    if nnz_pad % CHUNK:
        pad = CHUNK - nnz_pad % CHUNK
        row_ids = jnp.pad(row_ids, ((0, 0), (0, pad)), constant_values=m_pad)
        col_ids = jnp.pad(col_ids, ((0, 0), (0, pad)))
        values = jnp.pad(values, ((0, 0), (0, pad)) + ((0, 0),) * vec)
        nnz_pad += pad
    chunks = nnz_pad // CHUNK

    n_block, p = plan.n_block, plan.p
    if n_b % n_block:
        pad = p * n_block - n_b
        b = jnp.pad(b, ((0, 0), (0, 0), (0, pad)))
        if vec:
            values = jnp.pad(values, ((0, 0), (0, 0), (0, pad)))

    # ids and scalar values as (batch, chunks, CHUNK) panels: the block's
    # last two dims are the array's, and one chunk is one row
    def panel(t, dtype):
        return t.astype(dtype).reshape(batch, chunks, CHUNK)

    rids = panel(row_ids, jnp.int32)
    cids = panel(col_ids, jnp.int32)
    if vec:
        values = values.astype(jnp.float32)
        val_spec = pl.BlockSpec((1, nnz_pad, n_block),
                                lambda i, j, *_: (i, 0, j))
    else:
        values = panel(values, jnp.float32)
        val_spec = pl.BlockSpec((1, chunks, CHUNK), lambda i, j, *_: (i, 0, 0))

    # the kernel reduces sum or max; mean = sum kernel + XLA degree scale,
    # and max needs the empty-row identity fix-up — both via the true
    # per-row degree, an XLA scatter-add over the (cheap) index arrays
    kernel_reduce = "sum" if reduce == "mean" else reduce
    idx_spec = pl.BlockSpec((1, chunks, CHUNK), lambda i, j, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=int(nnz is not None),
        grid=(batch, p),
        in_specs=[idx_spec, idx_spec, val_spec,
                  pl.BlockSpec((1, m_pad, n_block),
                               lambda i, j, *_: (i, 0, j))],
        out_specs=pl.BlockSpec((1, m_pad, n_block),
                               lambda i, j, *_: (i, 0, j)),
    )
    operands = [rids, cids, values, b]
    if nnz is not None:
        operands.insert(0, nnz.astype(jnp.int32))

    out = pl.pallas_call(
        functools.partial(_kernel, m_pad=m_pad, chunks=chunks,
                          has_nnz=nnz is not None, op=op,
                          reduce=kernel_reduce, vec=vec),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, m_pad, p * n_block), b.dtype),
        interpret=interpret,
    )(*operands)
    out = out[..., :n_b]
    if reduce in ("mean", "max"):
        valid = (jnp.arange(nnz_pad)[None, :] < nnz[:, None]).astype(
            jnp.float32)
        deg = jax.vmap(
            lambda r, v: jnp.zeros((m_pad,), jnp.float32).at[
                jnp.clip(r.astype(jnp.int32), 0, m_pad - 1)].add(v)
        )(row_ids, valid)
        if reduce == "mean":
            out = out / jnp.maximum(deg, 1.0)[..., None].astype(out.dtype)
        else:
            out = jnp.where(deg[..., None] > 0, out,
                            jnp.zeros((), out.dtype))
    return out
