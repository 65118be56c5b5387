"""Mesh-sharded Batched SpMM — the batch axis across a device mesh.

The paper's core claim is that batching many small SpMMs into ONE kernel
launch is what saturates one device (§IV); this module is the next rung:
split the *batch* axis of a :class:`~repro.core.formats.BatchedCOO` (and its
dense operand) over a ``("data",)`` mesh axis with ``shard_map`` and run the
existing single-device batched kernels on each shard (DESIGN.md §6).

Design points:

- **Per-shard autotuning.** ``impl="auto"`` is resolved against the
  *per-shard* workload (``batch_padded // n_shards`` samples), not the global
  one — the adaptive dispatcher's cost model (DESIGN.md §5) sees the shapes
  the kernel will actually run at, so a global batch that would pick the GEMM
  class may correctly pick the scatter class once split 8 ways.
  :func:`resolve_sharded_impl` exposes that decision for audit.
- **Padding invariant (§IV-C).** A batch not divisible by the shard count is
  padded with zero-nnz samples (value 0.0, indices 0) — exactly the padded
  slots the kernels already tolerate — and the output is sliced back.
- **No forward all-gather.** ``out_specs=P(axis)`` keeps the output
  batch-sharded; consumers that keep reducing along non-batch axes never pay
  a gather. The custom-VJP backward runs inside the same ``shard_map``, so
  dValues and dB come out batch-sharded too.

``shard_map`` requires every float leaf to be rank ≥ 1 per shard — all
BatchedCOO leaves are batch-leading arrays, so the specs are uniform
``P(axis)`` on dim 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.formats import BatchedCOO
from repro.kernels import resolve_interpret
from repro.observability import trace as obs_trace

__all__ = [
    "pad_batch",
    "resolve_sharded_gspmm_impl",
    "resolve_sharded_impl",
    "shard_count",
    "sharded_batched_gspmm",
    "sharded_batched_spmm",
    "sharded_fused_graph_conv",
]


def shard_count(mesh: Mesh, axis: str = "data") -> int:
    """Number of shards the batch axis is split into on ``mesh``."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis not in sizes:
        raise ValueError(
            f"mesh has axes {mesh.axis_names}, no {axis!r} axis to shard the "
            "batch over")
    return sizes[axis]


def pad_batch(a: BatchedCOO, b: jax.Array, n_shards: int
              ) -> tuple[BatchedCOO, jax.Array, int]:
    """Pad the batch axis to a multiple of ``n_shards`` with zero-nnz samples
    (the §IV-C padding invariant: indices 0, values 0.0, nnz 0 contribute
    nothing). Returns (a, b, pad) with ``pad`` rows to slice off outputs."""
    batch = b.shape[0]
    pad = (-batch) % n_shards
    if pad == 0:
        return a, b, 0

    def padb(x):
        return jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)

    a = BatchedCOO(
        row_ids=padb(a.row_ids), col_ids=padb(a.col_ids),
        values=padb(a.values), nnz=padb(a.nnz),
        # padded samples keep the real m_pad so per-shard geometry is uniform
        n_rows=jnp.concatenate(
            [a.n_rows, jnp.full((pad,), b.shape[1], a.n_rows.dtype)]),
    )
    return a, padb(b), pad


def resolve_sharded_impl(
    a: BatchedCOO,
    b: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "data",
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
    precision: str = "f32",
):
    """Resolve ``impl`` against the PER-SHARD workload shapes.

    Returns an :class:`repro.autotune.Decision` whose ``plan``/``scores``
    describe one shard's call — batch ``ceil(batch / n_shards)``, everything
    else unchanged — which is the workload each device actually runs.
    ``precision`` admits the reduced-precision variants under ``impl="auto"``
    exactly like the local path (DESIGN.md §10).
    """
    from repro import autotune

    interpret = resolve_interpret(interpret)
    n = shard_count(mesh, axis)
    batch, m_pad, n_b = b.shape
    dtype = autotune.precision_of(impl)[1] if impl != "auto" else precision
    w = autotune.Workload(batch=batch, m_pad=m_pad,
                          nnz_pad=a.row_ids.shape[1], k_pad=k_pad,
                          n_b=n_b, itemsize=b.dtype.itemsize,
                          dtype=dtype).shard(n)
    if impl != "auto":
        return autotune.forced_decision(w, impl, note=f" ({n}-way sharded)")
    return autotune.select_impl(w, allow_pallas=not interpret,
                                cache=autotune.default_cache())


def sharded_batched_spmm(
    a: BatchedCOO,
    b: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "data",
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
    precision: str = "f32",
) -> jax.Array:
    """C[s] = A[s] @ B[s] with the batch axis sharded over ``mesh[axis]``.

    Semantically identical to :func:`repro.kernels.ops.batched_spmm` (the
    per-shard kernels are the same code); differentiable in ``a.values`` and
    ``b`` with batch-sharded cotangents. ``impl="auto"`` resolves against the
    per-shard workload (``precision`` admits reduced-precision variants to
    that ranking). Output stays batch-sharded (no forward all-gather).
    """
    from repro.kernels.ops import _forward, backward_db, batched_spmm, dvalues

    interpret = resolve_interpret(interpret)
    n = shard_count(mesh, axis)
    if n == 1:
        return batched_spmm(a, b, impl=impl, k_pad=k_pad, interpret=interpret,
                            precision=precision)

    batch = b.shape[0]
    a, b, pad = pad_batch(a, b, n)
    decision = resolve_sharded_impl(
        a, b, mesh, axis=axis, impl=impl, k_pad=k_pad,
        interpret=interpret, precision=precision)
    concrete = decision.impl

    spec = P(axis)      # dim-0 (batch) sharding for every operand
    row_ids, col_ids, nnz = a.row_ids, a.col_ids, a.nnz

    # The custom VJP lives OUTSIDE the shard_map and each side runs its own
    # shard_map over explicit operands: AD never differentiates *through* a
    # shard_map (no transpose, no scalar-residual issues), and the backward
    # is itself a batch-sharded batched SpMM + gather-dot, so dValues/dB come
    # out batch-sharded exactly like the forward output.
    def _fwd_local(rids, cids, nz, values, b_local):
        return _forward(rids, cids, nz, values, b_local,
                        impl=concrete, k_pad=k_pad, interpret=interpret)

    fwd_sharded = shard_map(
        _fwd_local, mesh=mesh, in_specs=(spec,) * 5, out_specs=spec,
        check_vma=False)

    def _bwd_local(rids, cids, nz, values, b_local, dc):
        # dB = Aᵀ·dC per shard: COO index swap, or csr_transpose for the
        # CSR class (kernels/ops.backward_db — same routing as the local VJP)
        db = backward_db(rids, cids, nz, values, dc,
                         impl=concrete, interpret=interpret)
        dval = dvalues(rids, cids, dc, b_local)
        return dval.astype(values.dtype), db.astype(b_local.dtype)

    bwd_sharded = shard_map(
        _bwd_local, mesh=mesh, in_specs=(spec,) * 6, out_specs=(spec, spec),
        check_vma=False)

    @jax.custom_vjp
    def f(values, bb):
        return fwd_sharded(row_ids, col_ids, nnz, values, bb)

    def fwd(values, bb):
        return f(values, bb), (values, bb)

    def bwd(res, dc):
        values, bb = res
        return bwd_sharded(row_ids, col_ids, nnz, values, bb, dc)

    f.defvjp(fwd, bwd)
    if obs_trace.enabled():
        # distributed-layer span (DESIGN.md §13): the per-SHARD workload key
        # is the decision's provenance — the same key the regret auditor and
        # tuning cache use for this dispatch's shapes
        w = decision.workload
        with obs_trace.TRACER.span(
                f"sharded_spmm/{concrete}", cat="kernel",
                args={"impl": concrete, "source": decision.source,
                      "n_shards": n, "padded": bool(pad),
                      "key": None if w is None else w.key()}):
            out = f(a.values, b)
    else:
        out = f(a.values, b)
    return out[:batch] if pad else out


def resolve_sharded_gspmm_impl(
    a: BatchedCOO,
    b: jax.Array,
    mesh: Mesh,
    *,
    op: str = "mul",
    reduce: str = "sum",
    axis: str = "data",
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
):
    """Resolve a g-SpMM ``impl`` against the PER-SHARD workload shapes — the
    :func:`resolve_sharded_impl` analogue with the ``(op, reduce, d_e)``
    workload axes set, so the ranked ladder is restricted to the
    g-SpMM-capable subset (DESIGN.md §11)."""
    from repro import autotune

    interpret = resolve_interpret(interpret)
    n = shard_count(mesh, axis)
    batch, m_pad, n_b = b.shape
    d_e = a.values.shape[2] if a.values.ndim == 3 else None
    w = autotune.Workload(batch=batch, m_pad=m_pad,
                          nnz_pad=a.row_ids.shape[1], k_pad=k_pad,
                          n_b=n_b, itemsize=b.dtype.itemsize,
                          d_e=d_e, reduce=reduce, op=op).shard(n)
    if impl != "auto":
        return autotune.forced_decision(w, impl, note=f" ({n}-way sharded)")
    return autotune.select_impl(w, allow_pallas=not interpret,
                                cache=autotune.default_cache())


def sharded_batched_gspmm(
    a: BatchedCOO,
    b: jax.Array,
    *,
    op: str = "mul",
    reduce: str = "sum",
    mesh: Mesh,
    axis: str = "data",
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """g-SpMM (``C[r] = reduce op(B[c], e)``, DESIGN.md §11) with the batch
    axis sharded over ``mesh[axis]``.

    Same structure as :func:`sharded_batched_spmm`: zero-nnz batch padding
    (harmless for every ``(op, reduce)`` corner — a padded sample has
    ``nnz = 0``, so all its slots are masked and every row takes the 0.0
    identity), per-shard ``impl="auto"`` resolution, custom VJP outside the
    shard_map with ``kernels.ops.gspmm_backward`` running per shard. The
    ``(mul, sum)`` scalar-edge corner delegates to
    :func:`sharded_batched_spmm` exactly like the local entry point.
    """
    from repro.autotune.cost_model import GSPMM_IMPLS, supports_gspmm
    from repro.kernels.ops import _forward, batched_gspmm, gspmm_backward

    interpret = resolve_interpret(interpret)
    if (op, reduce) == ("mul", "sum") and a.values.ndim == 2:
        return sharded_batched_spmm(a, b, mesh=mesh, axis=axis, impl=impl,
                                    k_pad=k_pad, interpret=interpret)
    n = shard_count(mesh, axis)
    if n == 1:
        return batched_gspmm(a, b, op=op, reduce=reduce, impl=impl,
                             k_pad=k_pad, interpret=interpret)

    batch = b.shape[0]
    a, b, pad = pad_batch(a, b, n)
    concrete = resolve_sharded_gspmm_impl(
        a, b, mesh, op=op, reduce=reduce, axis=axis, impl=impl,
        k_pad=k_pad, interpret=interpret).impl
    if not supports_gspmm(concrete):
        raise ValueError(
            f"impl {concrete!r} cannot run g-SpMM (op={op!r}, "
            f"reduce={reduce!r}); the capable set is {GSPMM_IMPLS} at f32")

    spec = P(axis)      # dim-0 (batch) sharding for every operand
    row_ids, col_ids, nnz = a.row_ids, a.col_ids, a.nnz

    def _fwd_local(rids, cids, nz, values, b_local):
        return _forward(rids, cids, nz, values, b_local, impl=concrete,
                        k_pad=k_pad, interpret=interpret, op=op,
                        reduce=reduce)

    fwd_sharded = shard_map(
        _fwd_local, mesh=mesh, in_specs=(spec,) * 5, out_specs=spec,
        check_vma=False)

    def _bwd_local(rids, cids, nz, values, b_local, c_local, dc):
        return gspmm_backward(rids, cids, nz, values, b_local, c_local, dc,
                              op=op, reduce=reduce, impl=concrete,
                              interpret=interpret)

    bwd_sharded = shard_map(
        _bwd_local, mesh=mesh, in_specs=(spec,) * 7,
        out_specs=(spec, spec), check_vma=False)

    @jax.custom_vjp
    def f(values, bb):
        return fwd_sharded(row_ids, col_ids, nnz, values, bb)

    def fwd(values, bb):
        c = f(values, bb)
        # only the max backward consumes the forward output (argmax routing)
        return c, (values, bb, c if reduce == "max" else None)

    def bwd(res, dc):
        values, bb, c = res
        cf = c if c is not None else jnp.zeros_like(dc)
        return bwd_sharded(row_ids, col_ids, nnz, values, bb, cf, dc)

    f.defvjp(fwd, bwd)
    out = f(a.values, b)
    return out[:batch] if pad else out


def sharded_fused_graph_conv(
    row_ids: jax.Array,     # (batch, channels, nnz_pad) int32
    col_ids: jax.Array,
    values: jax.Array,
    nnz: jax.Array,         # (batch, channels) int32
    x: jax.Array,           # (batch, m_pad, n_in)
    w: jax.Array,           # (channels, n_in, n_out) — replicated
    bias: jax.Array,        # (channels, n_out) — replicated
    *,
    mesh: Mesh,
    axis: str = "data",
    epilogue: str = "none",
    interpret: bool | None = None,
    impl: str = "fused",
) -> jax.Array:
    """The fused graph-conv megakernel (DESIGN.md §7) with the batch axis
    sharded over ``mesh[axis]``: each shard runs ONE fused ``pallas_call``
    for its slice of the batch — per-shard fused dispatch.

    Same structure as :func:`sharded_batched_spmm`: zero-nnz batch padding,
    custom VJP outside the shard_map, batch-sharded dValues/dX. The layer
    parameters ``w``/``bias`` enter replicated, so their gradients are
    psum-reduced over the batch shards inside the backward shard_map and
    come out replicated — exactly the all-reduce GSPMD would insert for the
    unfused path's dense MatMul.
    """
    from repro.autotune.cost_model import precision_of
    from repro.core.batching import plan_fused_graph_conv, plan_hybrid
    from repro.kernels.fused_graph_conv import (
        fused_bwd,
        fused_forward,
        fused_graph_conv,
        fused_hybrid_forward,
        runtime_chunks,
    )
    from repro.kernels.ops import bwd_impl_for

    interpret = resolve_interpret(interpret)
    n = shard_count(mesh, axis)
    if n == 1:
        return fused_graph_conv(row_ids, col_ids, values, nnz, x, w, bias,
                                epilogue=epilogue, interpret=interpret,
                                impl=impl)

    batch, channels, nnz_pad = row_ids.shape
    m_pad, n_in = x.shape[1], x.shape[2]
    n_out = w.shape[-1]
    pad = (-batch) % n
    if pad:
        # §IV-C padding invariant: zero-nnz samples contribute nothing and
        # their runtime chunk count is 0, so the skew-aware loop never runs
        def padb(t):
            return jnp.concatenate(
                [t, jnp.zeros((pad,) + t.shape[1:], t.dtype)], axis=0)

        row_ids, col_ids, values, nnz, x = map(
            padb, (row_ids, col_ids, values, nnz, x))
    hybrid = precision_of(impl)[0] == "fused_hybrid"
    plan = plan_fused_graph_conv(
        batch=(batch + pad) // n, m_pad=m_pad, n_in=n_in, n_out=n_out,
        channels=channels, nnz_pad=nnz_pad, itemsize=x.dtype.itemsize,
        hybrid=hybrid)
    if plan.case == 3:
        raise ValueError(
            f"m_pad={plan.m_pad} is planner case 3 (> LARGE_M, or a working "
            "set past VMEM): use the unfused graph_conv_batched fallback")
    # 4th sharded forward operand: the hybrid prep re-derives chunk counts
    # AFTER hub extraction, so it needs the raw per-channel nnz; the plain
    # megakernel takes precomputed chunk counts
    meta = nnz.astype(jnp.int32) if hybrid else runtime_chunks(nnz)
    if hybrid:
        # per-shard plan: the shapes each device actually runs (DESIGN.md §6)
        hplan = plan_hybrid(batch=(batch + pad) // n, m_pad=m_pad,
                            n_b=n_out, nnz_pad=channels * nnz_pad,
                            itemsize=x.dtype.itemsize)
    bwd_impl = bwd_impl_for(impl) if not interpret else "ref"

    spec, repl = P(axis), P()
    rids, cids = row_ids, col_ids

    def _fwd_local(rids_l, cids_l, vals_l, meta_l, x_l, w_l, b_l):
        if hybrid:
            return fused_hybrid_forward(
                rids_l, cids_l, vals_l, meta_l, x_l, w_l, b_l, None,
                plan=plan, hplan=hplan, epilogue=epilogue,
                interpret=interpret)
        return fused_forward(rids_l, cids_l, vals_l, meta_l, x_l, w_l, b_l,
                             None, plan=plan, epilogue=epilogue,
                             interpret=interpret)

    fwd_sharded = shard_map(
        _fwd_local, mesh=mesh, in_specs=(spec,) * 5 + (repl, repl),
        out_specs=spec, check_vma=False)

    def _bwd_local(rids_l, cids_l, vals_l, x_l, w_l, b_l, y_l, dy_l):
        dvals, dx, dw, db, _ = fused_bwd(
            rids_l, cids_l, vals_l, x_l, w_l, b_l, y_l, dy_l,
            epilogue=epilogue, interpret=interpret, has_residual=False,
            bwd_impl=bwd_impl)
        # replicated params: all-reduce their grads over the batch shards
        return dvals, dx, jax.lax.psum(dw, axis), jax.lax.psum(db, axis)

    bwd_sharded = shard_map(
        _bwd_local, mesh=mesh,
        in_specs=(spec,) * 4 + (repl, repl) + (spec, spec),
        out_specs=(spec, spec, repl, repl), check_vma=False)

    @jax.custom_vjp
    def f(vals, xx, ww, bb):
        return fwd_sharded(rids, cids, vals, meta, xx, ww, bb)

    def fwd(vals, xx, ww, bb):
        y = f(vals, xx, ww, bb)
        return y, (vals, xx, ww, bb, y)

    def bwd(res, dy):
        vals, xx, ww, bb, y = res
        return bwd_sharded(rids, cids, vals, xx, ww, bb, y, dy)

    f.defvjp(fwd, bwd)
    out = f(values, x, w, bias)
    return out[:batch] if pad else out
