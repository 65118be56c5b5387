"""Public, differentiable wrappers around the batched kernels.

The app-level contract mirrors the paper's TensorFlow integration (§IV-D):
adjacency matrices arrive as SparseTensor-style COO batches; one call executes
the whole batch. ``impl`` selects an entry from the registry table appended
below — the table is GENERATED from :data:`IMPLS` at import time so it can
never drift from the registry again (every registered impl must carry a
description, asserted by tests).

The VJP follows the paper's backward-pass batching: dB = batched-SpMM with Aᵀ
(index swap — free in COO), and dValues is a batched gather-dot. Both run as
single batched ops.

g-SpMM (DESIGN.md §11): :func:`batched_gspmm` generalizes the inner
``C[rid] += val · B[cid]`` into message passing ``C[r] = reduce(op(B[c], e))``
with a static ``(op, reduce)`` pair — ``op ∈`` :data:`GSPMM_OPS`, ``reduce ∈``
:data:`GSPMM_REDUCES` — and edge values that may be per-edge feature VECTORS
``(batch, nnz_pad, d_e)``. The ``(mul, sum)`` corner with scalar edges IS
plain batched SpMM and delegates to :func:`batched_spmm` (full registry,
precision variants included); every other corner runs the f32 g-SpMM-capable
subset (``autotune.GSPMM_IMPLS``) with explicit padding masks.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from repro.autotune.cost_model import (
    GSPMM_IMPLS,
    PRECISION_IMPLS,
    precision_of,
    supports_gspmm,
    tpu_refusal,
)
from repro.observability import trace as obs_trace
from repro.core import batching
from repro.core.formats import (
    BatchedCOO,
    BatchedCSR,
    coo_to_csr,
    coo_to_dense,
    coo_to_ell,
    narrow_col_ids,
    quantize_values_i8,
    row_degrees,
    validate_ell_k_pad,
)
from repro.kernels import ref, resolve_interpret
from repro.kernels.batched_gemm import batched_gemm
from repro.kernels.batched_spmm_coo import batched_spmm_coo
from repro.kernels.batched_spmm_csr import batched_spmm_csr
from repro.kernels.batched_spmm_ell import batched_spmm_ell
from repro.kernels.batched_spmm_hybrid import (
    batched_spmm_hybrid,
    batched_spmm_hybrid_xla,
)

# "fused" is the graph-conv layer megakernel (kernels/fused_graph_conv.py):
# it is selectable wherever a layer-level workload is being resolved
# (graph_conv_batched / resolve_graph_conv_impl), but is NOT a plain SpMM —
# batched_spmm(impl="fused") raises with a pointer to the layer entry point.
# The reduced-precision variants (…_bf16 / …_i8, DESIGN.md §10) are distinct
# registry entries: each runs its base impl's execution structure with a
# cheaper storage policy and an f32 accumulator.
IMPLS = ("auto", "ref", "ell", "pallas_ell", "csr", "pallas_csr",
         "pallas_coo", "hybrid", "pallas_hybrid", "dense", "pallas_gemm",
         "loop", "fused", "fused_hybrid") + tuple(PRECISION_IMPLS)

# The static g-SpMM axes (DESIGN.md §11). ``copy_lhs`` ignores the edge
# value entirely (pure neighborhood aggregation, e.g. R-GCN's mean).
GSPMM_OPS = ("mul", "add", "copy_lhs")
GSPMM_REDUCES = ("sum", "max", "mean")

# One description per BASE impl; precision variants derive theirs from
# (base, policy) so adding a variant never needs a new entry here.
_IMPL_NOTES = {
    "auto": "shape-keyed adaptive dispatch: the paper's §IV-B/§IV-C "
            "resource-assignment policy extended into a which-kernel "
            "decision by repro.autotune (cost model + optional measured "
            "tuning cache, DESIGN.md §5); trace-time, jit-safe",
    "ref": "pure-jnp batched oracle (scatter-add), XLA-fused",
    "ell": "pure-XLA ELL row-split (gather + contraction): the batched "
           "single-op semantics without the Pallas kernel",
    "pallas_ell": "Batched SWA-CSR analogue (row-split ELL Pallas kernel)",
    "csr": "pure-XLA CSR segment-sum reference (same conversion, "
           "searchsorted row recovery + scatter-add)",
    "pallas_csr": "Batched CSR row-split (GE-SpMM style: flat nnz arrays, "
                  "rpt-bounded dynamic slot loop — DESIGN.md §9)",
    "pallas_coo": "Batched SWA-SparseTensor analogue (one-hot-scatter "
                  "kernel)",
    "hybrid": "pure-XLA degree-split hybrid: dense hub-row slab GEMM + "
              "ELL remainder bounded by the hub threshold (the "
              "HC-SpMM-style routing without the Pallas kernel)",
    "pallas_hybrid": "degree-binned hybrid row dispatch: MXU-dense hub "
                     "tiles + rpt-bounded CSR remainder over sorted work "
                     "bins, inverse row permutation fused into the "
                     "epilogue (DESIGN.md §12)",
    "dense": "densify + batched GEMM (the cuBLAS gemmBatched baseline)",
    "pallas_gemm": "densify + MXU Pallas batched GEMM",
    "loop": "the NON-batched baseline: one sequential SpMM per sample, "
            "reproducing the paper's per-sample-kernel-launch structure",
    "fused": "graph-conv LAYER megakernel (needs W and bias; raises here — "
             "use graph_conv_batched, DESIGN.md §7)",
    "fused_hybrid": "graph-conv LAYER megakernel with degree-binned hybrid "
                    "dispatch: per-channel dense hub slabs + compacted COO "
                    "scatter chunks (needs W and bias; raises here — use "
                    "graph_conv_batched, DESIGN.md §12)",
}
_POLICY_NOTES = {
    "bf16": "bfloat16 storage, f32 in-kernel accumulate (DESIGN.md §10)",
    "i8": "int8 value codes + per-matrix f32 dequantization scale "
          "(DESIGN.md §10)",
}


def _impl_table() -> str:
    """Render the registry table appended to this module's docstring —
    derived from :data:`IMPLS` so docs cannot drift from the registry."""
    lines = []
    for name in IMPLS:
        base, policy = precision_of(name)
        note = (_IMPL_NOTES[base] if policy == "f32"
                else f"{base!r} execution with {_POLICY_NOTES[policy]}")
        lines.append(f"- ``{name!r}``: {note}")
    return "Registered ``impl`` values:\n\n" + "\n".join(lines)


__doc__ = (__doc__ or "") + "\n" + _impl_table() + "\n"


def resolve_impl(
    a: BatchedCOO,
    b: jax.Array,
    *,
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
    precision: str = "f32",
):
    """Resolve ``impl="auto"`` to the concrete impl for this call's shapes.

    Returns an ``repro.autotune.Decision`` (``.impl`` is the concrete
    string); a concrete ``impl`` passes through as a forced Decision so
    callers can introspect either path uniformly. ``precision`` is the
    caller's dtype policy (``"f32"``/``"bf16"``/``"i8"``): under
    ``impl="auto"`` it admits the matching reduced-precision variants to the
    ranking; a concrete impl carries its own policy and ignores it.
    """
    from repro import autotune

    interpret = resolve_interpret(interpret)
    batch, m_pad, n_b = b.shape
    if impl != "auto":
        w = autotune.Workload(batch=batch, m_pad=m_pad,
                              nnz_pad=a.row_ids.shape[1], k_pad=k_pad,
                              n_b=n_b, itemsize=b.dtype.itemsize,
                              dtype=precision_of(impl)[1])
        return autotune.forced_decision(w, impl)
    return autotune.resolve_auto(
        batch=batch, m_pad=m_pad, nnz_pad=a.row_ids.shape[1], k_pad=k_pad,
        n_b=n_b, itemsize=b.dtype.itemsize, interpret=interpret,
        dtype=precision)


def resolve_compute_dtype(a_dtype, b_dtype):
    """The deliberate mixed-dtype policy of the GEMM-class impls (DESIGN.md
    §10): compute in the PROMOTED dtype of the two operands so a
    full-precision operand is never silently downcast. Same lattice the
    precision variants use — bf16 meets f32 at f32."""
    return jnp.promote_types(a_dtype, b_dtype)


def _csr_forward(csr: BatchedCSR, b, *, impl, interpret, scale=None,
                 narrow=False):
    """Run a CSR-class impl on an already-converted :class:`BatchedCSR` —
    shared by the forward (COO→CSR) and the backward (``csr_transpose``).

    ``scale`` is the i8 policy's per-matrix dequantization factor (applied to
    the f32 accumulator — in-kernel on the Pallas path, post-hoc on the XLA
    fallbacks); ``narrow`` stores column ids as int16 on the Pallas wire."""
    if impl == "csr":
        out = ref.batched_spmm_csr_ref(csr, b)
        return out if scale is None else out * scale[:, None, None]
    plan = batching.plan_batched_spmm(
        batch=csr.batch, m_pad=csr.m_pad, n_b=b.shape[-1],
        slots=csr.nnz_pad, itemsize=b.dtype.itemsize)
    if plan.case == 3:
        # Paper case 3: matrices too large for the batched strategy — same
        # per-sample fallback as the COO/ELL kernels.
        out = ref.batched_spmm_csr_ref(csr, b)
        return out if scale is None else out * scale[:, None, None]
    cids = narrow_col_ids(csr.col_ids, csr.m_pad) if narrow else csr.col_ids
    return batched_spmm_csr(csr.rpt, cids, csr.values, b,
                            plan=plan, scale=scale, interpret=interpret)


def _forward(row_ids, col_ids, nnz, values, b, *, impl, k_pad, interpret,
             op="mul", reduce="sum"):
    """Dispatch one batched SpMM forward. A precision variant (DESIGN.md §10)
    decomposes into (base impl, storage policy): bf16 casts values and the
    dense operand to bfloat16 (f32 accumulate in-kernel, output cast back to
    the caller's dtype); i8 quantizes values to int8 codes with a per-matrix
    f32 scale applied once to the accumulator (exact, by linearity) while the
    dense operand stays full-precision. Both narrow the Pallas-side index
    storage to int16 behind :func:`repro.core.formats.narrow_col_ids`'s
    host-side overflow guard.

    A non-default ``(op, reduce)`` or 3D (vector-edge) ``values`` routes to
    the g-SpMM dispatch (:func:`_gspmm_forward`): f32-only, explicit padding
    masks, restricted to ``autotune.GSPMM_IMPLS``.

    A compiled call (``interpret=False``) naming a kernel the TPU refuses
    (``autotune.cost_model.TPU_REFUSED``) raises here, before Mosaic."""
    refused = None if interpret else tpu_refusal(impl, reduce)
    if refused:
        raise ValueError(f"impl {impl!r} does not compile for the TPU: "
                         f"{refused}")
    if (op, reduce) != ("mul", "sum") or values.ndim == 3:
        if not supports_gspmm(impl):
            raise ValueError(
                f"impl {impl!r} cannot run g-SpMM (op={op!r}, "
                f"reduce={reduce!r}, values.ndim={values.ndim}); the capable "
                f"set is {GSPMM_IMPLS} at f32")
        return _gspmm_forward(row_ids, col_ids, nnz, values, b,
                              impl=precision_of(impl)[0], k_pad=k_pad,
                              interpret=interpret, op=op, reduce=reduce)
    base, policy = precision_of(impl)
    out_dtype = b.dtype
    scale = None
    if policy == "bf16":
        values = values.astype(jnp.bfloat16)
        b = b.astype(jnp.bfloat16)
    elif policy == "i8":
        values, scale = quantize_values_i8(values)
    out = _forward_base(row_ids, col_ids, nnz, values, b, impl=impl,
                        base=base, k_pad=k_pad, interpret=interpret,
                        scale=scale, narrow=policy != "f32")
    # Reduced policies restore the caller's dtype; the f32 path returns the
    # branch's own result dtype (the GEMM class may deliberately PROMOTE on
    # mixed-dtype inputs — see resolve_compute_dtype).
    return out if policy == "f32" else out.astype(out_dtype)


def _forward_base(row_ids, col_ids, nnz, values, b, *, impl, base, k_pad,
                  interpret, scale, narrow):
    batch, m_pad, n_b = b.shape
    a = BatchedCOO(row_ids, col_ids, values, nnz, jnp.full((batch,), m_pad))

    def dequant(out):
        # XLA fallback for the i8 policy: the kernel-side accumulator scale,
        # applied after the (linear) unscaled SpMM of the codes
        return out if scale is None else out * scale[:, None, None]

    if base == "ref":
        return dequant(ref.batched_spmm_coo_ref(a, b, m_pad))
    if base == "loop":
        # Non-batched baseline: sequential per-sample SpMM (paper Fig. 2 / the
        # "TF" bars in Fig. 8). Structured as a scan so each sample is its own
        # sequential step, like one kernel launch per sample.
        def step(_, args):
            r, c, v, bb = args
            return None, ref.spmm_coo_single(r, c, v, bb, m_pad)

        _, out = jax.lax.scan(step, None, (row_ids, col_ids, values, b))
        return out
    if base in ("dense", "pallas_gemm"):
        a_dense = coo_to_dense(a, m_pad)
        # Deliberate mixed-dtype resolution (not a silent downcast to
        # b.dtype): both operands meet at the promoted dtype, so e.g. f32
        # adjacency values × bf16 features compute — and return — f32.
        compute = resolve_compute_dtype(a_dense.dtype, b.dtype)
        a_dense, bb = a_dense.astype(compute), b.astype(compute)
        if base == "dense":
            return ref.batched_gemm_ref(a_dense, bb)
        plan = batching.plan_batched_gemm(
            batch=batch, m=m_pad, n=n_b, k=m_pad, itemsize=bb.dtype.itemsize
        )
        return batched_gemm(a_dense, bb, plan=plan, interpret=interpret)
    if base in ("csr", "pallas_csr"):
        return _csr_forward(coo_to_csr(a, m_pad), b, impl=base,
                            interpret=interpret, scale=scale, narrow=narrow)
    if base in ("hybrid", "pallas_hybrid"):
        assert scale is None, "hybrid has no i8 variant"
        hplan = batching.plan_hybrid(
            batch=batch, m_pad=m_pad, n_b=n_b, nnz_pad=row_ids.shape[1],
            itemsize=b.dtype.itemsize)
        if base == "hybrid":
            return batched_spmm_hybrid_xla(a, b, m_pad, plan=hplan)
        if hplan.spmm.case == 3:
            # Paper case 3: same per-sample fallback as the other kernels.
            return ref.batched_spmm_coo_ref(a, b, m_pad)
        return batched_spmm_hybrid(row_ids, col_ids, values, nnz, b,
                                   plan=hplan, narrow=narrow,
                                   interpret=interpret)
    if base in ("pallas_ell", "ell"):
        if k_pad is None:
            raise ValueError(f"{impl} requires k_pad (max nnz/row)")
        # Silent-drop guard: coo_to_ell zeroes any nnz beyond k_pad in a row.
        # Eager (concrete) calls raise host-side here; traced calls cannot
        # branch on data and skip (callers own k_pad sizing under jit —
        # coo_to_ell(check=True) installs a runtime debug-assert instead).
        validate_ell_k_pad(a, m_pad, k_pad)
    plan = batching.plan_batched_spmm(
        batch=batch, m_pad=m_pad, n_b=n_b,
        slots=k_pad if base == "pallas_ell" else row_ids.shape[1],
        itemsize=b.dtype.itemsize, onehot=base == "pallas_coo",
    )
    if plan.case == 3:
        # Paper case 3: matrices too large for the batched shared-memory
        # strategy — take the per-sample path.
        return dequant(ref.batched_spmm_coo_ref(a, b, m_pad))
    if base in ("pallas_ell", "ell"):
        ell = coo_to_ell(a, m_pad, k_pad)
        if base == "ell":
            # pure-XLA batched row-split (gather + contraction): the batched
            # single-op semantics without the Pallas kernel
            return dequant(ref.batched_spmm_ell_ref(ell, b))
        cids = narrow_col_ids(ell.col_ids, m_pad) if narrow else ell.col_ids
        return batched_spmm_ell(cids, ell.values, b, plan=plan,
                                scale=scale, interpret=interpret)
    if base == "pallas_coo":
        # no int16 narrowing: the kernel reads int32 chunk-rows
        return batched_spmm_coo(row_ids, col_ids, values, b, plan=plan,
                                interpret=interpret)
    raise ValueError(f"unknown impl {impl!r}; expected one of {IMPLS}")


def _gspmm_forward(row_ids, col_ids, nnz, values, b, *, impl, k_pad,
                   interpret, op, reduce):
    """Dispatch one batched g-SpMM forward over the capable impl subset.

    Every path masks padding EXPLICITLY from the true per-matrix ``nnz`` /
    per-row degree: the §IV-C padding invariant (value 0.0 is neutral) only
    holds for ``(mul, sum)``. The paper's case-3 guard falls back to the
    batched pure-jnp oracle, like the plain-SpMM branches."""
    batch, m_pad, n_b = b.shape
    a = BatchedCOO(row_ids, col_ids, values, nnz, jnp.full((batch,), m_pad))
    if impl == "ref":
        return ref.batched_gspmm_ref(a, b, m_pad, op=op, reduce=reduce)
    if impl == "loop":
        # Non-batched baseline: sequential per-sample g-SpMM (scan), the
        # per-sample-kernel-launch structure of the paper's "TF" bars.
        def step(_, args):
            r, c, v, n, bb = args
            return None, ref.gspmm_coo_single(r, c, v, bb, m_pad, n,
                                              op=op, reduce=reduce)

        _, out = jax.lax.scan(step, None, (row_ids, col_ids, values, nnz, b))
        return out

    def fallback():
        return ref.batched_gspmm_ref(a, b, m_pad, op=op, reduce=reduce)

    if impl in ("csr", "pallas_csr"):
        csr = coo_to_csr(a, m_pad)
        if impl == "csr":
            return ref.batched_gspmm_csr_ref(csr, b, op=op, reduce=reduce)
        plan = batching.plan_batched_spmm(
            batch=batch, m_pad=m_pad, n_b=n_b, slots=csr.nnz_pad,
            itemsize=b.dtype.itemsize)
        if plan.case == 3:
            return fallback()
        return batched_spmm_csr(csr.rpt, csr.col_ids, csr.values, b,
                                plan=plan, op=op, reduce=reduce,
                                interpret=interpret)
    if impl in ("ell", "pallas_ell"):
        if k_pad is None:
            raise ValueError(f"{impl} requires k_pad (max nnz/row)")
        validate_ell_k_pad(a, m_pad, k_pad)
        # the ELL layout cannot distinguish a real zero-valued edge from a
        # padded slot, so the per-row live bound travels beside it
        rlen = row_degrees(a, m_pad)
        ell = coo_to_ell(a, m_pad, k_pad)
        if impl == "ell":
            return ref.batched_gspmm_ell_ref(ell, rlen, b,
                                             op=op, reduce=reduce)
        plan = batching.plan_batched_spmm(
            batch=batch, m_pad=m_pad, n_b=n_b, slots=k_pad,
            itemsize=b.dtype.itemsize)
        if plan.case == 3:
            return fallback()
        return batched_spmm_ell(ell.col_ids, ell.values, b, plan=plan,
                                rlen=rlen, op=op, reduce=reduce,
                                interpret=interpret)
    if impl == "pallas_coo":
        plan = batching.plan_batched_spmm(
            batch=batch, m_pad=m_pad, n_b=n_b, slots=row_ids.shape[1],
            itemsize=b.dtype.itemsize, onehot=True)
        if plan.case == 3:
            return fallback()
        return batched_spmm_coo(row_ids, col_ids, values, b, plan=plan,
                                nnz=nnz, op=op, reduce=reduce,
                                interpret=interpret)
    raise ValueError(
        f"unknown g-SpMM impl {impl!r}; expected one of {GSPMM_IMPLS}")


def _traced_dispatch(f, values, b, *, impl, decision, workload):
    """Run one dispatch under a telemetry span (DESIGN.md §13).

    Only reached when ``observability.enabled()`` — the hot path pays a
    single predicate otherwise. The caller's ``spmm/<impl>`` name scope is
    on either way, so the compiled program is the same. The span carries
    the workload geometry, the auto-decision provenance, and the cost
    model's *predicted* seconds and minimum HBM bytes, so a trace viewer
    (and the regret auditor) can line predicted up against measured. Eager
    (non-traced) dispatches also feed the default regret auditor's online
    calibration stream; traced calls record the span (trace-time wall) but
    skip the auditor — a trace is not an execution.
    """
    from repro.autotune.cost_model import estimate
    from repro.observability.regret import default_auditor

    pred = dict(decision.scores).get(impl) if decision is not None else None
    if pred is None:
        try:
            pred = estimate(workload, impl)
        except ValueError:
            pred = None
        if pred == float("inf"):
            pred = None
    it = workload.itemsize
    # impl-independent floor: value+index slots once, B and C once each
    pred_bytes = (workload.batch * workload.nnz_pad * (it + 8)
                  + 2 * workload.batch * workload.m_pad * workload.n_b * it)
    args = {
        "impl": impl, "key": workload.key(), "batch": workload.batch,
        "m_pad": workload.m_pad, "nnz_pad": workload.nnz_pad,
        "k_pad": workload.k_pad, "n_b": workload.n_b,
        "dtype": workload.dtype, "op": workload.op,
        "reduce": workload.reduce, "predicted_s": pred,
        "predicted_bytes": pred_bytes,
    }
    if decision is not None:
        args["source"] = decision.source
        args["case"] = decision.case
    eager = not isinstance(values, jax.core.Tracer)
    t0 = time.perf_counter()
    with obs_trace.TRACER.span(f"spmm/{impl}", cat="kernel", args=args):
        out = f(values, b)
    if eager and pred is not None:
        default_auditor().record(workload.key(), impl, predicted_s=pred,
                                 measured_s=time.perf_counter() - t0)
    return out


_VARIANT_BWD = {
    # bf16 forwards keep a bf16-class backward (grads accumulate f32
    # in-kernel, cast on the way out); ELL-class forwards fall to the COO
    # class like their f32 bases. i8 forwards take a FULL-PRECISION
    # straight-through backward: the VJP residuals hold the original f32
    # values (quantization happens inside _forward), so dB is computed
    # against the unquantized operator — the class mapping of the f32 base.
    "ell_bf16": "ref",
    "csr_bf16": "csr_bf16",
    "pallas_ell_bf16": "pallas_coo_bf16",
    "pallas_csr_bf16": "pallas_csr_bf16",
    "pallas_coo_bf16": "pallas_coo_bf16",
    "pallas_ell_i8": "pallas_coo",
    "pallas_csr_i8": "pallas_csr",
    "fused_bf16": "pallas_coo_bf16",
    "pallas_hybrid_bf16": "pallas_csr_bf16",
}


def bwd_impl_for(impl: str) -> str:
    """The impl the backward pass (dB = Aᵀ @ dC) runs for a forward ``impl``.

    Aᵀ loses the per-row ELL bound, so ELL-class forwards fall back to the
    COO/scatter class; CSR-class forwards stay CSR — ``csr_transpose`` is an
    exact device-side Aᵀ with no per-row bound to lose. Shared by the local
    and the mesh-sharded VJP. The fused megakernel's dU = Aᵀ·dZ is itself a
    plain batched SpMM, so it takes the same COO-class backward. Precision
    variants map first (before the pallas catch-all) via ``_VARIANT_BWD``.

    The hybrid class maps to the CSR class (its sparse remainder IS the
    rpt-bounded CSR loop): the forward's inverse-permute epilogue sits
    inside the custom-VJP boundary, so cotangents arrive in ORIGINAL row
    order and the backward permutes nothing — it must not re-sort Aᵀ by
    *its* degrees, because dB = Aᵀ·dC is exact in any evaluation order and
    re-deriving a permutation for the transpose would pay the sort twice
    for no bound on Aᵀ's rows.
    """
    if impl in _VARIANT_BWD:
        return _VARIANT_BWD[impl]
    if impl in ("csr", "pallas_csr"):
        return impl
    if impl == "hybrid":
        return "csr"
    if impl == "pallas_hybrid":
        return "pallas_csr"
    if impl.startswith("pallas") or impl.startswith("fused"):
        return "pallas_coo"
    return impl if impl in ("ref", "loop", "dense") else "ref"


def backward_db(row_ids, col_ids, nnz, values, dc, *, impl, interpret):
    """dB = Aᵀ @ dC for a forward ``impl`` — batched SpMM with the transposed
    adjacency (paper §IV-D), shared by the local and the mesh-sharded VJP.

    Every class transposes by swapping the COO index arrays (free); for the
    CSR class ``_forward`` then row-sorts the swapped COO, which IS the
    device-side transposed CSR in one sort —
    ``csr_transpose(coo_to_csr(A))`` collapsed, since the VJP still holds
    the raw COO triples. :func:`repro.core.formats.csr_transpose` is the
    same Aᵀ for callers that hold only a ``BatchedCSR``.
    """
    return _forward(col_ids, row_ids, nnz, values, dc,
                    impl=bwd_impl_for(impl), k_pad=None, interpret=interpret)


def dvalues(row_ids, col_ids, dc, b):
    """dValues[i] = <dC[rid[i]], B[cid[i]]> — the batched gather-dot of the
    VJP (paper §IV-D), shared by the local and the mesh-sharded backward."""

    def one(rid, cid, dcc, bb):
        return jnp.sum(
            jnp.take(dcc, rid, axis=0) * jnp.take(bb, cid, axis=0), axis=-1)

    return jax.vmap(one)(row_ids, col_ids, dc, b)


def gspmm_backward(row_ids, col_ids, nnz, values, b, c, dc, *, op, reduce,
                   impl, interpret):
    """(dValues, dB) for one g-SpMM forward — shared by the local and the
    mesh-sharded VJP, like :func:`backward_db`/:func:`dvalues` for plain
    SpMM.

    ``mean`` pre-scales the cotangent by 1/deg (d mean = d sum / deg) and
    then reduces to the sum backward. The ``(mul, sum/mean)`` scalar-edge
    corner IS the plain-SpMM backward and keeps its in-class batched path
    (dB = Aᵀ @ dC via :func:`backward_db`, dValues a batched gather-dot —
    only the padding-slot gradient needs an explicit mask now). Every other
    corner runs a generic gather/scatter VJP:

    - ``max`` routes each row's cotangent to the winning edge(s) by an
      argmax mask ``msg == C[rid]`` — exact f32 equality is sound because
      the forward computes ``msg`` with the identical f32 expression; ties
      (e.g. duplicate edges under ``copy_lhs``) split the cotangent evenly,
      matching XLA's scatter-max autodiff convention;
    - dB scatters ``∂msg/∂B = e`` (mul) or ``1`` (add / copy_lhs) by column;
    - dValues is the feature-summed (scalar) or elementwise (vector) product
      with the gathered B rows for ``mul``, the bare cotangent for ``add``,
      and identically 0 for ``copy_lhs``.
    """
    batch, m_pad, _ = b.shape
    nnz_pad = row_ids.shape[1]
    valid = jnp.arange(nnz_pad)[None, :] < nnz[:, None]    # (batch, nnz_pad)
    dcf = dc.astype(jnp.float32)
    if reduce == "mean":
        a = BatchedCOO(row_ids, col_ids, values, nnz,
                       jnp.full((batch,), m_pad))
        deg = row_degrees(a, m_pad).astype(jnp.float32)    # (batch, m_pad)
        dcf = dcf / jnp.maximum(deg, 1.0)[..., None]
    scalar = values.ndim == 2
    if op == "mul" and reduce in ("sum", "mean") and scalar:
        # padded slots carry no semantics here (dB is linear in the values),
        # so zero them instead of trusting the padding-is-0.0 invariant
        vals_m = values * valid.astype(values.dtype)
        db = backward_db(row_ids, col_ids, nnz, vals_m, dcf,
                         impl=impl, interpret=interpret)
        dval = dvalues(row_ids, col_ids, dcf, b) * valid
        return dval.astype(values.dtype), db.astype(b.dtype)

    def one(rid, cid, val, n, bf, cf, dcc):
        vmask = (jnp.arange(nnz_pad) < n)[:, None]         # (nnz_pad, 1)
        rid_c = jnp.clip(rid.astype(jnp.int32), 0, m_pad - 1)
        cid_c = cid.astype(jnp.int32)
        u = jnp.take(bf, cid_c, axis=0).astype(jnp.float32)
        dmsg = jnp.take(dcc, rid_c, axis=0)
        if reduce == "max":
            msg = ref.gspmm_combine(u, val, op)
            win = ((msg == jnp.take(cf, rid_c, axis=0)) & vmask).astype(
                jnp.float32)
            # ties (e.g. duplicate edges under copy_lhs) split the cotangent
            # evenly — XLA's scatter-max autodiff convention
            nwin = jnp.zeros(cf.shape, jnp.float32).at[rid_c].add(win)
            dmsg = win * dmsg / jnp.maximum(
                jnp.take(nwin, rid_c, axis=0), 1.0)
        else:
            dmsg = jnp.where(vmask, dmsg, 0.0)
        if op == "mul":
            e = val.astype(jnp.float32)
            if scalar:
                e = e[:, None]
            db = jnp.zeros(bf.shape, jnp.float32).at[cid_c].add(dmsg * e)
            dval = jnp.sum(dmsg * u, axis=-1) if scalar else dmsg * u
        elif op == "add":
            db = jnp.zeros(bf.shape, jnp.float32).at[cid_c].add(dmsg)
            dval = jnp.sum(dmsg, axis=-1) if scalar else dmsg
        else:   # copy_lhs: the edge value never enters the forward
            db = jnp.zeros(bf.shape, jnp.float32).at[cid_c].add(dmsg)
            dval = jnp.zeros(val.shape, jnp.float32)
        return dval, db

    # only the max backward consults the forward output (argmax routing);
    # the linear reduces pass a placeholder so the residual can drop `c`
    cf = c.astype(jnp.float32) if reduce == "max" else jnp.zeros_like(dcf)
    dval, db = jax.vmap(one)(row_ids, col_ids, values, nnz, b, cf, dcf)
    return dval.astype(values.dtype), db.astype(b.dtype)


def resolve_gspmm_impl(
    a: BatchedCOO,
    b: jax.Array,
    *,
    op: str = "mul",
    reduce: str = "sum",
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
):
    """Resolve ``impl="auto"`` for one g-SpMM call — the
    :func:`resolve_impl` analogue with the ``(op, reduce, d_e)`` workload
    axes set, so ``Workload.is_gspmm`` restricts the ranked ladder to the
    capable subset and the tuning-cache key never collides with the plain
    SpMM entry for the same shapes."""
    from repro import autotune

    interpret = resolve_interpret(interpret)
    batch, m_pad, n_b = b.shape
    d_e = a.values.shape[2] if a.values.ndim == 3 else None
    w = autotune.Workload(batch=batch, m_pad=m_pad,
                          nnz_pad=a.row_ids.shape[1], k_pad=k_pad, n_b=n_b,
                          itemsize=b.dtype.itemsize, d_e=d_e, reduce=reduce,
                          op=op)
    if impl != "auto":
        return autotune.forced_decision(w, impl)
    from repro.autotune.cache import default_cache
    return autotune.select_impl(w, allow_pallas=not interpret,
                                cache=default_cache())


def batched_gspmm(
    a: BatchedCOO,
    b: jax.Array,
    *,
    op: str = "mul",
    reduce: str = "sum",
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
    mesh=None,
    mesh_axis: str = "data",
) -> jax.Array:
    """Generalized SpMM / message passing: per sample s,
    ``C[s][r] = reduce_{edges (r, c)} op(B[s][c], e)`` — the g-SpMM of
    DESIGN.md §11 (DGL's gspmm shape, arXiv:1909.01315).

    ``a.values`` holds the edge values ``e``: scalars ``(batch, nnz_pad)``
    or per-edge feature vectors ``(batch, nnz_pad, d_e)`` with ``d_e`` equal
    to B's feature width. Differentiable in ``a.values`` and ``b`` (custom
    VJP; ``max`` keeps its argmax routing, ``mean`` its degree scaling,
    zero-degree rows emit the 0.0 identity with 0 gradient).

    ``(op, reduce) == ("mul", "sum")`` with scalar edges IS plain batched
    SpMM and delegates to :func:`batched_spmm` — full registry, precision
    variants, identical numerics. Every other corner resolves over the
    f32 g-SpMM-capable subset (``autotune.GSPMM_IMPLS``).
    """
    if op not in GSPMM_OPS:
        raise ValueError(f"unknown g-SpMM op {op!r}; expected {GSPMM_OPS}")
    if reduce not in GSPMM_REDUCES:
        raise ValueError(
            f"unknown g-SpMM reduce {reduce!r}; expected {GSPMM_REDUCES}")
    if (op, reduce) == ("mul", "sum") and a.values.ndim == 2:
        return batched_spmm(a, b, impl=impl, k_pad=k_pad,
                            interpret=interpret, mesh=mesh,
                            mesh_axis=mesh_axis)
    interpret = resolve_interpret(interpret)
    if mesh is not None:
        from repro.distributed.spmm import sharded_batched_gspmm

        return sharded_batched_gspmm(a, b, op=op, reduce=reduce,
                                     mesh=mesh, axis=mesh_axis, impl=impl,
                                     k_pad=k_pad, interpret=interpret)
    tele = obs_trace.enabled()
    gdecision = None
    if impl == "auto" or tele:
        gdecision = resolve_gspmm_impl(a, b, op=op, reduce=reduce,
                                       impl=impl, k_pad=k_pad,
                                       interpret=interpret)
        impl = gdecision.impl
    if not supports_gspmm(impl):
        raise ValueError(
            f"impl {impl!r} cannot run g-SpMM (op={op!r}, reduce={reduce!r});"
            f" the capable set is {GSPMM_IMPLS} at f32")

    row_ids, col_ids, nnz = a.row_ids, a.col_ids, a.nnz

    @jax.custom_vjp
    def f(values, b):
        return _forward(row_ids, col_ids, nnz, values, b, impl=impl,
                        k_pad=k_pad, interpret=interpret, op=op,
                        reduce=reduce)

    def fwd(values, b):
        c = f(values, b)
        # the argmax routing of the max backward needs the forward output;
        # the linear reduces don't — drop it from their residual
        return c, (values, b, c if reduce == "max" else None)

    def bwd(res, dc):
        values, b, c = res
        dval, db = gspmm_backward(row_ids, col_ids, nnz, values, b, c, dc,
                                  op=op, reduce=reduce, impl=impl,
                                  interpret=interpret)
        return dval, db

    f.defvjp(fwd, bwd)
    with jax.named_scope(f"spmm/{impl}"):
        if tele and gdecision is not None and gdecision.workload is not None:
            return _traced_dispatch(f, a.values, b, impl=impl,
                                    decision=gdecision,
                                    workload=gdecision.workload)
        return f(a.values, b)


def batched_spmm(
    a: BatchedCOO,
    b: jax.Array,
    *,
    impl: str = "auto",
    k_pad: int | None = None,
    interpret: bool | None = None,
    mesh=None,
    mesh_axis: str = "data",
    precision: str = "f32",
) -> jax.Array:
    """C[s] = A[s] @ B[s] for every sample s in the batch, one device op.

    a: BatchedCOO over square (m_pad, m_pad) adjacencies; b: (batch, m_pad, n).
    Differentiable in ``a.values`` and ``b``. ``impl="auto"`` (default)
    resolves to a concrete implementation from the call's static shapes via
    ``repro.autotune`` before any tracing-dependent work happens.

    ``precision`` is the dtype policy for ``impl="auto"``: ``"bf16"``/
    ``"i8"`` let the ranking pick a reduced-precision variant (DESIGN.md
    §10). A concrete ``impl`` already encodes its policy (``"csr_bf16"``
    runs bf16 regardless of ``precision``).

    ``mesh=`` routes the call through the mesh-sharded path
    (:func:`repro.distributed.spmm.sharded_batched_spmm`): the batch axis is
    split over ``mesh_axis`` and the per-shard kernels run under shard_map,
    with ``impl="auto"`` resolved against the per-shard workload.
    """
    if precision_of(impl)[0].startswith("fused"):
        raise ValueError(
            f"impl={impl!r} is the graph-conv LAYER megakernel (it needs W "
            "and bias, not a bare dense operand) — call "
            "repro.core.graph_conv.graph_conv_batched(impl='fused') or "
            "repro.kernels.fused_graph_conv.fused_graph_conv directly")
    interpret = resolve_interpret(interpret)
    if mesh is not None:
        from repro.distributed.spmm import sharded_batched_spmm

        return sharded_batched_spmm(a, b, mesh=mesh, axis=mesh_axis,
                                    impl=impl, k_pad=k_pad,
                                    interpret=interpret, precision=precision)
    tele = obs_trace.enabled()
    decision = None
    if impl == "auto" or tele:
        # telemetry also resolves CONCRETE impls (a forced Decision) so the
        # span carries the same auditable plan/case/workload provenance
        decision = resolve_impl(a, b, impl=impl, k_pad=k_pad,
                                interpret=interpret, precision=precision)
        impl = decision.impl

    row_ids, col_ids, nnz = a.row_ids, a.col_ids, a.nnz

    @jax.custom_vjp
    def f(values, b):
        return _forward(row_ids, col_ids, nnz, values, b,
                        impl=impl, k_pad=k_pad, interpret=interpret)

    def fwd(values, b):
        return f(values, b), (values, b)

    def bwd(res, dc):
        values, b = res
        # dB = Aᵀ @ dC (paper §IV-D: "The Batched SpMM is also applied to
        # backward propagation") — COO index swap, or csr_transpose for the
        # CSR class.
        db = backward_db(row_ids, col_ids, nnz, values, dc,
                         impl=impl, interpret=interpret)
        dval = dvalues(row_ids, col_ids, dc, b).astype(values.dtype)
        return dval, db.astype(b.dtype)

    f.defvjp(fwd, bwd)
    with jax.named_scope(f"spmm/{impl}"):
        if tele and decision is not None and decision.workload is not None:
            return _traced_dispatch(f, a.values, b, impl=impl,
                                    decision=decision,
                                    workload=decision.workload)
        return f(a.values, b)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dense_batched_matmul(a, b, *, interpret: bool | None = None):
    """Standalone MXU batched GEMM entry point (benchmark use)."""
    plan = batching.plan_batched_gemm(
        batch=a.shape[0], m=a.shape[1], n=b.shape[-1], k=a.shape[2],
        itemsize=b.dtype.itemsize,
    )
    return batched_gemm(a, b, plan=plan, interpret=interpret)
