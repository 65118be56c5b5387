"""Analytic per-implementation cost model for Batched SpMM (DESIGN.md §5).

The paper's §IV-B/§IV-C resource-assignment policy decides *how* a batch is
blocked (``repro.core.batching.BatchPlan``); this module extends that case
analysis into a *which-kernel* decision by estimating wall time for each of
the six implementations in ``repro.kernels.ops.IMPLS`` on a shape-keyed
workload. The estimate is a two-term roofline (compute vs HBM traffic — the
same hardware constants as ``repro.analysis.roofline.HW``) plus the dispatch /
grid-step overheads that batching exists to amortize:

    t(impl) = max(flops / unit_peak, bytes / hbm_bw) + overheads

The model sees only static shapes — ``(batch, m_pad, nnz_pad, k_pad, n_b,
itemsize)`` — so selection is trace-safe: ``nnz_pad`` (the padded non-zero
slot count) stands in for density, exactly like the planner's ``slots``
argument. Padded slots cost real bandwidth on TPU (they are multiplied by
0.0, not skipped), so charging them is faithful, not pessimistic.

Per-impl traffic/compute accounting (see each kernel's module docstring for
the execution structure being modeled):

- ``ref``      scatter-add: gathers one B row per non-zero, then a
               segment-sum into the output; the scatter is charged a
               read-modify-write penalty on the output.
- ``ell``      XLA row-split: one B gather per ELL slot column, no scatter
               (each output row is owned by one reduction).
- ``pallas_ell``  same arithmetic, but panel-blocked: inputs are re-read once
               per column panel and the output block stays VMEM-resident.
- ``csr``      XLA CSR segment-sum: the ref traffic plus the rpt arrays —
               the scatter stays, only the layout changes.
- ``pallas_csr``  CSR row-split (DESIGN.md §9): the ELL arithmetic with the
               inner loop bounded by the true max row degree (statically:
               ``k_pad`` when known, else the uniform ``nnz_pad / m_pad``
               estimate) and HBM traffic on the FLAT nnz arrays —
               ``nnz_pad`` slots, not ``m_pad · k_pad`` — which is what
               makes CSR win skewed-degree batches (GE-SpMM's case).
               Format conversions (COO→ELL, COO→CSR, densify) are charged
               to no impl: every non-COO path converts inside ``_forward``,
               so the ranking compares like with like; the real conversion
               cost is measured by ``benchmarks/bench_formats.py``.
- ``pallas_coo``  one-hot MXU gather and scatter: each CHUNK of non-zeros
               costs two (CHUNK × m_pad × n_block) contractions at HIGHEST
               precision (``HIGHEST_PASSES``) and reads int32 ids and f32
               values (``PANEL_SLOT_BYTES``) under every storage policy.
- ``dense`` / ``pallas_gemm``  densify (write + read m_pad² per matrix) then
               a batched GEMM at MXU tile efficiency.
- ``loop``     the non-batched baseline: ``batch`` sequential steps, each
               paying the per-step dispatch latency the paper's Fig. 2
               measures — modeled, like measured, as strictly dominated for
               real batch sizes.
"""
from __future__ import annotations

import dataclasses
import functools

# Reduced-precision kernel variants (DESIGN.md §10): variant impl →
# (base impl, storage policy). The base impl defines the execution structure
# (and therefore the roofline branch); the policy defines the bytes each
# value/index element costs on the wire. "bf16" stores values, features and
# column indices (int16) at 2 bytes; "i8" stores values as int8 quantization
# codes (1 byte) + int16 indices while B and the output stay at the caller's
# f32. Every variant accumulates in f32 inside the kernel.
PRECISION_IMPLS = {
    "ell_bf16": ("ell", "bf16"),
    "csr_bf16": ("csr", "bf16"),
    "pallas_ell_bf16": ("pallas_ell", "bf16"),
    "pallas_csr_bf16": ("pallas_csr", "bf16"),
    "pallas_coo_bf16": ("pallas_coo", "bf16"),
    "pallas_ell_i8": ("pallas_ell", "i8"),
    "pallas_csr_i8": ("pallas_csr", "i8"),
    "fused_bf16": ("fused", "bf16"),
    "pallas_hybrid_bf16": ("pallas_hybrid", "bf16"),
}


def precision_of(impl: str) -> tuple[str, str]:
    """(base impl, storage policy) for any registry impl — ("csr", "bf16")
    for a variant, (impl, "f32") for the full-precision impls."""
    return PRECISION_IMPLS.get(impl, (impl, "f32"))


# Impls that implement the full g-SpMM matrix (op × reduce × edge-feature
# width — DESIGN.md §11). The GEMM class (dense/pallas_gemm) IS the
# (mul, sum) product and cannot express other reduces; the precision
# variants stay (mul, sum)-only for now, so a g-SpMM workload (reduce !=
# "sum" or d_e set) restricts the candidate ladder to this set at f32.
GSPMM_IMPLS = ("ref", "loop", "ell", "pallas_ell", "csr", "pallas_csr",
               "pallas_coo")


def supports_gspmm(impl: str) -> bool:
    """Whether ``impl`` can run a non-(mul, sum) or vector-edge workload."""
    base, policy = precision_of(impl)
    return base in GSPMM_IMPLS and policy == "f32"


# Pallas kernels Mosaic refuses to compile for the TPU (the v5e compile
# rehearsal in tests/test_tpu_compile.py), by base impl. They stay in the
# registry because interpret mode runs them as CPU-side references, but
# they never enter the compiled (``allow_pallas``) ladder, and a compiled
# call that names one raises (``kernels/ops.py``) instead of failing deep
# inside Mosaic. Precision variants inherit their base's refusal.
TPU_REFUSED = {
    "pallas_ell": "its slot loop gathers B rows in-kernel with jnp.take by "
                  "an index vector, which Mosaic does not lower",
    "pallas_csr": "it gathers column ids and B rows in-kernel with jnp.take "
                  "at flat CSR offsets, which Mosaic does not lower",
    "pallas_hybrid": "its sparse remainder is the pallas_csr gather loop, "
                     "and its (1, n_bins) SMEM bound block is not tile-"
                     "aligned",
}


def tpu_refusal(impl: str, reduce: str = "sum") -> str | None:
    """Why ``impl`` cannot compile for the TPU (None when it can).

    Besides :data:`TPU_REFUSED`, the COO kernel's ``max`` reduce is refused:
    its 3-D one-hot select does not lower."""
    base = precision_of(impl)[0]
    if base in TPU_REFUSED:
        return TPU_REFUSED[base]
    if base == "pallas_coo" and reduce == "max":
        return ("its max reduce folds a 3-D one-hot select, which Mosaic "
                "does not lower")
    return None


def _traffic(policy: str, itemsize: int) -> tuple[int, int, int, int]:
    """(value, index, feature, output) bytes-per-element under a storage
    policy. f32 keeps the legacy accounting (4-byte indices, caller
    itemsize elsewhere) so full-precision estimates are unchanged."""
    if policy == "bf16":
        return 2, 2, 2, 2
    if policy == "i8":
        return 1, 2, itemsize, itemsize
    return itemsize, 4, itemsize, itemsize


# These imports sit BELOW the variant registry on purpose: repro.core's
# package __init__ pulls in kernels/ops.py, which imports PRECISION_IMPLS /
# precision_of / tpu_refusal from this module at import time. Keeping the
# registry above the repro.core import makes that re-entrant import find the
# names bound even while this module is still initializing.
from repro.analysis.roofline import HW  # noqa: E402
from repro.core.batching import (  # noqa: E402
    CHUNK,
    BatchPlan,
    plan_batched_gemm,
    plan_batched_spmm,
    plan_fused_graph_conv,
    plan_hybrid,
)

# Overhead constants (seconds). These are *relative* knobs, not measurements:
# the model only needs ordering, and the ordering is validated against the
# ref oracle in tests/test_autotune.py and refined on-device by
# repro.autotune.cache when a tuning cache is enabled.
OP_OVERHEAD = 2e-6       # one fused XLA op inside a jitted program
SCAN_STEP_OVERHEAD = 2e-6  # one sequential scan iteration (the 'loop' path)
GRID_STEP_OVERHEAD = 0.2e-6  # one Pallas grid step
SCATTER_PENALTY = 3.0    # read-modify-write amplification of scatter-adds
_COO_CHUNK = CHUNK       # the COO/fused kernels' non-zero chunk (batching.py)
# The COO and fused kernels run every MXU product (the one-hot gather and
# scatter, the fused X·W and the hybrid inverse permutation) at HIGHEST
# precision, which costs about six bf16 passes of an f32 operand.
HIGHEST_PASSES = 6.0
# Bytes per non-zero slot those kernels read: int32 row and column ids and
# an f32 value under every storage policy (Mosaic cannot load a packed
# 16-bit chunk-row at a dynamic offset, so the bf16 variants widen them).
PANEL_SLOT_BYTES = 4 + 4 + 4


def _mxu_eff(m: int, n: int) -> float:
    """Fraction of the 128x128 MXU tile a (m, n) product actually fills."""
    return max(min(1.0, m / 128.0) * min(1.0, n / 128.0), 1e-3)


@dataclasses.dataclass(frozen=True)
class Workload:
    """Static shape key for one batched SpMM call (hashable, trace-safe).

    ``nnz_pad`` is the COO slot count per matrix (the density proxy: the
    planner and the kernels both pay for padded slots), ``k_pad`` the ELL
    slots per row or None when no ELL conversion is available.

    A *graph-conv layer* workload additionally carries ``channels`` (edge
    channels summed by the layer) and ``n_in`` (the X feature width feeding
    the fused MatMul); both None means "plain SpMM call" and keeps the key
    format unchanged. ``nnz_avg`` is the skew knob: the mean real non-zeros
    per (sample × channel) when host metadata knows it — the fused kernel's
    per-sample chunk loop pays for the MEAN, every other impl pays for the
    padded max.

    A *g-SpMM* workload (DESIGN.md §11) additionally carries ``op``
    (``"mul"``/``"add"``/``"copy_lhs"``), ``reduce`` (``"sum"``/``"max"``/
    ``"mean"``) and ``d_e`` (the per-edge feature-vector width, None for
    scalar edges): the defaults mean "plain SpMM" and keep the key format
    unchanged; non-defaults restrict the candidate ladder to
    :data:`GSPMM_IMPLS` and charge the extra value traffic.
    """

    batch: int
    m_pad: int
    nnz_pad: int
    k_pad: int | None
    n_b: int
    itemsize: int = 4
    channels: int | None = None
    n_in: int | None = None
    nnz_avg: int | None = None
    dtype: str = "f32"      # precision policy: "f32" | "bf16" | "i8"
    d_e: int | None = None  # edge-feature width (g-SpMM vector edges)
    reduce: str = "sum"     # g-SpMM reduce kind: "sum" | "max" | "mean"
    op: str = "mul"         # g-SpMM combine op: "mul" | "add" | "copy_lhs"
    # the SKEW knob for the row-split classes: per-matrix (batch-max) max
    # row degree from host metadata. The CSR kernel's slot loop runs this
    # many trips — the serialization bound it actually pays — and the
    # hybrid split amortizes only when it exceeds the hub threshold. None
    # keeps every legacy estimate and cache key unchanged.
    max_deg: int | None = None
    # the sampled-BLOCK knob (DESIGN.md §14): padded dst-row count of a
    # bipartite block embedded in the (m_pad, m_pad) square — only the
    # first `block` rows are real outputs. Output traffic scales to it for
    # every impl, and the row-split (CSR/hybrid) classes additionally bound
    # their per-row work by it (rows past n_dst have rlen 0 — predicated
    # off), while dense still densifies the full square and ELL still runs
    # every padded row's k_pad slots. That asymmetry is exactly why
    # CSR-class kernels win sampled blocks. None (a non-block workload)
    # keeps every legacy estimate and cache key unchanged.
    block: int | None = None

    def key(self) -> str:
        """Stable string key for the persistent tuning cache (DESIGN.md §5).
        The dtype / g-SpMM (op, reduce, edge-feature) suffixes appear only
        for non-default values so every pre-existing cache entry keeps its
        key."""
        k = self.k_pad if self.k_pad is not None else 0
        base = (f"b{self.batch}_m{self.m_pad}_nnz{self.nnz_pad}"
                f"_k{k}_n{self.n_b}_i{self.itemsize}")
        if self.channels is not None:
            base += f"_c{self.channels}_nin{self.n_in or 0}"
        if self.dtype != "f32":
            base += f"_d{self.dtype}"
        if self.d_e is not None:
            base += f"_e{self.d_e}"
        if self.reduce != "sum":
            base += f"_r{self.reduce}"
        if self.op != "mul":
            base += f"_o{self.op}"
        if self.max_deg is not None:
            base += f"_md{self.max_deg}"
        if self.block is not None:
            base += f"_blk{self.block}"
        return base

    @property
    def is_gspmm(self) -> bool:
        """True when this workload needs a g-SpMM-capable impl."""
        return (self.op != "mul" or self.reduce != "sum"
                or self.d_e is not None)

    def shard(self, n_shards: int) -> "Workload":
        """The per-shard view of this workload on an ``n_shards``-way mesh:
        batch ``ceil(batch / n_shards)`` (the batch axis is padded to a
        multiple before sharding), every other dimension unchanged. This is
        the workload each device actually runs under
        ``repro.distributed.spmm.sharded_batched_spmm``, and therefore the
        one ``impl="auto"`` must be resolved against (DESIGN.md §6)."""
        return dataclasses.replace(self, batch=-(-self.batch // n_shards))


def spmm_plan(w: Workload, impl: str | None = None) -> BatchPlan:
    """The planner decision this workload falls under, with the SAME slot
    accounting as kernels/ops.py: ``k_pad`` slots for the ELL kernel,
    ``nnz_pad`` (COO) slots for everything else. ``impl=None`` means
    "best available" (ELL accounting when k_pad is known). The case-3
    boundary depends only on m_pad, so it is identical either way, except
    for the COO kernel, whose one-hot tiles must also fit the scoped VMEM.
    Precision variants plan as their base impl; the bf16 policy blocks at
    2-byte elements (the features are cast too), the i8 policy keeps the
    caller itemsize (B and the output stay f32)."""
    base, policy = (None, "f32") if impl is None else precision_of(impl)
    if base in (None, "ell", "pallas_ell") and w.k_pad is not None:
        slots = w.k_pad
    else:
        slots = w.nnz_pad
    itemsize = 2 if policy == "bf16" else w.itemsize
    return plan_batched_spmm(batch=w.batch, m_pad=w.m_pad, n_b=w.n_b,
                             slots=slots, itemsize=itemsize,
                             onehot=base == "pallas_coo")


def _roofline(flops: float, bytes_: float, unit_peak: float,
              hw: HW) -> float:
    return max(flops / unit_peak, bytes_ / hw.hbm_bw)


def estimate(w: Workload, impl: str, hw: HW = HW()) -> float:
    """Estimated seconds for one batched call of ``impl`` on workload ``w``.

    Precision variants reuse their base impl's roofline branch with the
    policy's bytes-per-element (``_traffic``): same execution structure,
    cheaper wire traffic. The pricing follows the IMPL's policy, not
    ``w.dtype`` — on a bf16-policy workload the full-precision candidates
    still pay full-precision bytes, which is exactly why a variant can
    out-rank its base."""
    base, policy = precision_of(impl)
    f32_path = policy == "f32"
    vb, ib, fb, ob = _traffic(policy, w.itemsize)
    vpu_peak = hw.peak_flops / 16.0           # vector (non-MXU) arithmetic
    # sampled blocks (DESIGN.md §14): only the first `block` rows are real
    # outputs; non-block workloads keep rows_out == m_pad (legacy estimates
    # bitwise unchanged)
    rows_out = w.block if w.block is not None else w.m_pad
    out_bytes = w.batch * rows_out * w.n_b * ob
    b_bytes = w.batch * w.m_pad * w.n_b * fb
    # g-SpMM extras (DESIGN.md §11), zero for plain SpMM so every legacy
    # estimate is unchanged: vector edges read (d_e - 1) extra value
    # elements per slot; a max/mean reduce pays one post-kernel fix-up pass
    # over the output (degree rewrite / scale).
    d_x = (w.d_e - 1) if w.d_e else 0
    gfix = out_bytes if w.reduce != "sum" else 0.0

    if base in ("ref", "loop"):
        gather = w.batch * w.nnz_pad * w.n_b * fb
        idx = w.batch * w.nnz_pad * (8 if f32_path else 2 * ib)
        flops = 2.0 * w.batch * w.nnz_pad * w.n_b
        bytes_ = (gather + idx + SCATTER_PENALTY * out_bytes
                  + w.batch * w.nnz_pad * d_x * vb + gfix)
        t = _roofline(flops, bytes_, vpu_peak, hw) + OP_OVERHEAD
        if base == "loop":
            # sequential per-sample execution: no cross-sample overlap, one
            # step latency per sample — the Fig. 2 structure.
            t = w.batch * (t / w.batch + SCAN_STEP_OVERHEAD)
        return t

    if base in ("ell", "pallas_ell"):
        if w.k_pad is None:
            return float("inf")
        slots = w.batch * w.m_pad * w.k_pad
        flops = 2.0 * slots * w.n_b
        if base == "ell":
            bytes_ = slots * (w.n_b * fb + (8 if f32_path else ib + vb)) \
                + out_bytes + slots * d_x * vb + gfix
            return _roofline(flops, bytes_, vpu_peak, hw) + OP_OVERHEAD
        plan = spmm_plan(w, impl)
        if plan.case == 3:
            return float("inf")   # kernels/ops.py falls back before Pallas
        # per (matrix × panel) grid step: B panel + ELL arrays read from HBM,
        # output panel written once; gathers happen VMEM-side.
        per_step = (w.m_pad * plan.n_block * fb
                    + w.m_pad * w.k_pad
                    * ((w.itemsize + 4) if f32_path else (vb + ib)))
        bytes_ = (w.batch * plan.p * per_step + out_bytes
                  + slots * d_x * vb + gfix)
        steps = w.batch * plan.p
        return (_roofline(flops, bytes_, vpu_peak, hw)
                + steps * GRID_STEP_OVERHEAD + OP_OVERHEAD)

    if base in ("csr", "pallas_csr"):
        # The kernel's dynamic per-matrix row bound IS the max row degree —
        # one hub row serializes the whole matrix's slot loop. Price the
        # host-measured ``max_deg`` when known (the serialization bound the
        # kernel actually pays on skewed batches); fall back to ``k_pad``
        # (the same quantity, when an ELL bound was sized) and only then to
        # the uniform-degree estimate.
        row_bound = w.max_deg if w.max_deg is not None else (
            w.k_pad if w.k_pad is not None else max(
                1, -(-w.nnz_pad // w.m_pad)))
        if base == "csr":
            # segment-sum reference: ref's gather/scatter traffic + rpt
            gather = w.batch * w.nnz_pad * w.n_b * fb
            idx = w.batch * (w.nnz_pad * (8 if f32_path else 2 * ib)
                             + w.m_pad * 4)
            flops = 2.0 * w.batch * w.nnz_pad * w.n_b
            bytes_ = (gather + idx + SCATTER_PENALTY * out_bytes
                      + w.batch * w.nnz_pad * d_x * vb + gfix)
            return _roofline(flops, bytes_, vpu_peak, hw) + OP_OVERHEAD
        plan = spmm_plan(w, impl)
        if plan.case == 3:
            return float("inf")   # kernels/ops.py falls back before Pallas
        # row-split work is per REAL output row: block rows past n_dst have
        # rlen 0 and are predicated off
        flops = 2.0 * w.batch * rows_out * row_bound * w.n_b
        # per (matrix × panel) grid step: B panel + FLAT cid/val arrays +
        # start/rlen row pointers (always int32); output panel written once.
        per_step = (w.m_pad * plan.n_block * fb
                    + w.nnz_pad * ((4 + w.itemsize) if f32_path else (ib + vb))
                    + 2 * w.m_pad * 4)
        bytes_ = (w.batch * plan.p * per_step + out_bytes
                  + w.batch * w.nnz_pad * d_x * vb + gfix)
        steps = w.batch * plan.p
        return (_roofline(flops, bytes_, vpu_peak, hw)
                + steps * GRID_STEP_OVERHEAD + OP_OVERHEAD)

    if base in ("hybrid", "pallas_hybrid"):
        # Degree-binned hybrid split (DESIGN.md §12): hub rows (deg >= dmin)
        # run as ONE MXU dense tile, the remainder runs the CSR slot loop
        # whose trip count is bounded by dmin - 1 BY CONSTRUCTION — skew
        # cannot serialize it. The price of that bound is the one-time
        # permutation (sort/rank/pointer gathers) and the slab densify,
        # charged below so ``auto`` picks hybrid only when binning amortizes
        # (i.e. when the measured ``max_deg`` actually exceeds dmin).
        plan = spmm_plan(w, impl)
        if base == "pallas_hybrid" and plan.case == 3:
            return float("inf")   # kernels/ops.py falls back before Pallas
        hp = plan_hybrid(batch=w.batch, m_pad=w.m_pad, n_b=w.n_b,
                         nnz_pad=w.nnz_pad,
                         itemsize=2 if policy == "bf16" else w.itemsize)
        # one-time costs both siblings pay: slab build+read, degree/argsort/
        # rank/pointer-permute passes
        slab_bytes = 2.0 * w.batch * hp.d_pad * w.m_pad * vb
        perm_bytes = 6.0 * w.batch * w.m_pad * 4
        n_prep = 6   # degrees, argsort, rank, pointer permutes, slab, bins
        if base == "hybrid":
            # pure-XLA sibling: the remainder is an ELL gather over a STATIC
            # k = dmin - 1 slot budget (sound because non-hub rows have
            # deg < dmin) — per-slot n_b-float gathers like the segment-sum
            # classes — plus the hub einsum on the MXU
            k_sp = min(w.m_pad, max(1, hp.dmin - 1))
            slots = w.batch * w.m_pad * k_sp
            flops_s = 2.0 * slots * w.n_b
            bytes_ = (slots * (w.n_b * fb + 8)
                      + SCATTER_PENALTY * out_bytes + slab_bytes + perm_bytes)
            t = _roofline(flops_s, bytes_, vpu_peak, hw)
            if hp.d_pad:
                flops_d = 2.0 * w.batch * hp.d_pad * w.m_pad * w.n_b
                t += flops_d / (hw.peak_flops * _mxu_eff(hp.d_pad, w.n_b))
            return t + (1 + n_prep) * OP_OVERHEAD
        if w.max_deg is not None:
            # measured skew: hubs above dmin leave the slot loop, so the
            # serialization bound drops to min(max_deg, dmin - 1)
            row_bound = min(w.max_deg, max(1, hp.dmin - 1))
        else:
            # no skew evidence — price the SAME bound as the CSR class, so
            # hybrid's strictly-positive extras (slab, permutation, MXU
            # tiles) keep it from winning on uniform-looking workloads
            row_bound = (w.k_pad if w.k_pad is not None
                         else max(1, -(-w.nnz_pad // w.m_pad)))
        flops_s = 2.0 * w.batch * rows_out * row_bound * w.n_b
        # CSR-remainder traffic + the permuted row pointers and rank vector
        per_step = (w.m_pad * plan.n_block * fb
                    + w.nnz_pad * ((4 + w.itemsize) if f32_path else (ib + vb))
                    + 4 * w.m_pad * 4)
        bytes_ = (w.batch * plan.p * per_step + out_bytes
                  + slab_bytes + perm_bytes)
        t = _roofline(flops_s, bytes_, vpu_peak, hw)
        if hp.d_pad:
            flops_d = 2.0 * w.batch * hp.d_pad * w.m_pad * w.n_b
            t += flops_d / (hw.peak_flops * _mxu_eff(hp.d_pad, plan.n_block))
        steps = w.batch * plan.p
        return (t + steps * GRID_STEP_OVERHEAD
                + (1 + n_prep) * OP_OVERHEAD)

    if base == "pallas_coo":
        plan = spmm_plan(w, impl)
        if plan.case == 3:
            return float("inf")
        chunks = -(-w.nnz_pad // _COO_CHUNK)
        # per (chunk × matrix × panel): the one-hot gather onehot(cid)·B and
        # the one-hot scatter Pᵀ·G, each a CHUNK×m_pad×n_block contraction
        flops = (2.0 * 2.0 * w.batch * plan.p * chunks * _COO_CHUNK
                 * w.m_pad * plan.n_block)
        per_step = (w.m_pad * plan.n_block * fb
                    + chunks * _COO_CHUNK * PANEL_SLOT_BYTES)
        bytes_ = (w.batch * plan.p * per_step + out_bytes
                  + w.batch * w.nnz_pad * d_x * vb + gfix)
        steps = w.batch * plan.p
        eff = _mxu_eff(w.m_pad, plan.n_block)
        return (_roofline(flops, bytes_, hw.peak_flops * eff / HIGHEST_PASSES,
                          hw)
                + steps * GRID_STEP_OVERHEAD + OP_OVERHEAD)

    if base in ("fused", "fused_hybrid"):
        # Fused graph-conv megakernel (DESIGN.md §7): per (matrix × panel)
        # grid step, `channels` MXU feature transforms + one-hot gather and
        # scatter SpMMs accumulate into one VMEM panel; intermediates never
        # touch HBM and the nnz loop is skew-aware (mean chunks, not padded
        # max). Every product runs at HIGHEST precision.
        if w.channels is None or w.n_in is None:
            return float("inf")   # not a layer workload — fused can't run
        itemsize = 2 if policy == "bf16" else w.itemsize
        plan = plan_fused_graph_conv(
            batch=w.batch, m_pad=w.m_pad, n_in=w.n_in, n_out=w.n_b,
            channels=w.channels, nnz_pad=w.nnz_pad, itemsize=itemsize,
            hybrid=base == "fused_hybrid")
        if plan.case == 3:
            return float("inf")
        nnz_eff = w.nnz_avg if w.nnz_avg is not None else w.nnz_pad
        steps = w.batch * plan.p
        extra = 0.0
        if base == "fused_hybrid":
            # hybrid fold-in (DESIGN.md §12): hub rows leave the one-hot
            # chunk loop for a per-channel dense slab dot; the split pays
            # the one-time permutation + slab densify, and the kernel's
            # epilogue an (m_pad × m_pad) inverse-permutation product. Only
            # a measured ``max_deg`` past the hub threshold shrinks the
            # chunk count, so without skew metadata fused_hybrid prices
            # >= fused and ``auto`` keeps the plain megakernel.
            hp = plan_hybrid(batch=w.batch, m_pad=w.m_pad, n_b=w.n_b,
                             nnz_pad=w.channels * w.nnz_pad,
                             itemsize=itemsize)
            md = w.max_deg if w.max_deg is not None else 0
            if md >= hp.dmin:
                nnz_eff = max(0, nnz_eff - (-(-md // w.channels)))
            flops_d = (2.0 * steps * w.channels * hp.d_pad
                       * w.m_pad * plan.n_block)
            flops_perm = 2.0 * steps * w.m_pad * w.m_pad * plan.n_block
            slab_bytes = 2.0 * w.batch * w.channels * hp.d_pad * w.m_pad * vb
            perm_bytes = 6.0 * w.batch * w.m_pad * 4
            extra = (HIGHEST_PASSES * (
                flops_d / (hw.peak_flops
                           * _mxu_eff(max(hp.d_pad, 1), plan.n_block))
                + flops_perm / (hw.peak_flops
                                * _mxu_eff(w.m_pad, plan.n_block)))
                + (slab_bytes + perm_bytes) / hw.hbm_bw
                + 5 * OP_OVERHEAD)
        chunks = max(1, -(-nnz_eff // _COO_CHUNK))
        # per channel: the X·W transform, then per chunk the one-hot gather
        # and the one-hot scatter (two CHUNK×m_pad×n_block contractions)
        flops = (2.0 * steps * w.channels * w.m_pad * plan.n_block
                 * (w.n_in + 2 * chunks * _COO_CHUNK))
        per_step = (w.m_pad * w.n_in * fb                           # X panel
                    + w.channels * w.n_in * plan.n_block * fb       # W
                    + w.channels * chunks * _COO_CHUNK * PANEL_SLOT_BYTES)
        bytes_ = steps * per_step + out_bytes       # output written ONCE
        eff = _mxu_eff(w.m_pad, plan.n_block)
        return (_roofline(flops, bytes_, hw.peak_flops * eff / HIGHEST_PASSES,
                          hw)
                + steps * GRID_STEP_OVERHEAD + OP_OVERHEAD + extra)

    if impl in ("dense", "pallas_gemm"):
        densify = 2.0 * w.batch * w.m_pad * w.m_pad * w.itemsize  # write+read
        flops = 2.0 * w.batch * w.m_pad * w.m_pad * w.n_b
        bytes_ = densify + b_bytes + out_bytes
        eff = _mxu_eff(w.m_pad, w.n_b)
        t = _roofline(flops, bytes_, hw.peak_flops * eff, hw) + 2 * OP_OVERHEAD
        if impl == "pallas_gemm":
            plan = plan_batched_gemm(batch=w.batch, m=w.m_pad, n=w.n_b,
                                     k=w.m_pad, itemsize=w.itemsize)
            t += w.batch * plan.p * GRID_STEP_OVERHEAD
        return t

    raise ValueError(f"unknown impl {impl!r}")


def _candidates(dtype: str, allow_pallas: bool) -> list[str]:
    """The SpMM candidate ladder for a precision policy. ``dtype="f32"``
    reproduces the legacy candidate set exactly; reduced policies ADD their
    variants next to the full-precision impls (the model decides whether the
    byte savings beat f32, it is never forced).

    ``allow_pallas`` is the compiled TPU posture, so it admits only the
    Pallas kernels that compile there: ``pallas_ell``, ``pallas_csr``,
    ``pallas_hybrid`` and their bf16/i8 variants are left out for the
    reasons in :data:`TPU_REFUSED` (which also leaves the i8 policy with no
    Pallas variant of its own)."""
    cands = ["ref", "ell", "csr", "hybrid", "dense", "loop"]
    if dtype in ("bf16", "i8"):
        cands += ["ell_bf16", "csr_bf16"]
    if allow_pallas:
        cands += ["pallas_coo", "pallas_gemm"]
        if dtype in ("bf16", "i8"):
            cands += ["pallas_coo_bf16"]
    return cands


@functools.lru_cache(maxsize=4096)
def rank(w: Workload, *, allow_pallas: bool = True,
         hw: HW = HW()) -> tuple[tuple[str, float], ...]:
    """All runnable impls for ``w``, cheapest-first, as (impl, est-seconds).

    ``allow_pallas=False`` (the CPU/interpret posture — Pallas interpret mode
    is a Python emulator, never a performance path) restricts candidates to
    the XLA-lowered impls. ``w.dtype`` widens the ladder with the matching
    reduced-precision variants (DESIGN.md §10).
    """
    cands = _candidates(w.dtype, allow_pallas)
    if w.is_gspmm:
        # op × reduce × edge-feature workloads only admit the g-SpMM-capable
        # impls (DESIGN.md §11): the GEMM class IS the (mul, sum) product,
        # and the precision variants are (mul, sum)-only. A compiled ladder
        # also drops what the TPU refuses for this reduce (pallas_coo's max)
        cands = [c for c in cands if supports_gspmm(c)
                 and not (allow_pallas and tpu_refusal(c, w.reduce))]
    scored = [(i, estimate(w, i, hw)) for i in cands]
    scored = [(i, t) for i, t in scored if t != float("inf")]
    return tuple(sorted(scored, key=lambda it: it[1]))


def estimate_layer(w: Workload, impl: str, hw: HW = HW()) -> float:
    """Estimated seconds for one WHOLE graph-conv layer (Fig. 7) on a
    channels-aware workload: ``Y = Σ_ch A_ch·(X·W_ch + b_ch)``.

    - ``impl="fused"``: the megakernel — one device op, no HBM intermediates
      (priced by :func:`estimate`).
    - any SpMM impl: the stacked fallback path — ONE (channels·batch) batched
      SpMM call plus the dense feature-transform (MXU matmul, U written to
      and re-read from HBM) and the channel sum, as separate XLA ops.
    """
    if w.channels is None or w.n_in is None:
        raise ValueError(f"not a layer workload (channels/n_in unset): {w}")
    if precision_of(impl)[0].startswith("fused"):
        return estimate(w, impl, hw)
    stacked = dataclasses.replace(w, batch=w.batch * w.channels,
                                  channels=None, n_in=None, nnz_avg=None)
    t_spmm = estimate(stacked, impl, hw)
    if t_spmm == float("inf"):
        return t_spmm
    ch, b = w.channels, w.batch
    u_bytes = ch * b * w.m_pad * w.n_b * w.itemsize     # the HBM intermediate
    x_bytes = b * w.m_pad * (w.n_in or 0) * w.itemsize
    out_bytes = b * w.m_pad * w.n_b * w.itemsize
    # MatMul+Add: read X (once; XLA keeps it hot across channels is optimistic
    # — charge one read per layer), write U once per channel.
    mm_flops = 2.0 * ch * b * w.m_pad * (w.n_in or 0) * w.n_b
    t_mm = _roofline(mm_flops, x_bytes + u_bytes,
                     hw.peak_flops * _mxu_eff(w.m_pad, w.n_b), hw)
    # channel sum: read the `ch` SpMM outputs, write Y.
    t_sum = _roofline(ch * b * w.m_pad * w.n_b,
                      (ch + 1) * out_bytes, hw.peak_flops / 16.0, hw)
    # op count: ch fused MatMul+Add ops + 1 stacked SpMM (inside t_spmm) +
    # 1 channel-sum op.
    return t_spmm + t_mm + t_sum + (ch + 1) * OP_OVERHEAD


@functools.lru_cache(maxsize=4096)
def rank_layer(w: Workload, *, allow_pallas: bool = True,
               hw: HW = HW()) -> tuple[tuple[str, float], ...]:
    """All runnable impls for a graph-conv LAYER workload, cheapest-first.

    Candidates are the SpMM impls of :func:`rank` (each priced as the stacked
    fallback layer) plus ``"fused"`` when Pallas is allowed — the megakernel
    is Pallas-only, so the CPU/interpret posture never selects it. Reduced
    policies add ``fused_bf16`` alongside the SpMM variants.
    """
    candidates = _candidates(w.dtype, allow_pallas)
    if allow_pallas:
        candidates += ["fused", "fused_hybrid"]
        if w.dtype in ("bf16", "i8"):
            candidates += ["fused_bf16"]
    scored = [(i, estimate_layer(w, i, hw)) for i in candidates]
    scored = [(i, t) for i, t in scored if t != float("inf")]
    return tuple(sorted(scored, key=lambda it: it[1]))
