"""Batching planner — the paper's §IV-B/§IV-C resource-assignment logic,
re-derived for the TPU memory hierarchy.

Paper (P100/CUDA)                         | Here (TPU v5e/Pallas)
------------------------------------------+----------------------------------
shared memory per block: 32-64 KB         | VMEM per core: ~16 MiB usable
case 1: m_A*n_B*4 <= smem -> whole output | case 1: working set <= VMEM_TILE_BUDGET
        resident in shared memory         |         -> one grid step per matrix
case 2: column cache-blocking into p subs | case 2: split n_B into p column
        (Fig. 5-(b)/(d))                  |         panels (multiples of 128 lanes)
case 3: m_A > 8192 -> don't batch, use a  | case 3: m_pad > LARGE_M -> fall back
        large-matrix kernel               |         to the non-batched path
one thread block per (matrix x panel)     | one grid step per (matrix x panel)
subWarp = next_pow2(n_B) capped at 32     | the 128-wide lane axis covers n_B
                                          | columns; sublanes cover rows/slots

The planner is *static*: it sees only shapes (batch, m_pad, k_pad/nnz_pad,
n_B, dtype bytes) and emits a BatchPlan that the kernels, the reference path
and the benchmarks all share — so "batched vs non-batched" comparisons use
identical blocking decisions.
"""
from __future__ import annotations

import dataclasses
import math

# TPU constants: ~16 MiB VMEM per TensorCore (v5e), with a conservative
# per-step budget because Pallas double-buffers every block for pipelining.
VMEM_BYTES = 16 * 1024 * 1024
VMEM_TILE_BUDGET = 4 * 1024 * 1024  # per-grid-step working set target
LANES = 128                         # vector lane width (last dim tiling)
SUBLANES = 8                        # second-to-last dim tiling (f32)
LARGE_M = 8192                      # paper's case-3 threshold, kept verbatim
CHUNK = 128                         # COO non-zero chunk (one MXU sublane tile)
# share of VMEM_BYTES (the scoped limit) that a one-hot kernel's estimated
# step may take: Mosaic's own scratch varies with the shape (the v5e
# rehearsal measured a fused step at 16.2 MiB for m_pad 3712 but 18.5 MiB
# for the smaller 3328), so a quarter is kept free
ONEHOT_VMEM_LIMIT = 3 * VMEM_BYTES // 4


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def tile_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """VMEM bytes of a ``(rows, cols)`` block: Mosaic pads the last dim to
    ``LANES`` and the second-to-last to ``SUBLANES``."""
    return _round_up(max(rows, 1), SUBLANES) * _round_up(max(cols, 1),
                                                         LANES) * itemsize


def onehot_kernel_vmem(*, m_pad: int, n_block: int, block_bytes: int,
                       temps: int) -> int:
    """Scoped VMEM that one grid step of a one-hot gather/scatter kernel
    (the COO and fused kernels) needs on the TPU: its blocks double-buffered
    (``block_bytes`` is one copy, lane-padded — :func:`tile_bytes`),
    ``temps`` f32 ``(m_pad, n_block)`` temporaries, and the gather and
    scatter one-hot tiles. Checked against the v5e compile rehearsal: a fused
    step (4 channels, 62→64, three temporaries: accumulator, ``U_ch`` and
    the product) at m_pad 3712 is estimated at 17.3 MiB where Mosaic needs
    16.2 MiB, at 4096 at 19.0 MiB where it needs 17.9 MiB; the COO kernel
    compiles at m_pad 4608 (estimate 16.0 MiB) and not at 8192. Plans whose
    estimate exceeds :data:`ONEHOT_VMEM_LIMIT` are case 3."""
    return (2 * block_bytes + temps * tile_bytes(m_pad, n_block)
            + 2 * tile_bytes(CHUNK, m_pad))


@dataclasses.dataclass(frozen=True)
class BatchPlan:
    """Static blocking decision for one batched SpMM/GEMM call.

    ``sample_chunks`` is the skew-aware nnz packing decision for the fused
    graph-conv path: per-sample CHUNK counts (``Σ_ch ceil(nnz_ch / CHUNK)``
    — what the per-channel loop executes — batch-major), known only when
    the planner saw host-side nnz metadata. ``None`` means "bound every
    sample by the batch max" —
    the pre-skew-aware behavior. The kernels themselves always take the
    runtime per-(sample × channel) chunk-count array (trace-safe, derived
    from ``BatchedCOO.nnz``); this field is the *static, auditable* record
    of the same decision for the cost model, benchmarks and EXPERIMENTS.md.
    """

    batch: int
    m_pad: int          # padded rows per matrix (multiple of SUBLANES)
    n_b: int            # dense operand columns
    n_block: int        # column panel width (multiple of LANES, or n_b if small)
    p: int              # number of column panels = ceil(n_b / n_block)
    case: int           # 1, 2 or 3 (paper Fig. 5)
    bytes_per_step: int # VMEM working-set estimate per grid step
    sample_chunks: tuple[int, ...] | None = None  # skew-aware per-sample chunks

    @property
    def grid(self) -> tuple[int, int]:
        return (self.batch, self.p)

    @property
    def max_chunks(self) -> int:
        """Batch-max CHUNK count a skew-oblivious loop would run per sample
        (``sample_chunks`` known only)."""
        return max(self.sample_chunks) if self.sample_chunks else 0


def plan_batched_spmm(
    *,
    batch: int,
    m_pad: int,
    n_b: int,
    slots: int,
    itemsize: int = 4,
    onehot: bool = False,
) -> BatchPlan:
    """Size the column panels the way the paper sizes cache blocks.

    ``slots`` is k_pad (ELL) or nnz_pad (COO) — it contributes the index/value
    working set. The per-step working set is:

        out panel   m_pad * n_block * itemsize
        B panel     m_pad * n_block * itemsize   (same rows, same panel)
        indices     ~ 2 * slots_bytes

    ``onehot=True`` plans the COO kernel (one-hot gather and scatter), whose
    step must also fit the scoped VMEM (:func:`onehot_kernel_vmem` within
    :data:`ONEHOT_VMEM_LIMIT`); a matrix too large for that even at one
    128-lane panel is case 3.
    """
    m_pad = _round_up(max(m_pad, 1), SUBLANES)
    if m_pad > LARGE_M:
        # Paper case 3: too large to benefit from batching; callers take the
        # per-sample large-matrix path.
        return BatchPlan(batch, m_pad, n_b, n_b, 1, 3, 0)

    idx_bytes = 2 * slots * 8  # int32 ids + values, per matrix

    def fits(nb: int) -> bool:
        if not onehot:
            return True
        panels = 3 * tile_bytes(-(-slots // CHUNK), CHUNK)   # ids, values
        return onehot_kernel_vmem(
            m_pad=m_pad, n_block=nb, temps=1,
            block_bytes=panels + 2 * tile_bytes(m_pad, nb, itemsize),
        ) <= ONEHOT_VMEM_LIMIT

    n_block = _round_up(n_b, LANES) if n_b >= LANES else n_b
    while n_block > LANES:
        step = 2 * m_pad * n_block * itemsize + idx_bytes
        if step <= VMEM_TILE_BUDGET and fits(n_block):
            break
        # halve along 128-lane multiples, mirroring the paper's "divide the
        # output along the column" (Fig. 5-(b)/(d))
        n_block = _round_up(n_block // 2, LANES)
    step = 2 * m_pad * n_block * itemsize + idx_bytes
    if not fits(n_block):
        return BatchPlan(batch, m_pad, n_b, n_b, 1, 3, step)
    p = -(-n_b // n_block)
    case = 1 if p == 1 else 2
    return BatchPlan(batch, m_pad, n_b, n_block, p, case, step)


# Hybrid dispatch defaults (DESIGN.md §12): a row whose density
# ``deg / m_pad`` reaches TAU is routed to the dense MXU slab; everything
# below stays in the rpt-bounded CSR remainder, whose per-row trip count is
# then bounded by ``dmin - 1`` *by construction*. NBINS_TARGET bins the
# sorted row axis into similar-work groups so adjacent program ids get
# near-equal fori_loop trip counts.
HYBRID_TAU = 0.25
HYBRID_NBINS = 8


@dataclasses.dataclass(frozen=True)
class HybridPlan:
    """Static decision record for the degree-binned hybrid SpMM path.

    ``spmm`` carries the shared column-panel blocking (same grid as the CSR
    kernel). ``dmin`` is the hub threshold in non-zeros per row
    (``ceil(tau * m_pad)``; a row with ``deg >= dmin`` — density exactly AT
    the threshold included — is a hub). ``d_pad`` is the static height of
    the dense hub slab: since every hub holds at least ``dmin`` non-zeros,
    at most ``nnz_pad // dmin`` rows can ever classify dense, so the slab is
    provably tall enough and ``d_pad == 0`` means *no* MXU tile group is
    emitted at all (the degenerate-input guard: all-empty batches and
    ``nnz_pad < dmin`` never reach the dense dot). ``bins`` are static
    ``(start, stop)`` slices of the degree-sorted row axis, every edge a
    SUBLANES multiple so per-bin accumulators tile cleanly.
    """

    spmm: BatchPlan
    tau: float
    dmin: int           # hub threshold, in nnz per row (>= comparison)
    d_pad: int          # static dense-slab height (0 => no dense tile group)
    bins: tuple[tuple[int, int], ...]  # sorted-row-axis work bins

    @property
    def nbins(self) -> int:
        return len(self.bins)


def plan_hybrid(
    *,
    batch: int,
    m_pad: int,
    n_b: int,
    nnz_pad: int,
    itemsize: int = 4,
    tau: float = HYBRID_TAU,
    nbins: int = HYBRID_NBINS,
) -> HybridPlan:
    """Plan the degree-binned hybrid split (DESIGN.md §12).

    Static-only, like every planner here: the *threshold* and *capacity*
    are shape-derived; which rows actually classify dense is runtime data
    (``hybrid_operands`` in kernels/batched_spmm_hybrid.py).
    """
    if not 0.0 < tau <= 1.0:
        raise ValueError(f"tau must be in (0, 1], got {tau}")
    base = plan_batched_spmm(batch=batch, m_pad=m_pad, n_b=n_b,
                             slots=nnz_pad, itemsize=itemsize)
    m_pad = base.m_pad
    dmin = max(1, math.ceil(tau * m_pad))
    # slab capacity: each hub row holds >= dmin nnz, so nnz_pad // dmin
    # bounds the hub count. nnz_pad < dmin => no row can be a hub => d_pad=0
    # and the kernel never materialises a dense operand (satellite guard).
    if nnz_pad < dmin:
        d_pad = 0
    else:
        d_pad = min(m_pad, _round_up(max(1, nnz_pad // dmin), SUBLANES))
    rows_per_bin = max(SUBLANES,
                       _round_up(max(1, m_pad // max(1, nbins)), SUBLANES))
    bins = tuple((s, min(s + rows_per_bin, m_pad))
                 for s in range(0, m_pad, rows_per_bin))
    return HybridPlan(base, tau, dmin, d_pad, bins)


def chunk_counts(nnz_per_sample) -> tuple[int, ...]:
    """Skew-aware packing: the CHUNK count the fused kernel actually runs per
    sample, from host-side nnz metadata. Accepts per-sample totals (a
    sequence of ints → ``ceil(nnz / CHUNK)`` each) or per-(sample × channel)
    counts (a sequence of sequences → ``Σ_ch ceil(nnz_ch / CHUNK)``, which is
    what the per-channel loop executes — ceils do NOT commute with the
    channel sum). A zero-nnz sample runs zero chunks (the kernel writes its
    zero output unconditionally)."""

    def one(n):
        try:
            return sum(-(-int(c) // CHUNK) for c in n)
        except TypeError:
            return -(-int(n) // CHUNK)

    return tuple(one(n) for n in nnz_per_sample)


def plan_fused_graph_conv(
    *,
    batch: int,
    m_pad: int,
    n_in: int,
    n_out: int,
    channels: int,
    nnz_pad: int,
    itemsize: int = 4,
    nnz_per_sample=None,
    hybrid: bool = False,
) -> BatchPlan:
    """Blocking plan for the fused graph-conv megakernel (DESIGN.md §7).

    One grid step computes, for one (matrix × output-column panel), the whole
    layer: ``channels`` MXU products ``X·W_ch + b_ch`` immediately consumed by
    the one-hot gather and scatter SpMM, accumulated into a single
    VMEM-resident panel. The per-step working set is therefore:

        X panel      m_pad * n_in * itemsize
        W panel      channels * n_in * n_block * itemsize
        bias panel   channels * n_block * itemsize
        indices      channels * nnz_chunks * CHUNK * 12   (int32 ids, f32 values)
        acc/out      2 * m_pad * n_block * 4       (f32 accumulator + store)

    Panels are halved until that fits ``VMEM_TILE_BUDGET`` and the step's
    scoped VMEM (:func:`onehot_kernel_vmem`: double-buffered lane-padded
    blocks, the accumulator, ``U_ch`` and their product, the one-hot tiles)
    fits :data:`ONEHOT_VMEM_LIMIT`. ``hybrid=True`` plans the hybrid fold-in
    (``fused_hybrid``), whose step also holds its hub slabs (``d_pad`` from
    :func:`plan_hybrid` over the layer's whole edge budget), the rank row
    and the ``(m_pad, m_pad)`` f32 inverse-permutation tile. A step that
    does not fit even at one 128-lane panel is case 3: the megakernel is
    not offered.

    ``nnz_per_sample`` (host-side: per-sample totals, or per-(sample ×
    channel) rows for the exact sum-of-ceils — see :func:`chunk_counts`)
    makes the plan skew-aware: ``sample_chunks`` records each graph's real
    chunk count so the kernel's nnz loop — and the cost model — stop paying
    for the batch-max ``nnz_pad`` on skewed batches.
    """
    m_pad = _round_up(max(m_pad, 1), SUBLANES)
    sample_chunks = (chunk_counts(nnz_per_sample)
                     if nnz_per_sample is not None else None)
    if m_pad > LARGE_M:
        # paper case 3: matrices this large do not batch — callers fall back
        # to the unfused per-sample path, same as plan_batched_spmm.
        return BatchPlan(batch, m_pad, n_out, n_out, 1, 3, 0, sample_chunks)

    chunks_pad = max(1, -(-nnz_pad // CHUNK))
    idx_bytes = channels * chunks_pad * CHUNK * 12
    x_bytes = m_pad * n_in * itemsize
    # scoped VMEM beyond the panels: id/value panels, X, and for the hybrid
    # fold-in the hub slabs, the rank row and the inverse permutation
    fixed_blocks = (3 * channels * tile_bytes(chunks_pad, CHUNK)
                    + tile_bytes(m_pad, n_in, itemsize))
    fixed_temps = 0
    if hybrid:
        d_pad = plan_hybrid(batch=batch, m_pad=m_pad, n_b=n_out,
                            nnz_pad=channels * nnz_pad,
                            itemsize=itemsize).d_pad
        if d_pad:
            fixed_blocks += (channels * tile_bytes(d_pad, m_pad, itemsize)
                             + tile_bytes(1, m_pad))
            fixed_temps = tile_bytes(m_pad, m_pad)
    n_block = _round_up(n_out, LANES) if n_out >= LANES else n_out

    def step_bytes(nb: int) -> int:
        return (x_bytes + channels * n_in * nb * itemsize
                + channels * nb * itemsize + idx_bytes
                + 2 * m_pad * nb * 4)

    def fits(nb: int) -> bool:
        blocks = (fixed_blocks + channels * tile_bytes(n_in, nb, itemsize)
                  + tile_bytes(channels, nb, itemsize)
                  + tile_bytes(m_pad, nb, itemsize))           # + out panel
        return onehot_kernel_vmem(m_pad=m_pad, n_block=nb, temps=3,
                                  block_bytes=blocks) \
            + fixed_temps <= ONEHOT_VMEM_LIMIT

    while n_block > LANES and (step_bytes(n_block) > VMEM_TILE_BUDGET
                               or not fits(n_block)):
        # halve along 128-lane multiples — the paper's "divide the output
        # along the column" (Fig. 5-(b)/(d)) applied to the fused epilogue
        n_block = _round_up(n_block // 2, LANES)
    if not fits(n_block):
        # even the narrowest panel overflows the scoped VMEM: like case 3
        return BatchPlan(batch, m_pad, n_out, n_out, 1, 3,
                         step_bytes(n_block), sample_chunks)
    p = -(-n_out // n_block)
    case = 1 if p == 1 else 2
    return BatchPlan(batch, m_pad, n_out, n_block, p, case,
                     step_bytes(n_block), sample_chunks)


def tier_ladder(
    *,
    m_max: int,
    nnz_max: int,
    levels: int = 3,
    m_min: int = 2 * SUBLANES,
    nnz_min: int = 64,
) -> tuple[tuple[int, int], ...]:
    """Geometry ladder for the serving scheduler's bucketing policy
    (DESIGN.md §8): ``levels`` (m_pad, nnz_pad) rungs halving down from the
    dataset maxima, each rounded to the same hardware multiples the
    :class:`BatchPlan` constructors use (``SUBLANES`` rows; nnz slots to 8,
    matching ``coo_from_lists``). The top rung always covers
    (``m_max``, ``nnz_max``) so every admissible request has a bucket; lower
    rungs stop small molecules paying worst-case padding.
    """
    if levels < 1:
        raise ValueError(f"levels must be >= 1, got {levels}")
    m_top = _round_up(max(m_max, 1), SUBLANES)
    nnz_top = _round_up(max(nnz_max, 1), 8)
    ms, nnzs = [], []
    m, nz = m_top, nnz_top
    for _ in range(levels):
        ms.append(max(_round_up(m, SUBLANES), _round_up(m_min, SUBLANES)))
        nnzs.append(max(_round_up(nz, 8), _round_up(nnz_min, 8)))
        m //= 2
        nz //= 2
    rungs = sorted(set(zip(ms, nnzs)))
    return tuple(rungs)


def plan_batched_gemm(
    *, batch: int, m: int, n: int, k: int, itemsize: int = 4
) -> BatchPlan:
    """Panel plan for the densified (gemmBatched-analogue) path."""
    m_pad = _round_up(max(m, 1), SUBLANES)
    k_pad = _round_up(max(k, 1), SUBLANES)
    n_block = _round_up(n, LANES) if n >= LANES else n
    while n_block > LANES:
        step = (m_pad * n_block + k_pad * n_block + m_pad * k_pad) * itemsize
        if step <= VMEM_TILE_BUDGET:
            break
        n_block = _round_up(n_block // 2, LANES)
    step = (m_pad * n_block + k_pad * n_block + m_pad * k_pad) * itemsize
    p = -(-n // n_block)
    return BatchPlan(batch, m_pad, n, n_block, p, 1 if p == 1 else 2, step)
