"""Shape-keyed implementation selection for ``impl="auto"`` (DESIGN.md §5).

``select_impl`` turns a :class:`~repro.autotune.cost_model.Workload` into a
:class:`Decision`: the concrete impl string ``kernels/ops.py`` should run,
plus everything needed to audit the choice (the planner case, the model's
full ranking, and whether a measured tuning-cache entry overrode the model).

Decision precedence:

1. planner case 3 (``m_pad > LARGE_M``) — forced to the per-sample ``ref``
   fallback, mirroring the guard inside ``kernels/ops.py``;
2. a measured winner from the persistent tuning cache, when one exists for
   this workload key and names a runnable candidate;
3. the analytic cost model's cheapest candidate.

The regimes the model separates (asserted by tests/test_autotune.py):

- *small-dense* (small m_pad, high nnz density) → the GEMM class: densify is
  cheap at m_pad², the MXU does the rest — the paper's §V-A observation that
  gemmBatched wins on small dense matrices;
- *large-m fallback* (m_pad > 8192, planner case 3) → ``ref``;
- *column-paneled sparse* (case 2: wide n_b split into panels, low density)
  → the ELL row-split class, the paper's headline batched kernel.
"""
from __future__ import annotations

import dataclasses

from repro.analysis.roofline import HW
from repro.autotune.cost_model import (
    PRECISION_IMPLS,
    Workload,
    precision_of,
    rank,
    rank_layer,
    spmm_plan,
)
from repro.core.batching import BatchPlan, plan_fused_graph_conv


def _layer_plan(w: Workload, impl: str) -> BatchPlan:
    """The blocking plan a layer impl runs: the fused megakernel's own plan
    for the fused class (variants block at their policy's element size), the
    stacked (channels·batch) SpMM plan otherwise."""
    base, policy = precision_of(impl)
    if base.startswith("fused"):
        return plan_fused_graph_conv(
            batch=w.batch, m_pad=w.m_pad, n_in=w.n_in or 0, n_out=w.n_b,
            channels=w.channels or 1, nnz_pad=w.nnz_pad,
            itemsize=2 if policy == "bf16" else w.itemsize,
            hybrid=base == "fused_hybrid")
    return spmm_plan(dataclasses.replace(
        w, batch=w.batch * (w.channels or 1), channels=None, n_in=None,
        nnz_avg=None), impl)

# impl string → kernel class, for tests and reporting: the class is the
# decision the paper's policy makes; pallas-vs-XLA within a class is a
# backend posture (allow_pallas), not a policy change, and a precision
# variant keeps its base impl's class (DESIGN.md §10 — precision is a
# storage policy, not an execution structure). "fused" is its own class:
# the graph-conv layer megakernel (DESIGN.md §7).
KINDS = {
    "ref": "scatter", "loop": "scatter",
    "ell": "ell", "pallas_ell": "ell",
    "csr": "csr", "pallas_csr": "csr",
    "pallas_coo": "coo",
    "hybrid": "hybrid", "pallas_hybrid": "hybrid",
    "dense": "gemm", "pallas_gemm": "gemm",
    "fused": "fused", "fused_hybrid": "fused",
}
KINDS.update({v: KINDS[base] for v, (base, _) in PRECISION_IMPLS.items()})


@dataclasses.dataclass(frozen=True)
class Decision:
    """An auditable ``impl="auto"`` resolution."""

    impl: str                       # concrete impl for kernels/ops.py
    kind: str                       # kernel class (KINDS[impl])
    case: int                       # planner case 1/2/3 for this workload
    plan: BatchPlan                 # the blocking decision behind `case`
    scores: tuple[tuple[str, float], ...]  # model ranking, cheapest first
    source: str                     # "model" | "cache" | "forced"
    reason: str                     # one-line human-readable justification
    workload: Workload | None = None  # the shape key this decision resolved
                                      # (telemetry/regret audit provenance)


def forced_decision(w: Workload, impl: str, *, note: str = "") -> Decision:
    """The Decision for a caller-pinned concrete ``impl``: no ranking, but
    the same auditable plan/case fields as a model decision. Shared by the
    local (``kernels/ops.py``) and mesh-sharded (``distributed/spmm.py``)
    resolution paths so the forced-path semantics cannot diverge. A LAYER
    workload (``channels``/``n_in`` set) reports the plan the layer impl
    actually runs — the fused megakernel's own plan, or the stacked
    (channels·batch) SpMM plan — not a bare per-channel SpMM plan."""
    if w.channels is not None and w.n_in is not None:
        plan = _layer_plan(w, impl)
    else:
        plan = spmm_plan(w, impl)
    return Decision(
        impl=impl, kind=KINDS.get(impl, impl), case=plan.case, plan=plan,
        scores=(), source="forced", workload=w,
        reason=f"caller pinned impl={impl!r}{note}")


def select_impl(
    w: Workload,
    *,
    allow_pallas: bool = True,
    cache=None,
    hw: HW = HW(),
) -> Decision:
    """Resolve ``impl="auto"`` for one workload. Pure in shapes: safe to call
    at trace time (and cached upstream via ``cost_model.rank``)."""
    scores = rank(w, allow_pallas=allow_pallas, hw=hw)
    if spmm_plan(w).case == 3:          # case 3 depends only on m_pad
        plan = spmm_plan(w, "ref")
        return Decision(
            impl="ref", kind="scatter", case=3, plan=plan, scores=scores,
            source="forced", workload=w,
            reason=(f"m_pad={w.m_pad} > LARGE_M: paper case 3 — batching "
                    "does not pay, per-sample scatter-add fallback"),
        )
    allowed = {i for i, _ in scores}
    if cache is not None:
        measured = cache.best(w.key())
        if measured in allowed:
            plan = spmm_plan(w, measured)   # the plan this impl will run
            return Decision(
                impl=measured, kind=KINDS[measured], case=plan.case,
                plan=plan, scores=scores, source="cache", workload=w,
                reason=f"measured winner for key {w.key()} (tuning cache)",
            )
    impl, est = scores[0]
    plan = spmm_plan(w, impl)
    runner_up = f"; runner-up {scores[1][0]} @ {scores[1][1]:.2e}s" \
        if len(scores) > 1 else ""
    return Decision(
        impl=impl, kind=KINDS[impl], case=plan.case, plan=plan,
        scores=scores, source="model", workload=w,
        reason=f"cost model: {impl} @ {est:.2e}s (case {plan.case}, "
               f"p={plan.p}){runner_up}",
    )


def select_graph_conv_impl(
    w: Workload,
    *,
    allow_pallas: bool = True,
    cache=None,
    hw: HW = HW(),
) -> Decision:
    """Resolve ``impl="auto"`` for one graph-conv LAYER workload
    (``w.channels``/``w.n_in`` set): the candidates are every SpMM impl
    priced as the stacked fallback layer plus the fused megakernel
    (``cost_model.rank_layer``). Same precedence as :func:`select_impl`:
    case-3 force → measured tuning-cache winner → model winner."""
    if w.channels is None or w.n_in is None:
        raise ValueError(f"not a layer workload (channels/n_in unset): {w}")
    scores = rank_layer(w, allow_pallas=allow_pallas, hw=hw)
    if spmm_plan(w).case == 3:          # case 3 depends only on m_pad
        plan = spmm_plan(w, "ref")
        return Decision(
            impl="ref", kind="scatter", case=3, plan=plan, scores=scores,
            source="forced", workload=w,
            reason=(f"m_pad={w.m_pad} > LARGE_M: paper case 3 — neither "
                    "batching nor fusion pays, per-sample scatter-add "
                    "fallback"),
        )
    allowed = {i for i, _ in scores}
    if cache is not None:
        measured = cache.best(w.key())
        if measured in allowed:
            plan = _layer_plan(w, measured)
            return Decision(
                impl=measured, kind=KINDS[measured], case=plan.case,
                plan=plan, scores=scores, source="cache", workload=w,
                reason=f"measured winner for key {w.key()} (tuning cache)",
            )
    impl, est = scores[0]
    plan = _layer_plan(w, impl)
    runner_up = f"; runner-up {scores[1][0]} @ {scores[1][1]:.2e}s" \
        if len(scores) > 1 else ""
    return Decision(
        impl=impl, kind=KINDS[impl], case=plan.case, plan=plan,
        scores=scores, source="model", workload=w,
        reason=f"layer cost model: {impl} @ {est:.2e}s "
               f"(channels={w.channels}, case {plan.case}){runner_up}",
    )


def resolve_auto(
    *,
    batch: int,
    m_pad: int,
    nnz_pad: int,
    k_pad: int | None,
    n_b: int,
    itemsize: int,
    interpret: bool = True,
    cache=None,
    dtype: str = "f32",
) -> Decision:
    """Entry point used by ``kernels/ops.py``: build the Workload from the
    static shapes of one ``batched_spmm`` call and select.

    ``interpret=True`` (the CPU posture) disables Pallas candidates — in
    interpret mode they are Python emulation, correct but never fastest.
    ``dtype`` is the caller's precision policy: ``"bf16"``/``"i8"`` admit
    the matching reduced-precision variants to the ranking.
    """
    if cache is None:
        from repro.autotune.cache import default_cache
        cache = default_cache()
    w = Workload(batch=batch, m_pad=m_pad, nnz_pad=nnz_pad, k_pad=k_pad,
                 n_b=n_b, itemsize=itemsize, dtype=dtype)
    return select_impl(w, allow_pallas=not interpret, cache=cache)
