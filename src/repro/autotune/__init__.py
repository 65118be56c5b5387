"""Adaptive dispatch + autotune for Batched SpMM (DESIGN.md §5).

Makes ``impl="auto"`` a first-class value in ``repro.core.spmm.batched_spmm``:

- :mod:`repro.autotune.cost_model` — shape-keyed analytic ranking of the
  implementations (roofline terms + dispatch overheads over the planner's
  case analysis);
- :mod:`repro.autotune.selector` — the Decision object and precedence rules
  (case-3 force → tuning-cache winner → model winner);
- :mod:`repro.autotune.cache` — persistent JSON cache of on-device
  measurements ($REPRO_TUNE_CACHE), refining the model per workload key.
"""
from repro.autotune.cache import (  # noqa: F401
    ENV_VAR,
    TuningCache,
    autotune,
    default_cache,
    measure_workload,
)
from repro.autotune.cost_model import (  # noqa: F401
    GSPMM_IMPLS,
    PRECISION_IMPLS,
    TPU_REFUSED,
    Workload,
    estimate,
    estimate_layer,
    precision_of,
    rank,
    rank_layer,
    spmm_plan,
    supports_gspmm,
    tpu_refusal,
)
from repro.autotune.selector import (  # noqa: F401
    KINDS,
    Decision,
    forced_decision,
    resolve_auto,
    select_graph_conv_impl,
    select_impl,
)

__all__ = [
    "ENV_VAR", "TuningCache", "autotune", "default_cache", "measure_workload",
    "GSPMM_IMPLS", "PRECISION_IMPLS", "Workload", "estimate",
    "estimate_layer", "precision_of", "rank", "rank_layer", "spmm_plan",
    "supports_gspmm", "KINDS", "Decision", "forced_decision", "resolve_auto",
    "select_graph_conv_impl", "select_impl",
]
