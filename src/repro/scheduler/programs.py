"""Per-tier program cache: each wave geometry compiles exactly once.

Every :class:`~repro.scheduler.bucketing.GeometryTier` maps to one
:class:`~repro.serving.engine.GraphServeEngine` whose jitted apply is the
tier's compiled program — the continuous-batching analogue of the paper's
"one compiled step per epoch" static-shape discipline. The cache also
records the adaptive layer decision (``repro.autotune`` via
``engine.layer_decision()``) the tier's wave workload resolves to, so ops
can audit WHICH kernel each geometry runs without re-deriving it
(DESIGN.md §5/§8). ``compile_count`` is the invariant the metrics module
surfaces: number of programs == number of tiers actually used.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

from repro.scheduler.bucketing import GeometryTier
from repro.serving.engine import GraphServeEngine


@dataclasses.dataclass
class TierProgram:
    """One tier's executor + its audited autotune layer decision."""

    tier: GeometryTier
    engine: GraphServeEngine
    decision: object            # repro.autotune.Decision for the tier workload
    warmed: bool = False

    def warm(self) -> None:
        """Force the tier's one compilation now (empty wave: all-empty
        slots still trace and compile the full program). Idempotent —
        repeated warms don't re-execute."""
        if not self.warmed:
            self.engine.run_wave([])
            self.warmed = True


class ProgramCache:
    """Lazy tier → TierProgram map; ``factory(tier)`` builds the engine."""

    def __init__(self, factory: Callable[[GeometryTier], GraphServeEngine]):
        self._factory = factory
        self._programs: dict[GeometryTier, TierProgram] = {}

    def get(self, tier: GeometryTier) -> TierProgram:
        prog = self._programs.get(tier)
        if prog is None:
            engine = self._factory(tier)
            prog = TierProgram(tier=tier, engine=engine,
                               decision=engine.layer_decision())
            self._programs[tier] = prog
        return prog

    @property
    def compile_count(self) -> int:
        """Distinct compiled wave programs — equals the number of geometry
        tiers that have served (or been warmed)."""
        return len(self._programs)

    def tiers(self) -> tuple[GeometryTier, ...]:
        return tuple(sorted(self._programs))

    def decisions(self) -> dict[str, object]:
        """tier key → autotune Decision, for audit/metrics."""
        return {t.key: p.decision for t, p in self._programs.items()}

    def jit_cache_sizes(self) -> dict[str, int]:
        """tier key → entries in the tier engine's jit cache. The one-
        compilation-per-tier invariant holds iff every value is 1."""
        return {t.key: p.engine.compiled_programs()
                for t, p in self._programs.items()}
