"""Garbage-collector pauses on the profiler's clock (DESIGN.md §13).

:func:`install` adds one ``gc.callbacks`` hook (``repro.observability``
installs it on import). Each collection runs inside a ``host/gc`` profiler
annotation whose ``gen`` argument is the generation collected, and adds to
two process counters on the registry: ``process_gc_pause_seconds_total`` and
``process_gc_collections_total``, labelled ``gen``. The counters' series are
bound at install, so apart from the annotation a collection makes no object
in the hook.
"""
from __future__ import annotations

import gc
import time

SPAN = "host/gc"
GENERATIONS = (0, 1, 2)


class _Hook:
    """The callback: the annotation of the running collection, its start,
    and the counter cells per generation."""

    __slots__ = ("annotation", "pauses", "counts", "open", "t0")

    def __init__(self, annotation, pauses, counts):
        self.annotation, self.pauses, self.counts = annotation, pauses, counts
        self.open, self.t0 = None, 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.open = self.annotation(SPAN, gen=info["generation"])
            self.open.__enter__()
            self.t0 = time.perf_counter()
        elif self.open is not None:
            gen = info["generation"]
            self.pauses[gen][0] += time.perf_counter() - self.t0
            self.counts[gen][0] += 1
            self.open.__exit__(None, None, None)
            self.open = None


def installed() -> _Hook | None:
    """The hook in ``gc.callbacks``, if one is there (also one made by an
    earlier import of this module)."""
    return next((cb for cb in gc.callbacks
                 if type(cb).__name__ == "_Hook"
                 and type(cb).__module__ == __name__), None)


def install(registry=None) -> _Hook:
    """Add the hook to ``gc.callbacks`` once per process; returns it."""
    hook = installed()
    if hook is not None:
        return hook
    from jax.profiler import TraceAnnotation

    from repro.observability.metrics import default_registry

    registry = registry if registry is not None else default_registry()
    pause = registry.counter("process_gc_pause_seconds_total",
                             "seconds inside garbage collections")
    count = registry.counter("process_gc_collections_total",
                             "garbage collections run")
    hook = _Hook(TraceAnnotation,
                 {g: pause.cell(gen=str(g)) for g in GENERATIONS},
                 {g: count.cell(gen=str(g)) for g in GENERATIONS})
    gc.callbacks.append(hook)
    return hook
