"""Finer reductions of a profiler trace: the spans the program opens inside
its step and its wave, and the name scopes of its compiled programs.

``trace_reduce`` loads the harness's window and probe spans and three
program spans (``train/step``, ``serve/wave``, ``sched/wave``). The program
also opens, on the same profiler clock:

- ``train/iter`` around one loop iteration of ``GCNTrainer.fit``, holding
  ``train/batch`` (fetch, guard, placement), ``train/step`` (the jitted
  step's enqueue), ``train/sync`` (the host syncs) and ``train/checkpoint``;
- ``serve/assemble``, ``serve/dispatch`` and ``serve/fetch`` inside
  ``serve/wave``, and ``sched/wait`` while the scheduler waits for an
  arrival or a flush;
- ``host/gc`` around each garbage collection.

Inside compiled programs, conv layer ``i`` is traced under the name scope
``conv<i>`` and each SpMM dispatch under ``spmm/<impl>``. The trace's device
events do not carry an operation's scope where ``jax.profiler.ProfileData``
can read it, so the scopes come from the compiled program's HLO text
(``jitted.lower(...).compile().as_text()``): each instruction's
``op_name`` metadata. A fusion takes the scope of its root, as XLA's own
metadata gives it.

- detail spans: host durations in the window per name of
  ``DETAIL_SPANS``;
- idle by span: the idle seconds of device 0 in the window, split by the
  innermost span of ``trace_reduce.PROGRAM_SPANS`` and ``DETAIL_SPANS``
  open at each instant, or ``none``;
- scope device time: device seconds in the window per scope of one
  program's operations.
"""
from __future__ import annotations

import dataclasses
import re

from chipbench import trace_reduce

DETAIL_SPANS = ("train/iter", "train/batch", "train/sync",
                "train/checkpoint", "serve/assemble", "serve/dispatch",
                "serve/fetch", "sched/wait", "host/gc")
NS = trace_reduce.NS
# a scope component of an op_name: conv<i> or spmm/<impl>, bounded by the
# name stack's separators ("jit(step)/transpose(jvp(conv0))/spmm/ref/...")
_SCOPE = re.compile(r"(?<!\w)(conv\d+|spmm/\w+)(?=[/)]|$)")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bop_name="([^"]*)"')


def detail_host(path: str) -> list[tuple[float, float, str]]:
    """``(start, end, name)`` in ns of the host spans of ``DETAIL_SPANS``."""
    from jax.profiler import ProfileData

    return sorted((e.start_ns, e.end_ns, e.name)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name == "/host:CPU" for line in plane.lines
                  for e in line.events if e.name in DETAIL_SPANS)


def load(path: str) -> trace_reduce.Trace:
    """``trace_reduce.load`` with the spans of ``DETAIL_SPANS`` added to
    ``host``."""
    trace = trace_reduce.load(path)
    return dataclasses.replace(
        trace, host=sorted(trace.host + detail_host(path)))


def detail_spans(trace: trace_reduce.Trace) -> dict[str, list[float]]:
    """Host seconds of each span of ``DETAIL_SPANS`` inside the window."""
    lo, hi = trace_reduce.window_of(trace)
    out: dict[str, list[float]] = {}
    for s, e, n in trace.host:
        if n in DETAIL_SPANS and lo <= s and e <= hi:
            out.setdefault(n, []).append((e - s) * NS)
    return out


def idle_by_span(trace: trace_reduce.Trace) -> dict[str, float]:
    """Device 0's idle seconds in the window by the innermost (shortest)
    program or detail span open, ``none`` where none is: one sweep over
    the gaps' and the spans' ends."""
    lo, hi = trace_reduce.window_of(trace)
    dev = sorted(trace.ops)[0]
    holes = trace_reduce.gaps(trace_reduce.union(trace.ops[dev], lo, hi),
                              lo, hi)
    names = trace_reduce.PROGRAM_SPANS + DETAIL_SPANS
    # (time, kind, key): kind +1/-1 opens/closes a span, +2/-2 a gap
    ev = [(t, k, (e - s, n)) for s, e, n in trace.host
          if n in names and e > lo and s < hi
          for t, k in ((max(s, lo), 1), (min(e, hi), -1))]
    ev += [(t, k, None) for s, e in holes for t, k in ((s, 2), (e, -2))]
    ev.sort(key=lambda x: (x[0], x[1]))
    open_: dict[tuple, int] = {}
    out: dict[str, float] = {}
    idle, t_prev = False, lo
    for t, kind, key in ev:
        if idle and t > t_prev:
            label = min(open_)[1] if open_ else "none"
            out[label] = out.get(label, 0.0) + (t - t_prev) * NS
        t_prev = t
        if abs(kind) == 2:
            idle = kind > 0
        elif kind > 0:
            open_[key] = open_.get(key, 0) + 1
        elif open_.get(key, 0) > 1:
            open_[key] -= 1
        else:
            open_.pop(key, None)
    return out


def hlo_op_names(text: str) -> dict[str, str]:
    """Instruction name → its ``op_name`` metadata, from a compiled
    program's HLO text."""
    return {m.group(1): m.group(2) for m in map(_INSTRUCTION.match,
                                                text.splitlines()) if m}


def hlo_scopes(text: str) -> dict[str, str]:
    """Instruction name → scope (``conv0``, ``conv0/spmm/dense``, …) from a
    compiled program's HLO text; instructions with no scope are left out."""
    out = {}
    for inst, op_name in hlo_op_names(text).items():
        found = list(dict.fromkeys(_SCOPE.findall(op_name)))
        if found:
            out[inst] = "/".join(found)
    return out


def scope_device_s(trace: trace_reduce.Trace, program: str,
                   scopes: dict[str, str]) -> dict[str, float]:
    """Device seconds in the window per scope of the operations of the
    program named ``program(...)``, summed over devices."""
    lo, hi = trace_reduce.window_of(trace)
    out: dict[str, float] = {}
    for d in sorted(trace.ops):
        for s, e, label in trace_reduce.label_ops(trace.ops[d],
                                                  trace.modules[d]):
            prog, _, inst = label.partition("/")
            scope = scopes.get(inst) if prog == program else None
            s, e = max(s, lo), min(e, hi)
            if scope and e > s:
                out[scope] = out.get(scope, 0.0) + (e - s) * NS
    return out


def summarize(trace: trace_reduce.Trace) -> dict:
    """``detail_spans`` and ``idle_by_span`` of a trace loaded by
    :func:`load`."""
    return {"detail_spans": detail_spans(trace),
            "idle_by_span": idle_by_span(trace)}
