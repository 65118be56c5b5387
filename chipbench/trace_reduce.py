"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device work is read from each ``/device:TPU:<n>`` plane: its ``XLA Ops``
line gives every operation's interval, its ``XLA Modules`` line every
program run. Host spans are ``jax.profiler.TraceAnnotation`` events on the
``/host:CPU`` plane. The device's clock runs a millisecond or two off the
host's, so device times are moved by the skew that the host's enqueue and
completion events of each program run bound. The harness marks the
measured window with a ``chipbench/window`` span.

- busy: the union of operation intervals inside the window, per device,
  averaged over the devices; the idle share is 1 minus busy over the
  window;
- top device operations: seconds per operation inside the window, named
  ``<program>/<instruction>`` as the trace names them;
- idle gaps: the holes in device 0's busy union inside the window, each
  labelled with the innermost program span open at its middle, or
  ``none``;
- device time of named programs: summed ``XLA Modules`` durations of the
  programs whose name starts with a given prefix, anywhere in the trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

WINDOW = "chipbench/window"
PROGRAM_SPANS = ("train/step", "serve/wave", "sched/wave")
HOST_SPANS = (WINDOW, "chipbench/probe") + PROGRAM_SPANS
NS = 1e-9


@dataclasses.dataclass
class Trace:
    """Intervals in ns: ``ops[d]``/``modules[d]`` are ``(start, end, name)``
    per device ``d``; ``host`` is ``(start, end, name)`` of the spans in
    ``HOST_SPANS``."""

    ops: dict[str, list[tuple[float, float, str]]]
    modules: dict[str, list[tuple[float, float, str]]]
    host: list[tuple[float, float, str]]


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: str) -> Trace:
    """Read a trace; device intervals are moved onto the host's clock
    (``clock_skew``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    runs: dict[int, tuple[float, float]] = {}
    enqueue: dict[int, float] = {}
    complete: dict[int, float] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = sorted(
                        (e.start_ns, e.end_ns, e.name) for e in line.events)
                elif line.name == "XLA Modules":
                    modules[plane.name] = []
                    for e in line.events:
                        modules[plane.name].append((e.start_ns, e.end_ns,
                                                    e.name))
                        run = dict(e.stats).get("run_id")
                        if run is not None:
                            runs[run] = (e.start_ns, e.end_ns)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.start_ns, e.end_ns, e.name))
                    elif e.name in ("DoEnqueueProgram", "CompleteCallbacks"):
                        run = dict(e.stats).get("run_id")
                        into = enqueue if e.name == "DoEnqueueProgram" \
                            else complete
                        if run is not None:
                            into.setdefault(run, e.start_ns)
    skew = clock_skew(runs, enqueue, complete)

    def moved(events):
        return sorted((s + skew, e + skew, n) for s, e, n in events)

    ops = {d: moved(v) for d, v in ops.items()}
    modules = {d: moved(modules.get(d, [])) for d in ops}
    return Trace(ops=ops, modules=modules, host=sorted(host))


def clock_skew(runs, enqueue, complete) -> float:
    """ns to add to device times to put them on the host's clock. A program
    run cannot start on the device before the host enqueued it, nor end
    after the host's completion callback for it: the skew is the middle of
    the range those bounds leave (the lower bound where they cross)."""
    lo = max((enqueue[r] - s for r, (s, _) in runs.items() if r in enqueue),
             default=None)
    hi = min((complete[r] - e for r, (_, e) in runs.items()
              if r in complete), default=None)
    if lo is None or hi is None:
        return lo if hi is None and lo is not None else (hi or 0.0)
    return (lo + hi) / 2 if lo <= hi else lo


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """The holes of a merged interval list inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_label(module: str, op: str) -> str:
    """``<program>/<instruction>`` from the trace's names: the program name
    without its fingerprint, the HLO instruction without its text."""
    prog = module.split("(", 1)[0] if module else "?"
    inst = op.split(" = ", 1)[0].lstrip("%").strip()
    return f"{prog}/{inst}"


def label_ops(ops, modules):
    """``(start, end, label)`` of each op, labelled by its enclosing
    program run."""
    starts = [m[0] for m in modules]
    out = []
    for s, e, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = modules[i][2] if i >= 0 and modules[i][1] >= s else ""
        out.append((s, e, op_label(mod, name)))
    return out


def window_of(trace: Trace, name: str = WINDOW) -> tuple[float, float]:
    spans = [(s, e) for s, e, n in trace.host if n == name]
    if not spans:
        raise ValueError(f"no {name!r} span in the trace")
    return spans[0]


def span_at(host, t: float, names=PROGRAM_SPANS) -> str:
    """The innermost (shortest) span of ``names`` open at ``t``."""
    open_ = [(e - s, n) for s, e, n in host if n in names and s <= t < e]
    return min(open_)[1] if open_ else "none"


def summarize(trace: Trace, *, top: int = 10) -> dict:
    """Window length, busy seconds (mean over devices), idle share, the top
    device operations and the longest labelled idle gaps, and the host
    durations of the program spans inside the window."""
    lo, hi = window_of(trace)
    devices = sorted(trace.ops)
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    busy_by_dev = {d: union(trace.ops[d], lo, hi) for d in devices}
    busy_ns = sum(sum(e - s for s, e in b) for b in busy_by_dev.values()) \
        / len(devices)
    per_op: dict[str, float] = {}
    for d in devices:
        for s, e, label in label_ops(trace.ops[d], trace.modules[d]):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                per_op[label] = per_op.get(label, 0.0) + (e - s) * NS
    holes = sorted(gaps(busy_by_dev[devices[0]], lo, hi),
                   key=lambda g: g[0] - g[1])[:top]
    spans: dict[str, list[float]] = {}
    for s, e, n in trace.host:
        if n in PROGRAM_SPANS and lo <= s and e <= hi:
            spans.setdefault(n, []).append((e - s) * NS)
    window_s = (hi - lo) * NS
    return {
        "window_s": window_s,
        "busy_s": busy_ns * NS,
        "idle_share": 1.0 - busy_ns / (hi - lo),
        "device_ops": sorted(([k, v] for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[span_at(trace.host, (s + e) / 2), (e - s) * NS]
                      for s, e in holes],
        "spans": spans,
    }


def program_device_s(trace: Trace, prefix: str) -> float:
    """Device seconds of every run of the programs named ``prefix(...)``,
    summed over devices."""
    return sum((e - s) * NS for mods in trace.modules.values()
               for s, e, name in mods if name.startswith(prefix + "("))
