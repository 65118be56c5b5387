#!/usr/bin/env python3
"""Where the host stalls inside a cell's window: garbage collections and
stretches in which the main thread held the interpreter or stood still.

    python3 chipbench/tools/stalls.py --workload tox21.serve.poisson \\
        --seeds 11,12 --seconds 20 [--min-ms 20]

For each seed, in one process, builds the cell's driver, then runs one
window (no output check) with two recorders on:

- ``gc.callbacks``: every collection's generation, start and length;
- a sampler thread that reads the main thread's stack every
  ``SAMPLE_S`` seconds. A stretch of at least ``--min-ms`` with no sample
  at all means the main thread held the interpreter lock throughout (a
  collection or a long call that keeps the lock); one whose samples all
  show the same stack means it stood in that one place (a scheduler
  sleeping until its next arrival or flush is left out).

Prints, per seed, the collections per generation with their total and
longest pause, each stretch of at least ``--min-ms`` with the collections
inside it and the stack at its start, and, for a cell that serves, the
waves whose service took at least ``--min-ms``.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import threading
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parents[2]
SAMPLE_S = 0.002
STACK_DEPTH = 8


class Recorder:
    """Collections and main-thread stack samples, on ``time.monotonic``."""

    def __init__(self):
        self.collections: list[tuple[int, float, float]] = []
        self.samples: list[tuple[float, tuple]] = []
        self._gc_start: tuple[int, float] | None = None
        self._main = threading.main_thread().ident
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._sample, daemon=True)

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.monotonic()
        if phase == "start":
            self._gc_start = (info["generation"], now)
        elif self._gc_start is not None:
            gen, t0 = self._gc_start
            self.collections.append((gen, t0, now - t0))
            self._gc_start = None

    def _sample(self) -> None:
        while not self._stop.is_set():
            frame = sys._current_frames().get(self._main)
            stack = () if frame is None else tuple(
                f"{pathlib.Path(s.filename).name}:{s.lineno} {s.name}"
                for s in traceback.extract_stack(frame)[-STACK_DEPTH:])
            self.samples.append((time.monotonic(), stack))
            time.sleep(SAMPLE_S)

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)

    def stretches(self, min_s: float) -> list[dict]:
        """Stretches of at least ``min_s`` with no sample, or with one
        stack in every sample (other than a scheduler's wait for work)."""
        out, s = [], self.samples
        i = 0
        while i + 1 < len(s):
            j = i
            while j + 1 < len(s) and s[j + 1][1] == s[i][1]:
                j += 1
            idle = s[i][1] and s[i][1][-1].endswith(" sleep_until")
            if j > i and s[j][0] - s[i][0] >= min_s and not idle:
                out.append({"kind": "same stack", "t0": s[i][0],
                            "t1": s[j][0], "stack": s[i][1]})
            if j + 1 < len(s) and s[j + 1][0] - s[j][0] >= min_s:
                out.append({"kind": "no sample", "t0": s[j][0],
                            "t1": s[j + 1][0], "stack": s[j][1]})
            i = j + 1
        return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--min-ms", type=float, default=20.0)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from chipbench import run as harness
    from chipbench.peaks import PEAKS
    from repro.compile_cache import enable_compile_cache

    cell = harness.resolve_cell(
        harness.load_json(ROOT / "BENCHMARK.json"), args.workload)
    devices = harness.device_check(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["precision"]["matmul"])
    driver = harness.load_module(harness.HERE / "drivers"
                                 / f"{cell.spec['kind']}.py")
    min_s = args.min_ms / 1e3
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(cell=cell, seed=seed, seconds=args.seconds,
                          trace=False, peak=PEAKS[devices[0].device_kind])
        state = driver.setup(run)
        gc.collect()
        gc.freeze()
        with Recorder() as rec:
            win = driver.window(state, run)
        t0, t1 = win["t_start"], win["t_end"]
        cols = [c for c in rec.collections if t0 <= c[1] <= t1]
        per_gen = {}
        for gen, _, dur in cols:
            n, tot, top = per_gen.get(gen, (0, 0.0, 0.0))
            per_gen[gen] = (n + 1, tot + dur, max(top, dur))
        print("STALLS " + json.dumps({
            "workload": cell.name, "seed": seed, "window_s": t1 - t0,
            "samples": len(rec.samples),
            "gc": {str(g): {"count": n, "total_ms": tot * 1e3,
                            "longest_ms": top * 1e3}
                   for g, (n, tot, top) in sorted(per_gen.items())},
            "gc_frozen": gc.get_freeze_count(),
            "gc_tracked_after": len(gc.get_objects())}), flush=True)
        for st in rec.stretches(min_s):
            if not t0 <= st["t0"] <= t1:
                continue
            inside = [(g, round((a - t0) * 1e3, 3), round(d * 1e3, 3))
                      for g, a, d in cols
                      if st["t0"] - 1e-3 <= a <= st["t1"] + 1e-3]
            print("STRETCH " + json.dumps({
                "kind": st["kind"], "at_ms": (st["t0"] - t0) * 1e3,
                "ms": (st["t1"] - st["t0"]) * 1e3,
                "gc_inside (gen, at_ms, ms)": inside,
                "stack": list(st["stack"])}), flush=True)
        sched = state.get("sched")
        if sched is not None:
            for w in sched.metrics.waves:
                if w.service_time >= min_s and t0 <= w.dispatch <= t1:
                    inside = [(g, round(d * 1e3, 3)) for g, a, d in cols
                              if w.dispatch <= a <= w.dispatch
                              + w.service_time]
                    print("WAVE " + json.dumps({
                        "tier": w.tier_key, "at_ms": (w.dispatch - t0) * 1e3,
                        "ms": w.service_time * 1e3,
                        "gc_inside (gen, ms)": inside}), flush=True)
        gc.unfreeze()
        state.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
