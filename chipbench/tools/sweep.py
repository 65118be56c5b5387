#!/usr/bin/env python3
"""Find a serving cell's knee: the highest offered rate whose backlog does
not grow over the window.

    python3 chipbench/tools/sweep.py --workload tox21.serve.poisson \\
        --rates 2000,4000,8000 --seconds 6 [--seed 5]

For each rate, in one process, builds the cell's driver at that rate and
runs one window (no output check). A growing backlog shows as requests due
late in the window waiting longer than those due early: the sweep prints,
per rate, the requests, waves, p50 and p99 latency, the mean latency of the
first and last fifth of the requests by due time, and how long after the
last due time the last answer came. The knee is read from these once and
written into the cell's file as a number.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import numpy as np

    from chipbench import run as harness
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
    from chipbench.peaks import PEAKS

    for rate in (float(r) for r in args.rates.split(",")):
        cell.spec["arrivals"] = dict(cell.spec["arrivals"], rate_per_s=rate)
        run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace=False, peak=PEAKS[devices[0].device_kind])
        state = driver.setup(run)
        win = driver.window(state, run)
        pend = sorted(state["sched"].completed, key=lambda p: p.arrival)
        lat = np.array([p.finish - p.arrival for p in pend])
        fifth = max(1, len(lat) // 5)
        row = {"rate": rate, "requests": win["attempted"],
               "failed": win["failed"], "waves": win["counters"]["waves"],
               "p50_ms": win["counters"]["p50_ms"],
               "p99_ms": win["counters"]["p99_ms"],
               "first_fifth_ms": float(lat[:fifth].mean()) * 1e3,
               "last_fifth_ms": float(lat[-fifth:].mean()) * 1e3,
               "tail_after_last_due_s": win["t_end"] - max(
                   p.arrival for p in pend)}
        print("SWEEP " + json.dumps(row), flush=True)
        state.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
