#!/usr/bin/env python3
"""Readings of the output check over many seeds, in one process.

    python3 chipbench/tools/calibrate.py --workload <cell> \\
        --seeds 11,12,13 --seconds 3 [--extra control,half_batch] \\
        [--out .chipbench_out/calibrate]

Runs the cell as ``run.py`` does (set-up, a window of ``--seconds`` at the
cell's own load, the check) once per seed, and records for each the
program's readings and, where ``--extra`` names them, the readings of the
control (the reference in float32 at ``high``, the three-pass bfloat16
split, in the program's place) and of the faults planted in the reference
(``half_batch``: each step's loss over half the batch), each judged
against the cell's limits as the program's readings are (``correct``).
The limits in a cell's file are set from these readings (PERF.md says
how). Writes one JSON line per seed to ``<out>/<cell>.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--extra", default="")
    ap.add_argument("--out", default=".chipbench_out/calibrate")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import run as harness

    cell = harness.resolve_cell(
        harness.load_json(ROOT / "BENCHMARK.json"), args.workload)
    devices = harness.device_check(cell.chips)
    extra = tuple(e for e in args.extra.split(",") if e)
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    with open(out / f"{args.workload}.jsonl", "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            res = harness.run_cell(cell, seed, args.seconds, False,
                                   t_process=time.monotonic(),
                                   devices=devices, extra=extra)
            line = {"workload": args.workload, "seed": seed,
                    "seconds": args.seconds, "correct": res["correct"],
                    "metrics": res["metrics"],
                    "readings": {k: {n: v for n, v in r.items()
                                     if n != "_where"} | {
                                         "where": r.get("_where", {}),
                                         "correct": harness.judge(
                                             r, cell.spec["limits"])[1]}
                                 for k, r in res["_readings"].items()}}
            f.write(json.dumps(line) + "\n")
            f.flush()
            print("CALIB " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
