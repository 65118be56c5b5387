#!/usr/bin/env python3
"""The finer spans and scopes of a cell's traced window (``trace_detail``).

    python3 chipbench/tools/detail.py --workload <cell> --seeds 11,12,13 \\
        [--seconds 5] [--out .chipbench_out/detail]

For each seed, in one process, builds the cell's driver and runs one
window of at most ``run.TRACE_SECONDS`` under the profiler, as a
``--trace 1`` run does (no output check). A training cell's step is
compiled once more to read its HLO text, which maps the step's device
operations to their name scopes. Prints one ``DETAIL`` JSON line per seed,
and appends it to ``<out>/<cell>.jsonl``:

- ``window``: the end-to-end numbers of the traced window (the profiler
  on);
- ``metrics``: ``iter_ms`` (median ``train/iter``), ``conv_ms`` (device ms
  of the step's operations under ``conv*`` scopes per ``train/iter``),
  ``assemble_ms``, ``dispatch_ms`` and ``fetch_ms`` (medians of the wave's
  parts), ``wave_ms`` (median ``serve/wave``) and ``gc_max_ms`` (the
  longest ``host/gc`` span, 0 if none). A number whose span or scope the
  program does not make is ``null``;
- ``spans``: count, median, 90th percentile, longest and total ms of each
  program and detail span in the window;
- ``idle_by_span``: device 0's idle seconds by the innermost open span;
- ``scopes``: the step's device seconds per scope, ``step_device_s`` all
  of the step's, and ``top_step_ops`` its longest operations with their
  ``op_name`` (training cells).
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import shutil
import statistics
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
STEP = "jit_step"


def step_hlo(state) -> str:
    """The compiled HLO text of the training step the driver's trainer
    runs."""
    trainer = state["trainer"]
    batch = state["stream"][state["first"][0]]
    params, opt_state = trainer.init_state()
    adj = [(a.row_ids, a.col_ids, a.values, a.nnz, a.n_rows)
           for a in batch["adj"]]
    return trainer._step.lower(params, opt_state, adj, batch["x"],
                               batch["n_nodes"], batch["labels"]
                               ).compile().as_text()


def span_stats(durations: list[float]) -> dict:
    ms = sorted(d * 1e3 for d in durations)
    return {"count": len(ms), "median_ms": statistics.median(ms),
            "p90_ms": ms[min(len(ms) - 1, int(0.9 * len(ms)))],
            "max_ms": ms[-1], "total_ms": sum(ms)}


def gc_spans_on() -> bool:
    """Whether the program opens a ``host/gc`` span per collection."""
    return any(type(cb).__module__ == "repro.observability.collector"
               for cb in gc.callbacks)


def reduce(path: str, hlo: str) -> dict:
    """The ``DETAIL`` numbers of one trace; ``hlo`` is the training step's
    compiled HLO text (empty for a serving cell)."""
    from chipbench import trace_detail, trace_reduce

    trace = trace_detail.load(path)
    base = trace_reduce.summarize(trace)
    detail = trace_detail.detail_spans(trace)
    spans = dict(base["spans"], **detail)

    def median_ms(name):
        d = spans.get(name)
        return 1e3 * statistics.median(d) if d else None

    op_names = trace_detail.hlo_op_names(hlo)
    scopes = trace_detail.hlo_scopes(hlo)
    by_scope = trace_detail.scope_device_s(trace, STEP, scopes)
    conv_s = sum(v for k, v in by_scope.items() if k.startswith("conv"))
    lo, hi = trace_reduce.window_of(trace)
    step_s = trace_reduce.NS * sum(
        min(e, hi) - max(s, lo) for d in trace.ops
        for s, e, label in trace_reduce.label_ops(trace.ops[d],
                                                  trace.modules[d])
        if label.startswith(STEP + "/") and e > lo and s < hi)
    iters = len(detail.get("train/iter", ()))
    gcs = detail.get("host/gc", ())
    return {
        "window_s": base["window_s"], "busy_s": base["busy_s"],
        "idle_share": base["idle_share"],
        "metrics": {
            "iter_ms": median_ms("train/iter"),
            "conv_ms": 1e3 * conv_s / iters if conv_s and iters else None,
            "assemble_ms": median_ms("serve/assemble"),
            "dispatch_ms": median_ms("serve/dispatch"),
            "fetch_ms": median_ms("serve/fetch"),
            "wave_ms": median_ms("serve/wave"),
            "gc_max_ms": (1e3 * max(gcs, default=0.0)
                          if gc_spans_on() else None)},
        "conv_layers": sorted({k.split("/")[0] for k in by_scope
                               if k.startswith("conv")}),
        "step_device_s": step_s,
        "spans": {n: span_stats(d) for n, d in sorted(spans.items())},
        "idle_by_span": dict(sorted(
            trace_detail.idle_by_span(trace).items(), key=lambda kv: -kv[1])),
        "scopes": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
        "top_step_ops": [[label, sec, op_names.get(label.split("/", 1)[1])]
                         for label, sec in base["device_ops"]
                         if label.startswith(STEP + "/")],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=".chipbench_out/detail")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from chipbench import run as harness
    from chipbench import trace_reduce
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
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(cell=cell, seed=seed,
                          seconds=min(args.seconds, harness.TRACE_SECONDS),
                          trace=True, peak=PEAKS[devices[0].device_kind])
        state = driver.setup(run)
        hlo = step_hlo(state) if "trainer" in state else ""
        gc.collect()
        gc.freeze()
        tmp = tempfile.mkdtemp(prefix="chipbench-detail-")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
            try:
                win = driver.window(state, run)
                run.close_window()
            finally:
                jax.profiler.stop_trace()
            line = {"workload": cell.name, "seed": seed,
                    "window": win["metrics"],
                    **reduce(trace_reduce.find_xplane(tmp), hlo)}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        with open(out / f"{cell.name}.jsonl", "a") as f:
            f.write(json.dumps(line) + "\n")
        print("DETAIL " + json.dumps(line), flush=True)
        gc.unfreeze()
        state.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
