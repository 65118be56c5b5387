#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip it is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run it from a checkout (it puts the checkout's ``src`` on the path). The
harness knows no cell by name. It finds everything from the cell's name in
``BENCHMARK.json``:

- ``chipbench/cells/<cell>.json``: the configuration, the traffic kind and
  its parameters, and the limits of the output check;
- ``chipbench/configs/<config>.json``: the model's sizes and precision;
- ``chipbench/drivers/<kind>.py``: the traffic kind. ``setup(run)``
  builds the program and warms up every shape the window uses;
  ``window(state, run)`` measures and returns the window's record,
  calling ``run.open_window()`` and ``run.close_window()`` where the
  measured window opens and closes;
  an optional ``probe(state, run)`` runs after the window inside a traced
  run; ``check(state, run, extra)`` frees the program's state and returns
  the readings of the output check (``extra`` names controls and faults
  for the calibration tool);
- ``chipbench/metrics/<metric>.py``: one per-layer metric. ``read(inputs)``
  returns its value, or ``None`` where it finds nothing to read.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a profiler trace covers the first ``TRACE_SECONDS`` of the
window and the result carries the per-layer metrics. No compilation may
happen inside the window: the run counts them and fails if there is one.
The last line of stdout is one JSON object; the numbers of the output
check, each beside its limit, are the last lines of stderr.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()    # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_SECONDS = 5.0     # longest traced window
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
EXIT_NO_CHECKOUT, EXIT_NO_CHIP, EXIT_COMPILED = 2, 3, 4


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a file by path (metric files carry dots in their names)."""
    name = "chipbench_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict              # the cell's file
    config: dict            # its configuration's file
    chips: int
    end_to_end: list[dict]  # BENCHMARK.json entries this cell reports
    per_layer: list[dict]


def resolve_cell(bench: dict, name: str, root: pathlib.Path = ROOT) -> Cell:
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    spec = load_json(root / "chipbench" / "cells" / f"{name}.json")
    if spec["config"] != entry["config"]:
        raise ValueError(f"cell {name}: file names config {spec['config']!r}"
                         f", BENCHMARK.json {entry['config']!r}")
    conf = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, spec=spec, config=load_json(root / conf["file"]),
                chips=entry["chips"], end_to_end=e2e, per_layer=per_layer)


@dataclasses.dataclass
class Run:
    """What a driver is given: the cell, the run's seed and window, and the
    chip's peaks (``None`` off the chip)."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    peak: dict | None
    log: object = log
    _span: object = None

    def open_window(self) -> None:
        """Drivers call this when the measured window opens, and
        ``close_window`` when it closes: in a traced run the two mark the
        window the trace is reduced over."""
        if self.trace and self._span is None:
            import jax

            from chipbench.trace_reduce import WINDOW

            self._span = jax.profiler.TraceAnnotation(WINDOW)
            self._span.__enter__()

    def close_window(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def gcn_config(self):
        from repro.core.gcn import GCNConfig

        g = dict(self.cell.config["gcn"])
        g["conv_widths"] = tuple(g["conv_widths"])
        return GCNConfig(**g)


@dataclasses.dataclass
class MetricInputs:
    """What a per-layer metric reads: the reduced trace (``None`` without
    one), the driver's counters, the chip's peaks and the cell."""

    trace: dict | None
    programs: dict          # program prefix -> device seconds in the trace
    counters: dict
    peak: dict | None
    chips: int
    cell: Cell


class CompileCounter:
    """Counts traces and compilations while ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.counts = dict.fromkeys(COMPILE_EVENTS, 0)
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event in self.counts:
            self.counts[event] += 1

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on)

    @property
    def total(self) -> int:
        return sum(self.counts.values())


def device_check(chips: int):
    """The chips of this run, or exit: JAX must see an accelerator whose
    kind has published peaks, and at least ``chips`` of them."""
    import jax

    from chipbench.peaks import PEAKS

    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform == "cpu" or kind not in PEAKS or len(devs) < chips:
        print(f"chipbench: needs {chips} accelerator(s) with known peaks; "
              f"JAX sees {len(devs)} x {devs[0].platform} ({kind!r})",
              file=sys.stderr, flush=True)
        sys.exit(EXIT_NO_CHIP)
    from repro.kernels import require_compiled

    try:
        require_compiled()      # and the Pallas kernels compiled, too
    except RuntimeError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        sys.exit(EXIT_NO_CHIP)
    return devs[:chips]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, devices, extra=()) -> dict:
    """Set up, measure, check; returns the result object (and, under
    ``_readings``, every reading the check took, controls included)."""
    import jax

    from chipbench import peaks, trace_reduce
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["precision"]["matmul"])
    kind = devices[0].device_kind
    run = Run(cell=cell, seed=seed, trace=trace,
              seconds=min(seconds, TRACE_SECONDS) if trace else seconds,
              peak=peaks.PEAKS.get(kind))
    log(f"cell {cell.name}: seed {seed}, window {run.seconds} s, trace "
        f"{int(trace)}, {len(devices)} x {kind}, compile cache {cache}")
    driver = load_module(HERE / "drivers" / f"{cell.spec['kind']}.py")
    state = driver.setup(run)
    # what set-up made (the pool, every request of the window) is never
    # freed before the window ends: keep the collector from scanning it
    gc.collect()
    gc.freeze()
    log(f"set-up objects kept out of garbage collection: "
        f"{gc.get_freeze_count()}")

    counter = CompileCounter()
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            counter.active = True
            win = driver.window(state, run)
            run.close_window()
            counter.active = False
            if trace and hasattr(driver, "probe"):
                with jax.profiler.TraceAnnotation("chipbench/probe"):
                    driver.probe(state, run)
        finally:
            counter.active = False
            if trace:
                jax.profiler.stop_trace()
        counter.close()
        log(f"compilations inside the window: {counter.total} "
            f"({counter.counts})")
        if counter.total:
            print(f"chipbench: {counter.total} compilation(s) inside the "
                  "window", file=sys.stderr, flush=True)
            sys.exit(EXIT_COMPILED)

        stats = [d.memory_stats() or {} for d in devices]
        mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        gc.unfreeze()
        readings = driver.check(state, run, extra)
        del state

        metrics, device = {}, {
            "platform": devices[0].platform, "kind": kind,
            "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
        result = {"correct": None, "attempted": win["attempted"],
                  "failed": win["failed"]}
        if trace:
            tr = trace_reduce.load(trace_reduce.find_xplane(tmp))
            summary = trace_reduce.summarize(tr)
            inputs = MetricInputs(
                trace=summary, counters=win["counters"], peak=run.peak,
                chips=len(devices), cell=cell,
                programs={p: trace_reduce.program_device_s(tr, p)
                          for p in win["counters"].get("programs", ())})
            for m in cell.per_layer:
                reader = load_module(HERE / "metrics" / f"{m['name']}.py")
                value = reader.read(inputs)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device.update(busy_s=summary["busy_s"],
                          window_s=summary["window_s"])
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
            log(f"trace: window {summary['window_s']} s, busy "
                f"{summary['busy_s']} s, idle share {summary['idle_share']}")
        else:
            values = dict(win["metrics"],
                          setup_s=win["t_start"] - t_process)
            for m in cell.end_to_end:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)

    checks, ok = judge(readings["program"], cell.spec["limits"])
    result["correct"] = win["failed"] == 0 and ok
    result.update(metrics=metrics, device=device, checks=checks,
                  _readings=readings)
    return result


def judge(readings: dict, limits: dict) -> tuple[dict, bool]:
    """Each number of the output check beside its limit, and whether every
    one is finite and within it (a number missing from ``readings`` fails)."""
    checks = {k: {"value": readings.get(k, math.inf), "limit": limits[k]}
              for k in limits}
    return checks, all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                       for c in checks.values())


def emit(result: dict) -> None:
    """The check's numbers as the last lines of stderr; the result as the
    last line of stdout (``checks`` its last key)."""
    out = {k: v for k, v in result.items() if not k.startswith("_")}
    checks = out.pop("checks")
    where = result.get("_readings", {}).get("program", {}).get("_where", {})
    log(json.dumps(dict(out, checks=checks)))
    for k, c in checks.items():
        at = f" (at {where[k]})" if k in where else ""
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}{at}",
              file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program under {ROOT / 'src'}; run from a "
              "checkout of the repository", file=sys.stderr)
        return EXIT_NO_CHECKOUT
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # impl="auto" resolves as a user's would, from the cost model: no
    # tuning cache of measured times
    os.environ.pop("REPRO_TUNE_CACHE", None)
    cell = resolve_cell(load_json(ROOT / "BENCHMARK.json"), args.workload)
    devices = device_check(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_process=T_PROCESS, devices=devices)
    emit(result)
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
