"""Benchmark driver: one harness per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full | --smoke]

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/common.py for
the CPU-timing caveat). ``--full`` uses paper-scale dataset sizes; the
default keeps the whole suite under a few minutes; ``--smoke`` is the CI
mode — tiny shapes, SpMM figures, the adaptive-dispatch decisions and the
serving-scheduler sweep, a couple of minutes on a CPU runner.

Suites named in ``PERSISTED`` additionally write their rows to
``BENCH_<suite>.json`` at the repo root (machine-readable perf trajectory
across PRs; CI uploads them as build artifacts).
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks.common import header, results_snapshot, write_bench_json

# suites whose rows are persisted as BENCH_<name>.json at the repo root so
# the perf trajectory stays machine-readable across PRs
PERSISTED = {"fused", "serve", "formats", "gspmm", "sampling"}
# persisted only on full runs: the precision speedup gate (check_bench_json
# enforces best_speedup >= 1.0 on the summary row) needs paper-scale
# geometries to amortize the cast overhead — smoke shapes would overwrite
# the committed artifact with sub-1.0 noise. Smoke still RUNS the suite so
# a broken variant fails CI; it just doesn't persist.
FULL_ONLY_PERSISTED = {"precision"}


def _smoke_suites():
    from benchmarks import (
        bench_fig8,
        bench_fig9,
        bench_fig10,
        bench_formats,
        bench_fused,
        bench_gspmm,
        bench_precision,
        bench_sampling,
    )

    def decisions():
        """Print the impl="auto" decision for the acceptance regimes."""
        from benchmarks.common import row
        from repro.autotune import Workload, select_impl

        probes = {
            "small_dense": Workload(batch=20, m_pad=56, nnz_pad=512,
                                    k_pad=16, n_b=64),
            "large_m": Workload(batch=2, m_pad=9000, nnz_pad=36000,
                                k_pad=4, n_b=64),
            "col_paneled": Workload(batch=20, m_pad=2048, nnz_pad=8192,
                                    k_pad=4, n_b=512),
        }
        for name, w in probes.items():
            d = select_impl(w, allow_pallas=False)
            row(f"auto/{name}", 0.0, f"{d.impl}(case{d.case},{d.source})")

    from benchmarks import bench_serve

    return [
        ("fig8", lambda: bench_fig8.run(batch=20, dim=20, nnz=2,
                                        n_bs=(16, 64))),
        ("fig9", lambda: bench_fig9.one(20, 32, 2, n_b=64)),
        ("fig10", lambda: bench_fig10.main(batch=20, n_bs=(64,))),
        ("fused", lambda: bench_fused.main(smoke=True)),
        ("formats", lambda: bench_formats.main(smoke=True)),
        ("auto", decisions),
        ("serve", lambda: bench_serve.graph_sweep(smoke=True)),
        ("precision", lambda: bench_precision.main(smoke=True)),
        ("gspmm", lambda: bench_gspmm.main(smoke=True)),
        ("sampling", lambda: bench_sampling.main(smoke=True)),
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny shapes, SpMM suites only")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="enable telemetry (REPRO_TELEMETRY) for the run and "
                         "export the span buffer as Chrome-trace JSON — "
                         "loads in Perfetto / chrome://tracing")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.trace:
        from repro.observability import set_enabled

        set_enabled(True)

    header()
    if args.smoke:
        suites = _smoke_suites()
    else:
        from benchmarks import (
            bench_chemgcn,
            bench_conversion,
            bench_fig8,
            bench_fig9,
            bench_fig10,
            bench_formats,
            bench_fused,
            bench_gspmm,
            bench_kernel_breakdown,
            bench_moe,
            bench_precision,
            bench_sampling,
            bench_serve,
        )

        suites = [
            ("fig8", lambda: bench_fig8.main()),
            ("fig9", lambda: bench_fig9.main()),
            ("fig10", lambda: bench_fig10.main()),
            ("fused", lambda: bench_fused.main()),
            ("table4", lambda: bench_kernel_breakdown.main()),
            ("conversion", lambda: bench_conversion.main()),
            ("formats", lambda: bench_formats.main()),
            ("chemgcn", lambda: bench_chemgcn.main(small=not args.full)),
            ("moe", lambda: bench_moe.main()),
            ("serve", lambda: bench_serve.main(persist=False)),
            ("precision", lambda: bench_precision.main()),
            ("gspmm", lambda: bench_gspmm.main(smoke=not args.full)),
            ("sampling", lambda: bench_sampling.main(smoke=not args.full)),
        ]
    failed = []
    for name, fn in suites:
        start = results_snapshot()
        extra = None
        try:
            if args.trace:
                from repro.observability import TRACER

                with TRACER.span(f"suite/{name}", cat="bench"):
                    out = fn()
            else:
                out = fn()
            if name == "serve" and isinstance(out, dict):
                extra = {"graph_sweep": out}
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
            continue
        persist = name in PERSISTED or (
            name in FULL_ONLY_PERSISTED and not args.smoke)
        if persist:
            path = write_bench_json(name, start=start, extra=extra)
            print(f"wrote {path}", file=sys.stderr)
    if args.trace:
        from repro.observability import default_auditor, export_chrome_trace

        print(f"wrote {export_chrome_trace(args.trace)}", file=sys.stderr)
        print(default_auditor().format_report(), file=sys.stderr)
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
