"""Benchmark utilities.

CPU-timing caveat (applies to every harness here): this container runs XLA's
CPU backend, so absolute numbers are NOT TPU numbers. What transfers is the
*structural* comparison the paper makes — batched-one-op vs sequential
per-sample ops — because the dispatch/launch overhead being amortized exists
on both runtimes. Pallas kernels run in interpret mode (Python), so they are
validated for correctness here and their TPU performance is modeled in the
roofline (EXPERIMENTS.md §Roofline), not wall-clocked.
"""
from __future__ import annotations

import json
import pathlib
import time

import jax
import numpy as np

# Every row() lands here too, so drivers can persist a suite's rows as a
# machine-readable BENCH_*.json (repo root) — the cross-PR perf trajectory.
RESULTS: list[dict] = []
REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def time_fn(fn, *args, warmup: int = 2, iters: int = 10) -> float:
    """Median wall time per call in seconds (blocks on the result)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def row(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)
    RESULTS.append({"name": name, "us_per_call": us_per_call,
                    "derived": derived})


def header() -> None:
    print("name,us_per_call,derived", flush=True)


def results_snapshot() -> int:
    """Marker into RESULTS; pair with ``write_bench_json(..., start=...)``."""
    return len(RESULTS)


def write_bench_json(suite: str, *, start: int = 0,
                     extra: dict | None = None,
                     path: pathlib.Path | None = None) -> pathlib.Path:
    """Persist rows[start:] as ``BENCH_<suite>.json`` at the repo root —
    machine-readable across PRs (name/us_per_call/derived per row, plus any
    ``extra`` structured payload a harness wants to attach).

    STRICT JSON: Python's default ``json.dumps`` emits bare ``NaN``/
    ``Infinity`` literals (not JSON — strict parsers and most non-Python
    tooling reject the file), so non-finite floats are serialized as
    ``null`` and ``allow_nan=False`` guarantees none slip through."""
    from repro.observability import sanitize_json

    path = path or REPO_ROOT / f"BENCH_{suite}.json"
    dev = jax.devices()[0]
    payload = {
        "suite": suite,
        "backend": jax.default_backend(),
        "device": {"platform": dev.platform, "device_kind": dev.device_kind,
                   "count": len(jax.devices())},
        "rows": RESULTS[start:],
    }
    if extra:
        payload.update(extra)
    path.write_text(json.dumps(sanitize_json(payload), indent=2,
                               sort_keys=True, allow_nan=False) + "\n")
    return path
