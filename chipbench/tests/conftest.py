"""CPU tests of the benchmark: ``python -m pytest chipbench/tests``.

They run on one CPU device, with the Pallas kernels in interpret mode and
the persistent compilation cache off, at sizes a test run holds."""
import os
import pathlib
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# tiny stand-ins for the cells' sizes: molecules in the pool, and the
# training batch or the serving rate (requests/s)
TINY = {"train": {"molecules": 240, "batch": 20},
        "serve": {"molecules": 200, "rate_per_s": 150.0}}


@pytest.fixture
def no_compile_cache(monkeypatch):
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: "off")


@pytest.fixture
def tiny_cell(no_compile_cache):
    """``tiny_cell(name)``: the named cell of BENCHMARK.json, its pool and
    load cut to a test's size (a serving cell's tiers re-derived for the
    smaller pool, as its file derives them for the full one)."""
    from chipbench import run as harness
    from chipbench import traffic

    bench = harness.load_json(ROOT / "BENCHMARK.json")

    def make(name: str):
        cell = harness.resolve_cell(bench, name)
        kind = cell.spec["kind"]
        spec = dict(cell.spec, molecules=TINY[kind]["molecules"])
        if kind == "train":
            spec["batch"] = TINY[kind]["batch"]
        else:
            from repro.scheduler import TierPolicy

            spec["arrivals"] = dict(spec["arrivals"],
                                    rate_per_s=TINY[kind]["rate_per_s"])
            pool = traffic.molecule_pool(cell.config, spec)
            tiers = TierPolicy.from_requests(
                [(m.n_nodes, max(len(r) for r in m.rows)) for m in pool],
                levels=len(spec["tiers"]["m_pads"]),
                batch=spec["tiers"]["batch"]).tiers
            spec["tiers"] = {"m_pads": [t.m_pad for t in tiers],
                             "nnz_pads": [t.nnz_pad for t in tiers],
                             "batch": spec["tiers"]["batch"]}
        cell.spec = spec
        return cell

    return make


@pytest.fixture
def run_tiny(tiny_cell):
    """``run_tiny(name, seed, seconds=1.0, extra=())``: one CPU run of the
    tiny cell through the harness; returns the result object."""
    import time

    import jax

    from chipbench import run as harness

    def go(name: str, seed: int, seconds: float = 1.0, extra=()):
        return harness.run_cell(tiny_cell(name), seed, seconds, False,
                                t_process=time.monotonic(),
                                devices=jax.devices(), extra=extra)

    return go
