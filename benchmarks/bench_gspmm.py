"""g-SpMM message-passing sweep (DESIGN.md §11): the (op × reduce) matrix
timed across the XLA-lowered g-SpMM impls, persisted to ``BENCH_gspmm.json``.

Per corner, three kinds of rows:

- ``gspmm/<op>_<reduce>/<impl>`` — wall time of each XLA-lowered impl
  (Pallas impls are interpret-mode Python on CPU: correctness paths, never
  timed here) plus its forward ``maxerr=`` against the pure-jnp oracle
  (``dtype=f32`` — every g-SpMM impl is full precision, so
  ``check_bench_json.py`` holds these to the f32 ceiling);
- ``gspmm/<op>_<reduce>/best`` — the fastest impl for the corner with its
  ``ratio=`` speedup over the ``ref`` scatter baseline (≥ 1.0 by
  construction — ref is in the candidate set);
- ``gspmm/gat_vector/…`` — vector edge features under ``(mul, sum)`` (the
  shape GAT's aggregation had before it took scalar attention values), the
  one corner the scalar matrix does not cover.

``check_bench_json.py`` additionally requires all 9 (op × reduce) ``best``
rows to be present — a corner silently dropped from the sweep fails CI.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn
from repro.core import max_row_degree, random_batch
from repro.core.spmm import GSPMM_OPS, GSPMM_REDUCES, batched_gspmm
from repro.kernels import ref

# XLA-lowered (wall-clockable on CPU) g-SpMM impls; the Pallas members of
# autotune.GSPMM_IMPLS are accuracy-checked by tests/oracle.py instead.
TIMED_IMPLS = ("ref", "loop", "csr", "ell")


def _inputs(batch, dim, nnz, n_b, *, d_e=None, seed=17):
    rng = np.random.default_rng(seed)
    coo, m_pad = random_batch(rng, batch=batch, dim=dim, nnz_per_row=nnz)
    if d_e is not None:
        valid = (np.arange(coo.nnz_pad)[None, :]
                 < np.asarray(coo.nnz)[:, None])
        vv = rng.normal(size=(batch, coo.nnz_pad, d_e)).astype(np.float32)
        coo = dataclasses.replace(
            coo, values=jnp.asarray(np.where(valid[..., None], vv, 0.0)))
    b = jnp.asarray(rng.normal(size=(batch, m_pad, n_b)), jnp.float32)
    k_pad = max(1, int(np.asarray(max_row_degree(coo, m_pad)).max()))
    return coo, m_pad, b, k_pad


def _max_abs_error(coo, b, m_pad, k_pad, impl, op, reduce) -> float:
    want = np.asarray(
        ref.batched_gspmm_ref(coo, b, m_pad, op=op, reduce=reduce),
        np.float32)
    got = np.asarray(batched_gspmm(coo, b, op=op, reduce=reduce, impl=impl,
                                   k_pad=k_pad), np.float32)
    return float(np.max(np.abs(got - want))) if want.size else 0.0


def sweep_corner(op: str, reduce: str, coo, m_pad, b, k_pad, *, iters: int):
    times: dict[str, float] = {}
    for impl in TIMED_IMPLS:
        fn = jax.jit(functools.partial(batched_gspmm, op=op, reduce=reduce,
                                       impl=impl, k_pad=k_pad))
        times[impl] = time_fn(fn, coo, b, warmup=2, iters=iters)
        err = _max_abs_error(coo, b, m_pad, k_pad, impl, op, reduce)
        row(f"gspmm/{op}_{reduce}/{impl}", times[impl] * 1e6,
            f"dtype=f32 maxerr={err:.6f}")
    best = min(times, key=times.get)
    row(f"gspmm/{op}_{reduce}/best", times[best] * 1e6,
        f"best={best} ratio={times['ref'] / times[best]:.2f}")


def gat_vector_rows(*, batch, dim, nnz, n_b, iters: int):
    """(mul, sum) with d_e == n_b vector edge features — exercises the
    vector-edge kernel path the scalar matrix cannot reach."""
    coo, m_pad, b, k_pad = _inputs(batch, dim, nnz, n_b, d_e=n_b)
    times: dict[str, float] = {}
    for impl in TIMED_IMPLS:
        fn = jax.jit(functools.partial(batched_gspmm, op="mul", reduce="sum",
                                       impl=impl, k_pad=k_pad))
        times[impl] = time_fn(fn, coo, b, warmup=2, iters=iters)
        err = _max_abs_error(coo, b, m_pad, k_pad, impl, "mul", "sum")
        row(f"gspmm/gat_vector/{impl}", times[impl] * 1e6,
            f"dtype=f32 maxerr={err:.6f}")
    best = min(times, key=times.get)
    row("gspmm/gat_vector/best", times[best] * 1e6,
        f"best={best} ratio={times['ref'] / times[best]:.2f}")


def main(smoke: bool = False):
    batch, dim, nnz, n_b = (8, 24, 3, 32) if smoke else (64, 50, 4, 128)
    iters = 3 if smoke else 10
    coo, m_pad, b, k_pad = _inputs(batch, dim, nnz, n_b)
    for op in GSPMM_OPS:
        for reduce in GSPMM_REDUCES:
            sweep_corner(op, reduce, coo, m_pad, b, k_pad, iters=iters)
    gat_vector_rows(batch=batch, dim=dim, nnz=nnz,
                    n_b=min(n_b, 32), iters=iters)


if __name__ == "__main__":
    main()
