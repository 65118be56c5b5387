"""Persistent on-device tuning cache for ``impl="auto"`` (DESIGN.md §5).

The analytic cost model ranks implementations from shapes alone; this module
*refines* that ranking by measurement, the way ``repro.tuning`` records every
dry-run flag set: each record is keyed by the workload's stable shape key and
stores the per-impl median seconds actually observed, so every §Perf data
point in EXPERIMENTS.md is reproducible from the cache file.

The cache is a flat JSON document::

    {"version": 1,
     "records": {"b100_m64_nnz256_k8_n128_i4": {
         "best": "ell",
         "times": {"ell": 1.1e-4, "ref": 2.0e-4, "dense": 3.2e-4},
         "interpret": true}}}

Writes are atomic (tmp + rename). The default location comes from the
``REPRO_TUNE_CACHE`` environment variable; unset means no persistent cache
(selection stays purely analytic).
"""
from __future__ import annotations

import functools
import json
import os
import tempfile

from repro.autotune.cost_model import Workload, precision_of, rank, rank_layer

ENV_VAR = "REPRO_TUNE_CACHE"
_VERSION = 1


def _merge_records(disk: dict, mine: dict) -> dict:
    """Union of two record maps (see :meth:`TuningCache.save`): disk-only
    keys survive, shared keys merge their ``times`` at per-impl min with
    ``best`` recomputed; ``interpret`` follows the merged best's side."""
    merged = dict(disk)
    for key, rec in mine.items():
        other = merged.get(key)
        if other is None:
            merged[key] = rec
            continue
        times = dict(other.get("times", {}))
        for impl, t in rec.get("times", {}).items():
            times[impl] = min(t, times[impl]) if impl in times else t
        best = min(times, key=times.get) if times else rec.get("best")
        interpret = (rec if best in rec.get("times", {})
                     and rec["times"].get(best) == times.get(best)
                     else other).get("interpret")
        merged[key] = {"best": best, "times": times, "interpret": interpret}
    return merged


class TuningCache:
    """Workload-key → measured per-impl seconds, persisted as JSON."""

    def __init__(self, path: str | None):
        self.path = path
        self.records: dict[str, dict] = {}
        if path and os.path.exists(path):
            try:
                with open(path) as f:
                    doc = json.load(f)
                if doc.get("version") == _VERSION:
                    self.records = doc.get("records", {})
            except (json.JSONDecodeError, OSError):
                self.records = {}

    def best(self, key: str) -> str | None:
        rec = self.records.get(key)
        return rec.get("best") if rec else None

    def times(self, key: str) -> dict[str, float]:
        rec = self.records.get(key)
        return dict(rec.get("times", {})) if rec else {}

    def put(self, key: str, times: dict[str, float], *,
            interpret: bool) -> str:
        best = min(times, key=times.get)
        self.records[key] = {"best": best, "times": times,
                             "interpret": interpret}
        self.save()
        return best

    def save(self) -> None:
        """Merge-on-save then atomic replace.

        Atomic-replace alone is last-write-wins: two processes sharing one
        cache path (CI dtype-matrix lanes, a sampler worker next to the
        trainer) would silently drop each other's measurements. Before
        writing we re-read the file and union its records into ours —
        disk-only keys are adopted; for keys both sides measured, the
        per-impl ``times`` merge at min (each measurement is a median of a
        noisy timer, the lower one is the better estimate of the same
        quantity) and ``best`` is recomputed from the merged map. The merged
        view also updates ``self.records`` so a subsequent ``best()`` in
        this process sees what it just persisted."""
        if not self.path:
            return
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        if os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    doc = json.load(f)
                if doc.get("version") == _VERSION:
                    self.records = _merge_records(doc.get("records", {}),
                                                  self.records)
            except (json.JSONDecodeError, OSError):
                pass    # a torn/corrupt file loses the merge, not the save
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"version": _VERSION, "records": self.records},
                          f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)


@functools.lru_cache(maxsize=8)
def _cache_for(path: str) -> TuningCache:
    return TuningCache(path)


def default_cache() -> TuningCache | None:
    """Process-default cache, from $REPRO_TUNE_CACHE (None when unset).

    Memoized per path: every ``impl="auto"`` resolution consults this, so
    the JSON file is parsed once per process, not once per call. External
    edits to the file during the process's lifetime are not re-read;
    ``autotune``'s own puts update the memoized instance AND the file.
    """
    path = os.environ.get(ENV_VAR)
    return _cache_for(path) if path else None


def measure_workload(
    w: Workload,
    impls: tuple[str, ...] | None = None,
    *,
    interpret: bool = True,
    warmup: int = 1,
    iters: int = 5,
    seed: int = 0,
    calls: int = 1,
) -> dict[str, float]:
    """Time each candidate impl on synthetic inputs matching ``w`` EXACTLY.

    The inputs are constructed directly at the workload's static shapes —
    (batch, nnz_pad) COO arrays, (batch, m_pad, n_b) dense operand, dtype
    from ``itemsize`` (2 → bfloat16, else float32) — so the measured record
    is keyed by precisely the shapes it ran, never an approximation.

    A LAYER workload (``w.channels``/``n_in`` set — the graph-conv keys of
    ``select_graph_conv_impl``) is measured as the layer it keys: one whole
    ``graph_conv_batched`` call per candidate (fused megakernel or stacked
    fallback, matmul + SpMM + channel sum included), never a bare SpMM —
    otherwise the record would override the layer model with a timing of a
    different computation. A candidate that fails to compile or run raises
    ``RuntimeError`` naming it.

    Each of the ``iters`` timings is the wall time of ``calls`` back-to-back
    calls divided by ``calls``, blocking once at the end: ``calls=1`` times
    one call with its dispatch and sync, a larger ``calls`` lets dispatch
    overlap the device the way a training loop does. Imports are local to
    avoid a cycle with ``kernels/ops.py`` (which imports this package for
    ``impl="auto"``).
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.formats import BatchedCOO
    from repro.kernels.ops import batched_spmm

    layer = w.channels is not None and w.n_in is not None
    ell_lossy = (w.k_pad is not None and w.nnz_pad > w.m_pad * w.k_pad)
    if impls is None:
        ranked = (rank_layer if layer else rank)(
            w, allow_pallas=not interpret)
        impls = tuple(i for i, _ in ranked)
        if ell_lossy:
            # ELL cannot represent this workload losslessly (more slots
            # than m_pad·k_pad cells) — timing its candidates would measure
            # a silently truncated product and poison the cache record.
            # Class membership via precision_of so bf16/i8 ELL variants are
            # filtered too.
            impls = tuple(i for i in impls
                          if precision_of(i)[0] not in ("ell", "pallas_ell"))
    elif ell_lossy and any(precision_of(i)[0] in ("ell", "pallas_ell")
                           for i in impls):
        # an EXPLICITLY requested unmeasurable impl must fail loudly, not
        # silently vanish from the record
        raise ValueError(
            f"workload {w.key()}: nnz_pad={w.nnz_pad} > m_pad*k_pad="
            f"{w.m_pad * w.k_pad} — the requested ELL impl(s) cannot "
            "represent it losslessly, so their timings would be bogus")

    rng = np.random.default_rng(seed)
    dtype = jnp.bfloat16 if w.itemsize == 2 else jnp.float32

    def make_coo():
        if w.k_pad is not None and w.nnz_pad <= w.m_pad * w.k_pad:
            # Bound every row to ≤ k_pad non-zeros so the ELL candidates
            # measure the SAME computation as the rest — fully random row
            # ids can exceed k_pad and coo_to_ell would silently drop the
            # overflow, timing a smaller product under this workload's key.
            base = (np.arange(w.nnz_pad, dtype=np.int64) // w.k_pad) % w.m_pad
            rid = np.stack([
                rng.permutation(w.m_pad).astype(np.int32)[base]
                for _ in range(w.batch)])
        else:
            rid = rng.integers(0, w.m_pad,
                               (w.batch, w.nnz_pad)).astype(np.int32)
        cid = rng.integers(0, w.m_pad, (w.batch, w.nnz_pad)).astype(np.int32)
        return BatchedCOO(
            row_ids=jnp.asarray(rid), col_ids=jnp.asarray(cid),
            values=jnp.asarray(rng.normal(size=(w.batch, w.nnz_pad)), dtype),
            nnz=jnp.full((w.batch,), w.nnz_pad, jnp.int32),
            n_rows=jnp.full((w.batch,), w.m_pad, jnp.int32))

    if layer:
        from repro.core.graph_conv import graph_conv_batched

        adj = [make_coo() for _ in range(w.channels)]
        x = jnp.asarray(rng.normal(size=(w.batch, w.m_pad, w.n_in)), dtype)
        params = {
            "w": jnp.asarray(
                rng.normal(size=(w.channels, w.n_in, w.n_b)), dtype),
            "b": jnp.zeros((w.channels, w.n_b), dtype),
        }

        def make_fn(impl):
            return jax.jit(functools.partial(
                graph_conv_batched, impl=impl, k_pad=w.k_pad,
                interpret=interpret)), (params, adj, x)
    else:
        coo, b = make_coo(), jnp.asarray(
            rng.normal(size=(w.batch, w.m_pad, w.n_b)), dtype)

        def make_fn(impl):
            return jax.jit(functools.partial(
                batched_spmm, impl=impl, k_pad=w.k_pad,
                interpret=interpret)), (coo, b)

    times: dict[str, float] = {}
    for impl in impls:
        fn, args = make_fn(impl)
        try:
            for _ in range(warmup):
                jax.block_until_ready(fn(*args))
        except Exception as e:
            # a candidate that cannot compile or run is a bug in the ladder
            # (or a kernel the backend refuses): fail loudly, never let it
            # vanish from the record
            raise RuntimeError(
                f"workload {w.key()}: candidate {impl!r} failed to compile "
                f"or run: {type(e).__name__}: {e}") from e
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)     # calls run in order on a device
            ts.append((time.perf_counter() - t0) / calls)
        times[impl] = float(np.median(ts))
    return times


def autotune(
    w: Workload,
    *,
    cache: TuningCache,
    impls: tuple[str, ...] | None = None,
    interpret: bool = True,
    refresh: bool = False,
) -> str:
    """Measured-best impl for ``w``, memoized in ``cache``."""
    key = w.key()
    if not refresh:
        best = cache.best(key)
        if best is not None:
            return best
    times = measure_workload(w, impls, interpret=interpret)
    if not times:
        raise RuntimeError(f"no candidate impl ran for workload {key}")
    return cache.put(key, times, interpret=interpret)
