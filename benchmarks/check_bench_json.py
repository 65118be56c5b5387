"""CI sanity gate for the persisted ``BENCH_*.json`` artifacts.

Two classes of failure, both cheap to hit when a harness regresses silently:

1. **Schema** — a persisted file missing its required top-level keys
   (``suite``/``backend``/``rows``) or rows missing ``name``/``us_per_call``
   /``derived`` would break the cross-PR perf-trajectory tooling downstream.
2. **Regression guard** — rows that publish an explicit ``ratio=<float>``
   field in ``derived`` (e.g. ``bench_formats``'s ``best=csr,ratio=1.31``
   rows, defined so the ratio is ≥ 1.0 by construction) must never report
   below ``MIN_RATIO``: a value that low means the measured comparison
   inverted — the harness or the kernel it guards broke, not timing noise.
   Free-form ``...x`` annotations (like the fused bench's CPU wall ratios)
   are NOT guarded; only the explicit ``ratio=`` marker opts a row in.
3. **Precision gates** (``BENCH_precision.json`` only, suite="precision"):
   every row publishing ``maxerr=`` must stay within the per-dtype error
   ceiling (the measured counterpart of the tests/oracle.py tolerance
   table), and the ``precision/summary/auto`` row must report
   ``reduced_selected=1`` with ``best_speedup>=1.0`` — the ISSUE 6
   acceptance: ``impl="auto"`` under a bf16 policy picks a reduced variant
   that measured at least parity against its own f32 base on one of the
   Fig. 8–10 geometries. Note the precision rows use a ``speedup=`` marker,
   not ``ratio=`` — a same-class dtype comparison on a paper geometry is
   real measurement (gated at 1.0 on the summary's best), not the
   ≥-1.0-by-construction ``best=`` rows the loose MIN_RATIO floor guards.

4. **Formats gates** (``BENCH_formats.json`` only, suite="formats"): every
   geometry must carry a measured ``hybrid`` row (the degree-binned dispatch
   stays in the sweep), and the degree-skewed ``powerlaw`` geometry must be
   present.

5. **g-SpMM gates** (``BENCH_gspmm.json`` only, suite="gspmm"): every
   ``maxerr=`` row must stay within the f32 ceiling (all g-SpMM impls are
   full precision), and all 9 ``gspmm/<op>_<reduce>/best`` rows plus the
   ``gspmm/gat_vector/best`` vector-edge row must be present — the sweep
   covering the full message-passing matrix is itself part of the ISSUE 7
   acceptance.

6. **Sampling gates** (``BENCH_sampling.json`` only, suite="sampling"): the
   cache on/off rows must both publish ``bytes=`` with cache-on fetching no
   more than cache-off, and every ``sampled_vs_full`` row must report
   ``ratio >= 1.0`` — the ISSUE 10 acceptance that fanout-sampled minibatch
   steps beat the full-batch step at the largest benchmarked geometry.

Exit code 1 with one line per problem; silent 0 otherwise.

    PYTHONPATH=src python -m benchmarks.check_bench_json [paths...]
"""
from __future__ import annotations

import json
import pathlib
import re
import sys

from benchmarks.common import REPO_ROOT

REQUIRED_TOP = ("suite", "backend", "rows")
REQUIRED_ROW = ("name", "us_per_call", "derived")
RATIO_RE = re.compile(r"(?:^|[ ,;])ratio=([-+0-9.eE]+)")
MIN_RATIO = 0.5

# --- precision-suite gates (class 3 above) -------------------------------
MAXERR_RE = re.compile(r"(?:^|[ ,;])maxerr=([-+0-9.eE]+)")
DTYPE_RE = re.compile(r"(?:^|[ ,;])dtype=(\w+)")
# per-dtype forward max-abs-error ceilings vs the f32 ref oracle, sized
# ~2x above the tests/oracle.py tolerances (bench geometries are larger
# than the oracle cases, so storage rounding accumulates more slack)
MAX_ERR = {"f32": 1e-4, "bf16": 0.15, "i8": 0.5}
SUMMARY_ROW = "precision/summary/auto"
SUMMARY_RE = re.compile(
    r"reduced_selected=([01]).*best_speedup=([-+0-9.eE]+)")
MIN_BEST_SPEEDUP = 1.0

# --- formats-suite gates (BENCH_formats.json, suite="formats") ------------
def _check_formats_rows(path, rows) -> list[str]:
    errors: list[str] = []
    names = {r.get("name") for r in rows}
    geos = sorted({n.split("/")[1] for n in names
                   if isinstance(n, str) and n.startswith("formats/")})
    for g in geos:
        if f"formats/{g}/hybrid" not in names:
            errors.append(
                f"{path.name}: geometry {g!r} has no measured hybrid row — "
                "the degree-binned dispatch fell out of the sweep")
    if not any(g.startswith("powerlaw") for g in geos):
        errors.append(
            f"{path.name}: no powerlaw geometry — the degree-skewed family "
            "the hybrid path targets is no longer benchmarked")
    return errors


# --- gspmm-suite gates (BENCH_gspmm.json, suite="gspmm") ------------------
# every g-SpMM impl is f32, so its maxerr= rows are held to the f32 ceiling
# by the shared maxerr machinery; additionally the sweep must cover the
# FULL (op × reduce) message-passing matrix — a corner silently dropped
# from bench_gspmm.py would otherwise read as "covered" downstream.
GSPMM_CORNERS = tuple(
    f"gspmm/{op}_{red}/best"
    for op in ("mul", "add", "copy_lhs")
    for red in ("sum", "max", "mean")) + ("gspmm/gat_vector/best",)


def _check_gspmm_rows(path, rows) -> list[str]:
    errors: list[str] = []
    names = {r.get("name") for r in rows}
    for corner in GSPMM_CORNERS:
        if corner not in names:
            errors.append(
                f"{path.name}: missing required row {corner!r} — the "
                "(op × reduce) sweep no longer covers the full matrix")
    for i, r in enumerate(rows):
        derived = str(r.get("derived", ""))
        m = MAXERR_RE.search(derived)
        if m and float(m.group(1)) > MAX_ERR["f32"]:
            errors.append(
                f"{path.name}: rows[{i}] ({r.get('name')}) maxerr="
                f"{float(m.group(1))} > {MAX_ERR['f32']} — g-SpMM impls "
                "are f32, this is an oracle-parity regression")
    return errors


# --- sampling-suite gates (BENCH_sampling.json, suite="sampling") ---------
# the two A-B comparisons the sampled tier exists for (ISSUE 10): the
# hot-node cache must never INCREASE backing-store traffic, and a sampled
# minibatch step must beat the full-batch step at the largest geometry
# (its sampled_vs_full row publishes ratio= full/sampled, so the shared
# RATIO_RE machinery already floors it at MIN_RATIO; the suite gate holds
# it to >= 1.0).
BYTES_RE = re.compile(r"(?:^|[ ,;])bytes=([0-9]+)")
MIN_SAMPLED_RATIO = 1.0


def _check_sampling_rows(path, rows) -> list[str]:
    errors: list[str] = []
    by_name = {r.get("name"): r for r in rows}
    fetch = {}
    for arm in ("on", "off"):
        name = f"sampling/cache_{arm}/fetch"
        r = by_name.get(name)
        m = BYTES_RE.search(str(r.get("derived", ""))) if r else None
        if m is None:
            errors.append(f"{path.name}: missing required row {name!r} "
                          "(with bytes=) — the cache A-B fell out of the "
                          "sweep")
        else:
            fetch[arm] = int(m.group(1))
    if len(fetch) == 2 and fetch["on"] > fetch["off"]:
        errors.append(
            f"{path.name}: cache-on fetched {fetch['on']} bytes > cache-off "
            f"{fetch['off']} — the hot-node cache is amplifying traffic "
            "(ISSUE 10 gate)")
    vs = [(n, r) for n, r in by_name.items()
          if isinstance(n, str) and n.endswith("/sampled_vs_full")]
    if not vs:
        errors.append(
            f"{path.name}: no sampled_vs_full row — the full-batch baseline "
            "comparison is no longer benchmarked")
    for n, r in vs:
        m = RATIO_RE.search(str(r.get("derived", "")))
        if m is None or float(m.group(1)) < MIN_SAMPLED_RATIO:
            errors.append(
                f"{path.name}: {n} ratio="
                f"{m.group(1) if m else '<missing>'} < {MIN_SAMPLED_RATIO} "
                "— the sampled step no longer beats full-batch at the "
                "largest geometry (ISSUE 10 gate)")
    return errors


def _check_precision_rows(path, rows) -> list[str]:
    errors: list[str] = []
    summary = None
    for i, r in enumerate(rows):
        derived = str(r.get("derived", ""))
        m = MAXERR_RE.search(derived)
        if m:
            dt = DTYPE_RE.search(derived)
            bound = MAX_ERR.get(dt.group(1)) if dt else None
            if bound is None:
                errors.append(
                    f"{path.name}: rows[{i}] ({r.get('name')}) has maxerr= "
                    f"but no recognised dtype= in derived={derived!r}")
            elif float(m.group(1)) > bound:
                errors.append(
                    f"{path.name}: rows[{i}] ({r.get('name')}) maxerr="
                    f"{float(m.group(1))} > {bound} for dtype="
                    f"{dt.group(1)} — precision regression")
        if r.get("name") == SUMMARY_ROW:
            summary = (i, derived)
    if summary is None:
        errors.append(f"{path.name}: missing required row {SUMMARY_ROW!r}")
        return errors
    i, derived = summary
    m = SUMMARY_RE.search(derived)
    if not m:
        errors.append(
            f"{path.name}: rows[{i}] ({SUMMARY_ROW}) unparseable summary "
            f"derived={derived!r}")
        return errors
    if m.group(1) != "1":
        errors.append(
            f"{path.name}: {SUMMARY_ROW} reduced_selected=0 — impl=\"auto\""
            " never picked a reduced-precision variant (ISSUE 6 gate)")
    if float(m.group(2)) < MIN_BEST_SPEEDUP:
        errors.append(
            f"{path.name}: {SUMMARY_ROW} best_speedup={float(m.group(2))}"
            f" < {MIN_BEST_SPEEDUP} — reduced variant lost to its f32 base"
            " on every Fig. 8-10 geometry (ISSUE 6 gate)")
    return errors


def _reject_non_finite(token: str):
    # json.loads only calls parse_constant for NaN/Infinity/-Infinity —
    # Python-only extensions that strict JSON parsers reject; a bench file
    # carrying one is unreadable to non-Python tooling downstream
    raise ValueError(f"non-finite JSON literal {token!r} "
                     "(write_bench_json must serialize these as null)")


def check_file(path: pathlib.Path) -> list[str]:
    errors: list[str] = []
    try:
        doc = json.loads(path.read_text(),
                         parse_constant=_reject_non_finite)
    except (OSError, ValueError) as e:
        return [f"{path.name}: unreadable ({e})"]
    for key in REQUIRED_TOP:
        if key not in doc:
            errors.append(f"{path.name}: missing required key {key!r}")
    for i, r in enumerate(doc.get("rows", [])):
        for key in REQUIRED_ROW:
            if key not in r:
                errors.append(f"{path.name}: rows[{i}] missing {key!r}")
                continue
        m = RATIO_RE.search(str(r.get("derived", "")))
        if m:
            try:
                ratio = float(m.group(1))
            except ValueError:
                errors.append(
                    f"{path.name}: rows[{i}] ({r.get('name')}) unparseable "
                    f"ratio in derived={r.get('derived')!r}")
                continue
            if ratio < MIN_RATIO:
                errors.append(
                    f"{path.name}: rows[{i}] ({r.get('name')}) reports "
                    f"ratio={ratio} < {MIN_RATIO} — regression guard")
    if doc.get("suite") == "precision":
        errors.extend(_check_precision_rows(path, doc.get("rows", [])))
    if doc.get("suite") == "formats":
        errors.extend(_check_formats_rows(path, doc.get("rows", [])))
    if doc.get("suite") == "gspmm":
        errors.extend(_check_gspmm_rows(path, doc.get("rows", [])))
    if doc.get("suite") == "sampling":
        errors.extend(_check_sampling_rows(path, doc.get("rows", [])))
    return errors


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    paths = [pathlib.Path(p) for p in argv] or \
        sorted(REPO_ROOT.glob("BENCH_*.json"))
    if not paths:
        print("check_bench_json: no BENCH_*.json files found", file=sys.stderr)
        return 1
    errors: list[str] = []
    for p in paths:
        errors.extend(check_file(p))
    for e in errors:
        print(f"check_bench_json: {e}", file=sys.stderr)
    if not errors:
        print(f"check_bench_json: {len(paths)} file(s) OK "
              f"({', '.join(p.name for p in paths)})")
    return 1 if errors else 0


if __name__ == "__main__":
    raise SystemExit(main())
