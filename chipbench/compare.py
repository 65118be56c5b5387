"""The numbers that decide ``correct``, each a gap between what the timed
path produced and what the reference computes.

Every gap is a share of the reference's own scale, so one limit holds for
any seed:

- ``loss``: the largest relative gap of a step's loss over the first steps,
  and ``loss1`` the first step's;
- ``grad1``: the first step's gradient, per leaf, as the gap between the
  program's norm and the reference's, over the larger of the reference
  leaf's norm and the median leaf's; the worst leaf;
- ``grad1_diff``: the first step's gradient, per leaf, as the norm of the
  difference over the same scale; the worst leaf. Element-wise errors of
  random sign, as a lower precision makes, cancel in a gap of norms but
  not here;
- ``dparam3``: the same for each leaf's change over the first steps,
  leaving out leaves whose first reference gradient is under a thousandth
  of the median leaf's (Adam moves those by round-off alone); the worst
  leaf, and ``dparam3_median`` the median leaf;
- ``logit_gap``: per served answer, the largest logit gap over the larger
  of the answer's own largest reference logit and the median of those;
  the worst answer.
"""
from __future__ import annotations

import jax
import numpy as np

NOUGHT_GRAD = 1e-3   # a leaf whose gradient is under this share of the
                     # median leaf's moves by round-off alone under Adam


def leaf_norms(tree) -> dict[str, float]:
    """``{path: L2 norm}`` of every leaf."""
    return {jax.tree_util.keystr(path): float(np.linalg.norm(np.asarray(
        leaf, np.float64)))
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def tree_sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def leaf_gaps(got: dict[str, float], want: dict[str, float],
              keep=None) -> dict[str, float]:
    """Each leaf's ``|got - want| / max(want, median want)``, over the
    leaves in ``keep`` (all by default)."""
    floor = float(np.median([want[k] for k in want]))
    gaps = {}
    for k in want:
        if keep is None or k in keep:
            gap = abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
            gaps[k] = gap if np.isfinite(gap) else float("inf")
    return gaps


def worst_leaf_gap(got: dict[str, float], want: dict[str, float],
                   keep=None) -> tuple[float, str]:
    """The worst leaf's gap (``leaf_gaps``) and the leaf."""
    gaps = leaf_gaps(got, want, keep)
    at = max(gaps, key=gaps.get)
    return gaps[at], at


def diff_gap(got, want) -> tuple[float, str]:
    """The worst leaf's ``|got - want| / max(|want|, median |want|)``, in
    L2 norms: the gap that element-wise errors make, which a gap of norms
    averages away."""
    diff = leaf_norms(tree_sub(got, want))
    scale = leaf_norms(want)
    floor = float(np.median(list(scale.values())))
    gaps = {k: diff[k] / max(scale[k], floor, 1e-30) for k in scale}
    gaps = {k: v if np.isfinite(v) else float("inf") for k, v in gaps.items()}
    at = max(gaps, key=gaps.get)
    return gaps[at], at


def moved_leaves(grad_norms: dict[str, float]) -> set[str]:
    floor = NOUGHT_GRAD * float(np.median(list(grad_norms.values())))
    return {k for k, v in grad_norms.items() if v >= floor}


def train_readings(got: dict, want: dict) -> dict:
    """``got``/``want``: ``losses`` (one per step), ``grad1`` (the first
    step's gradient tree) and ``dparams`` (each leaf's change over the
    steps). Returns the readings and, for the log, the leaves they fell
    on."""
    lg = [abs(g - w) / max(abs(w), 1e-30)
          for g, w in zip(got["losses"], want["losses"], strict=True)]
    g_want = leaf_norms(want["grad1"])
    grad1, grad_leaf = worst_leaf_gap(leaf_norms(got["grad1"]), g_want)
    grad1_diff, diff_leaf = diff_gap(got["grad1"], want["grad1"])
    d_gaps = leaf_gaps(leaf_norms(got["dparams"]),
                       leaf_norms(want["dparams"]), keep=moved_leaves(g_want))
    d_leaf = max(d_gaps, key=d_gaps.get)
    if not all(np.isfinite(got["losses"])):
        lg = [float("inf")] * len(lg)
    return {"loss": max(lg), "loss1": lg[0], "grad1": grad1,
            "grad1_diff": grad1_diff,
            "dparam3": d_gaps[d_leaf],
            "dparam3_median": float(np.median(list(d_gaps.values()))),
            "_where": {"loss": f"per step {lg}", "loss1": f"per step {lg}",
                       "grad1": grad_leaf, "grad1_diff": diff_leaf,
                       "dparam3": d_leaf}}


def logit_readings(got: np.ndarray, want: np.ndarray) -> dict:
    """``got``/``want``: ``(answers, tasks)`` logits."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or got.size == 0:
        return {"logit_gap": float("inf"), "_where": {}}
    scale = np.abs(want).max(axis=1)
    scale = np.maximum(scale, max(float(np.median(scale)), 1e-30))
    gap = np.abs(got - want).max(axis=1) / scale
    gap = np.where(np.isfinite(gap), gap, np.inf)
    i = int(np.argmax(gap))
    return {"logit_gap": float(gap[i]), "_where": {"logit_gap": f"answer {i}"}}
