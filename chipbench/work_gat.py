"""Useful work of GAT on PPI-shaped graphs, counted from the model's
mathematics (arXiv:1710.10903 §2.1).

Every count is taken at a graph's real node count ``n`` and real edge count
``e`` (self loops included): padding, recomputation and format conversion
do not count. Per layer of ``heads`` heads of width ``d`` over ``n_in``
inputs, the forward costs

- ``2 n n_in heads d`` for the transform ``h = X W`` (the same again for a
  skip projection), and ``2 · 2 n heads d`` for the two halves of the
  attention logit;
- per edge and head, 2 for the logit (add, LeakyReLU), 3 for the softmax
  (exponential, sum, division) and ``2 d`` for the weighted aggregation;
- ``n heads d`` for the bias, and ``n heads d`` more for a head mean.

Bytes per layer forward: X, the weights, the row and column id of each
edge, the ``d``-wide row of ``h`` that each edge gathers per head, the
attention weight of each edge and head, and the output. Training counts
the forward three times (forward, and a backward of twice the forward), as
``work.py`` does.
"""
from __future__ import annotations

from chipbench.work import F32, TRAIN_FACTOR


def layers(gcn: dict):
    """``(n_in, heads, d_head, skip, mean)`` of each layer of ``gcn`` (which
    holds ``n_features``, ``conv_widths``, ``heads`` and ``skip``); the last
    layer averages its heads."""
    n_in, out = gcn["n_features"], []
    last = len(gcn["conv_widths"]) - 1
    for i, (n_out, k) in enumerate(zip(gcn["conv_widths"], gcn["heads"])):
        out.append((n_in, k, n_out // k, i in gcn["skip"], i == last))
        n_in = n_out
    return out


def layer_flops(n: int, e: int, n_in: int, heads: int, d: int, skip: bool,
                mean: bool) -> int:
    """Forward FLOPs of one GAT layer on one graph."""
    width = heads * d
    dense = 2 * n * n_in * width * (2 if skip else 1) + 2 * 2 * n * width
    edges = e * heads * (2 + 3 + 2 * d)
    return dense + edges + n * width * (2 if mean else 1)


def layer_bytes(n: int, e: int, n_in: int, heads: int, d: int, skip: bool,
                mean: bool) -> int:
    """Forward bytes of one GAT layer on one graph (module docstring)."""
    width = heads * d
    weights = n_in * width * (2 if skip else 1) + 3 * width
    out = n * (d if mean else width)
    return F32 * (n * n_in + weights + 2 * e + e * width + e * heads + out)


def train_flops(n: int, e: int, gcn: dict) -> int:
    """Forward + backward FLOPs of the whole model on one graph."""
    return TRAIN_FACTOR * sum(layer_flops(n, e, *spec)
                              for spec in layers(gcn))


def layer_train(graphs: list[tuple[int, int]], spec) -> tuple[int, int]:
    """Forward + backward FLOPs and bytes of one layer (``layers`` entry)
    over a batch of graphs given as ``(n, e)``."""
    return (TRAIN_FACTOR * sum(layer_flops(n, e, *spec) for n, e in graphs),
            TRAIN_FACTOR * sum(layer_bytes(n, e, *spec) for n, e in graphs))
