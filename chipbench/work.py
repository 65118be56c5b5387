"""Useful work of ChemGCN, counted from the model's mathematics.

Every count is taken at a molecule's real node and edge counts: padding,
recomputation and format conversion do not count. One graph-conv layer is
``Y = sum_c A_c (X W_c + b_c)`` (arXiv:1903.11409 eq. 2), so per channel it
costs ``2 n n_in n_out`` for the transform and ``2 nnz_c n_out`` for the
aggregation. The head is ``readout @ W_head``. Training counts the forward
three times (forward, and a backward of twice the forward).

A molecule here is any object with ``n_nodes`` and per-channel ``rows``
(the repo's ``GraphSample`` and ``GraphRequest`` both qualify).
"""
from __future__ import annotations

F32 = 4            # bytes of a float32 or an int32
TRAIN_FACTOR = 3   # forward + backward


def conv_flops(n: int, nnz: list[int], n_in: int, n_out: int) -> int:
    """Forward FLOPs of one graph-conv layer on one molecule."""
    return sum(2 * n * n_in * n_out + 2 * z * n_out for z in nnz)


def forward_flops(mol, cfg: dict) -> int:
    """Forward FLOPs of the whole model on one molecule: every conv layer
    and the head. ``cfg`` holds ``n_features``, ``conv_widths``,
    ``n_tasks``."""
    nnz = [len(r) for r in mol.rows]
    total, n_in = 0, cfg["n_features"]
    for n_out in cfg["conv_widths"]:
        total += conv_flops(mol.n_nodes, nnz, n_in, n_out)
        n_in = n_out
    return total + 2 * n_in * cfg["n_tasks"]


def train_flops(mol, cfg: dict) -> int:
    return TRAIN_FACTOR * forward_flops(mol, cfg)


def conv_train_bytes(nodes: int, nnz: int, channels: int, n_in: int,
                     n_out: int) -> int:
    """Bytes that one forward+backward of a graph-conv layer must move over
    a batch with ``nodes`` real nodes and ``nnz`` real non-zeros over all
    channels: X, W and b, the row id, column id and value of each
    non-zero, and Y, plus the gradients dY (read), dX, dW and db
    (written)."""
    x = nodes * n_in
    w = channels * (n_in * n_out + n_out)
    y = nodes * n_out
    return F32 * (2 * x + 2 * w + 2 * y + 3 * nnz)


def conv_train_flops(nodes_nnz: list[tuple[int, list[int]]], n_in: int,
                     n_out: int) -> int:
    """FLOPs of one forward+backward of a graph-conv layer over a batch,
    given each molecule's ``(n_nodes, per-channel nnz)``."""
    return TRAIN_FACTOR * sum(conv_flops(n, nnz, n_in, n_out)
                              for n, nnz in nodes_nnz)


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes,
                                                            "memory")
