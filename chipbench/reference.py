"""Plain float32 ChemGCN: the reference every cell's output is compared with.

Written from the paper (arXiv:1903.11409 §II-A, §IV-D, §V-B) in
``jax.numpy`` with a dense adjacency per molecule, and no kernel, batching
format or cache. It imports nothing of the program under test and takes
nothing the program made: weights come from its own initialisation from the
seed, molecules from their raw per-channel edge lists.

- A conv layer is ``Y = sum_c A_c (X W_c + b_c)`` over the bond channels,
  with ``A_c[r, k]`` counting the channel-``c`` edges from node ``k`` into
  node ``r`` (channel 0 also holds the self loops).
- Batch norm is taken over real nodes only: over the whole batch in
  training (``bn_mode="batch"``), over each molecule's own nodes in serving
  (``"sample"``), with epsilon 1e-5; then ReLU and the node mask.
- The readout sums a molecule's nodes; the head is affine. Tox21 takes the
  mean sigmoid cross-entropy over its binary tasks, Reaction100 the mean
  softmax cross-entropy over its classes.
- Adam without weight decay or clipping.

Initialisation follows the scheme the configuration states for ``--seed``:
the seed's key split into one key per conv layer and one for the head;
each conv weight uniform in ``±1/sqrt(n_in)`` from the first half of its
layer key's split; biases zero; batch-norm scale one and bias zero.

``precision`` selects how every matrix product is computed:
``"highest"`` is float32; ``"high"`` is the three-pass bfloat16 split
(``a_hi b_hi + a_hi b_lo + a_lo b_hi``); ``"bf16"`` one bfloat16 pass. The
lower two are written out explicitly so that they mean the same on every
backend; they are the controls of the comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BN_EPS = 1e-5


# -- parameters -------------------------------------------------------------

def init_params(seed: int, gcn: dict):
    """``gcn`` holds ``n_features``, ``channels``, ``conv_widths`` and
    ``n_tasks``; returns the parameter tree (same layout as the program's,
    so leaves can be matched by path)."""
    widths = list(gcn["conv_widths"])
    keys = jax.random.split(jax.random.key(seed), len(widths) + 1)
    convs, bns = [], []
    n_in = gcn["n_features"]
    for i, n_out in enumerate(widths):
        k_w, _ = jax.random.split(keys[i])
        scale = 1.0 / jnp.sqrt(n_in)
        convs.append({
            "w": jax.random.uniform(k_w, (gcn["channels"], n_in, n_out),
                                    jnp.float32, -scale, scale),
            "b": jnp.zeros((gcn["channels"], n_out), jnp.float32)})
        bns.append({"scale": jnp.ones((n_out,), jnp.float32),
                    "bias": jnp.zeros((n_out,), jnp.float32)})
        n_in = n_out
    scale = 1.0 / jnp.sqrt(n_in)
    head = {"w": jax.random.uniform(keys[-1], (n_in, gcn["n_tasks"]),
                                    jnp.float32, -scale, scale),
            "b": jnp.zeros((gcn["n_tasks"],), jnp.float32)}
    return {"convs": convs, "bns": bns, "head": head}


# -- inputs -----------------------------------------------------------------

def dense_batch(mols, n_max: int, channels: int, n_features: int):
    """Dense inputs of a list of molecules (objects with ``rows``, ``cols``,
    ``n_nodes``, ``features``), each padded to ``n_max`` nodes: adjacency
    ``(B, C, n_max, n_max)``, features ``(B, n_max, F)``, node mask
    ``(B, n_max, 1)``."""
    b = len(mols)
    adj = np.zeros((b, channels, n_max, n_max), np.float32)
    x = np.zeros((b, n_max, n_features), np.float32)
    mask = np.zeros((b, n_max, 1), np.float32)
    for i, m in enumerate(mols):
        for c in range(channels):
            np.add.at(adj[i, c], (np.asarray(m.rows[c]),
                                  np.asarray(m.cols[c])), 1.0)
        x[i, :m.n_nodes] = m.features
        mask[i, :m.n_nodes] = 1.0
    return adj, x, mask


# -- arithmetic -------------------------------------------------------------

def _bf16(a):
    """``a`` rounded to bfloat16, kept in float32. ``reduce_precision``
    rounds on every backend; a cast there and back may be dropped by XLA
    where it lets float32 stand in for the narrower type."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def matmul(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` at the named precision (module docstring)."""
    mm = functools.partial(jnp.einsum, spec, precision=HIGHEST)
    if precision == "highest":
        return mm(a, b)
    a_hi, b_hi = _bf16(a), _bf16(b)
    if precision == "bf16":
        return mm(a_hi, b_hi)
    if precision == "high":
        a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
        return mm(a_hi, b_hi) + mm(a_hi, b_lo) + mm(a_lo, b_hi)
    raise ValueError(f"unknown precision {precision!r}")


def batch_norm(p, h, mask, mode: str):
    if mode == "sample":
        axes, keep = (1,), True
    elif mode == "batch":
        axes, keep = (0, 1), False
    else:
        raise ValueError(f"unknown bn_mode {mode!r}")
    count = jnp.maximum(jnp.sum(mask, axis=axes, keepdims=keep), 1.0)
    mean = jnp.sum(h * mask, axis=axes, keepdims=keep) / count
    var = jnp.sum(((h - mean) * mask) ** 2, axis=axes, keepdims=keep) / count
    return (h - mean) / jnp.sqrt(var + BN_EPS) * p["scale"] + p["bias"]


def forward(params, adj, x, mask, *, bn_mode: str, precision: str):
    """Logits ``(B, n_tasks)`` of a dense batch."""
    h = x
    for conv, bn in zip(params["convs"], params["bns"]):
        u = matmul("bnf,cfo->bcno", h, conv["w"], precision) \
            + conv["b"][None, :, None, :]
        y = matmul("bcrk,bcko->bro", adj, u, precision)
        h = jnp.maximum(batch_norm(bn, y * mask, mask, bn_mode), 0.0) * mask
    readout = jnp.sum(h, axis=1)
    return matmul("bf,ft->bt", readout, params["head"]["w"], precision) \
        + params["head"]["b"]


def loss_fn(params, adj, x, mask, labels, *, task: str, precision: str,
            bn_mode: str = "batch"):
    z = forward(params, adj, x, mask, bn_mode=bn_mode, precision=precision)
    if task == "multitask_binary":
        per = jnp.maximum(z, 0) - z * labels + jnp.log1p(jnp.exp(-jnp.abs(z)))
        return jnp.mean(per)
    if task == "multiclass":
        logp = jax.nn.log_softmax(z, axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, labels[:, None].astype(jnp.int32), axis=1))
    raise ValueError(f"unknown task {task!r}")


def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros, "t": 0}


def adam_step(params, grads, state, opt: dict):
    t = state["t"] + 1
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - opt["lr"] * (m / c1) / (jnp.sqrt(v / c2)
                                                   + opt["eps"]),
        params, m, v)
    return params, {"m": m, "v": v, "t": t}


# -- the two things a cell compares ----------------------------------------

def train(seed: int, gcn: dict, opt: dict, batches, *, precision: str,
          keep_fraction: float = 1.0):
    """Adam from the seed's initialisation over ``batches`` (a list of
    ``(adj, x, mask, labels)``): returns the loss before each step, the
    first step's gradient, and the parameters before and after.

    ``keep_fraction < 1`` takes each step's mean loss over the leading part
    of the batch only: the half-batch fault, planted in the reference."""
    vg = jax.jit(jax.value_and_grad(functools.partial(
        loss_fn, task=gcn["task"], precision=precision)))
    params0 = init_params(seed, gcn)
    params, state = params0, adam_init(params0)
    losses, grad1 = [], None
    for adj, x, mask, labels in batches:
        keep = int(round(len(x) * keep_fraction))
        loss, grads = vg(params, adj[:keep], x[:keep], mask[:keep],
                         labels[:keep])
        losses.append(float(loss))
        grad1 = grads if grad1 is None else grad1
        params, state = adam_step(params, grads, state, opt)
    return {"losses": losses, "grad1": jax.device_get(grad1),
            "params0": jax.device_get(params0),
            "params": jax.device_get(params)}


def serve_logits(seed: int, gcn: dict, mols, n_max: int, *, precision: str,
                 block: int = 512):
    """Logits ``(len(mols), n_tasks)`` with per-molecule batch norm, in
    blocks of ``block`` molecules (the last block padded, so one program
    serves all)."""
    params = init_params(seed, gcn)
    fwd = jax.jit(functools.partial(forward, bn_mode="sample",
                                    precision=precision))
    out = []
    for i in range(0, len(mols), block):
        part = list(mols[i:i + block])
        n_real = len(part)
        part += part[:1] * (block - n_real)
        adj, x, mask = dense_batch(part, n_max, gcn["channels"],
                                   gcn["n_features"])
        out.append(np.asarray(fwd(params, adj, x, mask))[:n_real])
    return np.concatenate(out) if out else np.zeros((0, gcn["n_tasks"]),
                                                    np.float32)
