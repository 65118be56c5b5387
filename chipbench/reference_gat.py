"""Plain float32 GAT on PPI-shaped graphs: the reference ``ppi_gat.train``
is compared with.

Written from the paper (arXiv:1710.10903 §2.1, §3.3) in ``jax.numpy`` with
a dense attention matrix per graph and head, and no kernel, sparse format or
batching of the program under test; it imports nothing of the program.
Weights come from its own initialisation from the seed, graphs from their
raw edge lists.

- One head computes ``h = X W_k``, ``e_ij = LeakyReLU_0.2(a_dst·h_i +
  a_src·h_j)`` for every edge ``j → i`` (self loops included), ``-inf``
  off the edges, ``alpha_ij = softmax_j(e_ij)`` and ``out_i = Σ_j alpha_ij
  h_j``; a row with no edge attends to nothing and outputs 0.
- Hidden layers concatenate their heads, add the bias, add ``X W_skip``
  where the layer has a skip projection, then take ELU and the node mask.
- The last layer adds the bias to each head's output and averages the
  heads into each node's logits; the loss is the mean sigmoid
  cross-entropy over real nodes × labels.
- Adam without weight decay or clipping (``reference.adam_step``).

Departure from the authors' code: their attention logits ``a_dst·h_i``
and ``a_src·h_j`` each carry a bias (a 1-wide convolution); here, as in
the program, they carry none.

Initialisation follows the scheme the configuration states for ``--seed``:
the seed's key split into one key per layer and one spare; each layer's key
split into three (four with a skip projection), for ``w`` ``(heads, n_in,
d_head)``, ``a_src`` and ``a_dst`` ``(heads, d_head)`` and ``w_skip``
``(n_in, heads · d_head)``, each uniform in ``±1/sqrt(n_in)``; biases zero.

``precision`` selects how every matrix product is computed, as in
``reference.py``: ``"highest"`` (float32), ``"high"`` (the three-pass
bfloat16 split) or ``"bf16"`` (one pass), the lower two written out.

The first gradient is not continuous in the weights: where an attention
logit sits within float32 rounding of LeakyReLU's kink, two float32
computations of the same model may take either slope there, and each
gradient is as right as the other. ``train(..., kinks=True)`` gives the
change of the first gradient when each such logit's slope is taken from
the other side, so a comparison can admit either side of each
(``admissible``).
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import adam_init, adam_step, matmul

NEG_SLOPE = 0.2
# logits nearer the kink than this share of their layer's RMS over edges
# may take either slope (float32 rounding, not the model, decides); at most
# KINK_MAX of them, the nearest, are given both
KINK_TOL = 1e-5
KINK_MAX = 6


def init_params(seed: int, gcn: dict):
    """``gcn`` holds ``n_features``, ``conv_widths``, ``heads`` (one per
    layer) and ``skip`` (the layers with a skip projection); returns the
    parameter tree, in the program's layout so leaves match by path."""
    widths, heads = list(gcn["conv_widths"]), list(gcn["heads"])
    keys = jax.random.split(jax.random.key(seed), len(widths) + 1)
    convs, n_in = [], gcn["n_features"]
    for i, (n_out, k) in enumerate(zip(widths, heads)):
        skip = i in gcn["skip"]
        ks = jax.random.split(keys[i], 4 if skip else 3)
        d = n_out // k
        scale = 1.0 / jnp.sqrt(n_in)

        def uniform(key, shape):
            return jax.random.uniform(key, shape, jnp.float32, -scale, scale)

        conv = {"w": uniform(ks[0], (k, n_in, d)),
                "a_src": uniform(ks[1], (k, d)),
                "a_dst": uniform(ks[2], (k, d)),
                "b": jnp.zeros((n_out,), jnp.float32)}
        if skip:
            conv["w_skip"] = uniform(ks[3], (n_in, n_out))
        convs.append(conv)
        n_in = n_out
    return {"convs": convs}


def dense_batch(graphs, n_max: int, n_features: int, n_labels: int):
    """Dense inputs of a list of graphs (objects with ``rows``, ``cols``,
    ``n_nodes``, ``features``, ``label``), each padded to ``n_max`` nodes:
    edge mask ``(B, n_max, n_max)`` with ``[i, j]`` set for an edge
    ``j → i``, features ``(B, n_max, F)``, node mask ``(B, n_max, 1)`` and
    labels ``(B, n_max, L)``."""
    b = len(graphs)
    adj = np.zeros((b, n_max, n_max), np.float32)
    x = np.zeros((b, n_max, n_features), np.float32)
    mask = np.zeros((b, n_max, 1), np.float32)
    labels = np.zeros((b, n_max, n_labels), np.float32)
    for i, g in enumerate(graphs):
        adj[i, np.asarray(g.rows[0]), np.asarray(g.cols[0])] = 1.0
        x[i, :g.n_nodes] = g.features
        mask[i, :g.n_nodes] = 1.0
        labels[i, :g.n_nodes] = g.label
    return adj, x, mask, labels


def attention(h, a_src, a_dst, adj, precision: str, flip=None):
    """``(heads, n, d)`` outputs of every head over one graph's edge mask
    ``adj`` ``(n, n)``: the masked dense softmax, then ``alpha @ h``; and
    the logits before LeakyReLU ``(heads, n, n)``. ``flip`` (boolean, as
    the logits) takes the other side's slope at the logits it marks."""
    s_src = matmul("knd,kd->kn", h, a_src, precision)
    s_dst = matmul("knd,kd->kn", h, a_dst, precision)
    pre = s_dst[:, :, None] + s_src[:, None, :]
    up = pre >= 0
    if flip is not None:
        up = up != flip
    e = jnp.where(up, pre, NEG_SLOPE * pre)
    edge = adj[None] > 0
    e = jnp.where(edge, e, -jnp.inf)
    top = jax.lax.stop_gradient(jnp.max(e, axis=-1, keepdims=True))
    z = jnp.where(edge, jnp.exp(e - jnp.where(edge, top, 0.0)), 0.0)
    alpha = z / jnp.maximum(jnp.sum(z, axis=-1, keepdims=True), 1e-30)
    return matmul("kij,kjd->kid", alpha, h, precision), pre


def _forward(params, adj, x, mask, precision: str, flips):
    """Per-node logits ``(n, n_labels)`` of one dense graph, and each
    layer's attention logits before LeakyReLU; ``flips``: one ``flip`` per
    layer, or ``None``."""
    convs, h, pres = params["convs"], x, []
    for i, conv in enumerate(convs):
        k, _, d = conv["w"].shape
        hk = matmul("nf,kfd->knd", h, conv["w"], precision)
        out, pre = attention(hk, conv["a_src"], conv["a_dst"], adj,
                             precision, None if flips is None else flips[i])
        pres.append(pre)
        out = out + conv["b"].reshape(k, 1, d)
        if i == len(convs) - 1:
            return jnp.mean(out, axis=0), pres
        out = out.transpose(1, 0, 2).reshape(-1, k * d)
        if "w_skip" in conv:
            out = out + matmul("nf,fo->no", h, conv["w_skip"], precision)
        h = jax.nn.elu(out) * mask
    raise ValueError("no layers")


def forward(params, adj, x, mask, *, precision: str):
    """Per-node logits ``(n, n_labels)`` of one dense graph."""
    return _forward(params, adj, x, mask, precision, None)[0]


def _loss(params, adj, x, mask, labels, precision: str, flips):
    """The loss, and each layer's attention logits before LeakyReLU
    ``(B, heads, n, n)``."""
    z, pres = jax.vmap(
        lambda a, x, m, f: _forward(params, a, x, m, precision, f),
        in_axes=(0, 0, 0, None if flips is None else 0))(adj, x, mask, flips)
    per = jnp.maximum(z, 0) - z * labels + jnp.log1p(jnp.exp(-jnp.abs(z)))
    return jnp.sum(per * mask) / (jnp.sum(mask) * z.shape[-1]), pres


def loss_fn(params, adj, x, mask, labels, *, precision: str):
    """Mean sigmoid cross-entropy over the real nodes × labels of a batch
    of dense graphs."""
    return _loss(params, adj, x, mask, labels, precision, None)[0]


@functools.cache
def _step(precision: str):
    """The loss and gradient of a batch, jitted, with the logit at flat
    index ``index`` of layer ``layer``'s ``(B, heads, n, n)`` taking its
    slope from the other side (``layer`` -1: none); and per layer the
    ``KINK_MAX`` edge logits nearest the kink, as distances over the
    layer's RMS over edges and flat indices."""
    def step(params, adj, x, mask, labels, layer, index):
        shapes = [(x.shape[0], c["w"].shape[0]) + adj.shape[1:]
                  for c in params["convs"]]
        flips = [jnp.zeros(s, bool).ravel().at[index].set(layer == i)
                 .reshape(s) for i, s in enumerate(shapes)]
        (loss, pres), grads = jax.value_and_grad(_loss, has_aux=True)(
            params, adj, x, mask, labels, precision, flips)
        near = []
        for pre in pres:
            edges = jnp.broadcast_to(adj[:, None] > 0, pre.shape)
            rms = jnp.sqrt(jnp.sum(jnp.where(edges, pre * pre, 0.0))
                           / jnp.sum(edges))
            dist = jnp.where(edges, jnp.abs(pre) / rms, jnp.inf).ravel()
            neg, at = jax.lax.top_k(-dist, KINK_MAX)
            near.append((-neg, at))
        return loss, grads, near

    return jax.jit(step)


def admissible(grad1, changes):
    """Every first gradient the kinks admit: ``grad1`` plus the changes of
    any subset of them (each kink's change taken alone)."""
    for picks in itertools.product((False, True), repeat=len(changes)):
        yield jax.tree.map(
            lambda g, *cs: g + sum((c for c, p in zip(cs, picks) if p),
                                   np.zeros_like(g)),
            grad1, *changes)


def train(seed: int, gcn: dict, opt: dict, batches, *, precision: str,
          keep_fraction: float = 1.0, kinks: bool = False):
    """Adam from the seed's initialisation over ``batches`` (a list of
    ``(adj, x, mask, labels)``), as ``reference.train``: the loss before
    each step, the first gradient, the parameters before and after.
    ``keep_fraction < 1`` takes each step's loss over the leading graphs of
    the batch only (the half-batch fault). ``kinks`` adds
    ``kink_changes``: for each attention logit of the first batch within
    ``KINK_TOL`` of the kink (at most ``KINK_MAX``, the nearest), the change
    of the first gradient when that logit takes the other side's slope."""
    step = _step(precision)
    params0 = init_params(seed, gcn)
    params, state = params0, adam_init(params0)
    losses, grad1, changes = [], None, []
    for adj, x, mask, labels in batches:
        keep = int(round(len(x) * keep_fraction))
        args = (adj[:keep], x[:keep], mask[:keep], labels[:keep])
        loss, grads, near = step(params, *args, -1, 0)
        losses.append(float(loss))
        if grad1 is None:
            grad1 = jax.device_get(grads)
            near = sorted((float(d), layer, int(i))
                          for layer, (ds, at) in enumerate(near)
                          for d, i in zip(np.asarray(ds), np.asarray(at))
                          if d < KINK_TOL)[:KINK_MAX] if kinks else []
            changes = [jax.tree.map(np.subtract, jax.device_get(
                step(params, *args, layer, i)[1]), grad1)
                for _, layer, i in near]
        params, state = adam_step(params, grads, state, opt)
    return {"losses": losses, "grad1": grad1, "kink_changes": changes,
            "params0": jax.device_get(params0),
            "params": jax.device_get(params)}
