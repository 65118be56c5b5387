"""Synthetic graph datasets: molecules shaped like the paper's (Table I),
PPI-shaped protein graphs (``ppi_like``) and one giant graph for the
sampled tier (``reddit_like``).

Tox21 and Reaction100 are not redistributable here, so we generate graphs with
the same statistics the paper reports — max dim 50 nodes, bond-degree ≤ 4,
multiple bond-type channels — and label them with a fixed hidden "teacher" GCN
so that training has real signal (loss decreases measurably; tests assert it).
The batching/padding path is exactly what a real featurizer would feed.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np
import jax.numpy as jnp

from repro.core.csc import CSCGraph, csc_from_edges
from repro.core.formats import BatchedCOO, coo_from_lists, powerlaw_degrees


@dataclasses.dataclass(frozen=True)
class GraphSample:
    rows: list[np.ndarray]      # per channel
    cols: list[np.ndarray]
    n_nodes: int
    features: np.ndarray        # (n_nodes, n_features)
    label: np.ndarray


@dataclasses.dataclass(frozen=True)
class GraphDatasetSpec:
    n_samples: int = 1024
    max_nodes: int = 50          # paper Table I: Max dim = 50
    min_nodes: int = 8
    max_degree: int = 4          # chemistry: ≤4 bonds
    channels: int = 4            # bond types
    n_features: int = 62
    n_tasks: int = 12
    task: str = "multitask_binary"
    size_dist: str = "uniform"   # node-count distribution: "uniform" over
                                 # [min_nodes, max_nodes], or "skewed" — a
                                 # clipped lognormal whose median sits well
                                 # below max_nodes, matching the paper's
                                 # Table I gap between Avg dim and Max dim
                                 # (most molecules are small; the serving
                                 # scheduler's bucketing exploits exactly
                                 # this skew)
    seed: int = 0

    @staticmethod
    def tox21_like(n_samples: int = 1024, **kw) -> "GraphDatasetSpec":
        return GraphDatasetSpec(n_samples=n_samples, n_tasks=12,
                                task="multitask_binary", **kw)

    @staticmethod
    def reaction100_like(n_samples: int = 1024, **kw) -> "GraphDatasetSpec":
        return GraphDatasetSpec(n_samples=n_samples, n_tasks=100,
                                task="multiclass", **kw)


def _random_molecule(rng: np.random.Generator, spec: GraphDatasetSpec):
    """Random connected graph with chemistry-like degree bound, bond types
    assigned per edge; channel 0 additionally carries the self-loops
    (a_uu = 1, paper §II-A)."""
    if spec.size_dist == "skewed":
        # median ≈ min + (max-min)/4, long right tail clipped at max_nodes
        med = spec.min_nodes + (spec.max_nodes - spec.min_nodes) / 4
        n = int(np.clip(round(rng.lognormal(np.log(med), 0.45)),
                        spec.min_nodes, spec.max_nodes))
    else:
        n = int(rng.integers(spec.min_nodes, spec.max_nodes + 1))
    deg = np.zeros(n, np.int32)
    edges = []
    for v in range(1, n):                       # random spanning tree
        u = int(rng.integers(0, v))
        if deg[u] < spec.max_degree and deg[v] < spec.max_degree:
            edges.append((u, v))
            deg[u] += 1
            deg[v] += 1
    extra = int(rng.integers(0, max(1, n // 4)))  # rings
    for _ in range(extra):
        u, v = rng.integers(0, n, 2)
        if u != v and deg[u] < spec.max_degree and deg[v] < spec.max_degree:
            edges.append((int(u), int(v)))
            deg[u] += 1
            deg[v] += 1
    bond = rng.integers(0, spec.channels, len(edges))
    rows = [[] for _ in range(spec.channels)]
    cols = [[] for _ in range(spec.channels)]
    for (u, v), ch in zip(edges, bond):
        rows[ch] += [u, v]
        cols[ch] += [v, u]
    for v in range(n):                          # self loops on channel 0
        rows[0].append(v)
        cols[0].append(v)
    atom_type = rng.integers(0, spec.n_features, n)
    feats = np.zeros((n, spec.n_features), np.float32)
    feats[np.arange(n), atom_type] = 1.0
    return (
        [np.asarray(r, np.int32) for r in rows],
        [np.asarray(c, np.int32) for c in cols],
        n,
        feats,
    )


def _teacher_logits(sample, spec: GraphDatasetSpec, w1, w2):
    """Fixed random 1-layer GCN teacher → learnable labels."""
    rows, cols, n, feats = sample
    a = np.zeros((n, n), np.float32)
    for r, c in zip(rows, cols):
        a[r, c] = 1.0
    h = np.maximum(a @ (feats @ w1), 0)
    return h.sum(0) @ w2


def generate(spec: GraphDatasetSpec) -> list[GraphSample]:
    rng = np.random.default_rng(spec.seed)
    w1 = rng.normal(size=(spec.n_features, 32)).astype(np.float32) * 0.3
    w2 = rng.normal(size=(32, spec.n_tasks)).astype(np.float32) * 0.3
    out = []
    for _ in range(spec.n_samples):
        rows, cols, n, feats = _random_molecule(rng, spec)
        logits = _teacher_logits((rows, cols, n, feats), spec, w1, w2)
        if spec.task == "multitask_binary":
            label = (logits > np.median(logits)).astype(np.float32)
        else:
            label = np.asarray(int(np.argmax(logits)) % spec.n_tasks)
        out.append(GraphSample(rows, cols, n, feats, label))
    return out


@dataclasses.dataclass(frozen=True)
class PPISpec:
    """Protein–protein-interaction graphs at the published totals of PPI
    (Zitnik & Leskovec 2017, as tabled in arXiv:1710.10903 Table 1): 20
    training graphs of 44,906 nodes in all, 28.8 directed edges per node
    on average, 50 features and 121 labels per node. The spread of graph
    sizes, the degree exponent and the label density are assumptions: the
    paper gives none of them."""

    n_graphs: int = 20
    total_nodes: int = 44_906
    min_nodes: int = 590           # assumed
    max_nodes: int = 3_480         # assumed
    avg_degree: float = 28.8       # directed edges per node, no self loops
    degree_alpha: float = 0.5      # assumed: power-law exponent
    n_features: int = 50
    n_labels: int = 121
    label_density: float = 0.3     # assumed
    seed: int = 0

    def dataset_spec(self) -> GraphDatasetSpec:
        """The ``GraphDatasetSpec`` that ``batches`` reads: one channel."""
        return GraphDatasetSpec(
            n_samples=self.n_graphs, max_nodes=self.max_nodes,
            min_nodes=self.min_nodes, channels=1,
            n_features=self.n_features, n_tasks=self.n_labels,
            task="node_multilabel", seed=self.seed)


def _ppi_sizes(rng: np.random.Generator, spec: PPISpec) -> np.ndarray:
    """Node counts of the graphs: one at each end of the range, the rest
    drawn uniformly and scaled so that all of them sum to the total."""
    n = spec.n_graphs
    rest = spec.total_nodes - spec.min_nodes - spec.max_nodes
    u = rng.uniform(spec.min_nodes, spec.max_nodes, n - 2)
    sizes = np.clip(np.rint(u * rest / u.sum()), spec.min_nodes,
                    spec.max_nodes).astype(np.int64)
    while sizes.sum() != rest:                  # rounding and clipping
        room = (sizes < spec.max_nodes if sizes.sum() < rest
                else sizes > spec.min_nodes)
        i = rng.choice(np.flatnonzero(room))
        sizes[i] += 1 if sizes.sum() < rest else -1
    out = np.concatenate([[spec.min_nodes, spec.max_nodes], sizes])
    return rng.permutation(out)


def _ppi_graph(rng: np.random.Generator, n: int, spec: PPISpec, w_teach):
    """One graph: Chung–Lu edges on power-law weights, made symmetric and
    without duplicates, ``round(avg_degree · n)`` directed edges, then a
    self loop on every node; features, and labels from a one-hop teacher
    thresholded per label at ``label_density``."""
    weights = powerlaw_degrees(rng, n, spec.avg_degree, spec.degree_alpha)
    # + 1: the tail's weights round to 0, and every node should have edges
    p = (weights + 1.0) / (weights + 1.0).sum()
    want = int(round(spec.avg_degree * n / 2))   # undirected pairs
    pairs = np.zeros((0,), np.int64)
    while len(pairs) < want:
        u, v = rng.choice(n, (2, 2 * want), p=p)
        keep = u != v
        key = np.minimum(u, v)[keep] * n + np.maximum(u, v)[keep]
        pairs = np.concatenate([pairs, key])
        _, first = np.unique(pairs, return_index=True)
        pairs = pairs[np.sort(first)]           # first draws win, in order
    pairs = pairs[:want]
    a, b = pairs // n, pairs % n
    loops = np.arange(n)
    rows = np.concatenate([a, b, loops]).astype(np.int32)
    cols = np.concatenate([b, a, loops]).astype(np.int32)
    feats = rng.standard_normal((n, spec.n_features)).astype(np.float32)
    agg = np.zeros_like(feats)
    np.add.at(agg, rows, feats[cols])
    agg /= np.bincount(rows, minlength=n)[:, None]
    logits = agg @ w_teach
    cut = np.quantile(logits, 1.0 - spec.label_density, axis=0)
    labels = (logits > cut).astype(np.float32)
    return GraphSample([rows], [cols], n, feats, labels)


def ppi_like(spec: PPISpec = PPISpec()) -> list[GraphSample]:
    """The PPI-shaped training graphs of ``spec``, a pure function of
    ``spec.seed``. Each sample's ``label`` is ``(n_nodes, n_labels)``."""
    rng = np.random.default_rng(spec.seed)
    w_teach = rng.standard_normal((spec.n_features, spec.n_labels))
    return [_ppi_graph(rng, int(n), spec, w_teach)
            for n in _ppi_sizes(rng, spec)]


def batches(
    data: list[GraphSample],
    spec: GraphDatasetSpec,
    batch_size: int,
    *,
    m_pad: int | None = None,
    nnz_pad: int | None = None,
    drop_remainder: bool = True,
    seed: int = 0,
    epochs: int = 1,
    start_epoch: int = 0,
) -> Iterator[dict]:
    """Padding batch iterator: pads every sample to the dataset max (static
    shapes → one compiled step), yields per-channel BatchedCOO + features,
    labels (a node task's padded to ``m_pad`` rows), and as host integers
    ``edges``, the real edges over every sample and channel, and
    ``edge_slots``, the padded slots that hold them.

    Each epoch's shuffle is a pure function of ``(seed, epoch)`` — NOT one
    sequentially-consumed RNG — so a checkpoint-restored run can rebuild any
    epoch's exact batch order without replaying the epochs before it:
    ``batches(..., start_epoch=e)`` reproduces the tail of a longer stream
    bitwise (the resume contract ``GCNTrainer.fit`` fast-forwards on)."""
    m_pad = m_pad or -(-max(s.n_nodes for s in data) // 8) * 8
    # Pad nnz to the DATASET max by default so every batch has identical
    # static shapes (single XLA compilation across the epoch).
    if nnz_pad is None:
        nnz_pad = -(-max(
            max(len(s.rows[ch]) for ch in range(spec.channels))
            for s in data) // 8) * 8
    for epoch in range(start_epoch, start_epoch + epochs):
        idx = np.random.default_rng((seed, epoch)).permutation(len(data))
        n_full = len(idx) // batch_size
        for i in range(n_full if drop_remainder else n_full + 1):
            sel = idx[i * batch_size:(i + 1) * batch_size]
            if len(sel) == 0:
                continue
            samples = [data[j] for j in sel]
            adj = []
            for ch in range(spec.channels):
                triples = [
                    (s.rows[ch], s.cols[ch],
                     np.ones(len(s.rows[ch]), np.float32))
                    for s in samples
                ]
                adj.append(coo_from_lists(
                    triples, [s.n_nodes for s in samples], nnz_pad=nnz_pad))
            feats = np.zeros((len(samples), m_pad, spec.n_features), np.float32)
            for k, s in enumerate(samples):
                feats[k, :s.n_nodes] = s.features
            if spec.task == "node_multilabel":
                labels = np.zeros((len(samples), m_pad, spec.n_tasks),
                                  np.float32)
                for k, s in enumerate(samples):
                    labels[k, :s.n_nodes] = s.label
            else:
                labels = np.stack([s.label for s in samples])
            yield {
                "adj": adj,
                "x": jnp.asarray(feats),
                "n_nodes": jnp.asarray([s.n_nodes for s in samples],
                                       jnp.int32),
                "labels": jnp.asarray(labels),
                "edges": sum(len(s.rows[ch]) for s in samples
                             for ch in range(spec.channels)),
                "edge_slots": len(samples) * spec.channels * nnz_pad,
            }


# -- giant-graph tier (DESIGN.md §14) -----------------------------------


@dataclasses.dataclass(frozen=True)
class NodeClassData:
    """One giant node-classification graph for the sampled tier: the static
    CSC sampling structure, per-node features/labels, and a train/val seed
    split. Everything is host-side NumPy — features enter the device only
    through the sampled-minibatch gather."""

    csc: CSCGraph
    features: np.ndarray   # (n_nodes, n_features) float32
    labels: np.ndarray     # (n_nodes,) int32 class ids
    train_ids: np.ndarray  # (n_train,) int64
    val_ids: np.ndarray    # (n_val,) int64
    n_classes: int


def reddit_like(
    n_nodes: int = 100_000,
    *,
    n_classes: int = 8,
    n_features: int = 64,
    avg_deg: int = 12,
    alpha: float = 1.2,
    homophily: float = 0.7,
    noise: float = 1.0,
    val_frac: float = 0.1,
    seed: int = 0,
) -> NodeClassData:
    """Synthetic "reddit-like" powerlaw node-classification graph.

    The two properties the sampled tier exercises, built in O(E + N)
    vectorized passes (a 100k-node / ~1M-edge graph generates in ~a second):

    * **Zipf-hot hubs** — per-node in-degrees follow the same powerlaw as
      ``random_powerlaw_batch`` (shared :func:`powerlaw_degrees` helper), so
      a handful of hub nodes appear in most sampled neighborhoods: exactly
      the skew the hot-node feature cache and the autotuner's ``max_deg``
      pricing are built for.
    * **Learnable labels** — planted partition: each edge's source is drawn
      from the destination's own class with probability ``homophily`` (else
      uniformly), and features are a noisy class centroid, so neighbor
      aggregation genuinely helps and a sampled GCN's accuracy climbs well
      above ``1 / n_classes`` (the e2e test's signal).

    Self-loops are added on every node (paper §II-A's ``a_uu = 1``), so a
    destination's own features survive fanout sampling.
    """
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, n_classes, n_nodes).astype(np.int32)
    # class-sorted node table: same-class sources are one fancy-index away
    order = np.argsort(labels, kind="stable")
    class_sizes = np.bincount(labels, minlength=n_classes)
    class_offsets = np.zeros(n_classes + 1, np.int64)
    np.cumsum(class_sizes, out=class_offsets[1:])
    # powerlaw IN-degrees: hubs are hot as destinations AND (by symmetry of
    # the uniform branch) as sampled sources
    deg = powerlaw_degrees(rng, n_nodes, avg_deg, alpha)
    dst = np.repeat(np.arange(n_nodes, dtype=np.int64), deg)
    e = len(dst)
    same = rng.random(e) < homophily
    dst_cls = labels[dst]
    within = rng.integers(0, np.maximum(class_sizes[dst_cls], 1))
    src = np.where(
        same,
        order[class_offsets[dst_cls] + within],   # same-class source
        rng.integers(0, n_nodes, e),              # long-range source
    )
    loops = np.arange(n_nodes, dtype=np.int64)
    src = np.concatenate([src, loops])
    dst = np.concatenate([dst, loops])
    csc = csc_from_edges(src, dst, n_nodes)
    centroids = rng.standard_normal((n_classes, n_features))
    features = (centroids[labels]
                + noise * rng.standard_normal((n_nodes, n_features))
                ).astype(np.float32)
    perm = rng.permutation(n_nodes).astype(np.int64)
    n_val = int(n_nodes * val_frac)
    return NodeClassData(csc=csc, features=features, labels=labels,
                         train_ids=perm[n_val:], val_ids=perm[:n_val],
                         n_classes=n_classes)
