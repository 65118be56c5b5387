"""Giant-graph tier end-to-end (DESIGN.md §14): train a node-classification
GCN on a synthetic 100k-node "reddit-like" powerlaw graph with CSC
neighbor-sampled minibatches, a hot-node feature cache, and the block-aware
``impl="auto"`` kernel dispatch.

    PYTHONPATH=src python examples/node_classification.py --nodes 100000

Prints per-epoch train metrics, the held-out validation accuracy (computed
through the same sampled-block forward), the cache hit rate and the number
of distinct compiled step programs (bounded by the bucket ladder, not the
epoch length).
"""
import argparse
import tempfile

import jax
import numpy as np

from repro.core.gcn import GCNConfig, apply_gcn_blocks
from repro.data.graphs import reddit_like
from repro.optim import AdamConfig
from repro.sampling import (
    FeatureStore,
    HotNodeCache,
    SampledNodeLoader,
    static_hot_ids,
)
from repro.training.trainer import GCNTrainer, TrainerConfig


def evaluate(params, cfg, loader, *, epochs_seed: int = 10_000):
    """Validation accuracy through the sampled forward: one pass over the
    loader's seed set (an out-of-range 'epoch' keeps the eval sample
    independent of any training epoch's randomness)."""
    hits = total = 0
    for batch in loader.epoch(epochs_seed):
        logits = apply_gcn_blocks(
            params, cfg, [b.adj for b in batch.blocks], batch.x,
            m_pads=tuple(b.m_pad for b in batch.blocks))
        pred = np.asarray(jax.numpy.argmax(logits[:len(batch.labels)], -1))
        hits += int((pred == batch.labels).sum())
        total += len(batch.labels)
    return hits / max(total, 1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=100_000)
    ap.add_argument("--classes", type=int, default=8)
    ap.add_argument("--features", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=512)
    ap.add_argument("--fanouts", type=int, nargs="+", default=[10, 5])
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--cache-nodes", type=int, default=4096)
    args = ap.parse_args()

    print(f"generating reddit-like graph: {args.nodes} nodes ...")
    data = reddit_like(args.nodes, n_classes=args.classes,
                       n_features=args.features)
    print(f"  {data.csc.n_edges} edges, "
          f"max in-degree {int(data.csc.in_degrees().max())}")

    store = FeatureStore(data.features)
    cache = HotNodeCache(
        store, args.cache_nodes, policy="static",
        hot_ids=static_hot_ids(data.csc.in_degrees(), args.cache_nodes))
    loader = SampledNodeLoader(
        data.csc, data.features, data.labels, data.train_ids,
        fanouts=args.fanouts, batch_size=args.batch_size, cache=cache)
    val_loader = SampledNodeLoader(
        data.csc, data.features, data.labels, data.val_ids,
        fanouts=args.fanouts, batch_size=args.batch_size, cache=cache)

    cfg = GCNConfig(n_features=args.features, channels=1,
                    conv_widths=(64,) * len(args.fanouts),
                    n_tasks=args.classes, task="multiclass", k_pad=None)
    with tempfile.TemporaryDirectory() as ckpt:
        trainer = GCNTrainer(
            cfg, AdamConfig(lr=5e-3),
            TrainerConfig(checkpoint_dir=ckpt, checkpoint_every=10_000,
                          log_every=20))
        params, _, metrics = trainer.fit_sampled(
            loader, epochs=args.epochs,
            on_metrics=lambda e, r: print(
                f"  epoch {e}: loss {r['loss']:.4f} acc {r['acc']:.3f} "
                f"programs {r['programs']}"))

    val_acc = evaluate(params, cfg, val_loader)
    print(f"val accuracy: {val_acc:.3f} "
          f"(chance {1.0 / args.classes:.3f})")
    print(f"cache hit rate: {cache.hit_rate():.3f} over "
          f"{len(cache)} cached rows")
    print(f"compiled programs: {metrics['programs']}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
