"""Giant-graph sampled tier (DESIGN.md §14): CSC structure, neighbor
sampling, bucketing, the feature cache and the end-to-end sampled trainer.

``hypothesis`` is an optional dev dependency (see pyproject.toml extras);
the property tests are defined only when it is installed, so tier-1
collection never fails on it and the deterministic tests always run.
"""
import tempfile

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ModuleNotFoundError:
    HAS_HYPOTHESIS = False

from repro.core.csc import CSCGraph, csc_from_edges, csc_to_coo
from repro.data.graphs import reddit_like
from repro.observability.metrics import MetricsRegistry
from repro.sampling import (
    FeatureStore,
    HotNodeCache,
    ItemSampler,
    Prefetcher,
    SampledNodeLoader,
    block_ladders,
    bucket_for,
    neighbor_sample,
    static_hot_ids,
)


def _random_graph(seed, n_nodes, n_edges):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_nodes, n_edges).astype(np.int64)
    dst = rng.integers(0, n_nodes, n_edges).astype(np.int64)
    return csc_from_edges(src, dst, n_nodes), src, dst


# ---------------------------------------------------------------------------
# CSC structure
# ---------------------------------------------------------------------------

def test_csc_round_trip_bitwise():
    """edges → CSC → edges → CSC is bitwise stable (csc_to_coo emits the
    canonical dst-major order, which csc_from_edges' counting sort
    preserves)."""
    csc, src, dst = _random_graph(0, 23, 150)
    src2, dst2 = csc_to_coo(csc)
    csc2 = csc_from_edges(src2, dst2, csc.n_nodes)
    np.testing.assert_array_equal(csc.indptr, csc2.indptr)
    np.testing.assert_array_equal(csc.indices, csc2.indices)
    # same multiset of edges as the original (order-insensitive)
    want = sorted(zip(dst.tolist(), src.tolist()))
    got = sorted(zip(dst2.tolist(), src2.tolist()))
    assert want == got


def test_csc_degrees_and_neighbors():
    #   0 ← 1, 0 ← 2, 1 ← 2, 2 ← 2 (self-loop)
    src = np.array([1, 2, 2, 2])
    dst = np.array([0, 0, 1, 2])
    csc = csc_from_edges(src, dst, 3)
    np.testing.assert_array_equal(csc.in_degrees(), [2, 1, 1])
    np.testing.assert_array_equal(np.sort(csc.in_neighbors(0)), [1, 2])
    np.testing.assert_array_equal(csc.in_neighbors(2), [2])


def test_csc_rejects_out_of_range_endpoints():
    with pytest.raises(ValueError):
        csc_from_edges(np.array([0, 5]), np.array([0, 1]), 3)
    with pytest.raises(ValueError):
        csc_from_edges(np.array([0, 1]), np.array([0, -1]), 3)


# ---------------------------------------------------------------------------
# Neighbor sampling: determinism, fanout bounds, compaction, chaining
# ---------------------------------------------------------------------------

def _block_edges(block):
    """(local rows, local cols) of the real (non-padding) entries."""
    nnz = block.nnz
    return (np.asarray(block.adj.row_ids[0][:nnz]),
            np.asarray(block.adj.col_ids[0][:nnz]))


def _assert_valid_blocks(csc, seeds, fanouts, blocks):
    assert len(blocks) == len(fanouts)
    np.testing.assert_array_equal(blocks[-1].dst_ids(), seeds)
    for i, (block, fanout) in enumerate(zip(blocks, fanouts)):
        rows, cols = _block_edges(block)
        # compacted ids: unique, dst set is the prefix of the src set
        assert len(np.unique(block.src_ids)) == len(block.src_ids)
        np.testing.assert_array_equal(block.src_ids[:block.n_dst],
                                      block.dst_ids())
        # fanout bound, per destination AND via the padded-format max_deg
        if len(rows):
            assert np.bincount(rows).max() <= fanout
        assert block.max_deg <= fanout
        # every sampled edge exists in the global graph
        for r, c in zip(rows[:64], cols[:64]):
            g_dst = int(block.src_ids[r])
            g_src = int(block.src_ids[c])
            assert g_src in csc.in_neighbors(g_dst), (i, g_src, g_dst)
        # chaining invariant the layered forward slices on
        if i + 1 < len(blocks):
            np.testing.assert_array_equal(block.dst_ids(),
                                          blocks[i + 1].src_ids)


def test_neighbor_sample_invariants():
    csc, _, _ = _random_graph(1, 60, 500)
    seeds = np.array([3, 17, 41, 8])
    fanouts = [4, 2]
    blocks = neighbor_sample(csc, seeds, fanouts, seed=(0, 0, 0))
    _assert_valid_blocks(csc, seeds, fanouts, blocks)


def test_neighbor_sample_deterministic():
    """Bitwise-equal blocks from the same (csc, seeds, fanouts, seed) —
    the addressability the checkpoint-resume path re-derives batches from."""
    csc, _, _ = _random_graph(2, 80, 700)
    seeds = np.arange(0, 80, 7)
    a = neighbor_sample(csc, seeds, [5, 3], seed=(9, 2, 4))
    b = neighbor_sample(csc, seeds, [5, 3], seed=(9, 2, 4))
    c = neighbor_sample(csc, seeds, [5, 3], seed=(9, 2, 5))
    for ba, bb in zip(a, b):
        np.testing.assert_array_equal(ba.src_ids, bb.src_ids)
        np.testing.assert_array_equal(np.asarray(ba.adj.row_ids),
                                      np.asarray(bb.adj.row_ids))
        np.testing.assert_array_equal(np.asarray(ba.adj.col_ids),
                                      np.asarray(bb.adj.col_ids))
        np.testing.assert_array_equal(np.asarray(ba.adj.values),
                                      np.asarray(bb.adj.values))
    # a different batch coordinate draws a different sample
    assert any(
        len(ba.src_ids) != len(bc.src_ids)
        or not np.array_equal(ba.src_ids, bc.src_ids)
        for ba, bc in zip(a, c))


def test_neighbor_sample_rejects_duplicate_seeds():
    csc, _, _ = _random_graph(3, 10, 40)
    with pytest.raises(ValueError, match="unique"):
        neighbor_sample(csc, np.array([1, 1, 2]), [2])


def test_neighbor_sample_mean_normalization():
    """With normalize="mean" each destination's incoming values sum to 1
    (its sampled-degree average) — zero-degree destinations contribute
    nothing."""
    csc, _, _ = _random_graph(4, 40, 300)
    seeds = np.arange(10)
    (block,) = neighbor_sample(csc, seeds, [3], seed=0)
    rows, _ = _block_edges(block)
    vals = np.asarray(block.adj.values[0][:block.nnz])
    sums = np.zeros(block.n_dst)
    np.add.at(sums, rows, vals)
    deg = np.bincount(rows, minlength=block.n_dst)
    np.testing.assert_allclose(sums[deg > 0], 1.0, atol=1e-6)


# ---------------------------------------------------------------------------
# ItemSampler + bucketing
# ---------------------------------------------------------------------------

def test_item_sampler_epoch_addressable():
    ids = np.arange(100, 164)
    s = ItemSampler(ids, 16, seed=3)
    e0a = [b for _, b in s.epoch(0)]
    e0b = [b for _, b in s.epoch(0)]
    e1 = [b for _, b in s.epoch(1)]
    for a, b in zip(e0a, e0b):
        np.testing.assert_array_equal(a, b)     # replayable
    assert not all(np.array_equal(a, b) for a, b in zip(e0a, e1))
    # full coverage when batch_size divides the set
    assert set(np.concatenate(e0a).tolist()) == set(ids.tolist())
    assert s.batches_per_epoch() == 4


def test_bucket_for_picks_smallest_covering_rung():
    ladders = block_ladders(64, [4, 2], levels=3)
    assert len(ladders) == 2
    for ladder in ladders:
        assert len(ladder) <= 3
        # rungs ascend; smallest covering rung is returned
        m0, z0 = ladder[0]
        assert bucket_for(ladder, m0, z0) == (m0, z0)
        assert bucket_for(ladder, 1, 1) == (m0, z0)
        m_top, z_top = ladder[-1]
        assert bucket_for(ladder, m_top, z_top) == (m_top, z_top)
        with pytest.raises(ValueError, match="top ladder rung"):
            bucket_for(ladder, m_top + 1, z_top)


def test_block_caps_clamped_by_graph_size():
    small = block_ladders(512, [10, 5], n_nodes=100, levels=2)
    for ladder in small:
        assert all(m <= 104 for m, _ in ladder)  # round_up(100, 8)


# ---------------------------------------------------------------------------
# Feature store / hot-node cache / prefetcher
# ---------------------------------------------------------------------------

def test_feature_store_counts_traffic():
    feats = np.random.default_rng(0).normal(size=(32, 8)).astype(np.float32)
    store = FeatureStore(feats, registry=MetricsRegistry())
    got = store.gather(np.array([3, 1, 3]))
    np.testing.assert_array_equal(got, feats[[3, 1, 3]])
    assert store._fetch_rows.total() == 3
    assert store._fetch_bytes.total() == 3 * 8 * 4


def test_hot_node_cache_static_reduces_traffic():
    feats = np.random.default_rng(1).normal(size=(64, 4)).astype(np.float32)
    deg = np.arange(64)          # node 63 hottest
    reg = MetricsRegistry()      # fresh registry: counters isolated per test
    store = FeatureStore(feats, registry=reg)
    cache = HotNodeCache(store, 8, policy="static",
                         hot_ids=static_hot_ids(deg, 8), registry=reg)
    ids = np.array([63, 62, 0, 1, 63])        # 3 hot hits, 2 cold misses
    np.testing.assert_array_equal(cache.gather(ids), feats[ids])
    assert cache.hit_rate() == pytest.approx(3 / 5)
    # only the misses touched the backing store (static fill is amortized)
    assert store._fetch_rows.total() == 2


def test_hot_node_cache_lru_evicts():
    feats = np.arange(20, dtype=np.float32).reshape(10, 2)
    reg = MetricsRegistry()
    store = FeatureStore(feats, registry=reg)
    cache = HotNodeCache(store, 2, policy="lru", registry=reg)
    cache.gather(np.array([0]))               # miss, cached {0}
    cache.gather(np.array([1]))               # miss, cached {0, 1}
    cache.gather(np.array([0]))               # hit, 0 most-recent
    cache.gather(np.array([2]))               # miss, evicts 1
    got = cache.gather(np.array([1, 0]))      # 1 miss, 0 hit
    np.testing.assert_array_equal(got, feats[[1, 0]])
    assert store._fetch_rows.total() == 4     # misses: 0, 1, 2, 1
    assert cache.hit_rate() == pytest.approx(2 / 6)


def test_static_hot_ids_ranks_by_degree():
    np.testing.assert_array_equal(
        static_hot_ids(np.array([5, 1, 9, 9, 0]), 3), [2, 3, 0])


def test_prefetcher_preserves_order_and_propagates_errors():
    def gen():
        yield from range(5)
        raise RuntimeError("boom")

    pf = Prefetcher(gen())
    it = iter(pf)
    assert [next(it) for _ in range(5)] == list(range(5))
    with pytest.raises(RuntimeError, match="boom"):
        next(it)


# ---------------------------------------------------------------------------
# Loader: bounded compile count, feature alignment
# ---------------------------------------------------------------------------

def test_loader_shape_keys_bounded_by_ladder():
    """An epoch of data-dependent sample shapes maps to at most
    ∏ len(ladder_i) distinct static geometries — the compile-count bound the
    sampled trainer inherits (acceptance: ISSUE 10)."""
    data = reddit_like(600, n_classes=4, n_features=8, seed=1)
    loader = SampledNodeLoader(
        data.csc, data.features, data.labels, data.train_ids,
        fanouts=[5, 3], batch_size=64, levels=3)
    keys = set()
    bound = 1
    for ladder in loader.ladders:
        bound *= len(ladder)
    for batch in loader.epoch(0):
        keys.add(batch.shape_key())
        for block in batch.blocks:
            # rebucketing preserved the real payload inside the padding
            assert block.n_src <= block.m_pad
            assert block.nnz <= block.nnz_pad
    assert 1 <= len(keys) <= bound
    # features are aligned with the input block's compacted src ids
    batch = loader.sample_batch(0, 0, data.train_ids[:64])
    b0 = batch.blocks[0]
    np.testing.assert_array_equal(batch.x[:b0.n_src],
                                  data.features[b0.src_ids])
    assert not batch.x[b0.n_src:].any()
    np.testing.assert_array_equal(batch.labels,
                                  data.labels[batch.seeds])


def test_loader_batches_replayable():
    data = reddit_like(400, n_classes=4, n_features=8, seed=2)
    loader = SampledNodeLoader(
        data.csc, data.features, data.labels, data.train_ids,
        fanouts=[4, 2], batch_size=32)
    a = loader.sample_batch(3, 1, data.train_ids[:32])
    b = loader.sample_batch(3, 1, data.train_ids[:32])
    np.testing.assert_array_equal(a.x, b.x)
    for ba, bb in zip(a.blocks, b.blocks):
        np.testing.assert_array_equal(ba.src_ids, bb.src_ids)
        np.testing.assert_array_equal(np.asarray(ba.adj.row_ids),
                                      np.asarray(bb.adj.row_ids))


# ---------------------------------------------------------------------------
# reddit_like generator
# ---------------------------------------------------------------------------

def test_reddit_like_structure():
    data = reddit_like(500, n_classes=4, n_features=8, seed=0)
    assert data.csc.n_nodes == 500
    assert data.labels.shape == (500,) and data.labels.max() < 4
    assert data.features.shape == (500, 8)
    # every node has a self-loop (no isolated destinations)
    assert data.csc.in_degrees().min() >= 1
    # train/val split is disjoint and covers the node set
    assert not set(data.train_ids) & set(data.val_ids)
    assert len(data.train_ids) + len(data.val_ids) == 500
    # homophily: same-class edges dominate (excluding self-loops)
    src, dst = csc_to_coo(data.csc)
    off = src != dst
    same = (data.labels[src[off]] == data.labels[dst[off]]).mean()
    assert same > 0.5


# ---------------------------------------------------------------------------
# Block-aware autotuning (acceptance: CSR-class wins skewed blocks)
# ---------------------------------------------------------------------------

def test_workload_block_axis_key_and_selection():
    from repro.autotune import Workload, select_impl

    w = Workload(batch=1, m_pad=1600, nnz_pad=3200, k_pad=None, n_b=64,
                 max_deg=16, block=360)
    assert w.key().endswith("_blk360")
    legacy = Workload(batch=1, m_pad=1600, nnz_pad=3200, k_pad=None, n_b=64,
                      max_deg=16)
    assert "_blk" not in legacy.key()
    # a skewed sampled block (few output rows, bounded row degree, wide
    # src padding) must route to a class that pays per real non-zero on the
    # TPU ladder — dense/ELL still pay the full m_pad² / m_pad·k_pad
    # geometry. The Pallas row-split kernels are refused for the TPU
    # (cost_model.TPU_REFUSED), so that class is the XLA scatter/CSR pair
    d = select_impl(w, allow_pallas=True)
    assert d.kind in ("scatter", "csr"), d
    assert d.kind not in ("gemm", "ell"), d


# ---------------------------------------------------------------------------
# End-to-end: fit_sampled learns and compiles a bounded program set
# ---------------------------------------------------------------------------

def test_fit_sampled_learns_node_classification():
    from repro.core.gcn import GCNConfig
    from repro.optim import AdamConfig
    from repro.training.trainer import GCNTrainer, TrainerConfig

    data = reddit_like(1500, n_classes=4, n_features=16, seed=0)
    loader = SampledNodeLoader(
        data.csc, data.features, data.labels, data.train_ids,
        fanouts=[5, 3], batch_size=128)
    cfg = GCNConfig(n_features=16, channels=1, conv_widths=(16, 16),
                    n_tasks=4, task="multiclass", k_pad=None)
    with tempfile.TemporaryDirectory() as ckpt:
        trainer = GCNTrainer(
            cfg, AdamConfig(lr=5e-3),
            TrainerConfig(checkpoint_dir=ckpt, checkpoint_every=10_000,
                          log_every=50))
        _, _, metrics = trainer.fit_sampled(loader, epochs=3,
                                            prefetch=True)
    assert metrics["acc"] > 0.5          # chance = 0.25
    bound = 1
    for ladder in loader.ladders:
        bound *= len(ladder)
    assert 1 <= metrics["programs"] <= bound


# ---------------------------------------------------------------------------
# Property tests (hypothesis) — decorators need hypothesis at definition
# time, so the whole block is conditional on the optional dep.
# ---------------------------------------------------------------------------

if HAS_HYPOTHESIS:
    @st.composite
    def graphs(draw):
        n_nodes = draw(st.integers(4, 50))
        n_edges = draw(st.integers(1, 6 * n_nodes))
        seed = draw(st.integers(0, 2**16))
        return _random_graph(seed, n_nodes, n_edges)[0]

    @settings(max_examples=25, deadline=None)
    @given(graphs())
    def test_property_csc_round_trip(csc):
        """∀ graphs: CSC → COO → CSC is bitwise stable."""
        src, dst = csc_to_coo(csc)
        csc2 = csc_from_edges(src, dst, csc.n_nodes)
        np.testing.assert_array_equal(csc.indptr, csc2.indptr)
        np.testing.assert_array_equal(csc.indices, csc2.indices)

    @settings(max_examples=15, deadline=None)
    @given(graphs(), st.integers(1, 6), st.integers(1, 4),
           st.integers(0, 2**16))
    def test_property_sample_invariants(csc, fanout0, fanout1, seed):
        """∀ (graph, fanouts, seed): determinism + fanout bounds +
        compacted-id validity + the chaining invariant."""
        rng = np.random.default_rng(seed)
        n_seeds = min(4, csc.n_nodes)
        seeds = rng.choice(csc.n_nodes, n_seeds, replace=False)
        fanouts = [fanout0, fanout1]
        blocks = neighbor_sample(csc, seeds, fanouts, seed=seed)
        _assert_valid_blocks(csc, seeds, fanouts, blocks)
        again = neighbor_sample(csc, seeds, fanouts, seed=seed)
        for a, b in zip(blocks, again):
            np.testing.assert_array_equal(a.src_ids, b.src_ids)
            np.testing.assert_array_equal(np.asarray(a.adj.col_ids),
                                          np.asarray(b.adj.col_ids))

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 64), st.integers(1, 12), st.integers(1, 12),
           st.integers(1, 400))
    def test_property_ladder_covers_caps(batch, f0, f1, n_nodes):
        """∀ sampling params: every admissible (n_src, nnz) — up to the
        closed-form caps — lands on some rung (bucket_for never raises)."""
        from repro.sampling.bucketing import block_caps

        caps = block_caps(batch, [f0, f1], n_nodes=n_nodes)
        ladders = block_ladders(batch, [f0, f1], n_nodes=n_nodes)
        for (m_cap, nnz_cap), ladder in zip(caps, ladders):
            assert bucket_for(ladder, m_cap, nnz_cap) == tuple(ladder[-1])
            assert bucket_for(ladder, 1, 0)
