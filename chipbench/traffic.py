"""The general traffic generator: molecule pools, epoch orders, and open-loop
arrivals, all made from data in a cell's file and the run's ``--seed``.

Datasets are fixed: a cell's molecule pool comes from the repo's molecule
generator with the generator seed in the cell's file, so every run has the
same padded shapes. ``--seed`` sets what a run draws from that pool, and
every seed draws the same multiset in another order: the same work, so runs
of different seeds compare like runs of one seed.
"""
from __future__ import annotations

import itertools

import numpy as np

SALT_CYCLE, SALT_ARRIVAL, SALT_DRAW = 1, 2, 3


def dataset_spec(config: dict, cell: dict):
    """The ``GraphDatasetSpec`` of a cell's pool: the configuration's node
    profile and tasks, the cell's count, size distribution and generator
    seed."""
    from repro.data.graphs import GraphDatasetSpec

    g, mol = config["gcn"], config["molecules"]
    return GraphDatasetSpec(
        n_samples=cell["molecules"], max_nodes=mol["max_nodes"],
        min_nodes=mol["min_nodes"], max_degree=mol["max_degree"],
        channels=g["channels"], n_features=g["n_features"],
        n_tasks=g["n_tasks"], task=g["task"], size_dist=cell["size_dist"],
        seed=cell["generator_seed"])


def molecule_pool(config: dict, cell: dict) -> list:
    from repro.data.graphs import generate

    return generate(dataset_spec(config, cell))


def epoch_batches(seed: int, n_molecules: int, batch: int) -> list:
    """Molecule ids of each batch of the first epoch, in the order in which
    ``repro.data.graphs.batches(..., seed=seed)`` builds them (the driver
    checks the two agree)."""
    idx = np.random.default_rng((seed, 0)).permutation(n_molecules)
    return [idx[i * batch:(i + 1) * batch]
            for i in range(n_molecules // batch)]


def batch_cycle(seed: int, n_batches: int):
    """Endless batch positions: each pass over the epoch's batches in an
    order of its own drawn from the seed."""
    for c in itertools.count():
        rng = np.random.default_rng((seed, SALT_CYCLE, c))
        yield from (int(i) for i in rng.permutation(n_batches))


def draws(pool_size: int, n: int, draw_seed: int, seed: int) -> np.ndarray:
    """``n`` pool ids drawn with replacement: one fixed multiset per cell
    (``draw_seed``), put in an order of the run's ``seed``."""
    ids = np.random.default_rng(draw_seed).integers(0, pool_size, n)
    return np.random.default_rng((seed, SALT_DRAW)).permutation(ids)


def arrival_offsets(n: int, rate: float, burst: int, draw_seed: int,
                    seed: int) -> np.ndarray:
    """Due times (s, from the window's start) of ``n`` open-loop arrivals
    at a mean of ``rate`` per second: Poisson for ``burst`` 1, else groups of ``burst``
    arriving together with exponential gaps of ``burst / rate`` between
    groups. The gaps are one fixed set per cell, put in the run's order."""
    groups = -(-n // burst)
    gaps = np.random.default_rng((draw_seed, SALT_ARRIVAL)).exponential(
        burst / rate, groups)
    gaps = np.random.default_rng((seed, SALT_ARRIVAL)).permutation(gaps)
    return np.repeat(np.cumsum(gaps), burst)[:n]
