"""Fault tolerance: atomic checkpoints, integrity, resume-after-kill."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, load_pytree, save_pytree

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_save_load_roundtrip(tmp_path):
    tree = {"a": jnp.arange(10.0), "b": [jnp.ones((3, 3)), jnp.zeros(())]}
    save_pytree(tree, str(tmp_path / "ck"))
    back = load_pytree(tree, str(tmp_path / "ck"))
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_integrity_check_fails_on_corruption(tmp_path):
    tree = {"a": jnp.arange(100.0)}
    save_pytree(tree, str(tmp_path / "ck"))
    npz = tmp_path / "ck" / "shard-0.npz"
    data = npz.read_bytes()
    npz.write_bytes(data[:-20] + b"x" * 20)
    with pytest.raises(IOError, match="integrity"):
        load_pytree(tree, str(tmp_path / "ck"))


def test_keep_k_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.ones(4)}
    for s in (10, 20, 30, 40):
        mgr.save(s, tree)
    assert mgr.steps() == [30, 40]
    assert mgr.latest_step() == 40


_RESUME_SCRIPT = r"""
import os, sys, json
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro import configs
from repro.training import Trainer, TrainerConfig
from repro.optim import AdamConfig
from repro.launch.mesh import make_mesh
from repro.launch import specs

cfg = configs.get("llama3-8b").reduced()
mesh = make_mesh((1, 1), ("data", "model"))
total = int(sys.argv[3])
tcfg = TrainerConfig(total_steps=total, checkpoint_every=5, log_every=5,
                     checkpoint_dir=sys.argv[2], zero1=False)
tr = Trainer(cfg, mesh, AdamConfig(lr=1e-3), tcfg)

def data():
    k = jax.random.key(0)
    while True:
        k, sub = jax.random.split(k)
        yield {"tokens": jax.random.randint(sub, (2, 16), 0, cfg.vocab)}

params, _ = tr.fit(data())
print("FINAL_STEP", tr.manager.latest_step())
"""


def test_gcn_resume_after_interruption(tmp_path):
    """Regression (ISSUE 5): ``GCNTrainer.fit`` used to call ``init_state()``
    unconditionally — checkpoints written by ``manager.save`` were never
    restored and the step counter restarted at 0, silently overwriting the
    saved trajectory. Save → kill (fresh trainer == fresh process: only the
    checkpoint dir survives) → resume."""
    from repro.core.gcn import GCNConfig
    from repro.data.graphs import GraphDatasetSpec, batches, generate
    from repro.training import GCNTrainer, TrainerConfig

    spec = GraphDatasetSpec.tox21_like(n_samples=8)
    ck = str(tmp_path / "gcn_ck")
    cfg = GCNConfig.tox21()
    tcfg = TrainerConfig(checkpoint_dir=ck, checkpoint_every=1)
    batches_a = list(batches(generate(spec), spec, 4, seed=0))   # 2 steps
    t1 = GCNTrainer(cfg, tcfg=tcfg)
    p1, _, _ = t1.fit(batches_a, epochs=1)
    assert t1.manager.latest_step() == 2

    # restore_or_init resumes the saved params AND the step counter
    t2 = GCNTrainer(cfg, tcfg=tcfg)
    p2, _, start = t2.restore_or_init()
    assert start == 2
    for a, c in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    # resume over DIFFERENT data with the same step budget: every batch is
    # already trained, so fit fast-forwards and returns the restored params
    # untouched. Pre-fix this re-inits, trains the new data from step 0 and
    # overwrites the saved checkpoints — the params would differ.
    spec_b = GraphDatasetSpec.tox21_like(n_samples=8, seed=1)
    batches_b = list(batches(generate(spec_b), spec_b, 4, seed=1))
    t3 = GCNTrainer(cfg, tcfg=tcfg)
    p3, _, _ = t3.fit(batches_b, epochs=1)
    assert t3.manager.latest_step() == 2
    for a, c in zip(jax.tree.leaves(p1), jax.tree.leaves(p3)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    # a longer budget continues training past the restored step
    t4 = GCNTrainer(cfg, tcfg=tcfg)
    t4.fit(batches_b, epochs=2)          # 4 batches: skip 2, train 2
    assert t4.manager.latest_step() == 4
    assert t4.restore_or_init()[2] == 4


def test_gcn_trainer_rejects_undersized_k_pad(tmp_path):
    """ELL silent-drop guard at the trainer's concrete boundary (ISSUE 5):
    a cfg.k_pad smaller than the data's true max row degree must fail fast
    instead of letting a jitted ELL path silently zero edges."""
    from repro.core.gcn import GCNConfig
    from repro.data.graphs import GraphDatasetSpec, batches, generate
    from repro.training import GCNTrainer, TrainerConfig

    spec = GraphDatasetSpec.tox21_like(n_samples=8)
    bs = list(batches(generate(spec), spec, 4, seed=0))
    # pinned ELL impl: generated molecules reach degree > 1, so k_pad=1
    # WOULD silently corrupt — the guard must fire before the jitted step
    cfg = GCNConfig.tox21(k_pad=1, impl="ell")
    trainer = GCNTrainer(cfg, tcfg=TrainerConfig(
        checkpoint_dir=str(tmp_path), checkpoint_every=1000))
    with pytest.raises(ValueError, match="max row degree"):
        trainer.fit(bs, epochs=1)


def test_resume_after_interruption(tmp_path):
    """Train 10 steps (checkpoint at 5, 10); then a second process resumes
    from step 10 and continues to 15 — restart-after-kill path."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    ckdir = str(tmp_path / "ck")

    def run(total):
        return subprocess.run(
            [sys.executable, "-c", _RESUME_SCRIPT, SRC, ckdir, str(total)],
            capture_output=True, text=True, env=env, timeout=300)

    r1 = run(10)
    assert "FINAL_STEP 10" in r1.stdout, r1.stdout + r1.stderr
    r2 = run(15)
    assert "FINAL_STEP 15" in r2.stdout, r2.stdout + r2.stderr
    # metrics log shows a contiguous, resumed history
    steps = [json.loads(line)["step"]
             for line in open(os.path.join(ckdir, "metrics.jsonl"))]
    assert 10 in steps and 15 in steps
    # resumed run must not restart from 0: 5 only appears once
    assert steps.count(5) == 1



_FLAT_CARRY_SCRIPT = r"""
import shutil, sys
sys.path.insert(0, sys.argv[1])
import jax, numpy as np
from repro.core.formats import BatchedCOO
from repro.core.gcn import GCNConfig, gcn_loss
from repro.data.graphs import GraphDatasetSpec, batches, generate
from repro.optim import adam_update
from repro.training import GCNTrainer, TrainerConfig

layer, ckdir = sys.argv[2], sys.argv[3]
spec = GraphDatasetSpec.tox21_like(n_samples=4, n_features=8, channels=2,
                                   seed=3)
batch = next(iter(batches(generate(spec), spec, 4, seed=0)))
cfg = GCNConfig(n_features=8, channels=2, conv_widths=(8, 8), n_tasks=12,
                layer=layer, heads=2)
tcfg = TrainerConfig(checkpoint_dir=ckdir, checkpoint_every=2)
trainer = GCNTrainer(cfg, tcfg=tcfg)
adj = [(a.row_ids, a.col_ids, a.values, a.nnz, a.n_rows)
       for a in batch["adj"]]

@jax.jit
def pytree_step(params, state, adj_arrays, x, n_nodes, labels):
    coo = [BatchedCOO(*a) for a in adj_arrays]
    (loss, _), grads = jax.value_and_grad(
        lambda p: gcn_loss(p, cfg, coo, x, n_nodes, labels),
        has_aux=True)(params)
    params, state = adam_update(trainer.opt, params, grads, state)
    return params, state, loss

params, state = trainer.init_state()
want, want_losses = [], []
for _ in range(3):
    params, state, loss = pytree_step(params, state, adj, batch["x"],
                                      batch["n_nodes"], batch["labels"])
    want.append(jax.tree.map(np.asarray, (params, state)))
    want_losses.append(float(loss))

losses = []
got_params, got_state, rec = trainer.fit(
    lambda e: [batch], epochs=3,
    on_metrics=lambda _, r: losses.append(r["loss"]))
assert losses == want_losses and rec["loss"] == want_losses[-1], losses
got = (got_params, got_state)
assert jax.tree.structure(got) == jax.tree.structure(want[-1])
for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want[-1])):
    assert not g.is_deleted()
    assert g.dtype == w.dtype
    np.testing.assert_array_equal(np.asarray(g), w)
assert int(got_state["step"]) == 3

# the step-2 checkpoint was written mid-fit, with the carry still in use
assert trainer.manager.steps() == [2, 3], trainer.manager.steps()
shutil.rmtree(trainer.manager._dir(3))
params2, state2, start = GCNTrainer(cfg, tcfg=tcfg).restore_or_init()
assert start == 2
for g, w in zip(jax.tree.leaves((params2, state2)), jax.tree.leaves(want[1])):
    np.testing.assert_array_equal(np.asarray(g), w)
print("PASS")
"""


@pytest.mark.parametrize("layer", ["gcn", "gat", "rgcn"])
def test_gcn_fit_flat_carry_matches_pytree_step(tmp_path, layer):
    """``fit`` carries params and Adam state as one donated flat buffer; over
    three steps it matches, bit for bit, the plain pytree step (``gcn_loss``
    → ``value_and_grad`` → ``adam_update``): params, ``m``, ``v``, ``step``
    and each step's loss. What it returns is live, and its mid-``fit``
    checkpoint restores through ``restore_or_init`` to the same values.

    Runs in a process whose CPU backend may not use FMA instructions
    (``--xla_cpu_max_isa=SSE4_2``): XLA contracts ``a * b + c`` into one
    FMA wherever a fusion holds both, and the two programs fuse Adam
    differently, so with FMA some elements of ``m`` differ by an ulp. Without
    it every operation rounds on its own and the comparison is exact."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": " ".join(filter(None, (
               os.environ.get("XLA_FLAGS"), "--xla_cpu_max_isa=SSE4_2")))}
    r = subprocess.run(
        [sys.executable, "-c", _FLAT_CARRY_SCRIPT, SRC, layer,
         str(tmp_path / "ck")],
        capture_output=True, text=True, env=env, timeout=300)
    assert "PASS" in r.stdout, r.stdout + r.stderr
