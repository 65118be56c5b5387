"""The output check sees a broken timed path: each fault a cell can have is
planted in the program underneath a tiny CPU run, and ``correct`` comes
out false. (One chip has no exchange between chips to leave out.)"""
import pytest

SEED = 2 ** 31 + 21


def state_unchanged(monkeypatch):
    import repro.training.trainer as trainer

    monkeypatch.setattr(trainer, "adam_update",
                        lambda cfg, params, grads, state: (params, state))


def half_batch(monkeypatch):
    import repro.training.trainer as trainer
    from repro.core.formats import BatchedCOO

    full = trainer.gcn_loss

    def loss(params, cfg, adj, x, n_nodes, labels, *, mesh=None):
        h = x.shape[0] // 2
        adj = [BatchedCOO(a.row_ids[:h], a.col_ids[:h], a.values[:h],
                          a.nnz[:h], a.n_rows[:h]) for a in adj]
        return full(params, cfg, adj, x[:h], n_nodes[:h], labels[:h],
                    mesh=mesh)

    monkeypatch.setattr(trainer, "gcn_loss", loss)


def answer_altered(monkeypatch):
    from repro.serving.engine import GraphServeEngine

    run = GraphServeEngine._run_wave_inner

    def altered(self, wave):
        report = run(self, wave)
        if wave and wave[0].logits is not None:
            wave[0].logits = wave[0].logits + 0.5
        return report

    monkeypatch.setattr(GraphServeEngine, "_run_wave_inner", altered)


@pytest.mark.parametrize("name,fault", [
    ("tox21.train", state_unchanged),
    ("tox21.train", half_batch),
    ("tox21.serve.poisson", answer_altered),
], ids=["state_unchanged", "half_batch", "answer_altered"])
def test_fault_is_not_correct(name, fault, run_tiny, monkeypatch):
    fault(monkeypatch)
    result = run_tiny(name, SEED)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
