"""``mfu.train``: the training step's share of the chips' peak.

Useful FLOPs per molecule (``work.train_flops``, at each molecule's real
node and edge counts, averaged over the cell's pool) times the molecules
per second of the traced window, over the bf16 peak times the chips."""


def read(m):
    c = m.counters
    if m.peak is None or not c.get("mol_per_s"):
        return None
    return 100.0 * c["mol_per_s"] * c["train_flops_per_mol"] / (
        m.peak["flops_per_s"] * m.chips)
