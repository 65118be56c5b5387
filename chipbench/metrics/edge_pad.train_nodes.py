"""``edge_pad.train_nodes``: the share of the edge slots that the window's
steps ran over that padding held (``pad_waste``: the trainer's
``train_edge_slots_total`` less ``train_edges_total``, over the slots,
counted over the window)."""

import math


def read(m):
    v = m.counters.get("pad_waste")
    return None if v is None or math.isnan(v) else 100.0 * v
