"""``pad_waste.serve``: padded nnz slots over nnz capacity, over the
window's waves (``ServeMetrics.padding_waste_nnz``): what bucketing leaves
of the pad-to-tier cost."""

import math


def read(m):
    v = m.counters.get("pad_waste")
    return None if v is None or math.isnan(v) else 100.0 * v
