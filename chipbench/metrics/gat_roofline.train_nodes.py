"""``gat_roofline.train_nodes``: the GAT layer's share of its roofline.

After the window each GAT layer's ``gat_layer`` runs forward and backward a
fixed number of times as its own program (``jit_chipbench_gat<i>``) on a
real batch. The share is the sum over layers of the least time
(``work.least_time`` of ``work_gat``'s useful FLOPs and bytes) over the sum
of those programs' device time in the trace."""


def read(m):
    from chipbench import work

    layers = m.counters.get("probe")
    if m.peak is None or not layers:
        return None
    device = sum(m.programs.get(p["program"], 0.0) for p in layers)
    if device <= 0:
        return None
    least = sum(work.least_time(p["flops"], p["bytes"], m.peak)[0]
                for p in layers)
    return 100.0 * least / device
