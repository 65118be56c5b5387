"""Share of the traced window in which no operation ran on the device: one
minus the union of the device's operation intervals over the window, as
``trace_reduce.summarize`` takes it (averaged over the chips)."""


def read(m):
    if m.trace is None:
        return None
    return 100.0 * m.trace["idle_share"]
