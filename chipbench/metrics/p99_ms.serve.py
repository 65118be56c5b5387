"""``p99_ms.serve``: the 99th percentile, over every request due in the
traced window, of the time from when the request was due to when its
logits were on the host (a failed request counts as infinite). The cell
bounds the median; this tail has no bound, so a change to it shows."""


def read(m):
    return m.counters.get("p99_ms")
