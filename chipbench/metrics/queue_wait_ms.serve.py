"""``queue_wait_ms.serve``: mean time from a request's due time to the
dispatch of its wave, over the traced window's requests (the scheduler's
own dispatch stamps)."""


def read(m):
    return m.counters.get("queue_wait_ms")
