"""``wave_ms.serve``: median host duration of the program's ``serve/wave``
span in the traced window: wave assembly, the device and the fetch of the
logits."""

import statistics


def read(m):
    waves = (m.trace or {}).get("spans", {}).get("serve/wave")
    return 1e3 * statistics.median(waves) if waves else None
