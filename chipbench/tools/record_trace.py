#!/usr/bin/env python3
"""Record a small profiler trace on the chip, for the trace-reduction test.

    python3 chipbench/tools/record_trace.py [--out DIR]

Runs a jitted program named ``chipbench_probe`` five times inside a
``chipbench/window`` host annotation, each call inside a ``train/step``
annotation and followed by a 2 ms host sleep (an idle gap the reduction
must find), then a second program ``chipbench_idle`` once outside any
program span. Writes the ``.xplane.pb`` to ``DIR/small.xplane.pb``
(default ``.chipbench_out/trace``) and prints every plane and line with a few
events, so the names the reduction keys on can be read.
"""
from __future__ import annotations

import argparse
import glob
import os
import pathlib
import shutil
import sys
import tempfile
import time


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=".chipbench_out/trace")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    if jax.default_backend() != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2

    def chipbench_probe(x):
        return jnp.tanh(x @ x) @ x

    def chipbench_idle(x):
        return (x * 2.0).sum()

    probe = jax.jit(chipbench_probe)
    idle = jax.jit(chipbench_idle)
    x = jnp.ones((512, 512), jnp.float32)
    probe(x).block_until_ready()
    idle(x).block_until_ready()

    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("chipbench/window"):
            for _ in range(5):
                with jax.profiler.TraceAnnotation("train/step"):
                    probe(x).block_until_ready()
                time.sleep(0.002)
        idle(x).block_until_ready()
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)[0]
        out = pathlib.Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, out / "small.xplane.pb")

    data = ProfileData.from_file(str(out / "small.xplane.pb"))
    print(f"size {os.path.getsize(out / 'small.xplane.pb')} bytes")
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:4]:
                print(f"    {e.name!r} start {e.start_ns} dur {e.duration_ns}"
                      f" stats {list(e.stats)[:6]}")
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind} {len(jax.devices())}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
