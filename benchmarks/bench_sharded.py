"""Device-count scaling of the mesh-sharded Batched SpMM (DESIGN.md §6).

Each device count runs in its own subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (XLA locks the host
device count at first init, so a sweep cannot share a process). The worker
jits one forward sharded_batched_spmm call on a fixed global workload and
reports median wall time; the parent prints a markdown table ready for
EXPERIMENTS.md §Sharding.

CPU caveat (benchmarks/common.py): forced host devices are threads on one
CPU, so absolute speedups understate a real multi-chip mesh — what the sweep
demonstrates is the *structure*: per-shard work drops as batch/N, the
forward path all-gathers nothing, and the per-shard ``impl="auto"``
decision re-resolves against the local workload.

    PYTHONPATH=src python -m benchmarks.bench_sharded [--smoke]
        [--devices 1,2,4,8] [--batch 64] [--dim 56] [--nnz-per-row 4]
        [--n-feat 64] [--impl auto]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

_WORKER = r"""
import sys
import jax, jax.numpy as jnp, numpy as np
from benchmarks.common import time_fn
from repro.core.formats import random_batch
from repro.distributed.spmm import resolve_sharded_impl, sharded_batched_spmm
from repro.kernels.ops import batched_spmm, resolve_impl

batch, dim, nnz_per_row, n_feat = (int(x) for x in sys.argv[1:5])
impl = sys.argv[5]
n_dev = len(jax.devices())
rng = np.random.default_rng(0)
a, m_pad = random_batch(rng, batch=batch, dim=dim, nnz_per_row=nnz_per_row)
b = jnp.asarray(rng.standard_normal((batch, m_pad, n_feat)), jnp.float32)

if n_dev == 1:
    fn = jax.jit(lambda v, bb: batched_spmm(a.with_values(v), bb, impl=impl))
    chosen = resolve_impl(a, b, impl=impl).impl
else:
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((n_dev,), ("data",))
    fn = jax.jit(lambda v, bb: sharded_batched_spmm(
        a.with_values(v), bb, mesh=mesh, impl=impl))
    chosen = resolve_sharded_impl(a, b, mesh, impl=impl).impl
t = time_fn(fn, a.values, b, warmup=2, iters=5)
print(f"ROW,{n_dev},{-(-batch // n_dev)},{chosen},{t * 1e3:.3f}")
"""


def sweep(devices: list[int], *, batch: int, dim: int, nnz_per_row: int,
          n_feat: int, impl: str) -> list[tuple[int, int, str, float]]:
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    rows = []
    for n in devices:
        env = {**os.environ,
               "XLA_FLAGS": f"--xla_force_host_platform_device_count={n}",
               "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": os.pathsep.join(
                   [src, os.path.join(src, "..")]
                   + [p for p in os.environ.get(
                       "PYTHONPATH", "").split(os.pathsep) if p])}
        r = subprocess.run(
            [sys.executable, "-c", _WORKER, str(batch), str(dim),
             str(nnz_per_row), str(n_feat), impl],
            capture_output=True, text=True, env=env, timeout=900)
        line = [ln for ln in r.stdout.splitlines() if ln.startswith("ROW,")]
        if not line:
            print(f"device_count={n} FAILED:\n{r.stdout}\n{r.stderr}",
                  file=sys.stderr)
            continue
        _, n_dev, local_b, chosen, ms = line[0].split(",")
        rows.append((int(n_dev), int(local_b), chosen, float(ms)))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + {1,2,8} devices (CI mode)")
    ap.add_argument("--devices", default="1,2,4,8")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--dim", type=int, default=56)
    ap.add_argument("--nnz-per-row", type=int, default=4)
    ap.add_argument("--n-feat", type=int, default=64)
    ap.add_argument("--impl", default="auto")
    args = ap.parse_args()

    devices = [int(x) for x in args.devices.split(",")]
    if args.smoke:
        devices = [1, 2, 8]
        args.batch, args.dim, args.n_feat = 32, 24, 32

    rows = sweep(devices, batch=args.batch, dim=args.dim,
                 nnz_per_row=args.nnz_per_row, n_feat=args.n_feat,
                 impl=args.impl)
    if not rows:
        raise SystemExit("no sweep rows produced")
    # normalize against the first SURVIVING row and label it honestly (a
    # failed n=1 worker must not masquerade as the 1-device baseline)
    base_dev, _, _, base = rows[0]
    print(f"\nglobal workload: batch={args.batch} dim={args.dim} "
          f"nnz/row={args.nnz_per_row} n_b={args.n_feat} "
          f"impl={args.impl} (CPU, forced host devices)")
    print(f"| devices | batch/shard | resolved impl | ms/call "
          f"| vs {base_dev} dev |")
    print("|---|---|---|---|---|")
    for n_dev, local_b, chosen, ms in rows:
        print(f"| {n_dev} | {local_b} | {chosen} | {ms:.2f} "
              f"| {base / ms:.2f}× |")


if __name__ == "__main__":
    main()
