"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
initialization — required because the dry-run overrides the platform device
count while tests/benchmarks must see one real device.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod ("data", "model"); 2 pods stack a leading "pod"
    axis (hierarchical data parallelism — gradient reduce-scatter in-pod,
    all-reduce across pods). When more placeholder devices exist than the
    mesh needs (the dry-run forces 512), the first prod(shape) are used."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"production mesh needs {need} devices, have {len(devices)} — "
            "run via repro.launch.dryrun (it forces 512 host devices)")
    return make_mesh(shape, axes, devices=devices[:need])


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *,
              devices=None):
    """The one mesh constructor of the repo: every axis is ``Auto``.

    ``jax.make_mesh`` defaults to Explicit axes, under which a plain slice
    of a batch-sharded array (the padded-batch ``out[:batch]`` of
    ``distributed/spmm.py``) or a ``jnp.take`` raises ``ShardingTypeError``;
    the code here relies on GSPMD propagation instead. Elastic: a trainer
    restart on a different device count simply re-lowers against a new mesh
    (sharding rules are mesh-parametric). ``devices`` defaults to the first
    ``prod(shape)`` of ``jax.devices()``."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)
