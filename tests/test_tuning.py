"""Tuning flags + best-effort sharding constraints."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import tuning


def test_flags_context_nesting():
    assert tuning.flags().moe_dispatch == "grouped"      # optimized default
    with tuning.use_flags(moe_dispatch="scatter", q_block=64):
        assert tuning.flags().moe_dispatch == "scatter"
        assert tuning.flags().q_block == 64
        with tuning.use_flags(q_block=32):
            assert tuning.flags().q_block == 32
            assert tuning.flags().moe_dispatch == "scatter"
        assert tuning.flags().q_block == 64
    assert tuning.flags().q_block == 1024


def test_parse_tune_args():
    out = tuning.parse_tune_args(
        ["q_block=256", "fsdp=true", "capacity_factor=2.0",
         "moe_dispatch=scatter"])
    assert out == {"q_block": 256, "fsdp": True, "capacity_factor": 2.0,
                   "moe_dispatch": "scatter"}
    with pytest.raises(KeyError):
        tuning.parse_tune_args(["nope=1"])


def test_constrain_is_identity_without_mesh():
    x = jnp.ones((8, 16))
    y = tuning.constrain(x, "data", "model")
    assert y is x


def test_constrain_skips_nondivisible():
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("model",))
    with tuning.use_mesh_hint(mesh):
        assert tuning.axis_size("model") == 1
        x = jnp.ones((7, 16))
        y = tuning.constrain(x, "model", None)   # 7 % 1 == 0 → applies
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        # unknown axis names are dropped silently
        z = tuning.constrain(x, ("pod", "data"), None)
        np.testing.assert_array_equal(np.asarray(z), np.asarray(x))
