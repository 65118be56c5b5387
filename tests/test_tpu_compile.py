"""Compile the main path's kernels for a TPU v5e that is described, not
attached.

JAX ships the TPU compiler, so ``jax.jit(...).lower(shapes).compile()``
against a described ``v5e:2x2`` topology raises whatever Mosaic/XLA would
raise on the chip (unaligned blocks, unsupported in-kernel gathers, VMEM
overflow) without spending chip time. Nothing runs, so these tests say
nothing about results or speed.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every xdist worker imports
every test module. The fixture skips where no topology can be described.
Every call passes ``interpret=False``, so the kernels lower through Mosaic
even though the test process runs on the CPU backend.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.autotune import Workload, rank, rank_layer, tpu_refusal
from repro.core.batching import plan_batched_spmm, plan_fused_graph_conv
from repro.core.formats import BatchedCOO
from repro.core.graph_conv import graph_conv_batched
from repro.core.spmm import IMPLS, batched_gspmm, batched_spmm

# the tox21 training layer geometry: batch 100 from tox21_like, m_pad 56,
# nnz_pad 88, 4 bond channels, 62 atom features
BATCH, M_PAD, NNZ_PAD, CHANNELS, N_FEAT = 100, 56, 88, 4, 62
TOX21_LAYER = Workload(batch=BATCH, m_pad=M_PAD, nnz_pad=NNZ_PAD, k_pad=8,
                       n_b=64, channels=CHANNELS, n_in=N_FEAT)


def tpu_layer_candidates() -> tuple[str, ...]:
    """Every impl ``impl="auto"`` may pick for a compiled tox21 layer, under
    each dtype policy serving can opt into."""
    names = []
    for dtype in ("f32", "bf16", "i8"):
        w = Workload(**{**TOX21_LAYER.__dict__, "dtype": dtype})
        names += [i for i, _ in rank_layer(w, allow_pallas=True)]
    return tuple(dict.fromkeys(names))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _layer_shapes(sharding, n_in, n_out):
    def coo():
        ids = _spec((BATCH, NNZ_PAD), jnp.int32, sharding)
        return BatchedCOO(ids, ids, _spec((BATCH, NNZ_PAD), jnp.float32,
                                          sharding),
                          _spec((BATCH,), jnp.int32, sharding),
                          _spec((BATCH,), jnp.int32, sharding))

    params = {"w": _spec((CHANNELS, n_in, n_out), jnp.float32, sharding),
              "b": _spec((CHANNELS, n_out), jnp.float32, sharding)}
    return params, [coo() for _ in range(CHANNELS)], _spec(
        (BATCH, M_PAD, n_in), jnp.float32, sharding)


def _compile_layer(sharding, impl, n_in, n_out):
    """Compile one graph-conv layer, forward and backward; return the HLO."""
    def loss(params, adj, x):
        y = graph_conv_batched(params, adj, x, impl=impl, k_pad=8,
                               interpret=False, epilogue="relu")
        return jnp.sum(y * y)

    step = jax.jit(jax.grad(loss, argnums=(0, 2)))
    return step.lower(*_layer_shapes(sharding, n_in, n_out)).compile() \
        .as_text()


@pytest.mark.parametrize("widths", [(N_FEAT, 64), (512, 512)],
                         ids=["tox21", "reaction100"])
def test_fused_fwd_bwd_compiles(one_chip, widths):
    """The megakernel forward and its pallas_coo backward lower at the
    tox21 and the reaction100 layer widths."""
    hlo = _compile_layer(one_chip, "fused", *widths)
    assert hlo.count("tpu_custom_call") >= 2     # fused fwd + coo bwd


def test_pallas_coo_fwd_bwd_compiles(one_chip):
    """The paper's batched COO SpMM, forward and backward, at the stacked
    (channels·batch) geometry the layer fallback runs."""
    b = CHANNELS * BATCH
    ids = _spec((b, NNZ_PAD), jnp.int32, one_chip)
    counts = _spec((b,), jnp.int32, one_chip)

    def loss(rids, cids, values, nnz, n_rows, d):
        a = BatchedCOO(rids, cids, values, nnz, n_rows)
        out = batched_spmm(a, d, impl="pallas_coo", interpret=False)
        return jnp.sum(out * out)

    hlo = jax.jit(jax.grad(loss, argnums=(2, 5))).lower(
        ids, ids, _spec((b, NNZ_PAD), jnp.float32, one_chip), counts, counts,
        _spec((b, M_PAD, 64), jnp.float32, one_chip)).compile().as_text()
    assert hlo.count("tpu_custom_call") == 2


def gspmm_corners() -> list[tuple[str, str, bool]]:
    """Every g-SpMM (op, reduce, vector-edge) corner for which the compiled
    ladder offers ``pallas_coo``, apart from the plain (mul, sum) product."""
    corners = []
    for op in ("mul", "add", "copy_lhs"):
        for reduce in ("sum", "mean", "max"):
            for vec in (False, True):
                if (op, reduce, vec) == ("mul", "sum", False) or (
                        vec and op == "copy_lhs"):
                    continue
                w = Workload(batch=BATCH, m_pad=M_PAD, nnz_pad=NNZ_PAD,
                             k_pad=8, n_b=64, d_e=64 if vec else None,
                             reduce=reduce, op=op)
                if "pallas_coo" in {i for i, _ in rank(w, allow_pallas=True)}:
                    corners.append((op, reduce, vec))
    return corners


@pytest.mark.parametrize("op,reduce,vec", gspmm_corners(),
                         ids=lambda v: str(v))
def test_pallas_coo_gspmm_compiles(one_chip, op, reduce, vec):
    """The COO kernel's g-SpMM corners (masked by the scalar-prefetched
    nnz; vector edges as a (1, nnz_pad, n_block) block), forward and
    backward, at the tox21 SpMM geometry."""
    ids = _spec((BATCH, NNZ_PAD), jnp.int32, one_chip)
    counts = _spec((BATCH,), jnp.int32, one_chip)
    vals = _spec((BATCH, NNZ_PAD) + ((64,) if vec else ()), jnp.float32,
                 one_chip)

    def loss(rids, cids, values, nnz, n_rows, d):
        a = BatchedCOO(rids, cids, values, nnz, n_rows)
        out = batched_gspmm(a, d, op=op, reduce=reduce, impl="pallas_coo",
                            interpret=False)
        return jnp.sum(out * out)

    hlo = jax.jit(jax.grad(loss, argnums=(2, 5))).lower(
        ids, ids, vals, counts, counts,
        _spec((BATCH, M_PAD, 64), jnp.float32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo


def largest_planned_m_pad(impl: str) -> int:
    """The largest m_pad (a multiple of 128, two edges per row) that the
    planner still offers to ``impl`` at the tox21 layer widths."""
    def offered(m):
        if impl == "pallas_coo":
            return plan_batched_spmm(batch=2, m_pad=m, n_b=64, slots=2 * m,
                                     onehot=True).case != 3
        return plan_fused_graph_conv(
            batch=2, m_pad=m, n_in=N_FEAT, n_out=64, channels=CHANNELS,
            nnz_pad=2 * m, hybrid=impl == "fused_hybrid").case != 3

    m = 128
    while offered(m + 128):
        m += 128
    return m


@pytest.mark.parametrize("impl", ["fused", "fused_hybrid", "pallas_coo"])
def test_largest_planned_geometry_compiles(one_chip, impl):
    """At the largest matrix its planner accepts, the kernel still fits
    the scoped VMEM: the planner's VMEM estimate never offers a plan that
    Mosaic refuses."""
    m = largest_planned_m_pad(impl)
    ids = _spec((2, 2 * m), jnp.int32, one_chip)
    counts = _spec((2,), jnp.int32, one_chip)
    vals = _spec((2, 2 * m), jnp.float32, one_chip)
    if impl == "pallas_coo":
        fn = jax.jit(lambda r, c, v, n, nr, d: batched_spmm(
            BatchedCOO(r, c, v, n, nr), d, impl=impl, interpret=False))
        args = (ids, ids, vals, counts, counts,
                _spec((2, m, 64), jnp.float32, one_chip))
    else:
        coo = BatchedCOO(ids, ids, vals, counts, counts)
        params = {"w": _spec((CHANNELS, N_FEAT, 64), jnp.float32, one_chip),
                  "b": _spec((CHANNELS, 64), jnp.float32, one_chip)}
        fn = jax.jit(lambda p, a, x: graph_conv_batched(
            p, a, x, impl=impl, interpret=False, epilogue="relu"))
        args = (params, [coo] * CHANNELS,
                _spec((2, m, N_FEAT), jnp.float32, one_chip))
    hlo = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, f"{impl} at m_pad {m} left Pallas"


@pytest.mark.parametrize("impl", tpu_layer_candidates())
def test_tpu_candidate_compiles(one_chip, impl):
    """Every impl on the compiled ladder lowers, with the backward that
    ``bwd_impl_for`` gives it, at the tox21 layer geometry."""
    assert tpu_refusal(impl) is None
    _compile_layer(one_chip, impl, N_FEAT, 64)


@pytest.mark.parametrize("impl", [i for i in IMPLS if tpu_refusal(i)])
def test_refused_impl_raises_when_compiled(impl):
    """A compiled call naming a kernel the TPU refuses raises before
    Mosaic, instead of failing inside it (no topology needed)."""
    params, adj, x = _layer_shapes(None, N_FEAT, 64)
    with pytest.raises(ValueError, match="does not compile for the TPU"):
        jax.eval_shape(lambda p, a, xx: graph_conv_batched(
            p, a, xx, impl=impl, k_pad=8, interpret=False), params, adj, x)


def test_gat_dense_blocks_stay_fused_at_ppi_width(one_chip):
    """The largest PPI GAT layer (6 heads of 121 over 1,024 inputs, 2
    graphs of m_pad 3,480) on the dense-block path, forward and backward:
    one scatter (the multiplicity block), no gather, and no row-wide
    ``reduce-window``; XLA fuses each (heads, batch, m_pad, m_pad) block
    into its producer and consumer, so the temporaries stay below one
    block's 581 MB."""
    from repro.models.gnn import gat_layer

    b, m, nnz, heads, d, n_in = 2, 3480, 103704, 6, 121, 1024
    ids = _spec((b, nnz), jnp.int32, one_chip)
    counts = _spec((b,), jnp.int32, one_chip)
    params = {"w": _spec((heads, n_in, d), jnp.float32, one_chip),
              "a_src": _spec((heads, d), jnp.float32, one_chip),
              "a_dst": _spec((heads, d), jnp.float32, one_chip),
              "b": _spec((heads * d,), jnp.float32, one_chip)}

    def loss(p, rids, cids, nnz, x):
        a = BatchedCOO(rids, cids, jnp.ones(rids.shape), nnz, nnz)
        y = gat_layer(p, a, x, mean_heads=True)
        return jnp.sum(y * y)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 4))).lower(
        params, ids, ids, counts,
        _spec((b, m, n_in), jnp.float32, one_chip)).compile()
    hlo = compiled.as_text()
    assert re.findall(r"= \S+ (scatter|gather|reduce-window)\(", hlo) \
        == ["scatter"]
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * heads * b * m * m
