"""Pipeline parallelism (GPipe schedule) via shard_map + collective_permute.

Layer blocks are stacked on axis 0 (the scan axis), so pipeline-stage
assignment is just sharding that axis over a "pipe" mesh axis: stage s owns
blocks [s·L/P, (s+1)·L/P). The schedule is the classic synchronous pipeline:
T = microbatches + P − 1 ticks; on tick t, stage s processes microbatch
t − s (when valid) and forwards its activation to stage s+1 with
``jax.lax.ppermute`` (whose VJP is the reverse permute, so backward
pipelines automatically under ``jax.grad``). The bubble fraction is
(P−1)/T — reported by ``pipeline_bubble_fraction``.

Embedding and LM head are replicated; only stage 0 embeds and only stage
P−1 computes logits/loss (their gradients are psum'd across stages).
Supported: homogeneous block-pattern architectures (all dense/MoE LMs here);
zamba's grouped hybrid and whisper's enc-dec would need per-stage
heterogeneous programs — out of scope, noted in DESIGN.md.

Tested end-to-end (loss parity vs the non-pipelined step) on a 4-stage CPU
mesh in tests/test_distributed.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.models.layers import rms_norm


def pipeline_bubble_fraction(microbatches: int, stages: int) -> float:
    return (stages - 1) / (microbatches + stages - 1)


def build_pp_loss(cfg: ModelConfig, mesh, *, microbatches: int,
                  pipe_axis: str = "pipe"):
    """Returns loss_fn(params, batch) running the block stack as a pipeline
    over `pipe_axis`. batch["tokens"]: (microbatches·b, T)."""
    assert not cfg.attn_every and not cfg.encoder_layers, \
        "pipeline path supports homogeneous block-pattern archs"
    stages = dict(zip(mesh.axis_names, mesh.devices.shape))[pipe_axis]
    assert cfg.n_blocks % stages == 0, (cfg.n_blocks, stages)

    def stage_blocks(blocks_local, h, positions):
        from repro.models.lm import _apply_sublayer

        def body(carry, blk):
            h, aux = carry
            for i, kind in enumerate(cfg.block_pattern):
                h, _, a = _apply_sublayer(
                    kind, blk[f"{i}_{kind}"], cfg, h, positions=positions,
                    cache=None, cache_pos=None)
                aux = aux + a
            return (h, aux), None

        (h, aux), _ = jax.lax.scan(body, (h, 0.0), blocks_local)
        return h, aux

    def loss_fn(params, batch):
        tokens = batch["tokens"]                       # (mb·b, T)
        n, t = tokens.shape
        b = n // microbatches
        mbs = tokens.reshape(microbatches, b, t)

        blocks_spec = jax.tree.map(lambda _: P(pipe_axis), params["blocks"])
        other_spec = jax.tree.map(lambda _: P(), {
            k: v for k, v in params.items() if k != "blocks"})

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=({"blocks": blocks_spec, **other_spec}, P()),
            out_specs=P(),
            check_vma=False)
        def run(params, mbs):
            stage = jax.lax.axis_index(pipe_axis)
            blocks_local = jax.tree.map(lambda x: x, params["blocks"])
            positions = jnp.broadcast_to(jnp.arange(t), (b, t)).astype(
                jnp.int32)
            ticks = microbatches + stages - 1
            d = cfg.d_model

            def tick(carry, ti):
                act_in, loss_sum, tok_sum = carry
                # stage 0 ingests microbatch `ti` (garbage after the ramp;
                # masked out on the loss side)
                mb_idx = jnp.clip(ti, 0, microbatches - 1)
                toks = jax.lax.dynamic_index_in_dim(
                    mbs, mb_idx, 0, keepdims=False)
                fresh = jnp.take(params["embed"], toks, axis=0)
                h = jnp.where(jnp.equal(stage, 0), fresh, act_in)
                h, _ = stage_blocks(blocks_local, h, positions)
                # last stage: loss for microbatch ti-(P-1) when valid
                out_idx = ti - (stages - 1)
                valid = (out_idx >= 0) & (out_idx < microbatches) & \
                    jnp.equal(stage, stages - 1)
                otoks = jax.lax.dynamic_index_in_dim(
                    mbs, jnp.clip(out_idx, 0, microbatches - 1), 0,
                    keepdims=False)
                hf = rms_norm(params["final_norm"], h, cfg.norm_eps)
                head = (params["embed"].T if cfg.tie_embeddings
                        else params["lm_head"])
                logits = (hf @ head)[:, :-1].astype(jnp.float32)
                targets = otoks[:, 1:]
                logz = jax.scipy.special.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(
                    logits, targets[..., None], axis=-1)[..., 0]
                # rank-1 (not scalar) accumulators: a rank-0 float residual
                # inside shard_map trips jax's scalar-residual _SpecError when
                # the loss is differentiated (shard_map transpose gives
                # residuals a mesh-axis spec that rank 0 cannot carry).
                nll = jnp.sum(logz - gold).reshape(1)
                loss_sum = loss_sum + jnp.where(valid, nll, 0.0)
                tok_sum = tok_sum + jnp.where(
                    valid, jnp.float32(targets.size).reshape(1), 0.0)
                # forward activation to the next stage
                act_out = jax.lax.ppermute(
                    h, pipe_axis,
                    [(i, i + 1) for i in range(stages - 1)])
                return (act_out, loss_sum, tok_sum), None

            act0 = jnp.zeros((b, t, d), jnp.dtype(cfg.dtype))
            (_, loss_sum, tok_sum), _ = jax.lax.scan(
                tick, (act0, jnp.zeros((1,), jnp.float32),
                       jnp.zeros((1,), jnp.float32)),
                jnp.arange(ticks))
            # only the last stage accumulated loss; share it with everyone
            loss_sum = jax.lax.psum(loss_sum, pipe_axis)
            tok_sum = jax.lax.psum(tok_sum, pipe_axis)
            return loss_sum / jnp.maximum(tok_sum, 1.0)

        return run(params, mbs)[0]

    return loss_fn
