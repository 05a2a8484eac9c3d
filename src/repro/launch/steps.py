"""Train / serve step builders.

``make_train_step`` assembles the production step: microbatched gradient
accumulation (lax.scan), mixed precision (fp32 masters, bf16 compute),
global-norm clipping, optional int8 gradient compression with error feedback,
AdamW, cosine LR — all shardable under pjit with the logical-axis rules.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelCfg
from repro.distributed.sharding import ShardingRules, logical_constraint
from repro.models import decode as D
from repro.models import transformer as T
from repro.optim import (adamw_update, clip_by_global_norm, compressed_grads,
                         cosine_schedule)


def make_constrain(rules: ShardingRules, mesh):
    if rules is None or mesh is None:
        return T._noc
    return functools.partial(logical_constraint, rules=rules, mesh=mesh)


def make_train_step(cfg: ModelCfg, rules: ShardingRules = None, mesh=None, *,
                    microbatches: int = 1, peak_lr: float = 3e-4,
                    warmup: int = 100, total_steps: int = 10_000,
                    grad_clip: float = 1.0, compress: bool = False):
    constrain = make_constrain(rules, mesh)

    def loss(params, batch):
        return T.loss_fn(params, cfg, batch, constrain=constrain)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(
                params, batch)
        else:
            # split rows as (B/M, M) and scan over the trailing M: the
            # data-sharded batch axis stays on the per-microbatch rows, so
            # the scanned axis is unsharded (row i of microbatch k is row
            # i*M + k of the batch)
            mb = jax.tree.map(
                lambda x: jnp.swapaxes(
                    x.reshape((x.shape[0] // microbatches, microbatches)
                              + x.shape[1:]), 0, 1), batch)

            def body(acc, mbatch):
                g_acc, l_acc = acc
                (l, _), g = jax.value_and_grad(loss, has_aux=True)(params,
                                                                   mbatch)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (grads, lsum), _ = jax.lax.scan(body, (g0, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            l = lsum / microbatches
            metrics = {"xent": l, "aux": jnp.zeros((), jnp.float32)}

        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        if compress:
            grads, new_err = compressed_grads(grads, opt_state.get("err"))
        lr = cosine_schedule(opt_state["count"], peak_lr=peak_lr,
                             warmup=warmup, total=total_steps)
        new_params, new_opt = adamw_update(grads, opt_state, params, lr=lr)
        if compress:
            new_opt["err"] = new_err
        metrics = dict(metrics)
        metrics.update(loss=l, grad_norm=gnorm, lr=lr)
        return new_params, new_opt, metrics

    return train_step


def make_serve_step(cfg: ModelCfg, rules: ShardingRules = None, mesh=None):
    """One serving step for SOI and plain configs alike: the unified engine
    step (per-slot clocks, SOI phase resolved in-program) — a single
    compiled program per config, so the dry-run lowers exactly what
    deployment runs."""
    constrain = make_constrain(rules, mesh)
    from repro.engine.step import generate_step

    def serve_step(params, state, token):
        return generate_step(params, cfg, state, token, constrain=constrain)

    return serve_step


def make_prefill(cfg: ModelCfg, rules: ShardingRules = None, mesh=None, *,
                 max_len: int | None = None):
    constrain = make_constrain(rules, mesh)

    def prefill_step(params, batch):
        return D.prefill(params, cfg, batch["tokens"],
                         prefix_embeds=batch.get("patch_embeds"),
                         encoder_frames=batch.get("encoder_frames"),
                         max_len=max_len, constrain=constrain)

    return prefill_step
