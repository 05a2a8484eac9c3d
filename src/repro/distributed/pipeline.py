"""Pipeline parallelism skeleton (GPipe-style) over a mesh axis via shard_map.

The assigned configs all fit with FSDP+TP (shown in the dry-run), so PP is not
used by the production launch path; this module demonstrates the mechanism —
layers sharded over a "stage" axis, microbatches streamed with
``jax.lax.ppermute`` between stages — so the framework has a tested PP
building block for depth-dominated models (e.g. >500-layer stacks) where
FSDP gather traffic would exceed the pipeline bubble cost.

Schedule: classic GPipe fill-drain. With S stages and M microbatches, each
device runs ``M + S - 1`` ticks; at tick t, stage s processes microbatch
``t - s`` (when in range). Bubble fraction = (S-1)/(M+S-1).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(mesh: Mesh, axis: str, layer_fn, params_stacked, x,
                   *, microbatches: int):
    """Run ``y = layers(x)`` with layers split over ``axis``.

    params_stacked: (n_layers, ...) pytree, n_layers % stages == 0 — each
    stage owns a contiguous chunk of layers and scans over it locally.
    x: (batch, ...) global input; batch % microbatches == 0.
    """
    stages = mesh.shape[axis]

    def stage_body(stage_params, x_shard):
        # stage_params: (layers_per_stage, ...); x_shard: full batch (stage
        # axis shards layers, not data)
        s_idx = jax.lax.axis_index(axis)
        mb = x_shard.reshape((microbatches, x_shard.shape[0] // microbatches)
                             + x_shard.shape[1:])
        ticks = microbatches + stages - 1
        # mark carries as stage-varying for shard_map's manual-axes tracking
        out = jax.lax.pcast(jnp.zeros_like(mb), axis, to="varying")

        def chunk_fn(c):
            def body(h, lp):
                return layer_fn(lp, h), None
            h, _ = jax.lax.scan(body, c, stage_params)
            return h

        def tick(state, t):
            buf, out = state          # buf: incoming activation for this tick
            m = t - s_idx             # microbatch index this stage handles
            active = (m >= 0) & (m < microbatches)
            # stage 0 pulls fresh input; others use the permuted buffer
            src = jnp.where(s_idx == 0,
                            mb[jnp.clip(m, 0, microbatches - 1)], buf)
            y = jnp.where(active, chunk_fn(src), src)
            # last stage writes output
            upd = out.at[jnp.clip(m, 0, microbatches - 1)].set(y)
            out = jnp.where(active & (s_idx == stages - 1), upd, out)
            # forward activations to the next stage
            buf = jax.lax.ppermute(y, axis,
                                   [(i, (i + 1) % stages)
                                    for i in range(stages)])
            return (buf, out), None

        buf0 = jax.lax.pcast(jnp.zeros_like(mb[0]), axis, to="varying")
        (_, out), _ = jax.lax.scan(tick, (buf0, out), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them
        out = jax.lax.psum(
            jnp.where(s_idx == stages - 1, out, jnp.zeros_like(out)), axis)
        return out.reshape(x_shard.shape)

    fn = jax.shard_map(stage_body, mesh=mesh,
                       in_specs=(P(axis), P()), out_specs=P())
    with jax.set_mesh(mesh):
        return fn(params_stacked, x)


def bubble_fraction(stages: int, microbatches: int) -> float:
    return (stages - 1) / (microbatches + stages - 1)
