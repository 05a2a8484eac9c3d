"""Pallas TPU batched page copy — the device half of copy-on-write.

A serving step may COW several pages (one per slot crossing a shared page
boundary, per cache group). Dispatching one compiled copy per page put a
host->device round-trip and a whole XLA program launch on the per-token
path; this kernel fuses the step's entire COW set into ONE dispatch: the
``(2, n)`` src/dst id table rides in as a scalar-prefetch operand, the grid
walks the pairs, and each step moves exactly one pool page from ``src`` to
``dst``. The pool aliases input to output, so untouched pages are never
moved — the copy is in-place from XLA's point of view, exactly like the
single-page ``pool.at[dst].set(pool[src])`` it replaces.

A page block is ``(1,) + pool.shape[1:]``: its last two dims are the pool's
own, which the TPU's (8, 128) tiling always accepts. A 2-D pool (the
position lanes) is viewed as ``(n_pages, 1, page_size)`` for the same
reason. (A DMA straight from HBM would skip VMEM, but Mosaic refuses DMA
slices of pools whose minor dim is narrower than 128 lanes, such as the
positions and MLA's 64-wide rope pool.)

Correctness leans on two allocator invariants (see ``engine/pages.py``):
COW destinations are always freshly-allocated pages, so no pair's ``dst``
is another pair's ``src`` (order-free); and id 0 is the reserved null page,
so padding the table with ``(0, 0)`` self-copies is a no-op — one compiled
program serves every COW count up to the table size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(sd_ref, x_ref, o_ref):
    del sd_ref
    o_ref[...] = x_ref[...]


def copy_pages(pool, srcs, dsts, *, interpret=False):
    """pool: (n_pages, ...); srcs/dsts: (n,) int32 page ids (0-padded).
    Returns the pool with ``pool[dsts[i]] = pool[srcs[i]]`` applied."""
    n = srcs.shape[0]
    pages = pool[:, None] if pool.ndim == 2 else pool
    block = (1,) + pages.shape[1:]
    tail = (0,) * (pages.ndim - 1)
    sd = jnp.stack([jnp.asarray(srcs, jnp.int32),
                    jnp.asarray(dsts, jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec(block, lambda i, sd_: (sd_[0, i],) + tail)],
        out_specs=pl.BlockSpec(block, lambda i, sd_: (sd_[1, i],) + tail),
    )
    out = pl.pallas_call(
        _kernel,
        name="copy_pages",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        # index 0 is the scalar-prefetch table; the pool is input 1
        input_output_aliases={1: 0},
        interpret=interpret,
    )(sd, pages)
    return out.reshape(pool.shape)
