"""Pallas TPU single-token decode attention against a (ring-buffer) KV cache.

Grid: (B, k_blocks) — k innermost/sequential with online-softmax scratch.
Each grid step takes ALL KV heads of one key block, ``(block_k, Hkv, dh)``:
the block's last two dims are the cache's own, which the TPU's (8, 128)
tiling accepts, and the cache keeps its HBM layout (a flattened
``(S, Hkv*dh)`` view would cost a relayout copy of the whole cache per
call). Inside the block each KV head is a strided sublane read. The query
block is ``(Hkv, G, dh)``: each KV head scores its G grouped q heads in one
(G, dh) x (dh, block_k) product (GQA
kept grouped here, unlike prefill: at decode the q side is tiny and the
cache read is the bottleneck, so we never materialize broadcast KV).
Masking uses the cache's absolute-position lane (-1 = empty slot), viewed
as ``(B, 1, S)`` so each block is a lane row; the query position rides in
as a scalar-prefetch operand (SMEM). The same kernel is therefore correct
for linear and ring-buffer (sliding-window) caches.

``paged_decode_attention`` is the same online-softmax walk over *paged*
pools: the per-slot page list rides in as a scalar-prefetch operand, so the
BlockSpec index map sends block (bi, ki) straight to pool row
``page_map[bi, ki]`` — one ``(page_size, Hkv, dh)`` page of every KV head
streams from HBM exactly like the dense ring blocks, with no gathered
intermediate. Null-page entries (id 0) are masked inside the kernel body.

``paged_mla_decode_attention`` extends that walk to MLA-absorbed decode:
the latent/rope pools carry no head axis (every q head reads the same
(P, L) latent page), so the grid is just (slot, page) and the whole head
block rides in VMEM — replacing the reference path's per-step gather of a
dense (B, S_logical, L) view with a direct page-list traversal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import online_softmax as osm


def _gqa_step(q_ref, k_ref, v_ref, allow, o_ref, m_scr, l_scr, acc_scr, *,
              ki, n_k, scale):
    """One key block of grouped-query decode: q_ref (1, Hkv, G, dh),
    k/v_ref (1, bk, Hkv, dh), ``allow`` (1, bk) live-key mask."""

    @pl.when(ki == 0)
    def _init():
        osm.init(m_scr, l_scr, acc_scr)

    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32)                   # (G, dh)
        k = k_ref[0, :, h].astype(jnp.float32)                # (bk, dh)
        v = v_ref[0, :, h].astype(jnp.float32)
        s = jnp.where(allow, osm.scores(q, k, scale), osm.NEG_INF)
        osm.update(s, v, m_scr.at[h], l_scr.at[h], acc_scr.at[h])

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = osm.result(l_scr[...], acc_scr[...]).astype(o_ref.dtype)


def _live(pos, t, window):
    allow = (pos >= 0) & (pos <= t)
    if window is not None:
        allow = allow & (pos > t - window)
    return allow


def _kernel(t_ref, q_ref, k_ref, v_ref, pos_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, n_k, window):
    bi, ki = pl.program_id(0), pl.program_id(1)
    allow = _live(pos_ref[0], t_ref[bi], window)             # (1, bk)
    _gqa_step(q_ref, k_ref, v_ref, allow, o_ref, m_scr, l_scr, acc_scr,
              ki=ki, n_k=n_k, scale=scale)


def _gqa_scratch(hkv, g, dh):
    return [pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, dh), jnp.float32)]


def decode_attention(q, k_cache, v_cache, cache_positions, q_position, *,
                     window=None, scale=None, block_k=1024, interpret=False):
    """q: (B, H, dh); caches: (B, S, Hkv, dh); cache_positions: (B, S);
    q_position: (B,). Returns (B, H, dh).

    ``block_k`` must be a multiple of 128 unless it covers the whole cache
    (the TPU tiles the position block's lane dim by 128)."""
    b, h, dh = q.shape
    _, s, hkv, _ = k_cache.shape
    g = h // hkv
    scale = dh ** -0.5 if scale is None else scale
    block_k = min(block_k, s)
    pk = (-s) % block_k
    kc = jnp.pad(k_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vc = jnp.pad(v_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
    pos = jnp.pad(cache_positions, ((0, 0), (0, pk)), constant_values=-1)
    n_k = (s + pk) // block_k
    qg = q.reshape(b, hkv, g, dh)
    qp = jnp.broadcast_to(jnp.asarray(q_position, jnp.int32), (b,))

    kernel = functools.partial(_kernel, scale=scale, n_k=n_k, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_k),
        in_specs=[
            pl.BlockSpec((1, hkv, g, dh), lambda bi, ki, t_: (bi, 0, 0, 0)),
            pl.BlockSpec((1, block_k, hkv, dh),
                         lambda bi, ki, t_: (bi, ki, 0, 0)),
            pl.BlockSpec((1, block_k, hkv, dh),
                         lambda bi, ki, t_: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bi, ki, t_: (bi, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, dh),
                               lambda bi, ki, t_: (bi, 0, 0, 0)),
        scratch_shapes=_gqa_scratch(hkv, g, dh),
    )
    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        interpret=interpret,
    )(qp, qg, kc, vc, pos.reshape(b, 1, s + pk))
    return out.reshape(b, h, dh)


def _paged_kernel(pm_ref, t_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, n_k, window):
    bi, ki = pl.program_id(0), pl.program_id(1)
    # null-page entries (unallocated map slots / discarded writes) are dead
    allow = _live(pos_ref[0], t_ref[bi], window) & (pm_ref[bi, ki] > 0)
    _gqa_step(q_ref, k_ref, v_ref, allow, o_ref, m_scr, l_scr, acc_scr,
              ki=ki, n_k=n_k, scale=scale)


def paged_decode_attention(q, k_pool, v_pool, pos_pool, page_map, q_position,
                           *, window=None, scale=None, interpret=False):
    """q: (B, H, dh); pools: (n_pages, page_size, Hkv, dh); page_map:
    (B, n_pp) int32 (0 = null page); q_position: (B,). Returns (B, H, dh).

    One grid step per (slot, page): the page id is scalar-prefetched and
    used directly in the K/V/pos index maps, so each step DMAs exactly one
    page of every KV head — the paged analogue of the ring kernel's
    k-blocks.
    """
    b, h, dh = q.shape
    n_pages, p_sz, hkv, _ = k_pool.shape
    n_pp = page_map.shape[1]
    g = h // hkv
    scale = dh ** -0.5 if scale is None else scale
    qg = q.reshape(b, hkv, g, dh)
    qp = jnp.broadcast_to(jnp.asarray(q_position, jnp.int32), (b,))
    pm = jnp.asarray(page_map, jnp.int32)

    kernel = functools.partial(_paged_kernel, scale=scale, n_k=n_pp,
                               window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pp),
        in_specs=[
            pl.BlockSpec((1, hkv, g, dh),
                         lambda bi, ki, pm_, t_: (bi, 0, 0, 0)),
            pl.BlockSpec((1, p_sz, hkv, dh),
                         lambda bi, ki, pm_, t_: (pm_[bi, ki], 0, 0, 0)),
            pl.BlockSpec((1, p_sz, hkv, dh),
                         lambda bi, ki, pm_, t_: (pm_[bi, ki], 0, 0, 0)),
            pl.BlockSpec((1, 1, p_sz),
                         lambda bi, ki, pm_, t_: (pm_[bi, ki], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, dh),
                               lambda bi, ki, pm_, t_: (bi, 0, 0, 0)),
        scratch_shapes=_gqa_scratch(hkv, g, dh),
    )
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        interpret=interpret,
    )(pm, qp, qg, k_pool, v_pool, pos_pool.reshape(n_pages, 1, p_sz))
    return out.reshape(b, h, dh)


def _paged_mla_kernel(pm_ref, t_ref, ql_ref, qr_ref, lat_ref, rope_ref,
                      pos_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, n_k):
    bi, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        osm.init(m_scr, l_scr, acc_scr)

    ql = ql_ref[0].astype(jnp.float32)            # (H, L)
    qr = qr_ref[0].astype(jnp.float32)            # (H, R)
    lat = lat_ref[0].astype(jnp.float32)          # (page_size, L)
    rp = rope_ref[0].astype(jnp.float32)          # (page_size, R)
    s = osm.scores(ql, lat, scale) + osm.scores(qr, rp, scale)
    # null-page entries are dead even though the null page itself absorbs
    # discarded writes (its pos lane can hold live-looking values)
    allow = _live(pos_ref[0], t_ref[bi], None) & (pm_ref[bi, ki] > 0)
    osm.update(jnp.where(allow, s, osm.NEG_INF), lat, m_scr, l_scr, acc_scr)

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = osm.result(l_scr[...], acc_scr[...]).astype(o_ref.dtype)


def paged_mla_decode_attention(q_lat, q_rope, lat_pool, rope_pool, pos_pool,
                               page_map, q_position, *, scale, out_dtype=None,
                               interpret=False):
    """MLA-absorbed single-token attention over paged latent pools.

    q_lat: (B, H, L); q_rope: (B, H, R); pools: (n_pages, page_size, L/R)
    and (n_pages, page_size) positions; page_map: (B, n_pp) int32 (0 = null
    page); q_position: (B,). Returns o_lat (B, H, L).

    One grid step per (slot, page): the page id is scalar-prefetched into
    the latent/rope/pos index maps, so each step DMAs exactly one latent
    page — no dense (B, S_logical, L) view is ever materialized.
    """
    b, h, lat_d = q_lat.shape
    n_pages, p_sz = pos_pool.shape
    n_pp = page_map.shape[1]
    r = q_rope.shape[-1]
    out_dtype = q_lat.dtype if out_dtype is None else out_dtype
    qp = jnp.broadcast_to(jnp.asarray(q_position, jnp.int32), (b,))
    pm = jnp.asarray(page_map, jnp.int32)

    kernel = functools.partial(_paged_mla_kernel, scale=scale, n_k=n_pp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pp),
        in_specs=[
            pl.BlockSpec((1, h, lat_d), lambda bi, ki, pm_, t_: (bi, 0, 0)),
            pl.BlockSpec((1, h, r), lambda bi, ki, pm_, t_: (bi, 0, 0)),
            pl.BlockSpec((1, p_sz, lat_d),
                         lambda bi, ki, pm_, t_: (pm_[bi, ki], 0, 0)),
            pl.BlockSpec((1, p_sz, r),
                         lambda bi, ki, pm_, t_: (pm_[bi, ki], 0, 0)),
            pl.BlockSpec((1, 1, p_sz),
                         lambda bi, ki, pm_, t_: (pm_[bi, ki], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, lat_d),
                               lambda bi, ki, pm_, t_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, lat_d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="paged_mla_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lat_d), out_dtype),
        interpret=interpret,
    )(pm, qp, q_lat, q_rope, lat_pool, rope_pool,
      pos_pool.reshape(n_pages, 1, p_sz))
