"""Backend dispatch for the kernel layer.

On TPU the Pallas kernels run; elsewhere (CPU container, AOT dry-run lowering)
the pure-JAX chunked references run — identical math, identical FLOPs, so the
roofline terms derived from the lowered HLO are faithful to the TPU plan.

Set ``repro.kernels.ops.FORCE_MODE`` to "pallas" / "ref" / "interpret" to
override (tests use "interpret" to execute the kernel bodies on CPU).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.kernels import ref

FORCE_MODE: str | None = None      # None = auto by backend


def _mode() -> str:
    if FORCE_MODE is not None:
        return FORCE_MODE
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def flash_attention(q, k, v, *, causal=True, window=None, prefix_len=0,
                    q_offset=0, scale=None, logit_softcap=None,
                    block_q=256, block_k=512):
    mode = _mode()
    if mode in ("pallas", "interpret") and prefix_len == 0 and window is None:
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(
            q, k, v, causal=causal, q_offset=q_offset, scale=scale,
            logit_softcap=logit_softcap, block_q=block_q, block_k=block_k,
            interpret=(mode == "interpret"))
    if window is not None and not causal:
        raise ValueError("windowed attention requires causal=True")
    if window is not None and window < k.shape[1]:
        return ref.windowed_flash_attention(q, k, v, window=window,
                                            q_offset=q_offset, scale=scale,
                                            block_q=block_q)
    return ref.chunked_flash_attention(
        q, k, v, causal=causal, window=window, prefix_len=prefix_len,
        q_offset=q_offset, scale=scale, logit_softcap=logit_softcap,
        block_q=block_q, block_k=block_k)


def chunk_attention(q, k, v, q_positions, k_positions, *, window=None,
                    scale=None, logit_softcap=None, block_q=128, block_k=256):
    """Chunked-prefill attention: C queries at absolute ``q_positions``
    against cache+chunk K/V rows carrying absolute ``k_positions`` (-1 marks
    empty ring slots). Position-based masking makes it layout-independent,
    exactly like ``decode_attention`` — this IS the decode read generalized
    to C queries. On TPU a blocked online-softmax Pallas kernel tiles Sk
    (the reference path materializes the (B, H, C, Sk) score matrix);
    outputs agree to f32 ULP noise, not bit-exactly — see docs/KERNELS.md.
    """
    mode = _mode()
    if mode in ("pallas", "interpret"):
        from repro.kernels import chunk_attention as ca
        return ca.chunk_attention(
            q, k, v, q_positions, k_positions, window=window, scale=scale,
            logit_softcap=logit_softcap, block_q=block_q, block_k=block_k,
            interpret=(mode == "interpret"))
    return ref.naive_attention(q, k, v, causal=True, window=window,
                               q_positions=q_positions,
                               k_positions=k_positions, scale=scale,
                               logit_softcap=logit_softcap)


def mla_chunk_attention(q_lat, q_rope, latent, rope, q_positions,
                        k_positions, *, scale, out_dtype=None,
                        block_q=None, block_k=256):
    """Absorbed-matmul MLA chunk attention: q already carries W_UK, so the
    scores run directly over the latent cache (+ the rope side) and the
    value product reads the latent pool — no per-head K/V ever materializes.
    Same masking contract as :func:`chunk_attention` (no window/softcap:
    MLA configs don't use them). Returns o_lat (B, C, H, L)."""
    mode = _mode()
    if mode in ("pallas", "interpret"):
        from repro.kernels import chunk_attention as ca
        return ca.mla_chunk_attention(
            q_lat, q_rope, latent, rope, q_positions, k_positions,
            scale=scale, out_dtype=out_dtype, block_q=block_q,
            block_k=block_k, interpret=(mode == "interpret"))
    return ref.mla_chunk_attention(q_lat, q_rope, latent, rope, q_positions,
                                   k_positions, scale=scale,
                                   out_dtype=out_dtype)


def mla_decode_attention(q_lat, q_rope, latent, rope, positions, q_position,
                         *, scale, out_dtype=None):
    """Single-token absorbed MLA attention against a dense latent cache.
    Reference path on every backend: the dense read is already gather-free
    (the cache IS the operand), so the win a kernel buys here is marginal —
    the paged variant below is where the per-step gather lived."""
    return ref.mla_decode_attention(q_lat, q_rope, latent, rope, positions,
                                    q_position, scale=scale,
                                    out_dtype=out_dtype)


def decode_attention(q, k_cache, v_cache, cache_positions, q_position, *,
                     window=None, scale=None, logit_softcap=None,
                     block_k=1024):
    mode = _mode()
    if mode in ("pallas", "interpret") and logit_softcap is None:
        from repro.kernels import decode_attention as da
        return da.decode_attention(
            q, k_cache, v_cache, cache_positions, q_position, window=window,
            scale=scale, block_k=block_k, interpret=(mode == "interpret"))
    return ref.decode_attention(q, k_cache, v_cache, cache_positions,
                                q_position, window=window, scale=scale,
                                logit_softcap=logit_softcap)


def paged_decode_attention(q, k_pool, v_pool, pos_pool, page_map, q_position,
                           *, window=None, scale=None, logit_softcap=None):
    """Single-token attention against paged KV pools.

    Pools are ``(n_pages, page_size, Hkv, dh)`` (page 0 = reserved null
    page); ``page_map``: (B, n_pp) int32 per-slot page lists, 0 marking
    unallocated entries. On TPU the Pallas kernel walks the page list with
    scalar prefetch (the page id indexes the K/V block directly — no
    materialized gather); the reference path gathers a slot-major dense view
    and reuses the ring-cache oracle, which keeps the paged read bit-exact
    vs the dense layout.
    """
    mode = _mode()
    if mode in ("pallas", "interpret") and logit_softcap is None:
        from repro.kernels import decode_attention as da
        return da.paged_decode_attention(
            q, k_pool, v_pool, pos_pool, page_map, q_position, window=window,
            scale=scale, interpret=(mode == "interpret"))
    b, n_pp = page_map.shape
    p_sz = pos_pool.shape[1]
    k = k_pool[page_map].reshape((b, n_pp * p_sz) + k_pool.shape[2:])
    v = v_pool[page_map].reshape((b, n_pp * p_sz) + v_pool.shape[2:])
    pos = pos_pool[page_map].reshape(b, n_pp * p_sz)
    pos = jnp.where(jnp.repeat(page_map > 0, p_sz, axis=1), pos, -1)
    return ref.decode_attention(q, k, v, pos, q_position, window=window,
                                scale=scale, logit_softcap=logit_softcap)


def paged_mla_decode_attention(q_lat, q_rope, lat_pool, rope_pool, pos_pool,
                               page_map, q_position, *, scale,
                               out_dtype=None):
    """Single-token absorbed MLA attention against paged latent pools.

    Pools are ``(n_pages, page_size, L/R)`` (page 0 = reserved null page);
    ``page_map``: (B, n_pp) int32 per-slot page lists, 0 marking
    unallocated entries. On TPU the Pallas kernel walks the page list with
    scalar prefetch (no gathered intermediate); the reference path gathers
    a slot-major dense view — op-for-op the old ``paged_view`` read — and
    reuses the dense oracle, which keeps the paged read bit-exact vs the
    dense layout.
    """
    mode = _mode()
    if mode in ("pallas", "interpret"):
        from repro.kernels import decode_attention as da
        return da.paged_mla_decode_attention(
            q_lat, q_rope, lat_pool, rope_pool, pos_pool, page_map,
            q_position, scale=scale, out_dtype=out_dtype,
            interpret=(mode == "interpret"))
    b, n_pp = page_map.shape
    p_sz = pos_pool.shape[1]
    lat = lat_pool[page_map].reshape((b, n_pp * p_sz) + lat_pool.shape[2:])
    rope = rope_pool[page_map].reshape((b, n_pp * p_sz) + rope_pool.shape[2:])
    pos = pos_pool[page_map].reshape(b, n_pp * p_sz)
    pos = jnp.where(jnp.repeat(page_map > 0, p_sz, axis=1), pos, -1)
    return ref.mla_decode_attention(q_lat, q_rope, lat, rope, pos,
                                    q_position, scale=scale,
                                    out_dtype=out_dtype)


def gather_pages(pool, rows):
    """Contiguous logical view of pool rows: ``(n_pages, P, ...)`` pool +
    ``(n,)`` page ids -> ``(n * P, ...)``. The gather that materializes a
    prefix's cached pages into a dense prefill buffer (prefix-cache
    hydration); reference path is a plain XLA gather, and any future Pallas
    specialization (scalar-prefetch page walk, like the paged decode
    kernel) slots in here without touching callers.
    """
    n = rows.shape[0]
    return pool[rows].reshape((n * pool.shape[1],) + pool.shape[2:])


def copy_page(pool, src, dst):
    """Copy pool row ``src`` onto row ``dst`` — the device half of
    copy-on-write when a slot must write into a page shared with other
    slots or pinned by the prefix index. ``src``/``dst`` are traced
    scalars, so ONE compiled program serves every COW."""
    return pool.at[dst].set(pool[src])


def copy_pages(pool, srcs, dsts):
    """Batched :func:`copy_page`: ``pool[dsts[i]] = pool[srcs[i]]`` for a
    whole step's COW set in one dispatch. ``srcs``/``dsts`` are (n,) int32
    vectors zero-padded to a fixed length — (0, 0) pairs self-copy the
    reserved null page, a no-op — so ONE compiled program serves every COW
    count. Safe without ordering because COW destinations are always fresh
    pages (no pair's dst is another pair's src; see engine/pages.py). On
    TPU a scalar-prefetch Pallas kernel walks the pair table with the pool
    aliased in-place; the reference path is one batched scatter."""
    mode = _mode()
    if mode in ("pallas", "interpret"):
        from repro.kernels import page_copy as pc
        return pc.copy_pages(pool, srcs, dsts,
                             interpret=(mode == "interpret"))
    return pool.at[dsts].set(pool[srcs])


def stmc_conv(window, w, b=None):
    mode = _mode()
    if mode in ("pallas", "interpret"):
        from repro.kernels import stmc_conv as sc
        return sc.stmc_conv(window, w, b, interpret=(mode == "interpret"))
    return ref.stmc_conv(window, w, b)


def lru_scan(a, x, h0=None):
    mode = _mode()
    if mode in ("pallas", "interpret"):
        from repro.kernels import lru_scan as ls
        return ls.lru_scan(a, x, h0, interpret=(mode == "interpret"))
    return ref.lru_scan(a, x, h0)
