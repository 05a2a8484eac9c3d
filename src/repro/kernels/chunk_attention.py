"""Pallas TPU chunked-prefill attention (blocked online softmax).

The chunk read is the decode read generalized to C queries: C tokens at
absolute ``q_positions`` attend to cache+chunk K/V rows carrying absolute
``k_positions`` (-1 = empty ring slot). The reference path materializes the
full ``(B, H, C, Sk)`` score matrix; this kernel tiles it — grid over
(batch, q-block, k-block) with the k dimension innermost and sequential,
online-softmax stats (m, l, acc) living in VMEM scratch across k steps.
Each step takes every head: q blocks are ``(block_q, H, dh)`` and K/V
blocks ``(block_k, Hkv, dh)``, whose last two dims are the arrays' own, so
the TPU's (8, 128) tiling holds and no operand is relaid out in HBM; each
head is a strided sublane read inside the block. Query positions ride as a
``(B, C, 1)``
column and key positions as a ``(B, 1, Sk)`` row, so the mask is one
broadcast compare. Masking is position-based in-kernel, so the same
kernel is correct for linear caches, ring buffers, and sliding windows,
and the q-side pad rows a non-multiple chunk needs are simply given
``q_position = -1`` (every key fails ``kp <= qp`` against them, the row
normalizes to a finite value, and the wrapper slices it off).

``mla_chunk_attention`` is the absorbed-matmul MLA variant: scores are the
sum of a latent-space and a rope-space product, and the value product runs
against the latent pool itself — all H heads share one (Sk, L) latent
cache, so q is viewed as ``(B, C*H, L)``: a block is ``block_q * H`` rows of
(query, head) pairs scored in one 2-D product. ``block_q`` defaults to
256 // H queries, which keeps the f32 scratch and score tiles inside the
chip's scoped VMEM at latent 512.

Exactness class: same f32 accumulation and NEG_INF masking as the
reference, but the blocked GEMM + online-softmax rescaling reorders the
reductions — outputs match the reference to f32 ULP noise (~1e-6), not
bit-exactly. See docs/KERNELS.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import online_softmax as osm


def _kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, n_k, window, logit_softcap):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        osm.init(m_scr, l_scr, acc_scr)

    qp = qp_ref[0]                                # (block_q, 1)
    kp = kp_ref[0]                                # (1, block_k)
    allow = (kp >= 0) & (kp <= qp)
    if window is not None:
        allow = allow & (kp > qp - window)
    hkv = k_ref.shape[2]
    g = q_ref.shape[2] // hkv
    for hk in range(hkv):
        k = k_ref[0, :, hk].astype(jnp.float32)           # (block_k, dh)
        v = v_ref[0, :, hk].astype(jnp.float32)
        for j in range(hk * g, (hk + 1) * g):
            q = q_ref[0, :, j].astype(jnp.float32)        # (block_q, dh)
            s = osm.scores(q, k, scale)                   # (block_q, block_k)
            if logit_softcap:
                # cap BEFORE masking, like the reference: masked lanes must
                # not pass a saturated tanh(NEG_INF) through the where
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            osm.update(jnp.where(allow, s, osm.NEG_INF), v,
                       m_scr.at[j], l_scr.at[j], acc_scr.at[j])

    @pl.when(ki == n_k - 1)
    def _finalize():
        for j in range(o_ref.shape[2]):
            o_ref[0, :, j] = osm.result(l_scr[j],
                                        acc_scr[j]).astype(o_ref.dtype)


def _pad_positions(q_positions, k_positions, pq, pk):
    """q positions as a (B, C+pq, 1) column, k positions as a (B, 1, Sk+pk)
    row; pad rows/lanes get -1 (dead)."""
    qp = jnp.pad(jnp.asarray(q_positions, jnp.int32), ((0, 0), (0, pq)),
                 constant_values=-1)
    kp = jnp.pad(jnp.asarray(k_positions, jnp.int32), ((0, 0), (0, pk)),
                 constant_values=-1)
    return qp[:, :, None], kp[:, None, :]


def chunk_attention(q, k, v, q_positions, k_positions, *, window=None,
                    scale=None, logit_softcap=None, block_q=128, block_k=256,
                    interpret=False):
    """q: (B, C, H, dh); k/v: (B, Sk, Hkv, dh); q_positions: (B, C);
    k_positions: (B, Sk) absolute positions with -1 marking empty slots.
    Returns (B, C, H, dh).

    ``block_k`` must be a multiple of 128 (and ``block_q`` of 8) unless it
    covers the whole axis."""
    b, c, h, dh = q.shape
    _, sk, hkv, _ = k.shape
    scale = dh ** -0.5 if scale is None else scale
    block_q = min(block_q, c)
    block_k = min(block_k, sk)
    pq, pk = (-c) % block_q, (-sk) % block_k
    qf = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kf = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    qp, kp = _pad_positions(q_positions, k_positions, pq, pk)
    n_q, n_k = (c + pq) // block_q, (sk + pk) // block_k

    kernel = functools.partial(_kernel, scale=scale, n_k=n_k, window=window,
                               logit_softcap=logit_softcap)
    out = pl.pallas_call(
        kernel,
        name="chunk_attention",
        grid=(b, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, h, dh),
                         lambda bi, qi, ki: (bi, qi, 0, 0)),
            pl.BlockSpec((1, block_k, hkv, dh),
                         lambda bi, qi, ki: (bi, ki, 0, 0)),
            pl.BlockSpec((1, block_k, hkv, dh),
                         lambda bi, qi, ki: (bi, ki, 0, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bi, qi, ki: (bi, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, block_q, h, dh),
                               lambda bi, qi, ki: (bi, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c + pq, h, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, block_q, 1), jnp.float32),
            pltpu.VMEM((h, block_q, 1), jnp.float32),
            pltpu.VMEM((h, block_q, dh), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, qp, kp)
    return out[:, :c]


def _mla_kernel(ql_ref, qr_ref, lat_ref, rope_ref, qp_ref, kp_ref, o_ref,
                m_scr, l_scr, acc_scr, *, scale, n_k):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        osm.init(m_scr, l_scr, acc_scr)

    ql = ql_ref[0].astype(jnp.float32)            # (rows, L)
    qr = qr_ref[0].astype(jnp.float32)            # (rows, R)
    lat = lat_ref[0].astype(jnp.float32)          # (block_k, L)
    rp = rope_ref[0].astype(jnp.float32)          # (block_k, R)
    qp = qp_ref[0]                                # (rows, 1)
    kp = kp_ref[0]                                # (1, block_k)
    s = osm.scores(ql, lat, scale) + osm.scores(qr, rp, scale)
    allow = (kp >= 0) & (kp <= qp)
    osm.update(jnp.where(allow, s, osm.NEG_INF), lat, m_scr, l_scr, acc_scr)

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = osm.result(l_scr[...], acc_scr[...]).astype(o_ref.dtype)


def mla_chunk_attention(q_lat, q_rope, latent, rope, q_positions,
                        k_positions, *, scale, out_dtype=None, block_q=None,
                        block_k=256, interpret=False):
    """Absorbed-matmul MLA chunk attention. q_lat: (B, C, H, L); q_rope:
    (B, C, H, R); latent: (B, Sk, L); rope: (B, Sk, R); positions as in
    :func:`chunk_attention`. Returns o_lat (B, C, H, L)."""
    out_dtype = q_lat.dtype if out_dtype is None else out_dtype
    b, c, h, lat_d = q_lat.shape
    sk = latent.shape[1]
    r = q_rope.shape[-1]
    block_q = min(block_q or max(1, 256 // h), c)
    block_k = min(block_k, sk)
    pq, pk = (-c) % block_q, (-sk) % block_k
    rows = (c + pq) * h
    qlp = jnp.pad(q_lat, ((0, 0), (0, pq), (0, 0), (0, 0))).reshape(
        b, rows, lat_d)
    qrp = jnp.pad(q_rope, ((0, 0), (0, pq), (0, 0), (0, 0))).reshape(
        b, rows, r)
    latp = jnp.pad(latent, ((0, 0), (0, pk), (0, 0)))
    ropep = jnp.pad(rope, ((0, 0), (0, pk), (0, 0)))
    qp, kp = _pad_positions(q_positions, k_positions, pq, pk)
    qp = jnp.repeat(qp, h, axis=1)                # one row per (query, head)
    n_q, n_k = (c + pq) // block_q, (sk + pk) // block_k
    br = block_q * h

    kernel = functools.partial(_mla_kernel, scale=scale, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        name="mla_chunk_attention",
        grid=(b, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, br, lat_d), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, br, r), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, block_k, lat_d), lambda bi, qi, ki: (bi, ki, 0)),
            pl.BlockSpec((1, block_k, r), lambda bi, qi, ki: (bi, ki, 0)),
            pl.BlockSpec((1, br, 1), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bi, qi, ki: (bi, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, br, lat_d), lambda bi, qi, ki: (bi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, rows, lat_d), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((br, 1), jnp.float32),
            pltpu.VMEM((br, 1), jnp.float32),
            pltpu.VMEM((br, lat_d), jnp.float32),
        ],
        interpret=interpret,
    )(qlp, qrp, latp, ropep, qp, kp)
    return out.reshape(b, c + pq, h, lat_d)[:, :c]
