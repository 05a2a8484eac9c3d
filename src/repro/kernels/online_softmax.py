"""Online-softmax state shared by the blocked attention kernels.

A kernel walks key blocks in its innermost grid axis and keeps, per query
row, the running max ``m`` and denominator ``l`` (``(rows, 1)`` f32) and
the unnormalized output ``acc`` (``(rows, dv)`` f32) in VMEM scratch.
Every array is 2-D with rows on sublanes and keys or features on lanes,
the layout the TPU compiler tiles without relayouts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def update(s, v, m_ref, l_ref, acc_ref):
    """Fold one key block in: ``s`` (rows, bk) masked f32 scores, ``v``
    (bk, dv) f32 values; the refs are views of this block's rows."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = corr * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new


def result(l, acc):
    return acc / jnp.maximum(l, 1e-30)


def scores(q, k, scale):
    """(rows, d) x (bk, d) -> (rows, bk) f32 scores."""
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
