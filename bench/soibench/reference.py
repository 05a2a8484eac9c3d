"""The plain reference of an SOI language model: one full forward pass in
float32 at the highest matmul precision, in straightforward ``jax.numpy``,
with no kernel, cache, page or batch. It imports nothing of the program.

The mathematics follows the configuration file:

* pre-norm blocks: ``x += attn(rms(x))``, ``x += mlp(rms(x))``; RMSNorm
  with the (1 + scale) weight convention of the parameter layout;
  attention is GQA with optional per-head RMSNorm of q and k, rotary
  embeddings on interleaved pairs, a causal mask and an optional sliding
  window (a key is seen while ``k_pos > q_pos - window``); the MLP is
  ``down(silu(gate(h)) * up(h))``;
* SOI (Stefanski et al.): after layers ``[0, first)`` the sequence is
  compressed by a width-``stride``, stride-``stride`` causal convolution
  (frame ``j`` reads tokens ``j*stride - stride + 1 .. j*stride``, zero
  before the start), layers ``[first, last)`` run over the frames at frame
  positions, each token takes its frame's output back (duplication; "fp"
  shifts it one token later), and ``[duplicated; skip] @ fuse`` feeds
  layers ``[last, n)``;
* a final RMSNorm and the LM head (the embedding, transposed, when tied).

``quant="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with one scale per tensor, the step below the configuration's
bfloat16 that a later change might take.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
BLOCK = 512           # query rows per attention block, positions per logit block


def _fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, pos, theta):
    """x (S, H, dh): rotate interleaved pairs (x[2i], x[2i+1])."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, pos, window, quant):
    """q (S, H, dh), k/v (S, Hkv, dh): causal (windowed) GQA, in blocks of
    query rows."""
    s, h, dh = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    nb = s // BLOCK

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK, 0)
        qp = jax.lax.dynamic_slice_in_dim(pos, i * BLOCK, BLOCK, 0)
        sc = _mm("qhd,khd->hqk", qb, k, quant) / jnp.sqrt(jnp.float32(dh))
        allow = pos[None, :] <= qp[:, None]
        if window is not None:
            allow = allow & (pos[None, :] > qp[:, None] - window)
        sc = jnp.where(allow[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm("hqk,khd->qhd", p, v, quant)

    return jax.lax.map(block, jnp.arange(nb)).reshape(s, h, dh)


def _layers(x, blk, lo, hi, pos, s, quant):
    """Apply stacked layers ``[lo, hi)`` of ``blk`` to x (S, d)."""

    def body(i, x):
        w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), blk)
        a = w["attn"]
        h = _rms(x, w["ln1"]["scale"], s["eps"])
        q = _mm("sd,dhk->shk", h, a["wq"], quant)
        k = _mm("sd,dhk->shk", h, a["wk"], quant)
        v = _mm("sd,dhk->shk", h, a["wv"], quant)
        if s["qk_norm"]:
            q = _rms(q, a["q_norm"]["scale"], s["eps"])
            k = _rms(k, a["k_norm"]["scale"], s["eps"])
        q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
        o = _attention(q, k, v, pos, s["window"], quant)
        x = x + _mm("shk,hkd->sd", o, a["wo"], quant)
        h = _rms(x, w["ln2"]["scale"], s["eps"])
        m = w["mlp"]
        up = _mm("sd,df->sf", h, m["up"], quant)
        gate = _mm("sd,df->sf", h, m["gate"], quant)
        return x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, m["down"], quant)

    return jax.lax.fori_loop(lo, hi, body, x)


def hidden(w, s: dict, tokens, quant=None):
    """Final-norm hidden states (S, d) of ``tokens`` (S,), S a multiple of
    ``stride * BLOCK``. ``s`` is :func:`soibench.model.sizes`."""
    n = tokens.shape[0]
    st = s["stride"]
    first, last = s["mid"]
    blk = w["segments"][0]["sub0"]
    pos = jnp.arange(n, dtype=jnp.int32)
    x = jnp.take(w["embed"], tokens, axis=0)
    x = _layers(x, blk, 0, first, pos, s, quant)
    skip = x
    wc = w["soi"]["compress"]                      # (stride, d, d), oldest first
    xp = jnp.pad(x, ((st - 1, 0), (0, 0)))
    frames = jnp.stack([xp[i:i + n:st] for i in range(st)], axis=1)
    xc = _mm("jkd,kde->je", frames, wc, quant)     # (n / stride, d)
    xc = _layers(xc, blk, first, last, pos[:xc.shape[0]], s, quant)
    up = jnp.repeat(xc, st, axis=0)[:n]
    if s["fp"]:
        up = jnp.concatenate([jnp.zeros_like(up[:1]), up[:-1]], axis=0)
    x = _mm("sc,cd->sd", jnp.concatenate([up, skip], axis=-1),
            w["soi"]["fuse"], quant)
    x = _layers(x, blk, last, s["layers"], pos, s, quant)
    return _rms(x, w["final_norm"]["scale"], s["eps"])


def _head(w, s):
    return w["embed"].T if s["tied"] else w["lm_head"]


def _logit_blocks(w, s, h, quant, fn):
    """Apply ``fn(logits_block, block_index)`` over blocks of positions, so
    the (S, vocab) logits never exist at once."""
    head = _head(w, s)
    nb = h.shape[0] // BLOCK

    def block(i):
        hb = jax.lax.dynamic_slice_in_dim(h, i * BLOCK, BLOCK, 0)
        return fn(_mm("sd,dv->sv", hb, head, quant), i)

    out = jax.lax.map(block, jnp.arange(nb))
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), out)


def frozen(sizes: dict) -> tuple:
    """``sizes`` as the hashable static argument of the jitted entries."""
    return tuple(sorted(sizes.items()))


def padded_len(n: int, stride: int) -> int:
    step = stride * BLOCK
    return -(-n // step) * step


@functools.partial(jax.jit, static_argnames=("s",))
def gaps(w, s, tokens, targets):
    """Per position p: how far the reference's logit of ``targets[p]`` lies
    below its best logit at p (the token served for position p + 1).
    ``s`` is :func:`frozen` sizes."""
    s = dict(s)
    h = hidden(w, s, tokens)

    def fn(lg, i):
        t = jax.lax.dynamic_slice_in_dim(targets, i * BLOCK, BLOCK, 0)
        return (jnp.max(lg, axis=-1)
                - jnp.take_along_axis(lg, t[:, None], axis=-1)[:, 0])

    return _logit_blocks(w, s, h, None, fn)


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def control_gaps(w, s, tokens, quant="fp8"):
    """Per position: the reference's gap of the token the lower-precision
    reference puts first."""
    s = dict(s)
    h_ref = hidden(w, s, tokens)
    h_low = hidden(w, s, tokens, quant)
    head = _head(w, s)
    nb = h_ref.shape[0] // BLOCK

    def block(i):
        hr = jax.lax.dynamic_slice_in_dim(h_ref, i * BLOCK, BLOCK, 0)
        hl = jax.lax.dynamic_slice_in_dim(h_low, i * BLOCK, BLOCK, 0)
        ref = _mm("sd,dv->sv", hr, head, None)
        pick = jnp.argmax(_mm("sd,dv->sv", hl, head, quant), axis=-1)
        return (jnp.max(ref, axis=-1)
                - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0])

    return jax.lax.map(block, jnp.arange(nb)).reshape(-1)
