"""The plain reference of an SOI language model: one full forward pass in
float32 at the highest matmul precision, in straightforward ``jax.numpy``,
with no kernel, cache, page or batch. It imports nothing of the program.

The layers are the family's (``hidden`` and ``head`` of a module in
:mod:`soibench.families`, which upcasts weights held in bfloat16 to
float32); this module holds what every family shares:

* the matmul at ``Precision.HIGHEST`` and its float8 control, RMSNorm with
  the (1 + scale) weight convention of the parameter layout, and rotary
  embeddings on interleaved pairs;
* SOI (Stefanski et al.): after layers ``[0, first)`` the sequence is
  compressed by a width-``stride``, stride-``stride`` causal convolution
  (frame ``j`` reads tokens ``j*stride - stride + 1 .. j*stride``, zero
  before the start), layers ``[first, last)`` run over the frames at frame
  positions, each token takes its frame's output back (duplication; "fp"
  shifts it one token later), and ``[duplicated; skip] @ fuse`` feeds
  layers ``[last, n)``;
* the LM head's logits in blocks of positions, and the two readings the
  check takes from them (:func:`gaps`, :func:`control_gaps`).

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


def f32(tree):
    """``tree`` with every leaf in float32 (a float32 leaf is unchanged)."""
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(spec: str, a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def rope(x, pos, theta):
    """x (S, H, dh): rotate interleaved pairs (x[2i], x[2i+1])."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c],
                     axis=-1).reshape(x.shape)


def soi(w, s: dict, x, layers, quant):
    """The SOI schedule over the embedded tokens ``x`` (S, d), S a multiple
    of ``stride``. ``layers(x, lo, hi, pos)`` applies the model's layers
    ``[lo, hi)`` at positions ``pos``; ``w["soi"]`` holds the compress conv
    and the fuse."""
    n = x.shape[0]
    st = s["stride"]
    first, last = s["mid"]
    pos = jnp.arange(n, dtype=jnp.int32)
    x = layers(x, 0, first, pos)
    skip = x
    wc = w["soi"]["compress"]                      # (stride, d, d), oldest first
    xp = jnp.pad(x, ((st - 1, 0), (0, 0)))
    frames = jnp.stack([xp[i:i + n:st] for i in range(st)], axis=1)
    xc = mm("jkd,kde->je", frames, wc, quant)      # (n / stride, d)
    xc = layers(xc, first, last, pos[:xc.shape[0]])
    up = jnp.repeat(xc, st, axis=0)[:n]
    if s["fp"]:
        up = jnp.concatenate([jnp.zeros_like(up[:1]), up[:-1]], axis=0)
    x = mm("sc,cd->sd", jnp.concatenate([up, skip], axis=-1),
           w["soi"]["fuse"], quant)
    return layers(x, last, s["layers"], pos)


def _logit_blocks(fam, w, s, h, quant, fn):
    """Apply ``fn(logits_block, block_index)`` over blocks of positions, so
    the (S, vocab) logits never exist at once."""
    head = fam.head(w, s)
    nb = h.shape[0] // BLOCK

    def block(i):
        hb = jax.lax.dynamic_slice_in_dim(h, i * BLOCK, BLOCK, 0)
        return fn(mm("sd,dv->sv", hb, head, quant), i)

    out = jax.lax.map(block, jnp.arange(nb))
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), out)


def frozen(sizes: dict) -> tuple:
    """``sizes`` as the hashable static argument of the jitted entries."""
    return tuple(sorted(sizes.items()))


def padded_len(n: int, stride: int) -> int:
    step = stride * BLOCK
    return -(-n // step) * step


@functools.partial(jax.jit, static_argnames=("fam", "s"))
def gaps(fam, w, s, tokens, targets):
    """Per position p: how far the reference's logit of ``targets[p]`` lies
    below its best logit at p (the token served for position p + 1).
    ``fam`` is the family module, ``s`` its :func:`frozen` sizes."""
    s = dict(s)
    h = fam.hidden(w, s, tokens)

    def fn(lg, i):
        t = jax.lax.dynamic_slice_in_dim(targets, i * BLOCK, BLOCK, 0)
        return (jnp.max(lg, axis=-1)
                - jnp.take_along_axis(lg, t[:, None], axis=-1)[:, 0])

    return _logit_blocks(fam, w, s, h, None, fn)


@functools.partial(jax.jit, static_argnames=("fam", "s", "quant"))
def control_gaps(fam, w, s, tokens, quant="fp8"):
    """Per position: the reference's gap of the token the lower-precision
    reference puts first."""
    s = dict(s)
    h_ref = fam.hidden(w, s, tokens)
    h_low = fam.hidden(w, s, tokens, quant)
    head = fam.head(w, s)
    nb = h_ref.shape[0] // BLOCK

    def block(i):
        hr = jax.lax.dynamic_slice_in_dim(h_ref, i * BLOCK, BLOCK, 0)
        hl = jax.lax.dynamic_slice_in_dim(h_low, i * BLOCK, BLOCK, 0)
        ref = mm("sd,dv->sv", hr, head, None)
        pick = jnp.argmax(mm("sd,dv->sv", hl, head, quant), axis=-1)
        return (jnp.max(ref, axis=-1)
                - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0])

    return jax.lax.map(block, jnp.arange(nb)).reshape(-1)
