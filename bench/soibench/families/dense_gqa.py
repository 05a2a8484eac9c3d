"""Dense GQA: one stack of pre-norm blocks, each grouped-query attention
and a SwiGLU MLP (Qwen3, H2O-Danube).

* blocks: ``x += attn(rms(x))``, ``x += mlp(rms(x))``; attention is GQA
  with optional per-head RMSNorm of q and k, rotary embeddings on
  interleaved pairs, a causal mask and an optional sliding window (a key
  is seen while ``k_pos > q_pos - window``); the MLP is
  ``down(silu(gate(h)) * up(h))``;
* the SOI schedule over the stack (:func:`soibench.reference.soi`), a
  final RMSNorm and the LM head (the embedding, transposed, when tied).
"""

from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from soibench import reference
from soibench.model import ConfigMismatch
from soibench.reference import BLOCK, f32, mm, rms, rope


def sizes(config: dict) -> dict:
    """The sizes the reference and the weights follow, from the file."""
    soi = config["soi"]
    return {
        "d": config["hidden_size"], "ff": config["intermediate_size"],
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv": config["num_key_value_heads"], "dh": config["head_dim"],
        "vocab": config["vocab_size"], "tied": config["tie_word_embeddings"],
        "eps": config["rms_norm_eps"], "theta": float(config["rope_theta"]),
        "window": config.get("sliding_window"),
        "qk_norm": bool(config.get("qk_norm")),
        "stride": soi["stride"], "mid": (soi["first_layer"],
                                         soi["last_layer"]),
        "fp": soi["mode"] == "fp",
    }


def program_config(config: dict):
    """The program's ``ModelCfg`` for this file, checked field by field
    against the file's sizes: the config as run is the one the file
    states."""
    prog = config["program"]
    mod = importlib.import_module(
        "repro.configs." + prog["arch"].replace("-", "_").replace(".", "_"))
    # "smoke": the program's reduced same-family config, for the CPU tests
    make = mod.smoke_config if prog.get("smoke") else mod.config
    # the program computes in the dtype the file states
    cfg = dataclasses.replace(make(soi=prog["soi"]),
                              dtype=config["compute_dtype"])
    s = sizes(config)
    (seg,) = cfg.segments
    (blk,) = seg.blocks
    a = blk.attn
    got = {
        "d": cfg.d_model, "ff": blk.mlp.d_ff, "layers": cfg.n_layers,
        "heads": a.n_heads, "kv": a.n_kv, "dh": a.head_dim,
        "vocab": cfg.vocab, "tied": cfg.tie_embeddings, "eps": cfg.norm_eps,
        "theta": float(a.rope_theta), "window": a.window,
        "qk_norm": a.qk_norm, "stride": cfg.soi.stride,
        "mid": (cfg.soi.first_layer, cfg.soi.last_layer),
        "fp": cfg.soi.mode == "fp",
    }
    diff = {k: (s[k], got[k]) for k in s if s[k] != got[k]}
    if (diff or blk.mlp.kind != "swiglu" or blk.norm != "rmsnorm"
            or a.kind != "gqa" or a.softmax_scale is not None
            or a.logit_softcap is not None or a.rope_pct != 1.0
            or cfg.logits_softcap or cfg.embed_scale
            or cfg.soi.extrapolation != config["soi"]["extrapolation"]):
        raise ConfigMismatch(f"program config {cfg.name} departs from the "
                             f"configuration file: {diff}")
    return cfg


def leaf_specs(config: dict) -> list:
    """(path, shape, std, mean) of every parameter, in the program's
    layout: stacked layers under ``segments[0]["sub0"]``, RMSNorm scales in
    the (1 + scale) convention. ``mean`` is a number or "eye" (the identity
    matrix).

    Logits spread with a standard deviation of about 4. With a tied head
    the embedding rows have norm 1 and the final norm's weight is about 4,
    so a token's own embedding is a small part of the residual stream and
    the model does not merely repeat its input token."""
    s = sizes(config)
    d, ff, n, h, kv, dh, v = (s["d"], s["ff"], s["layers"], s["heads"],
                              s["kv"], s["dh"], s["vocab"])
    blk = "segments/0/sub0/"
    out = [
        ("embed", (v, d), d ** -0.5 if s["tied"] else 1.0, 0.0),
        ("final_norm/scale", (d,), 0.1, 3.0 if s["tied"] else 0.0),
        (blk + "ln1/scale", (n, d), 0.1, 0.0),
        (blk + "ln2/scale", (n, d), 0.1, 0.0),
        (blk + "attn/wq", (n, d, h, dh), d ** -0.5, 0.0),
        (blk + "attn/wk", (n, d, kv, dh), d ** -0.5, 0.0),
        (blk + "attn/wv", (n, d, kv, dh), d ** -0.5, 0.0),
        (blk + "attn/wo", (n, h, dh, d), (h * dh) ** -0.5, 0.0),
        (blk + "mlp/up", (n, d, ff), d ** -0.5, 0.0),
        (blk + "mlp/gate", (n, d, ff), d ** -0.5, 0.0),
        (blk + "mlp/down", (n, ff, d), ff ** -0.5, 0.0),
        ("soi/compress", (s["stride"], d, d), (s["stride"] * d) ** -0.5,
         0.0),
        # fuse = [middle; skip] -> d: the middle's share is as large as the
        # skip's, whose part is the identity plus noise
        ("soi/fuse_mid", (d, d), d ** -0.5, 0.0),
        ("soi/fuse_skip", (d, d), 0.02, "eye"),
    ]
    if s["qk_norm"]:
        out += [(blk + "attn/q_norm/scale", (n, dh), 0.1, 0.0),
                (blk + "attn/k_norm/scale", (n, dh), 0.1, 0.0)]
    if not s["tied"]:
        out.append(("lm_head", (d, v), 4.0 / np.sqrt(d), 0.0))
    return out


# -- the plain reference ---------------------------------------------------

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
        sc = mm("qhd,khd->hqk", qb, k, quant) / jnp.sqrt(jnp.float32(dh))
        allow = pos[None, :] <= qp[:, None]
        if window is not None:
            allow = allow & (pos[None, :] > qp[:, None] - window)
        sc = jnp.where(allow[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return mm("hqk,khd->qhd", p, v, quant)

    return jax.lax.map(block, jnp.arange(nb)).reshape(s, h, dh)


def _layers(x, blk, lo, hi, pos, s, quant):
    """Apply stacked layers ``[lo, hi)`` of ``blk`` to x (S, d)."""

    def body(i, x):
        w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), blk)
        a = w["attn"]
        h = rms(x, w["ln1"]["scale"], s["eps"])
        q = mm("sd,dhk->shk", h, a["wq"], quant)
        k = mm("sd,dhk->shk", h, a["wk"], quant)
        v = mm("sd,dhk->shk", h, a["wv"], quant)
        if s["qk_norm"]:
            q = rms(q, a["q_norm"]["scale"], s["eps"])
            k = rms(k, a["k_norm"]["scale"], s["eps"])
        q, k = rope(q, pos, s["theta"]), rope(k, pos, s["theta"])
        o = _attention(q, k, v, pos, s["window"], quant)
        x = x + mm("shk,hkd->sd", o, a["wo"], quant)
        h = rms(x, w["ln2"]["scale"], s["eps"])
        m = w["mlp"]
        up = mm("sd,df->sf", h, m["up"], quant)
        gate = mm("sd,df->sf", h, m["gate"], quant)
        return x + mm("sf,fd->sd", jax.nn.silu(gate) * up, m["down"], quant)

    return jax.lax.fori_loop(lo, hi, body, x)


def hidden(w, s: dict, tokens, quant=None):
    """Final-norm hidden states (S, d) of ``tokens`` (S,), S a multiple of
    ``stride * BLOCK``. ``s`` is :func:`sizes`."""
    w = f32(w)
    blk = w["segments"][0]["sub0"]
    x = jnp.take(w["embed"], tokens, axis=0)
    x = reference.soi(w, s, x, lambda x, lo, hi, pos: _layers(
        x, blk, lo, hi, pos, s, quant), quant)
    return rms(x, w["final_norm"]["scale"], s["eps"])


def head(w, s: dict):
    """The LM head (d, vocab) in float32."""
    return f32(w["embed"].T if s["tied"] else w["lm_head"])


# -- operations the schedule requires (soibench.flops) ---------------------

def _layer_weights(s: dict) -> int:
    d, h, kv, dh, ff = s["d"], s["heads"], s["kv"], s["dh"], s["ff"]
    return 2 * d * h * dh + 2 * d * kv * dh + 3 * d * ff


def _ctx(n: int, window) -> int:
    return n if window is None else min(n, window)


def token_flops(s: dict, pos: int, head: bool) -> float:
    """Operations for the token at position ``pos`` (context ``pos + 1``):
    2 x the weights it passes through, and attention's 4 x context x heads
    x head_dim per layer, the context capped at the window; the middle
    attends over frames."""
    first, last = s["mid"]
    n_mid = last - first
    n_outer = s["layers"] - n_mid
    st = s["stride"]
    attn = 4 * s["heads"] * s["dh"]
    w = (n_outer * _layer_weights(s) + 2 * s["d"] * s["d"]
         + (n_mid * _layer_weights(s) + st * s["d"] * s["d"]) / st)
    f = 2.0 * w + n_outer * attn * _ctx(pos + 1, s["window"])
    f += n_mid * attn * _ctx(pos // st + 1, s["window"]) / st
    if head:
        f += 2.0 * s["d"] * s["vocab"]
    return f
