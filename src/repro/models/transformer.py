"""Unified LM: segments of scanned blocks covering all 10 assigned
architectures (dense GQA / MLA+MoE / SWA / RG-LRU hybrid / RWKV-6 / prefix-LM
VLM / enc-dec audio), with SOI (the paper's technique) as a first-class option.

Entry points:
  init(rng, cfg)                   -> A-tree of params (abstract-init safe)
  loss_fn(params, cfg, batch, ...) -> (loss, metrics)      [train]
  forward(params, cfg, tokens,...) -> last-position logits [eval]
  init_decode_state / prefill / decode_step                [serving]
  (slot-based continuous batching: repro.engine)

SOI-LM (cfg.soi): layers [first_layer, last_layer) form the *compressed middle*
— a width-2 stride-2 causal conv over token embeddings compresses time before
the middle; duplication-extrapolation + skip fusion restores full rate after it
(the paper's S-CC pair at token granularity). Scattered decode runs the middle
only every `stride`-th token against half-length caches; "fp" mode shifts the
middle one token into the future so it can be precomputed while waiting for the
next token (paper's FP latency story).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.configs.base import BlockCfg, ModelCfg, Segment, SOILMCfg
from repro.distributed.sharding import A, split_axes
from repro.models import attention as attn
from repro.models import mlp as mlpm
from repro.models import moe as moem
from repro.models import rglru as rgm
from repro.models import rwkv as rkm
from repro.models.layers import dense_init, embed_init, norm_apply, norm_init

Array = jax.Array


def _dtype(cfg: ModelCfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def cast_params(params, cfg: ModelCfg):
    """Mixed precision: f32 master params -> compute dtype for fwd/bwd.
    jax.grad through the cast yields f32 grads for the f32 masters. The
    casts carry the ``cast_params`` scope in the compiled program."""
    dt = _dtype(cfg)
    with jax.named_scope("cast_params"):
        return jax.tree.map(
            lambda p: p.astype(dt) if hasattr(p, "dtype")
            and p.dtype == jnp.float32 else p, params)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _block_init(rng, b: BlockCfg, d: int) -> dict:
    ks = jax.random.split(rng, 8)
    p = {}
    if b.attn is not None:
        p["ln1"] = norm_init(b.norm, d)
        p["attn"] = attn.attn_init(ks[0], b.attn, d)
    if b.cross_attn is not None:
        p["lnx"] = norm_init(b.norm, d)
        p["cross"] = attn.attn_init(ks[1], b.cross_attn, d)
    if b.rglru is not None:
        p["ln1"] = norm_init(b.norm, d)
        p["rglru"] = rgm.rglru_init(ks[2], b.rglru, d)
    if b.rwkv is not None:
        p["ln1"] = norm_init(b.norm, d)
        p["rwkv"] = rkm.rwkv_init(ks[3], b.rwkv, d)
        p["ln2"] = norm_init(b.norm, d)
    if b.mlp is not None:
        p["ln2"] = norm_init(b.norm, d)
        p["mlp"] = mlpm.mlp_init(ks[4], b.mlp, d)
    if b.moe is not None:
        p["ln2"] = norm_init(b.norm, d)
        p["moe"] = moem.moe_init(ks[5], b.moe, d)
    return p


def _stack_block_init(rng, blocks: tuple, n_groups: int, d: int):
    """Stacked params for a scanned segment: leading 'layers' axis."""
    def group_init(key):
        sks = jax.random.split(key, len(blocks))
        return {f"sub{i}": split_axes(_block_init(sks[i], b, d))[0]
                for i, b in enumerate(blocks)}

    proto = {f"sub{i}": _block_init(k, b, d)
             for i, (k, b) in enumerate(zip(jax.random.split(rng, len(blocks)),
                                            blocks))}
    _, axes = split_axes(proto)
    keys = jax.random.split(rng, n_groups)
    vals = jax.vmap(group_init)(keys)
    axes = jax.tree.map(
        lambda ax: ("layers",) + ax, axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    return jax.tree.map(lambda v, ax: A(v, ax), vals, axes,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


def _segments_init(rng, segments: tuple, d: int):
    out = []
    for i, seg in enumerate(segments):
        key = jax.random.fold_in(rng, i)
        if seg.scan:
            out.append(_stack_block_init(key, seg.blocks, seg.n_groups, d))
        else:
            sks = jax.random.split(key, seg.n_layers)
            out.append([
                _block_init(sks[j], seg.blocks[j % len(seg.blocks)], d)
                for j in range(seg.n_layers)])
    return out


def init(rng, cfg: ModelCfg):
    """A-tree of all params. Safe under jax.eval_shape (abstract init)."""
    ks = jax.random.split(rng, 8)
    d = cfg.d_model
    params = {
        "embed": embed_init(ks[0], cfg.vocab, d),
        "final_norm": norm_init(cfg.segments[0].blocks[0].norm, d),
        "segments": _segments_init(ks[1], cfg.segments, d),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], (d, cfg.vocab),
                                       ("embed", "vocab"))
    if cfg.learned_pos_len:
        params["pos_embed"] = dense_init(ks[7], (cfg.learned_pos_len, d),
                                         ("seq_table", "embed"), scale=0.02)
    if cfg.encoder is not None:
        params["encoder"] = {
            "segments": _segments_init(ks[3], cfg.encoder.segments,
                                       cfg.encoder.d_model),
            "final_norm": norm_init("layernorm", cfg.encoder.d_model),
        }
        if cfg.encoder.d_model != d:
            params["encoder"]["proj"] = dense_init(
                ks[4], (cfg.encoder.d_model, d), ("stub", "embed"))
    if cfg.soi is not None:
        st = cfg.soi.stride
        # S-CC compress conv (kernel = stride) + identity-biased skip fusion.
        wc = dense_init(ks[5], (st, d, d), ("conv_k", "embed", "embed_act"),
                        scale=(st * d) ** -0.5)
        wf_new = 0.02 * jax.random.truncated_normal(ks[6], -3, 3, (d, d))
        wf = jnp.concatenate([wf_new, jnp.eye(d)], axis=0)     # [xu; skip]
        params["soi"] = {"compress": wc,
                         "fuse": A(wf, ("stub", "embed"))}
    return params


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------

def _noc(x, axes):
    return x


def _block_apply(p: dict, b: BlockCfg, cfg: ModelCfg, x, *, positions,
                 prefix_len=0, enc_out=None, fill_cache=None,
                 fill_true_length=None, constrain=_noc, rwkv_prev=None):
    """Full-sequence block. Returns (x, aux_loss, cache_out).

    ``fill_true_length`` masks a right-padded prefill's pad rows out of the
    cache fill (bucketed prefill). Recurrent mixers and MoE can't honor it —
    pad tokens would enter the scan state / expert-capacity race — so the
    masked path is gated to attention+MLP stacks (see
    ``repro.models.decode.supports_masked_prefill``).
    """
    aux = 0.0
    cache_out = {}
    eps = cfg.norm_eps
    if fill_true_length is not None and (b.rglru is not None
                                         or b.rwkv is not None
                                         or b.moe is not None):
        raise NotImplementedError(
            "length-masked prefill covers attention+MLP stacks only: "
            "recurrence states and MoE routing would absorb pad tokens")
    if b.attn is not None:
        h = norm_apply(b.norm, p["ln1"], x, eps=eps)
        h, c = attn.attn_forward(
            p["attn"], b.attn, h, positions=positions, prefix_len=prefix_len,
            norm_eps=eps,
            fill_cache=None if fill_cache is None else fill_cache.get("attn"),
            fill_true_length=fill_true_length,
            constrain=constrain)
        x = x + h
        if c is not None:
            cache_out["attn"] = c
    if b.rglru is not None:
        h = norm_apply(b.norm, p["ln1"], x, eps=eps)
        h, rg_state = rgm.rglru_forward(p["rglru"], b.rglru, h,
                                        constrain=constrain)
        x = x + h
        if fill_cache is not None:
            cache_out["rglru"] = rg_state
    if b.rwkv is not None:
        h = norm_apply(b.norm, p["ln1"], x, eps=eps)
        prev_tm = None if rwkv_prev is None else rwkv_prev.get("x_prev_tm")
        h, (x_last, S) = rkm.rwkv_time_mix(p["rwkv"], b.rwkv, h,
                                           x_prev=prev_tm,
                                           constrain=constrain)
        x = x + h
        if fill_cache is not None:
            cache_out["rwkv_tm"] = {"x_prev": x_last, "S": S}
        h2 = norm_apply(b.norm, p["ln2"], x, eps=eps)
        prev_cm = None if rwkv_prev is None else rwkv_prev.get("x_prev_cm")
        h2, x_last2 = rkm.rwkv_channel_mix(p["rwkv"], h2, x_prev=prev_cm)
        x = x + h2
        if fill_cache is not None:
            cache_out["rwkv_cm"] = x_last2
        return x, aux, cache_out
    if b.cross_attn is not None:
        h = norm_apply(b.norm, p["lnx"], x, eps=eps)
        h, _ = attn.attn_forward(p["cross"], b.cross_attn, h,
                                 positions=positions, kv_x=enc_out,
                                 norm_eps=eps, constrain=constrain)
        x = x + h
    if b.mlp is not None:
        h = norm_apply(b.norm, p["ln2"], x, eps=eps)
        x = x + mlpm.mlp_apply(p["mlp"], b.mlp, h, constrain=constrain)
    if b.moe is not None:
        h = norm_apply(b.norm, p["ln2"], x, eps=eps)
        y, a = moem.moe_apply(p["moe"], b.moe, h, constrain=constrain)
        x = x + y
        aux = aux + a
    return x, aux, cache_out


def _segment_forward(seg_p, seg: Segment, cfg: ModelCfg, x, *, positions,
                     prefix_len=0, enc_out=None, collect_cache=False,
                     batch=None, max_len=0, true_length=None, constrain=_noc):
    """Apply one segment (scanned or unrolled). Returns (x, aux, caches)."""
    dt = _dtype(cfg)

    def apply_group(x, gp, want_cache):
        aux = 0.0
        caches = {}
        for i, b in enumerate(seg.blocks):
            fill = None
            if want_cache:
                fill = {"attn": attn.init_cache(b.attn, batch, max_len, dt)
                        if b.attn is not None else None}
            x, a, c = _block_apply(gp[f"sub{i}"], b, cfg, x,
                                   positions=positions, prefix_len=prefix_len,
                                   enc_out=enc_out, fill_cache=fill,
                                   fill_true_length=true_length,
                                   constrain=constrain)
            aux = aux + a
            caches[f"sub{i}"] = c
        # Sequence-parallel the between-block carry: this is what the layer
        # scan stacks as remat residuals, so sharding it over the model axis
        # divides the dominant activation-memory term by the TP degree.
        x = constrain(x, ("batch", "seq_act", "embed_act"))
        return x, aux, caches

    if seg.scan:
        policy = None
        if cfg.remat_policy == "dots":
            # save matmul outputs: backward skips recomputing the MXU work
            # (the expensive part); only elementwise chains re-run
            policy = jax.checkpoint_policies.checkpoint_dots
        elif cfg.remat_policy == "names":
            # save only the tagged ffn hidden: biggest recompute win per byte
            policy = jax.checkpoint_policies.save_only_these_names(
                "ffn_hidden")

        def body(carry, gp):
            x, aux = carry
            if cfg.remat:
                x2, a, c = jax.checkpoint(
                    lambda x_, gp_: apply_group(x_, gp_, collect_cache),
                    prevent_cse=False, policy=policy)(x, gp)
            else:
                x2, a, c = apply_group(x, gp, collect_cache)
            return (x2, aux + jnp.asarray(a, jnp.float32)), c

        (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                        seg_p)
        return x, aux, caches
    else:
        aux = 0.0
        caches = []
        for j, bp in enumerate(seg_p):
            b = seg.blocks[j % len(seg.blocks)]
            fill = None
            if collect_cache:
                fill = {"attn": attn.init_cache(b.attn, batch, max_len, dt)
                        if b.attn is not None else None}
            x, a, c = _block_apply(bp, b, cfg, x, positions=positions,
                                   prefix_len=prefix_len, enc_out=enc_out,
                                   fill_cache=fill,
                                   fill_true_length=true_length,
                                   constrain=constrain)
            aux = aux + a
            caches.append(c)
        return x, aux, caches


# ---------------------------------------------------------------------------
# SOI segment partitioning
# ---------------------------------------------------------------------------

def soi_partition(cfg: ModelCfg):
    """Split cfg.segments into (pre, mid, post) segment lists at the SOI
    boundaries. Boundaries must align with block-pattern groups."""
    soi = cfg.soi
    pre, mid, post = [], [], []
    idx = 0
    for seg in cfg.segments:
        glen = len(seg.blocks)
        for part, lo, hi in (("pre", 0, soi.first_layer),
                             ("mid", soi.first_layer, soi.last_layer),
                             ("post", soi.last_layer, cfg.n_layers)):
            a = max(idx, lo)
            b = min(idx + seg.n_layers, hi)
            if b > a:
                assert (a - idx) % glen == 0 and (b - a) % glen == 0, \
                    "SOI boundary must align with the segment block pattern"
                sub = dataclasses.replace(seg, n_layers=b - a)
                {"pre": pre, "mid": mid, "post": post}[part].append(sub)
        idx += seg.n_layers
    return pre, mid, post


def _split_segment_params(params_segments, cfg: ModelCfg):
    """Slice stacked segment params along the layer axis at SOI boundaries.
    Segments already split (``split_soi_params``) pass through."""
    if isinstance(params_segments, dict):
        return (params_segments["pre"], params_segments["mid"],
                params_segments["post"])
    soi = cfg.soi
    pre, mid, post = [], [], []
    idx = 0
    for seg_p, seg in zip(params_segments, cfg.segments):
        glen = len(seg.blocks)
        for part, lo, hi in (("pre", 0, soi.first_layer),
                             ("mid", soi.first_layer, soi.last_layer),
                             ("post", soi.last_layer, cfg.n_layers)):
            a = max(idx, lo)
            b = min(idx + seg.n_layers, hi)
            if b > a:
                if seg.scan:
                    g0, g1 = (a - idx) // glen, (b - idx) // glen
                    sl = jax.tree.map(lambda v: v[g0:g1], seg_p)
                else:
                    sl = seg_p[a - idx:b - idx]
                {"pre": pre, "mid": mid, "post": post}[part].append(sl)
        idx += seg.n_layers
    return pre, mid, post


def split_soi_params(params, cfg: ModelCfg):
    """``params`` with its stacked segments split at the SOI boundaries,
    as ``{"pre": [...], "mid": [...], "post": [...]}``: a program given
    this tree reads each group's weights in place, where slicing the
    stacked tree copies them on every call. Plain configs pass through."""
    if cfg.soi is None:
        return params
    pre, mid, post = _split_segment_params(params["segments"], cfg)
    return dict(params, segments={"pre": pre, "mid": mid, "post": post})


def soi_compress(soi_p, soi: SOILMCfg, x):
    """S-CC compress: width-`stride` stride-`stride` *causal* conv over time —
    compressed frame s sees tokens <= s*stride (left-padded), so duplication
    extrapolation stays causal (PP) exactly as in the paper's conv setting.

    Any length S yields ceil(S/stride) frames — exactly the set of complete
    compression windows, which is what online prefill needs for prompts that
    aren't stride-multiples (training always uses multiples)."""
    from repro.core.stmc import causal_conv1d
    return causal_conv1d(x, soi_p["compress"].astype(x.dtype),
                         stride=soi.stride)


def soi_extrapolate(soi: SOILMCfg, xc, out_len: int):
    up = jnp.repeat(xc, soi.stride, axis=1)[:, :out_len]
    if soi.mode == "fp":
        pad = jnp.zeros_like(up[:, :1])
        up = jnp.concatenate([pad, up[:, :-1]], axis=1)
    return up


def soi_fuse(soi_p, xu, skip):
    cat = jnp.concatenate([xu, skip], axis=-1)
    return jnp.einsum("...c,cd->...d", cat, soi_p["fuse"].astype(cat.dtype))


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------

def _embed_tokens(params, cfg: ModelCfg, tokens, constrain=_noc,
                  positions=None):
    """``positions`` ((S,) absolute, possibly traced) overrides the default
    from-zero learned-position rows — chunked prefill embeds mid-sequence."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(_dtype(cfg))
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), _dtype(cfg))
    if cfg.learned_pos_len:
        pe = (params["pos_embed"][:tokens.shape[1]] if positions is None
              else jnp.take(params["pos_embed"], positions, axis=0))
        x = x + pe.astype(x.dtype)
    return constrain(x, ("batch", "seq", "embed_act"))


def encode(params, cfg: ModelCfg, frames, constrain=_noc):
    """Whisper audio encoder over stub frontend frames (B, n_frames, d_enc)."""
    params = cast_params(params, cfg)
    enc = cfg.encoder
    x = frames.astype(_dtype(cfg))
    positions = jnp.arange(x.shape[1])[None]
    for seg_p, seg in zip(params["encoder"]["segments"], enc.segments):
        x, _, _ = _segment_forward(seg_p, seg, cfg, x, positions=positions,
                                   constrain=constrain)
    x = norm_apply("layernorm", params["encoder"]["final_norm"], x,
                   eps=cfg.norm_eps)
    if "proj" in params["encoder"]:
        x = jnp.einsum("bsd,de->bse", x, params["encoder"]["proj"])
    return x


def trunk(params, cfg: ModelCfg, tokens, *, prefix_embeds=None, enc_out=None,
          constrain=_noc):
    """Token embeddings -> final norm hidden states (B, S, d)."""
    x = _embed_tokens(params, cfg, tokens, constrain)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    positions = jnp.arange(s)[None]
    prefix_len = cfg.frontend_len if cfg.prefix_lm else 0

    aux = 0.0
    if cfg.soi is None:
        for seg_p, seg in zip(params["segments"], cfg.segments):
            x, a, _ = _segment_forward(seg_p, seg, cfg, x, positions=positions,
                                       prefix_len=prefix_len, enc_out=enc_out,
                                       constrain=constrain)
            aux = aux + a
    else:
        soi = cfg.soi
        pre_s, mid_s, post_s = soi_partition(cfg)
        pre_p, mid_p, post_p = _split_segment_params(params["segments"], cfg)
        for seg_p, seg in zip(pre_p, pre_s):
            x, a, _ = _segment_forward(seg_p, seg, cfg, x, positions=positions,
                                       prefix_len=prefix_len, enc_out=enc_out,
                                       constrain=constrain)
            aux = aux + a
        skip = x
        xc = soi_compress(params["soi"], soi, x)
        cpos = jnp.arange(xc.shape[1])[None]
        for seg_p, seg in zip(mid_p, mid_s):
            xc, a, _ = _segment_forward(seg_p, seg, cfg, xc, positions=cpos,
                                        enc_out=enc_out, constrain=constrain)
            aux = aux + a
        xu = soi_extrapolate(soi, xc, s)
        x = soi_fuse(params["soi"], xu, skip)
        for seg_p, seg in zip(post_p, post_s):
            x, a, _ = _segment_forward(seg_p, seg, cfg, x, positions=positions,
                                       prefix_len=prefix_len, enc_out=enc_out,
                                       constrain=constrain)
            aux = aux + a

    x = norm_apply(cfg.segments[0].blocks[0].norm, params["final_norm"], x,
                   eps=cfg.norm_eps)
    return x, aux


def _head_weights(params, cfg: ModelCfg):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def chunked_xent(h, head_w, targets, *, softcap=None, chunk=256,
                 constrain=_noc):
    """Memory-sane cross entropy: scans sequence chunks so the (B, S, V)
    logits tensor never materializes (vital at vocab 256k)."""
    b, s, d = h.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    n = (s + pad) // chunk
    hc = jnp.moveaxis(h.reshape(b, n, chunk, d), 1, 0)
    tc = jnp.moveaxis(targets.reshape(b, n, chunk), 1, 0)

    def body(carry, inp):
        nll_sum, count = carry
        hb, tb = inp
        logits = jnp.einsum("bsd,dv->bsv", hb, head_w).astype(jnp.float32)
        if softcap:
            logits = softcap * jnp.tanh(logits / softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(tb, 0)[..., None],
                                 axis=-1)[..., 0]
        mask = (tb >= 0).astype(jnp.float32)
        nll_sum = nll_sum + jnp.sum((lse - ll) * mask)
        count = count + jnp.sum(mask)
        return (nll_sum, count), None

    body_fn = jax.checkpoint(body, prevent_cse=False)
    (nll, cnt), _ = jax.lax.scan(body_fn, (0.0, 0.0), (hc, tc))
    return nll / jnp.maximum(cnt, 1.0)


def loss_fn(params, cfg: ModelCfg, batch: dict, constrain=_noc):
    """batch: tokens (B,S), targets (B,S) [-1 = masked], optional
    patch_embeds / encoder_frames stubs."""
    params = cast_params(params, cfg)
    enc_out = None
    if cfg.encoder is not None:
        enc_out = encode(params, cfg, batch["encoder_frames"], constrain)
    prefix = batch.get("patch_embeds")
    h, aux = trunk(params, cfg, batch["tokens"], prefix_embeds=prefix,
                   enc_out=enc_out, constrain=constrain)
    targets = batch["targets"]
    if prefix is not None:   # loss only over token positions
        h = h[:, prefix.shape[1]:]
    loss = chunked_xent(h, _head_weights(params, cfg), targets,
                        softcap=cfg.logits_softcap, constrain=constrain)
    total = loss + aux
    return total, {"xent": loss, "aux": aux}


def forward(params, cfg: ModelCfg, tokens, *, prefix_embeds=None,
            enc_out=None, constrain=_noc):
    """Full logits (small inputs only — tests/examples)."""
    params = cast_params(params, cfg)
    h, _ = trunk(params, cfg, tokens, prefix_embeds=prefix_embeds,
                 enc_out=enc_out, constrain=constrain)
    logits = jnp.einsum("bsd,dv->bsv", h,
                        _head_weights(params, cfg)).astype(jnp.float32)
    if cfg.logits_softcap:
        logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    return logits
