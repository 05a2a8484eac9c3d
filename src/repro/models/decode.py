"""Serving path: decode-state construction, prefill, single-token decode, and
SOI *scattered decode* (the paper's inference pattern at token granularity).

State layout mirrors the model's segment structure; scanned segments carry
stacked (n_groups, ...) cache trees so the per-token step is itself a single
``lax.scan`` over layers (small HLO, fast compile, production-standard).

Scattered decode (cfg.soi), per-slot phase = t % stride:
  window complete (phase 0): pre -> compress conv (window buffer) -> middle
                         decode @ compressed position t//stride (half-length
                         caches) -> extrapolation queue -> fuse with fresh
                         skip -> post
  other phases:          pre -> push buffer -> pop queue (cached partial state)
                         -> fuse -> post        [middle entirely absent]
The middle block's KV caches hold S/stride entries: its attention cost drops
~stride^2-fold and its MLP cost stride-fold — the LM analogue of the paper's
MAC savings. "fp" mode serves from strictly-past middle outputs so the middle
can be *precomputed* between token arrivals (paper's FP latency win).

Deployment dispatch lives in ``repro.engine``: ONE jitted step resolves the
phase from the per-slot clocks (``state["t"]: (B,)``), so batches may mix
requests at different phases. (The old ``make_soi_steppers`` per-phase shim
is gone; phase-specialized wall-clock accounting now runs through
``generate_step`` with fixed clock vectors — see ``benchmarks/soi_lm_bench``.)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import BlockCfg, ModelCfg, Segment
from repro.models import attention as attn
from repro.models import mlp as mlpm
from repro.models import moe as moem
from repro.models import rglru as rgm
from repro.models import rwkv as rkm
from repro.models.layers import norm_apply
from repro.models.transformer import (_dtype, _head_weights, _noc,
                                      _segment_forward,
                                      _split_segment_params, encode,
                                      soi_partition)

Array = jax.Array


# ---------------------------------------------------------------------------
# Cache init
# ---------------------------------------------------------------------------

def _block_cache(b: BlockCfg, batch: int, max_len: int, d: int, dt,
                 paged=None) -> dict:
    c = {}
    if b.attn is not None:
        if paged is not None:
            c["attn"] = attn.init_paged_cache(b.attn, paged[0], paged[1], dt)
        else:
            c["attn"] = attn.init_cache(b.attn, batch, max_len, dt)
    if b.rglru is not None:
        c["rglru"] = rgm.rglru_init_state(b.rglru, d, batch, dt)
    if b.rwkv is not None:
        c["rwkv_tm"] = {"x_prev": jnp.zeros((batch, d), dt),
                        "S": jnp.zeros((batch, b.rwkv.n_heads,
                                        b.rwkv.head_dim, b.rwkv.head_dim),
                                       jnp.float32)}
        c["rwkv_cm"] = jnp.zeros((batch, d), dt)
    if b.cross_attn is not None:
        c["cross_k"] = None   # filled from encoder output at state init
        c["cross_v"] = None
    return c


def _stack(tree, n: int):
    """Replicate a per-layer cache prototype across the scanned layer axis
    (preserves sentinel values like the -1 'empty slot' positions)."""
    return jax.tree.map(lambda x: jnp.repeat(x[None], n, axis=0), tree)


def _segment_cache(seg: Segment, batch: int, max_len: int, d: int, dt,
                   paged=None):
    if seg.scan:
        group = {f"sub{i}": _block_cache(b, batch, max_len, d, dt, paged)
                 for i, b in enumerate(seg.blocks)}
        group = {k: {kk: vv for kk, vv in v.items() if vv is not None}
                 for k, v in group.items()}
        return _stack(group, seg.n_groups)
    out = []
    for j in range(seg.n_layers):
        c = _block_cache(seg.blocks[j % len(seg.blocks)], batch, max_len, d,
                         dt, paged)
        out.append({k: v for k, v in c.items() if v is not None})
    return out


def _segments_cache(segments, batch, max_len, d, dt, paged=None):
    return [_segment_cache(s, batch, max_len, d, dt, paged)
            for s in segments]


def _fill_cross_kv(params_segments, segments, enc_out):
    """Precompute encoder K/V for every decoder cross-attention layer."""
    out = []
    for seg_p, seg in zip(params_segments, segments):
        if all(b.cross_attn is None for b in seg.blocks):
            out.append(None)
            continue

        def kv_of(gp):
            kv = {}
            for i, b in enumerate(seg.blocks):
                if b.cross_attn is None:
                    continue
                pa = gp[f"sub{i}"]["cross"]
                kv[f"sub{i}"] = {
                    "k": jnp.einsum("bsd,dhk->bshk", enc_out, pa["wk"]),
                    "v": jnp.einsum("bsd,dhk->bshk", enc_out, pa["wv"]),
                }
            return kv

        if seg.scan:
            out.append(jax.lax.map(kv_of, seg_p))
        else:
            layer_kv = []
            for j, bp in enumerate(seg_p):
                b = seg.blocks[j % len(seg.blocks)]
                if b.cross_attn is None:
                    layer_kv.append(None)
                else:
                    layer_kv.append({
                        "k": jnp.einsum("bsd,dhk->bshk", enc_out,
                                        bp["cross"]["wk"]),
                        "v": jnp.einsum("bsd,dhk->bshk", enc_out,
                                        bp["cross"]["wv"]),
                    })
            out.append(layer_kv)
    return out


def _attn_logical_len(segments, max_len: int) -> int:
    """Logical (ring) cache length shared by a cache group's attention
    layers. Paging keys physical pages by logical index, so one page map
    serves a group only if every layer in it rings at the same length."""
    lens = set()
    for seg in segments:
        for b in seg.blocks:
            if b.attn is not None:
                lens.add(max_len if b.attn.window is None
                         else min(max_len, b.attn.window))
    if len(lens) > 1:
        raise NotImplementedError(
            f"paged KV needs a uniform ring length per cache group; "
            f"got window-capped lengths {sorted(lens)} — mixed-window "
            f"stacks need per-length page maps (not implemented)")
    return lens.pop() if lens else 0


def paged_group_lens(cfg: ModelCfg, max_len: int) -> tuple:
    """(outer_len, mid_len): logical cache lengths of the full-rate (outer)
    and compressed-middle cache groups; 0 = the group has no attention."""
    if cfg.soi is None:
        return _attn_logical_len(cfg.segments, max_len), 0
    pre, mid, post = soi_partition(cfg)
    outer = _attn_logical_len(list(pre) + list(post), max_len)
    mid_l = _attn_logical_len(mid, soi_mid_len(max_len, cfg.soi.stride))
    return outer, mid_l


def soi_mid_len(max_len: int, stride: int) -> int:
    """Length of the compressed middle caches: ceil(max_len/stride) positions,
    rounded up to a shardable multiple (a 16385-long cache would fall back to
    replication on a 16-way model axis — measured 3.4x decode state blow-up,
    EXPERIMENTS §Perf)."""
    mid_len = -(-max_len // stride)
    return -(-mid_len // 256) * 256 if mid_len > 256 else mid_len


def init_decode_state(params, cfg: ModelCfg, batch: int, max_len: int, *,
                      enc_out=None, paged=None) -> dict:
    """Decode state with per-slot clocks: state["t"] is (B,) so each batch row
    (a serving *slot*) carries its own absolute position — the substrate for
    continuous batching, where requests at different offsets (and different
    SOI phases) coexist in one batch.

    ``paged`` (an ``attention.PagedKV``) swaps the per-slot ring caches for
    shared page pools plus per-slot page maps in ``state["pages"]``; the
    compressed middle gets its own (smaller) pool — SOI's 1/stride state
    rate directly becomes 1/stride page-allocation rate. Recurrence states
    (RG-LRU, RWKV) and encoder cross-KV stay per-slot dense: they are O(1)
    or fixed-length per slot, so paging them buys nothing.
    """
    dt = _dtype(cfg)
    d = cfg.d_model
    state = {"t": jnp.zeros((batch,), jnp.int32)}
    po = pm = None
    if paged is not None:
        outer_len, mid_l = paged_group_lens(cfg, max_len)
        pages = {}
        if outer_len:
            if outer_len % paged.page_size:
                raise ValueError(f"page_size {paged.page_size} must divide "
                                 f"the outer cache length {outer_len}")
            po = (paged.page_size, paged.n_pages)
            pages["outer"] = jnp.zeros(
                (batch, outer_len // paged.page_size), jnp.int32)
        if mid_l:
            if mid_l % paged.page_size:
                raise ValueError(f"page_size {paged.page_size} must divide "
                                 f"the middle cache length {mid_l}")
            pm = (paged.page_size, paged.n_pages_mid)
            pages["mid"] = jnp.zeros(
                (batch, mid_l // paged.page_size), jnp.int32)
        state["pages"] = pages
    if cfg.soi is None:
        state["segments"] = _segments_cache(cfg.segments, batch, max_len, d,
                                            dt, paged=po)
    else:
        pre, mid, post = soi_partition(cfg)
        st = cfg.soi.stride
        mid_len = soi_mid_len(max_len, st)
        state["pre"] = _segments_cache(pre, batch, max_len, d, dt, paged=po)
        state["mid"] = _segments_cache(mid, batch, mid_len, d, dt, paged=pm)
        state["post"] = _segments_cache(post, batch, max_len, d, dt, paged=po)
        state["conv_buf"] = jnp.zeros((batch, st - 1, d), dt)
        state["queue"] = jnp.zeros((batch, st, d), dt)
    if enc_out is not None:
        state["cross_kv"] = _fill_cross_kv(params["segments"], cfg.segments,
                                           enc_out)
    return state


# ---------------------------------------------------------------------------
# One-token block / segment decode
# ---------------------------------------------------------------------------

def _block_decode(bp, b: BlockCfg, cfg: ModelCfg, x, cache, t, *,
                  cross_kv=None, pages=None, constrain=_noc):
    eps = cfg.norm_eps
    new_c = dict(cache)
    if b.attn is not None:
        h = norm_apply(b.norm, bp["ln1"], x, eps=eps)
        h, new_c["attn"] = attn.attn_decode(bp["attn"], b.attn, h,
                                            cache["attn"], t, norm_eps=eps,
                                            pages=pages, constrain=constrain)
        x = x + h
    if b.rglru is not None:
        h = norm_apply(b.norm, bp["ln1"], x, eps=eps)
        h, new_c["rglru"] = rgm.rglru_decode(bp["rglru"], b.rglru, h,
                                             cache["rglru"],
                                             constrain=constrain)
        x = x + h
    if b.rwkv is not None:
        h = norm_apply(b.norm, bp["ln1"], x, eps=eps)
        h, new_c["rwkv_tm"] = rkm.rwkv_time_mix_decode(bp["rwkv"], b.rwkv, h,
                                                       cache["rwkv_tm"])
        x = x + h
        h2 = norm_apply(b.norm, bp["ln2"], x, eps=eps)
        h2, new_c["rwkv_cm"] = rkm.rwkv_channel_mix_decode(bp["rwkv"], h2,
                                                           cache["rwkv_cm"])
        x = x + h2
        return x, new_c
    if b.cross_attn is not None:
        h = norm_apply(b.norm, bp["lnx"], x, eps=eps)
        h, _ = attn.attn_decode(bp["cross"], b.cross_attn, h, {}, t,
                                norm_eps=eps,
                                cross_kv=(cross_kv["k"], cross_kv["v"]),
                                constrain=constrain)
        x = x + h
    if b.mlp is not None:
        h = norm_apply(b.norm, bp["ln2"], x, eps=eps)
        x = x + mlpm.mlp_apply(bp["mlp"], b.mlp, h, constrain=constrain)
    if b.moe is not None:
        h = norm_apply(b.norm, bp["ln2"], x, eps=eps)
        y, _ = moem.moe_apply(bp["moe"], b.moe, h, constrain=constrain)
        x = x + y
    return x, new_c


def _segment_decode(seg_p, seg_c, seg: Segment, cfg: ModelCfg, x, t, *,
                    cross_kv=None, pages=None, constrain=_noc):
    # `pages` (the per-slot page map) is shared by every layer of the
    # segment: it rides into the scan body as a closure constant, not a
    # scanned operand.
    if seg.scan:
        def body(x, inp):
            gp, gc, ckv = inp
            new_gc = {}
            for i, b in enumerate(seg.blocks):
                sub_ckv = None if ckv is None else ckv.get(f"sub{i}")
                x, new_gc[f"sub{i}"] = _block_decode(
                    gp[f"sub{i}"], b, cfg, x, gc[f"sub{i}"], t,
                    cross_kv=sub_ckv, pages=pages, constrain=constrain)
            return x, new_gc

        if cross_kv is None:
            x, new_c = jax.lax.scan(lambda x_, inp: body(x_, (*inp, None)),
                                    x, (seg_p, seg_c))
        else:
            x, new_c = jax.lax.scan(body, x, (seg_p, seg_c, cross_kv))
        return x, new_c
    else:
        new_list = []
        for j, (bp, bc) in enumerate(zip(seg_p, seg_c)):
            b = seg.blocks[j % len(seg.blocks)]
            ckv = None if cross_kv is None else cross_kv[j]
            x, nc = _block_decode(bp, b, cfg, x, bc, t, cross_kv=ckv,
                                  pages=pages, constrain=constrain)
            new_list.append(nc)
        return x, new_list


def _embed_one(params, cfg: ModelCfg, token, constrain=_noc, t=None):
    x = jnp.take(params["embed"], token, axis=0).astype(_dtype(cfg))
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), _dtype(cfg))
    if cfg.learned_pos_len and t is not None:
        x = x + jnp.take(params["pos_embed"], t, axis=0).astype(x.dtype)
    return x


@jax.named_scope("lm_head")
def _logits_one(params, cfg: ModelCfg, x):
    h = norm_apply(cfg.segments[0].blocks[0].norm, params["final_norm"], x,
                   eps=cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", h,
                        _head_weights(params, cfg)).astype(jnp.float32)
    if cfg.logits_softcap:
        logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    return logits


# ---------------------------------------------------------------------------
# Standard decode
# ---------------------------------------------------------------------------

def decode_step(params, cfg: ModelCfg, state: dict, token, *, constrain=_noc):
    """token: (B,) int32. Returns (logits (B,V), new_state).

    state["t"] may be scalar or per-slot (B,): every position-dependent op
    (RoPE, ring-cache write, causal mask) handles per-row positions, so a
    batch may mix requests at different offsets (continuous batching).
    """
    if cfg.soi is not None:
        # a hard error, not an assert: under `python -O` an assert vanishes
        # and SOI state (conv buffer / queue / middle caches) silently rots
        raise NotImplementedError(
            "decode_step does not run SOI configs: use repro.engine "
            "(generate_step resolves the phase schedule in-program)")
    from repro.models.transformer import cast_params
    params = cast_params(params, cfg)
    t = state["t"]
    x = _embed_one(params, cfg, token, constrain, t=t)
    ckv_list = state.get("cross_kv")
    pg = state["pages"].get("outer") if "pages" in state else None
    new_segments = []
    for i, (seg_p, seg_c, seg) in enumerate(zip(params["segments"],
                                                state["segments"],
                                                cfg.segments)):
        ckv = ckv_list[i] if ckv_list is not None else None
        x, nc = _segment_decode(seg_p, seg_c, seg, cfg, x, t, cross_kv=ckv,
                                pages=pg, constrain=constrain)
        new_segments.append(nc)
    new_state = dict(state)
    new_state["segments"] = new_segments
    new_state["t"] = t + 1
    return _logits_one(params, cfg, x), new_state


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def supports_masked_prefill(cfg: ModelCfg) -> bool:
    """Whether ``prefill(..., true_length=...)`` / ``prefill_chunk`` cover
    this config. True-length masking relies on CAUSALITY to keep right-pad
    out of the real positions' outputs; it breaks where pad can flow
    backward or into non-positional state: prefix-LM / bidirectional
    decoder attention lets every query see positions inside the prefix
    window (incl. pad rows under ``frontend_len``), recurrent mixers
    (RG-LRU, RWKV) would carry pad into their scan state, and MoE routing
    lets pad compete for expert capacity. Those configs fall back to
    exact-length prefill (one compile per distinct prompt length)."""
    if cfg.prefix_lm:
        return False
    for seg in cfg.segments:
        for b in seg.blocks:
            if b.rglru is not None or b.rwkv is not None or b.moe is not None:
                return False
            if b.attn is not None and b.attn.kind == "bidir":
                return False
    return True


def _prefill_clock(b: int, s: int, tl):
    """Per-slot clocks after prefill: the TRUE prompt length (pad rows never
    advance the clock)."""
    return jnp.broadcast_to(jnp.asarray(s if tl is None else tl, jnp.int32),
                            (b,))


def _last_real(x, tl):
    """(B, S, d) -> (B, d): hidden state of the last REAL position (the row
    next-token logits are read from)."""
    if tl is None:
        return x[:, -1]
    return jax.lax.dynamic_index_in_dim(x, tl - 1, axis=1, keepdims=False)


def prefill(params, cfg: ModelCfg, tokens, *, prefix_embeds=None,
            encoder_frames=None, max_len: int | None = None,
            true_length=None, constrain=_noc):
    """Run the full-sequence path once, filling decode caches.

    Returns (last_logits (B, V), state) ready for a decode step at position S
    (state["t"] = S per slot). SOI models stream the prompt through the
    *compressed* trunk: the pre segments fill full-rate caches, the strided
    conv compresses the prompt to ceil(S/stride) frames which fill the middle
    caches, and the extrapolated+fused stream fills the post caches — plus
    the online partial states (conv window buffer, extrapolation queue) are
    left exactly where token-by-token streaming would have left them, so
    scattered decode continues bit-exactly.

    ``true_length`` (static or TRACED) enables bucketed prefill: ``tokens``
    is right-padded to a bucket length and only the first ``true_length``
    positions are real. Causality keeps pad out of the real positions'
    outputs; the cache fills, SOI partial states (conv window, extrapolation
    queue, compressed-middle frames) and last-token logits are all read at
    the true length, so the result is bit-identical to the unpadded prefill
    — while the compiled program is shared by every prompt in the bucket.

    Recurrence layers (RG-LRU, RWKV) collect their final scan state, so
    hybrid stacks (recurrentgemma) resume decode from position S too (those
    stacks don't support ``true_length``; see ``supports_masked_prefill``).
    """
    from repro.models.transformer import cast_params
    params = cast_params(params, cfg)
    b, s = tokens.shape
    if s == 0 and prefix_embeds is None:
        # zero tokens means zero complete SOI compression frames and no last
        # position to read logits from — reject instead of emitting a
        # malformed extrapolation queue / garbage logits
        raise ValueError("prefill requires a non-empty prompt")
    tl = None
    if true_length is not None:
        if not supports_masked_prefill(cfg):
            raise NotImplementedError(
                f"config '{cfg.name}' cannot mask pad (prefix-LM/"
                f"bidirectional attention, recurrence, or MoE — see "
                f"supports_masked_prefill): length-masked (bucketed) "
                f"prefill would leak pad tokens — prefill at the exact "
                f"prompt length instead")
        if prefix_embeds is not None:
            raise NotImplementedError(
                "true_length does not compose with prefix_embeds")
        tl = jnp.asarray(true_length, jnp.int32)
    max_len = max_len or s
    dt = _dtype(cfg)
    enc_out = None
    if cfg.encoder is not None:
        if encoder_frames is None:
            raise ValueError(
                f"config '{cfg.name}' has an encoder: prefill needs "
                f"encoder_frames (B, {cfg.encoder.n_frames}, "
                f"{cfg.encoder.d_model})")
        enc_out = encode(params, cfg, encoder_frames, constrain)
    from repro.models.transformer import _embed_tokens
    x = _embed_tokens(params, cfg, tokens, constrain)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    positions = jnp.arange(x.shape[1])[None]
    prefix_len = cfg.frontend_len if cfg.prefix_lm else 0

    if cfg.soi is None:
        caches = []
        for seg_p, seg in zip(params["segments"], cfg.segments):
            x, _, c = _segment_forward(seg_p, seg, cfg, x, positions=positions,
                                       prefix_len=prefix_len, enc_out=enc_out,
                                       collect_cache=True, batch=b,
                                       max_len=max_len, true_length=tl,
                                       constrain=constrain)
            caches.append(c)
        state = {"t": _prefill_clock(b, x.shape[1], tl), "segments": caches}
        if enc_out is not None:
            state["cross_kv"] = _fill_cross_kv(params["segments"],
                                               cfg.segments, enc_out)
        logits = _logits_one(params, cfg, _last_real(x, tl))
        return logits, state

    if prefix_embeds is not None or enc_out is not None or cfg.prefix_lm:
        # hard error (assert would vanish under `python -O` and the SOI
        # stream state below would be built from misaligned positions)
        raise NotImplementedError(
            "SOI prefill supports decoder-only causal token stacks "
            "(no prefix embeds / encoder / prefix-LM)")
    soi = cfg.soi
    st = soi.stride
    pre_s, mid_s, post_s = soi_partition(cfg)
    pre_p, mid_p, post_p = _split_segment_params(params["segments"], cfg)
    state = {"t": _prefill_clock(b, s, tl)}

    pre_c = []
    with jax.named_scope("soi_pre"):
        for seg_p, seg in zip(pre_p, pre_s):
            x, _, c = _segment_forward(seg_p, seg, cfg, x,
                                       positions=positions,
                                       collect_cache=True, batch=b,
                                       max_len=max_len, true_length=tl,
                                       constrain=constrain)
            pre_c.append(c)
    skip = x
    # Streaming conv window: the last stride-1 pre-trunk frames *before the
    # true length* (zero-padded for prompts shorter than the window) — what
    # the online step would hold after token true_length-1.
    if st > 1:
        padded = jnp.pad(x, ((0, 0), (st - 1, 0), (0, 0)))
        if tl is None:
            state["conv_buf"] = padded[:, padded.shape[1] - (st - 1):]
        else:
            state["conv_buf"] = jax.lax.dynamic_slice_in_dim(
                padded, tl, st - 1, axis=1)
    else:
        state["conv_buf"] = x[:, :0]

    # Compressed middle: frame j sees tokens <= j*stride; a prompt of any
    # length yields ceil(S/stride) complete frames — the same set streaming
    # would have computed by token S-1. Under padding, frames past
    # ceil(true_length/stride) are phantoms built from pad tokens: they run
    # through the middle (causality keeps them out of the real frames) but
    # never enter the caches or the queue.
    from repro.models.transformer import soi_compress
    xc = soi_compress(params["soi"], soi, x)
    cpos = jnp.arange(xc.shape[1])[None]
    mid_len = soi_mid_len(max_len, st)
    n_frames = None if tl is None else (tl + st - 1) // st
    mid_c = []
    with jax.named_scope("soi_middle"):
        for seg_p, seg in zip(mid_p, mid_s):
            xc, _, c = _segment_forward(seg_p, seg, cfg, xc, positions=cpos,
                                        collect_cache=True, batch=b,
                                        max_len=mid_len,
                                        true_length=n_frames,
                                        constrain=constrain)
            mid_c.append(c)
    # Extrapolation queue: stride copies of the last computed middle frame.
    # Any prompt of length >= 1 completes frame 0 (frame j sees tokens
    # <= j*stride, zero-padded like the streaming conv buffer at t=0); if a
    # caller nevertheless lands here with zero frames, fall back to the
    # zeros that token-by-token streaming holds before its first phase-0
    # step instead of silently emitting a zero-length queue.
    if xc.shape[1] == 0:
        state["queue"] = jnp.zeros((b, st, xc.shape[-1]), xc.dtype)
    else:
        last_frame = (xc[:, -1] if n_frames is None
                      else jax.lax.dynamic_index_in_dim(
                          xc, n_frames - 1, axis=1, keepdims=False))
        state["queue"] = jnp.repeat(last_frame[:, None], st, axis=1)

    from repro.models.transformer import soi_extrapolate, soi_fuse
    xu = soi_extrapolate(soi, xc, s)
    x = soi_fuse(params["soi"], xu, skip)
    post_c = []
    with jax.named_scope("soi_post"):
        for seg_p, seg in zip(post_p, post_s):
            x, _, c = _segment_forward(seg_p, seg, cfg, x,
                                       positions=positions,
                                       collect_cache=True, batch=b,
                                       max_len=max_len, true_length=tl,
                                       constrain=constrain)
            post_c.append(c)
    state["pre"], state["mid"], state["post"] = pre_c, mid_c, post_c
    logits = _logits_one(params, cfg, _last_real(x, tl))
    return logits, state


# ---------------------------------------------------------------------------
# Chunked prefill: ONE compiled chunk program, looped on the host
# ---------------------------------------------------------------------------

def _block_chunk(bp, b: BlockCfg, cfg: ModelCfg, x, cache, positions,
                 true_length, *, constrain=_noc):
    """One block over a prefill chunk (B, C, d): attention appends to the
    ring cache at a position offset; MLP is per-position. Returns
    (x, new_cache)."""
    eps = cfg.norm_eps
    if (b.rglru is not None or b.rwkv is not None or b.moe is not None
            or b.cross_attn is not None):
        raise NotImplementedError(
            "chunked prefill covers attention+MLP decoder stacks "
            "(recurrence / MoE / cross-attention blocks prefill whole)")
    new_c = dict(cache)
    if b.attn is not None:
        h = norm_apply(b.norm, bp["ln1"], x, eps=eps)
        h, new_c["attn"] = attn.attn_chunk(bp["attn"], b.attn, h,
                                           cache["attn"], positions,
                                           true_length, norm_eps=eps,
                                           constrain=constrain)
        x = x + h
    if b.mlp is not None:
        h = norm_apply(b.norm, bp["ln2"], x, eps=eps)
        x = x + mlpm.mlp_apply(bp["mlp"], b.mlp, h, constrain=constrain)
    return x, new_c


def _segment_chunk(seg_p, seg_c, seg: Segment, cfg: ModelCfg, x, positions,
                   true_length, *, constrain=_noc):
    """Chunked-prefill analogue of ``_segment_decode``: same layer-scan
    structure, C tokens wide."""
    if seg.scan:
        def body(x, inp):
            gp, gc = inp
            new_gc = {}
            for i, b in enumerate(seg.blocks):
                x, new_gc[f"sub{i}"] = _block_chunk(
                    gp[f"sub{i}"], b, cfg, x, gc[f"sub{i}"], positions,
                    true_length, constrain=constrain)
            return x, new_gc

        return jax.lax.scan(body, x, (seg_p, seg_c))
    new_list = []
    for j, (bp, bc) in enumerate(zip(seg_p, seg_c)):
        b = seg.blocks[j % len(seg.blocks)]
        x, nc = _block_chunk(bp, b, cfg, x, bc, positions, true_length,
                             constrain=constrain)
        new_list.append(nc)
    return x, new_list


def prefill_chunk(params, cfg: ModelCfg, state: dict, tokens, offset,
                  true_length, *, constrain=_noc):
    """Append one prefill chunk to the decode state's caches.

    ``tokens``: (B, C) at absolute positions [offset, offset+C);
    ``offset`` / ``true_length`` are TRACED scalars, so ONE compiled chunk
    program serves every chunk of every prompt — the host loops it::

        state = init_decode_state(params, cfg, 1, max_len=L)
        for i in range(ceil(padded_len / C)):
            logits, state = prefill_chunk(params, cfg, state,
                                          tokens[:, i*C:(i+1)*C], i*C, tl)

    Rows at positions >= ``true_length`` are pad: masked out of the cache
    merges, the SOI conv window / extrapolation queue, and the compressed-
    middle frames, so a chunk that is entirely pad is a no-op. Returns
    (logits, new_state): logits are next-token logits read at position
    ``true_length - 1`` — meaningful only for the chunk containing it (the
    host keeps that one). The state's clock lands on ``true_length``.

    SOI configs additionally require ``C % stride == 0`` and chunk-aligned
    offsets, so compression windows never straddle a chunk asymmetrically:
    the conv carry (``state["conv_buf"]``) supplies the stride-1 frames of
    left context, exactly like the streaming step.
    """
    from repro.models.transformer import cast_params
    params = cast_params(params, cfg)
    b, c = tokens.shape
    if cfg.encoder is not None or cfg.prefix_lm:
        raise NotImplementedError(
            "chunked prefill supports decoder-only causal token stacks")
    if not supports_masked_prefill(cfg):
        raise NotImplementedError(
            f"config '{cfg.name}' cannot mask pad (prefix-LM/bidirectional "
            f"attention, recurrence, or MoE — see supports_masked_prefill): "
            f"chunked prefill would leak pad tokens — prefill whole instead")
    from repro.models.transformer import _embed_tokens
    offset = jnp.asarray(offset, jnp.int32)
    tl = jnp.asarray(true_length, jnp.int32)
    positions = offset + jnp.arange(c, dtype=jnp.int32)
    x = _embed_tokens(params, cfg, tokens, constrain, positions=positions)
    new_state = dict(state)
    new_state["t"] = jnp.broadcast_to(tl, (b,))

    if cfg.soi is None:
        new_segments = []
        for seg_p, seg_c, seg in zip(params["segments"], state["segments"],
                                     cfg.segments):
            x, nc = _segment_chunk(seg_p, seg_c, seg, cfg, x, positions, tl,
                                   constrain=constrain)
            new_segments.append(nc)
        new_state["segments"] = new_segments
        li = jnp.clip(tl - 1 - offset, 0, c - 1)
        last = jax.lax.dynamic_index_in_dim(x, li, axis=1, keepdims=False)
        return _logits_one(params, cfg, last), new_state

    soi = cfg.soi
    st = soi.stride
    if c % st:
        raise ValueError(f"SOI chunked prefill needs chunk size {c} to be a "
                         f"multiple of the stride {st}")
    pre_s, mid_s, post_s = soi_partition(cfg)
    pre_p, mid_p, post_p = _split_segment_params(params["segments"], cfg)
    soi_p = params["soi"]

    new_pre = []
    with jax.named_scope("soi_pre"):
        for seg_p, seg_c, seg in zip(pre_p, state["pre"], pre_s):
            x, nc = _segment_chunk(seg_p, seg_c, seg, cfg, x, positions, tl,
                                   constrain=constrain)
            new_pre.append(nc)
    new_state["pre"] = new_pre
    skip = x

    # Compression across the chunk: the conv carry holds the stride-1
    # pre-trunk frames preceding the chunk, so window j*stride-(st-1)..j*st
    # is contiguous in [carry; x]. Chunk-aligned offsets (st | offset) make
    # the C/st windows exactly tile the first C rows of the concat.
    concatx = jnp.concatenate([state["conv_buf"].astype(x.dtype), x], axis=1)
    n_cf = c // st
    frames_in = concatx[:, :c].reshape(b, n_cf, st, x.shape[-1])
    xm = jnp.einsum("bfkd,kde->bfe", frames_in,
                    soi_p["compress"].astype(x.dtype))
    j0 = offset // st
    fpos = j0 + jnp.arange(n_cf, dtype=jnp.int32)
    n_true = (tl + st - 1) // st      # frames the TRUE prompt completes
    new_mid = []
    with jax.named_scope("soi_middle"):
        for seg_p, seg_c, seg in zip(mid_p, state["mid"], mid_s):
            xm, nc = _segment_chunk(seg_p, seg_c, seg, cfg, xm, fpos,
                                    n_true, constrain=constrain)
            new_mid.append(nc)
    new_state["mid"] = new_mid

    # Conv window carry -> last st-1 pre-trunk rows BEFORE the true length.
    # In concat coordinates token a sits at a - offset + (st-1), so the
    # window ending at min(offset+C, tl)-1 starts at clip(tl-offset, 0, C);
    # an all-pad chunk clips to 0 — which re-slices the carry unchanged.
    if st > 1:
        start = jnp.clip(tl - offset, 0, c)
        new_state["conv_buf"] = jax.lax.dynamic_slice_in_dim(
            concatx, start, st - 1, axis=1).astype(state["conv_buf"].dtype)
    # Queue: stride copies of the newest TRUE frame — a running carry, so
    # every chunk holding at least one real frame advances it (fp reads the
    # previous chunk's last frame back out of it, below); frames past the
    # true length never enter, and all-pad chunks keep it frozen.
    lvi = jnp.clip(n_true - 1 - j0, 0, n_cf - 1)
    has_real = j0 < n_true
    last_frame = jax.lax.dynamic_index_in_dim(xm, lvi, axis=1, keepdims=False)
    new_q = jnp.repeat(last_frame[:, None], st, axis=1)
    new_state["queue"] = jnp.where(has_real,
                                   new_q.astype(state["queue"].dtype),
                                   state["queue"])

    # Extrapolate + fuse for the chunk's own positions. pp: position p uses
    # frame p//st — all inside this chunk. fp: frame (p-1)//st — position
    # `offset` needs the PREVIOUS chunk's last frame, which is exactly the
    # queue head carried into this call (zeros at offset 0, matching
    # soi_extrapolate's zero pad).
    up = jnp.repeat(xm, st, axis=1)
    if soi.mode == "fp":
        prev = state["queue"][:, :1].astype(up.dtype)
        up = jnp.concatenate([prev, up[:, :-1]], axis=1)
    from repro.models.transformer import soi_fuse
    x = soi_fuse(soi_p, up, skip)
    new_post = []
    with jax.named_scope("soi_post"):
        for seg_p, seg_c, seg in zip(post_p, state["post"], post_s):
            x, nc = _segment_chunk(seg_p, seg_c, seg, cfg, x, positions, tl,
                                   constrain=constrain)
            new_post.append(nc)
    new_state["post"] = new_post
    li = jnp.clip(tl - 1 - offset, 0, c - 1)
    last = jax.lax.dynamic_index_in_dim(x, li, axis=1, keepdims=False)
    return _logits_one(params, cfg, last), new_state
