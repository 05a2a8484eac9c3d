"""The unified per-token serving step: ONE jitted program per config.

For SOI configs the paper's phase schedule (recompute the compressed middle
only when ``t % stride == 0``) is resolved *inside* the compiled program from
the per-slot clock vector ``state["t"]: (B,)``:

  * the pre/post segments and the conv window push run for every slot, every
    step (they are full-rate in the paper's schedule anyway);
  * the compressed middle runs under ``lax.cond`` — executed only when at
    least one slot's compression window is complete, so a phase-aligned (or
    all-out-of-phase) batch skips the middle's FLOPs entirely on the off
    phases, exactly like the per-phase specialized steppers did;
  * middle cache / extrapolation-queue updates are masked per slot, so slots
    that are mid-window keep serving their cached partial states while
    their neighbours recompute — mixed-phase batches decode bit-exactly.

The compiled step names its parts with ``jax.named_scope``: ``soi_pre``,
``soi_middle`` (the cond's true branch), ``soi_post`` and ``lm_head``
(``cast_params`` comes from the cast itself), so device time in a trace
can be put down to them.

This replaces the ``steppers[t % stride]`` caller-side dispatch of the old
``make_soi_steppers`` shim (removed): phase is data, not a compiled-program
index, which is what makes slot-based continuous batching possible.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelCfg
from repro.models import decode as D
from repro.models.transformer import (_noc, _split_segment_params,
                                      cast_params, soi_partition)


def _select_rows(mask, new, old, *, axis: int):
    """Per-slot select over a cache pytree; ``axis`` is the batch axis of the
    leaves (1 for scanned segments, whose leaves stack a leading layer axis)."""
    def sel(n, o):
        shape = [1] * n.ndim
        shape[axis] = mask.shape[0]
        return jnp.where(mask.reshape(shape), n, o)
    return jax.tree.map(sel, new, old)


def _select_mid_caches(mask, new, old, segs, *, paged: bool):
    """Commit the middle's cache updates only for complete-window slots.

    Dense layout: a per-slot ``where`` over the batch axis. Paged layout:
    attention pools have NO batch axis — their writes were already masked by
    routing mid-window slots through the null page — so only the per-slot
    leaves (recurrence states) still select by row.
    """
    out = []
    for nc, oc, seg in zip(new, old, segs):
        axis = 1 if seg.scan else 0
        if not paged:
            out.append(_select_rows(mask, nc, oc, axis=axis))
            continue

        def blk(n_blk, o_blk):
            return {k: (n_blk[k] if k == "attn"
                        else _select_rows(mask, n_blk[k], o_blk[k],
                                          axis=axis))
                    for k in n_blk}

        if seg.scan:
            out.append({sub: blk(n_blk, oc[sub])
                        for sub, n_blk in nc.items()})
        else:
            out.append([blk(n_blk, o_blk)
                        for n_blk, o_blk in zip(nc, oc)])
    return out


def _run_segments(parts_p, parts_s, caches, cfg, x, t, constrain,
                  pages=None):
    new = []
    for seg_p, seg_c, seg in zip(parts_p, caches, parts_s):
        x, nc = D._segment_decode(seg_p, seg_c, seg, cfg, x, t,
                                  pages=pages, constrain=constrain)
        new.append(nc)
    return x, new


def step_metrics(t, active, stride: int):
    """Per-step device telemetry vector, computed INSIDE the jitted step.

    Layout (int32, length ``stride + 2``)::

        [occ_phase_0, ..., occ_phase_{stride-1}, mid_fired, n_active]

    ``occ_phase_p`` counts active slots whose pre-step clock sits at
    ``t % stride == p`` (the phase-occupancy histogram — phase-aligned
    scheduling wants this mass concentrated); ``mid_fired`` is 1 iff the
    compressed middle's ``lax.cond`` predicate would fire this step (some
    active slot at phase 0); ``n_active`` is the live-slot count. Pass
    ``stride=1`` for non-SOI configs (one bucket, middle "fires" whenever
    any slot is active).

    The vector stays on device: the engine attaches it to
    ``ResultTokens.metrics`` and it reaches the host through the serving
    loop's one-step-deferred drain (``convert_to_numpy``), never through
    a per-step sync. ``repro.obs.registry.EngineTelemetry`` is the
    host-side consumer.
    """
    t = jnp.asarray(t, jnp.int32)
    b = t.shape[0]
    act = (jnp.ones((b,), bool) if active is None
           else jnp.asarray(active, bool))
    one = jnp.where(act, 1, 0).astype(jnp.int32)
    phase = t % stride
    hist = jnp.zeros((stride,), jnp.int32).at[phase].add(one)
    mid = jnp.any((phase == 0) & act).astype(jnp.int32)
    return jnp.concatenate([hist, mid[None], jnp.sum(one)[None]])


def generate_step(params, cfg: ModelCfg, state: dict, tokens, *,
                  active=None, constrain=_noc, draft: bool = False):
    """Advance every slot one token. tokens: (B,) int32; state["t"]: (B,).

    Returns (logits (B, V), new_state). Non-SOI configs take the standard
    per-slot decode path; SOI configs take the masked scattered-decode path
    described in the module docstring. Exactly one compiled program per
    config — slot phases are data.

    ``active`` (optional (B,) bool) marks occupied slots: inactive slots'
    clocks freeze and never trigger the middle's ``lax.cond``, so a
    partially occupied engine keeps the runtime FLOP skip. ``None`` means
    all slots active.

    ``draft=True`` forces every slot off-phase: the compressed middle never
    runs and every position is served from the extrapolation queue — the
    self-speculative *draft* schedule (see ``engine.speculative``). On
    slots whose true phase is already off, a draft step is bit-identical to
    a normal step; non-SOI configs have no middle to skip, so the flag is a
    no-op there (the model is its own perfect draft).
    """
    if cfg.soi is None:
        logits, ns = D.decode_step(params, cfg, state, tokens,
                                   constrain=constrain)
        if active is not None:
            ns["t"] = jnp.where(active, ns["t"], state["t"])
        return logits, ns

    params = cast_params(params, cfg)
    soi = cfg.soi
    st = soi.stride
    fp = soi.mode == "fp"
    pre_s, mid_s, post_s = soi_partition(cfg)
    pre_p, mid_p, post_p = _split_segment_params(params["segments"], cfg)
    soi_p = params["soi"]

    b = tokens.shape[0]
    t = jnp.broadcast_to(jnp.asarray(state["t"], jnp.int32), (b,))
    phase = t % st
    run_mid = phase == 0              # (B,) — this slot's window is complete
    if active is not None:
        run_mid = run_mid & active
    if draft:
        # off-phase-forced: the middle's cond predicate becomes any(False),
        # so its FLOPs vanish and every downstream read sees the stale
        # queue/caches — exactly an off-phase step for every slot
        run_mid = jnp.zeros_like(run_mid)
    new_state = dict(state)

    pages = state.get("pages", {})
    outer_pg = pages.get("outer") if pages else None
    mid_pg = pages.get("mid") if pages else None

    x = D._embed_one(params, cfg, tokens, constrain, t=t)
    with jax.named_scope("soi_pre"):
        x, new_state["pre"] = _run_segments(pre_p, pre_s, state["pre"], cfg,
                                            x, t, constrain, pages=outer_pg)
    skip = x
    window = jnp.concatenate([state["conv_buf"], x[:, None]], axis=1)
    xc = jnp.einsum("bkd,kde->be", window, soi_p["compress"].astype(x.dtype))
    s_pos = t // st                   # per-slot compressed position

    @jax.named_scope("soi_middle")
    def middle(_):
        # Paged middle: mid-window slots must not commit, so their page rows
        # are masked to the null page — the write lands on discarded memory
        # and their (garbage-window) read sees an empty cache.
        mp = None if mid_pg is None else jnp.where(run_mid[:, None],
                                                   mid_pg, 0)
        xm, new_mid = _run_segments(mid_p, mid_s, state["mid"], cfg, xc,
                                    s_pos, constrain, pages=mp)
        # Slots mid-window ran the middle on a garbage window — keep their
        # old caches; only complete-window slots commit frame s_pos.
        new_mid = _select_mid_caches(run_mid, new_mid, state["mid"], mid_s,
                                     paged=mid_pg is not None)
        return xm, new_mid

    def skip_middle(_):
        return jnp.zeros_like(xc), state["mid"]

    xm, new_state["mid"] = jax.lax.cond(jnp.any(run_mid), middle, skip_middle,
                                        None)

    queue = state["queue"]
    rows = jnp.arange(b)
    if fp:
        # FP serves strictly-past data: even on a complete window the output
        # comes from the queue head (the previous middle frame).
        xu = queue[rows, jnp.minimum(phase, st - 1)]
    else:
        stale = queue[rows, jnp.clip(phase - 1, 0, st - 1)]
        xu = jnp.where(run_mid[:, None], xm, stale)
    new_state["queue"] = jnp.where(run_mid[:, None, None],
                                   jnp.repeat(xm[:, None], st, axis=1), queue)
    new_state["conv_buf"] = window[:, 1:]

    fused = jnp.einsum("bc,cd->bd", jnp.concatenate([xu, skip], axis=-1),
                       soi_p["fuse"].astype(x.dtype))
    with jax.named_scope("soi_post"):
        x, new_state["post"] = _run_segments(post_p, post_s, state["post"],
                                             cfg, fused, t, constrain,
                                             pages=outer_pg)
    new_state["t"] = t + 1 if active is None else jnp.where(active, t + 1, t)
    return D._logits_one(params, cfg, x), new_state
