"""SOIEngine: slot-based continuous batching over the unified generate step.

One instance owns the static serving geometry (config, slot count, max
sequence length); params flow through every call so the same engine serves
checkpointed or sharded parameter trees. ``generate`` and ``insert`` are
jitted once each — slot index and per-slot clocks are traced data, so no
call ever re-specializes on a request's phase or position.

The jitted programs take the weights in the config's compute dtype, with
an SOI config's stacked layers already split into its pre / middle / post
groups. A tree of f32 master weights is cast and split once, on its first
call, and the copy serves every later call that passes the same leaves
(``param_casts`` counts the trees cast); without that every step would
cast and slice the whole tree again.

Two cache layouts, selected by the ``paged`` flag:

* dense rings (default): every slot owns ``max_len`` cache rows up front —
  simple, but serving HBM scales with ``max_concurrent_decodes × max_len``
  regardless of occupancy;
* paged pools: slots hold page *lists* into shared pools
  (``repro.engine.pages``), allocated on insert, grown one page at a time as
  a slot's clock crosses a page boundary, and released on ``free_slot``.
  Slot count can then far exceed the resident batch: the pool is sized for
  live tokens, not capacity. The SOI middle pages at 1/stride the outer
  rate, so the paper's compression directly becomes fewer resident pages.

``prefix_cache=True`` (requires ``paged`` + ``prefill_chunk``) layers a
copy-on-write prefix page cache on top: a host-side chain-hash index over
token-id page blocks maps a prompt's leading full pages to pages already
resident in the pools. On a hit, chunked prefill *skips the compute* for the
cached chunks — it gathers the cached pages into the batch-1 prefill buffer
(bit-identical K/V), restores the SOI conv window / extrapolation queue from
the entry's host snapshots, and resumes at the cached boundary — and
``insert`` maps the shared pages by bumping refcounts instead of copying.
Shared pages are read-only: a decode (or windowed-ring) write into one
triggers copy-on-write into a fresh page, so sharers never observe each
other. Entries pin their pages (they survive the last sharer's free) and are
evicted LRU under pool pressure.

Paged engines make host-side allocation decisions between jitted steps, so
one engine instance drives ONE live decode state and must see every
lifecycle transition (``insert`` / ``generate`` / ``free_slot``) of it; the
page maps enter the compiled step as data, never as trace-time constants.

The host work is instrumented with ``repro.obs.span``: ``engine.generate``
(and its parts), ``engine.prefill``, ``engine.insert``, ``engine.free_slot``
and one ``engine.dispatch`` around every jitted call, and
``engine.cast_params`` around the weight cast. ``compiles`` counts the
traces of each jitted program. Both are listed in
``docs/OBSERVABILITY.md``.
"""

from __future__ import annotations

import math
import weakref

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelCfg
from repro.engine.api import Engine, Prefix, ResultTokens
from repro.engine.contracts import JitEntry, checked_jit, host_get
from repro.engine.pages import PageTable, PrefixEntry, PrefixIndex, chain_keys
from repro.engine.speculative import speculative_window
from repro.engine.step import generate_step, step_metrics
from repro.kernels import ops as kops
from repro.models import attention as attn
from repro.models import decode as D
from repro.models.attention import PagedKV
from repro.models.transformer import (_dtype, _noc, cast_params,
                                      soi_partition, split_soi_params)
from repro.obs.spans import span

# the engine's jitted programs, as ``SOIEngine.compiles`` names them
PROGRAMS = ("gen", "specgen", "ins", "prefill", "prefill_chunk", "release",
            "scrub", "hydrate", "cow_batch", "fresh_prefix")


def _insert_seg_rows(dst, src, slot, *, axis: int):
    """Copy batch row 0 of ``src`` into batch row ``slot`` of ``dst`` for one
    segment's cache pytree (batch axis 1 for scanned segments)."""
    def put(d, s_):
        row = jnp.take(s_, 0, axis=axis).astype(d.dtype)
        return jax.lax.dynamic_update_index_in_dim(d, row, slot, axis)
    return jax.tree.map(put, dst, src)


def _paged_put(pool, dense, rows, axis: int):
    """Map a batch-1 dense prefill cache onto freshly allocated pages.

    ``dense`` is (..., 1, s_log, ...) with the batch at ``axis``; the s_log
    rows split into (n_pp, page_size) pages scattered to pool rows ``rows``
    (0-entries land on the always-masked null page, so prefix rows beyond
    the allocated prompt pages — and rows covered by *shared* pages, which
    must never be re-written — are discarded, not silently kept)."""
    n_pp = rows.shape[0]
    p_sz = pool.shape[axis + 1]
    row = jnp.take(dense, 0, axis=axis)
    lead = row.shape[:axis]
    vals = row.reshape(lead + (n_pp, p_sz) + row.shape[axis + 1:])
    vals = vals.astype(pool.dtype)
    if axis == 0:
        return pool.at[rows].set(vals)
    return pool.at[:, rows].set(vals)


def _insert_block(dstc: dict, srcc: dict, slot, axis: int, pages_row):
    """One block's cache dict: attention goes through pages (when paged),
    per-slot leaves (recurrence states) insert as batch rows."""
    out = {}
    for k, d in dstc.items():
        if pages_row is not None and k == "attn":
            out[k] = {kk: _paged_put(dd, srcc[k][kk], pages_row, axis)
                      for kk, dd in d.items()}
        else:
            out[k] = _insert_seg_rows(d, srcc[k], slot, axis=axis)
    return out


def _insert_seg_cache(dst, src, slot, axis: int, pages_row):
    if pages_row is None:
        return _insert_seg_rows(dst, src, slot, axis=axis)
    if isinstance(dst, dict):                      # scanned: {sub_i: block}
        return {k: _insert_block(v, src[k], slot, axis, pages_row)
                for k, v in dst.items()}
    return [_insert_block(d, s_, slot, axis, pages_row)
            for d, s_ in zip(dst, src)]


def _seg_axes(segs) -> list:
    return [1 if seg.scan else 0 for seg in segs]


def _insert_cross_kv(cfg: ModelCfg, dst: dict, src: dict, slot):
    """Per-slot encoder K/V: copy the prefix's row in, with loud errors for
    mismatched encoder state (a silent drop here decodes garbage later)."""
    if ("cross_kv" in dst) != ("cross_kv" in src):
        have, lack = (("decode state", "prefix") if "cross_kv" in dst
                      else ("prefix", "decode state"))
        raise ValueError(
            f"encoder state mismatch on insert: the {have} carries "
            f"cross-attention K/V but the {lack} does not — prefill "
            f"encoder-decoder configs with encoder_frames and build the "
            f"decode state from the same config")
    if "cross_kv" not in dst:
        return None

    def check(d, s_, ax):
        d_row = d.shape[:ax] + d.shape[ax + 1:]
        s_row = s_.shape[:ax] + s_.shape[ax + 1:]
        if d_row != s_row:
            raise ValueError(
                f"encoder state mismatch on insert: decode-state cross-KV "
                f"leaf {d.shape} vs prefix {s_.shape} — the prefill ran "
                f"with a different encoder frame count than the engine's "
                f"decode state was sized for")

    out = []
    for d, s_, ax in zip(dst["cross_kv"], src["cross_kv"],
                         _seg_axes(cfg.segments)):
        if d is None and s_ is None:
            out.append(None)
            continue
        if (d is None) != (s_ is None):
            raise ValueError("encoder state mismatch on insert: cross-KV "
                             "present for different segments")
        jax.tree.map(lambda dd, ss: check(dd, ss, ax), d, s_)
        out.append(_insert_seg_rows(d, s_, slot, axis=ax))
    return out


def insert_state(cfg: ModelCfg, dst: dict, src: dict, slot, *,
                 page_rows=None) -> dict:
    """Write the batch-1 model state ``src`` into slot ``slot`` of ``dst``.

    Structure-aware: scanned segments stack caches as (layers, B, ...), so
    the batch axis differs per segment; top-level leaves (clock, conv
    buffer, queue) insert on axis 0; per-slot encoder cross-KV copies its
    row. With ``page_rows`` ({"outer": (n_pp,), "mid": (n_ppm,)} write
    targets) the attention caches copy page *contents* into the shared
    pools instead of max_len batch rows; entries masked to 0 (shared or
    unallocated pages) write onto the discarded null page.
    """
    out = dict(dst)
    out["t"] = dst["t"].at[slot].set(src["t"][0])
    po = None if page_rows is None else page_rows.get("outer")
    pmid = None if page_rows is None else page_rows.get("mid")
    if cfg.soi is None:
        groups = [("segments", cfg.segments, po)]
    else:
        pre, mid, post = soi_partition(cfg)
        groups = [("pre", pre, po), ("mid", mid, pmid), ("post", post, po)]
        for key in ("conv_buf", "queue"):
            out[key] = jax.lax.dynamic_update_index_in_dim(
                dst[key], src[key][0].astype(dst[key].dtype), slot, 0)
    for key, segs, prow in groups:
        out[key] = [_insert_seg_cache(d, s_, slot, ax, prow)
                    for d, s_, ax in zip(dst[key], src[key],
                                         _seg_axes(segs))]
    ckv = _insert_cross_kv(cfg, dst, src, slot)
    if ckv is not None:
        out["cross_kv"] = ckv
    return out


def _scrub_group(seg_caches, segs, rows):
    """Mark released cache rows empty (pos = -1) so a later owner's reads
    can't resurrect a freed request's tokens. ``rows`` indexes the leading
    cache axis: released page ids into the shared pools (paged engines) or
    the freed slot's batch row in the dense rings (dense engines)."""
    out = []
    for seg_c, seg in zip(seg_caches, segs):
        axis = 1 if seg.scan else 0

        def scrub(blk):
            if "attn" not in blk:
                return blk
            a = dict(blk["attn"])
            a["pos"] = (a["pos"].at[:, rows].set(-1) if axis
                        else a["pos"].at[rows].set(-1))
            return dict(blk, attn=a)

        if seg.scan:
            out.append({k: scrub(v) for k, v in seg_c.items()})
        else:
            out.append([scrub(b) for b in seg_c])
    return out


def _hydrate_groups(dense_segs, pool_segs, segs, rows, limit):
    """Fill a batch-1 dense prefill cache's logical rows [0, limit) from the
    paged pools (the prefix-cache prefill skip)."""
    out = []
    for d_seg, p_seg, seg in zip(dense_segs, pool_segs, segs):
        axis = 1 if seg.scan else 0

        def blk(d_blk, p_blk):
            if "attn" not in d_blk:
                return d_blk
            return dict(d_blk, attn=attn.hydrate_cache_prefix(
                d_blk["attn"], p_blk["attn"], rows, limit, axis=axis))

        if seg.scan:
            out.append({k: blk(v, p_seg[k]) for k, v in d_seg.items()})
        else:
            out.append([blk(dv, pv) for dv, pv in zip(d_seg, p_seg)])
    return out


def _copy_group_page(seg_caches, segs, src, dst):
    """Copy pool row ``src`` -> ``dst`` in every attention pool of a cache
    group (the device half of copy-on-write)."""
    out = []
    for seg_c, seg in zip(seg_caches, segs):
        axis = 1 if seg.scan else 0

        def cp(blk):
            if "attn" not in blk:
                return blk
            a = {name: (pl.at[:, dst].set(pl[:, src]) if axis
                        else kops.copy_page(pl, src, dst))
                 for name, pl in blk["attn"].items()}
            return dict(blk, attn=a)

        if seg.scan:
            out.append({k: cp(v) for k, v in seg_c.items()})
        else:
            out.append([cp(b) for b in seg_c])
    return out


def _copy_stacked_pages(pool, srcs, dsts):
    """:func:`kops.copy_pages` on a scanned segment's ``(L, n_pages, ...)``
    pool: the layers merge into the page axis (a free reshape) and every
    pair repeats per layer at that layer's page offset, so all layers copy
    in the one kernel call. (0, 0) padding becomes each layer's null-page
    self-copy, still a no-op."""
    n_l, n_p = pool.shape[:2]
    off = (jnp.arange(n_l, dtype=jnp.int32) * n_p)[:, None]
    flat = kops.copy_pages(pool.reshape((n_l * n_p,) + pool.shape[2:]),
                           (srcs[None] + off).reshape(-1),
                           (dsts[None] + off).reshape(-1))
    return flat.reshape(pool.shape)


def _copy_group_pages(seg_caches, segs, srcs, dsts):
    """Batched :func:`_copy_group_page`: apply a whole step's COW pair set
    (``srcs``/``dsts`` fixed-length int32 vectors, (0, 0) null-page pairs as
    padding) to every attention pool of a cache group in one dispatch."""
    out = []
    for seg_c, seg in zip(seg_caches, segs):
        copy = _copy_stacked_pages if seg.scan else kops.copy_pages

        def cp(blk):
            if "attn" not in blk:
                return blk
            a = {name: copy(pl, srcs, dsts)
                 for name, pl in blk["attn"].items()}
            return dict(blk, attn=a)

        if seg.scan:
            out.append({k: cp(v) for k, v in seg_c.items()})
        else:
            out.append([cp(b) for b in seg_c])
    return out


class SOIEngine(Engine):
    """Engine over the unified step; handles SOI and plain configs alike.

    The decode state is ``{"model": <per-slot caches/clocks>, "tokens": (B,),
    "active": (B,)}`` — ``tokens`` holds each slot's next input token (the
    feedback path of greedy decoding; harnesses may overwrite it to force
    teacher-input evaluation), ``active`` gates result validity.

    ``paged=True`` swaps the dense ring caches for shared page pools.
    ``n_pages`` / ``n_pages_mid`` size the pools (pool rows incl. the null
    page); the default gives every slot full-length backing — byte-neutral
    but bit-exact vs dense, so correctness never depends on pool sizing.
    Servers shrink the pool to the resident token population; the page
    tables then enforce it, raising when the pool is truly exhausted.

    Prefill compiles O(1) programs regardless of traffic:

    * ``prefill_buckets`` (default "pow2") pads prompts to a bucket length
      and masks the pad by TRUE length — one compiled prefill per bucket
      instead of one per distinct prompt length, bit-exact vs unpadded;
    * ``prefill_chunk=C`` switches to chunked prefill: ONE compiled program
      appends C tokens to the caches at a traced position offset, looped on
      the host — the substrate for prefix-cache page sharing and
      prefill/decode interleaving.

    ``prefix_cache=True`` (requires ``paged`` and ``prefill_chunk``) shares
    the pages of repeated prompt prefixes across requests copy-on-write and
    skips the prefill compute over cached prefixes; see the module
    docstring and ``prefix_cache_stats``.

    Configs that can't mask pad — prefix-LM / bidirectional attention (pad
    inside the prefix window is visible to every query), recurrence scan
    states, MoE expert capacity; see
    ``repro.models.decode.supports_masked_prefill`` — silently fall back to
    exact-length prefill; an explicit ``prefill_chunk`` raises.
    """

    def __init__(self, cfg: ModelCfg, *, max_concurrent_decodes: int = 8,
                 max_len: int = 256, constrain=_noc, paged: bool = False,
                 page_size: int = 16, n_pages: int | None = None,
                 n_pages_mid: int | None = None,
                 prefill_buckets="pow2", prefill_chunk: int | None = None,
                 prefix_cache: bool = False, speculate: int | None = None,
                 telemetry: bool = False):
        self.cfg = cfg
        self.max_len = max_len
        self._slots = max_concurrent_decodes
        self._constrain = constrain
        self._paged = bool(paged)
        # telemetry=True: every generate step (or speculative window) also
        # computes the small per-step metrics vector (step_metrics layout)
        # INSIDE the compiled program and attaches it to
        # ResultTokens.metrics — it drains with the tokens, one step
        # deferred, so telemetry-on serving adds no host sync (consumer:
        # repro.obs.registry.EngineTelemetry; doc: docs/OBSERVABILITY.md)
        self._telemetry = bool(telemetry)
        self._metrics_stride = cfg.soi.stride if cfg.soi is not None else 1
        self._spec = None
        self._pt_outer = self._pt_mid = None
        self._occupied = np.zeros(self._slots, bool)
        self._clock = np.zeros(self._slots, np.int64)
        self._live = None           # the ONE live decode state (paged)
        if speculate is not None and int(speculate) < 1:
            raise ValueError(f"speculate must be >= 1, got {speculate}")
        self._speculate = None if speculate is None else int(speculate)
        # which slots run speculative windows (insert(..., speculate=...));
        # non-speculating slots commit exactly one token per window, so
        # speculative and plain requests coexist in one batch
        self._spec_slots = np.zeros(self._slots, bool)
        # fresh pages allocated for a window's candidate positions, per
        # slot: (table, page-map index, first backed position) — consumed
        # after the window (rejected positions' pages are dropped), cleared
        # by free_slot so a freed request never leaks speculative pages
        self._spec_pending = [[] for _ in range(self._slots)]
        self.spec_stats = {"windows": 0, "slot_windows": 0, "committed": 0,
                           "draft_candidates": 0, "draft_accepted": 0}
        # traces of each jitted program (PROGRAMS), counted in its body,
        # which runs once per trace: the serving-visible recompile counter
        self.compiles = dict.fromkeys(PROGRAMS, 0)
        # generate calls (and speculative windows) so far: the step number
        # of the engine.generate span
        self._steps = 0
        # the one cached weight cast (_compute_params): weak references to
        # the source tree's leaves, whose identity is the key, and the tree
        # cast and split from them; param_casts counts the trees cast
        self.param_casts = 0
        self._cast_src = None
        self._cast_out = None
        if cfg.learned_pos_len and max_len > cfg.learned_pos_len:
            # jnp.take clamps out-of-bounds rows, so decodes past the table
            # would silently reuse the LAST position embedding forever —
            # fail at construction, not garbage at token learned_pos_len
            raise ValueError(
                f"max_len {max_len} exceeds config '{cfg.name}'s learned "
                f"position table ({cfg.learned_pos_len} rows): positions "
                f">= {cfg.learned_pos_len} would silently clamp to the last "
                f"embedding — shrink max_len or grow learned_pos_len")
        self._masked_ok = D.supports_masked_prefill(cfg)
        self._buckets = self._resolve_buckets(prefill_buckets)
        self._chunk = int(prefill_chunk) if prefill_chunk else None
        if self._chunk is not None:
            if not self._masked_ok:
                raise ValueError(
                    f"chunked prefill is unsupported for config "
                    f"'{cfg.name}' (prefix-LM/bidirectional attention, "
                    f"recurrence, or MoE; see "
                    f"repro.models.decode.supports_masked_prefill)")
            if cfg.encoder is not None or cfg.prefix_lm:
                raise ValueError("chunked prefill supports decoder-only "
                                 "causal token stacks")
            if cfg.soi is not None and self._chunk % cfg.soi.stride:
                raise ValueError(
                    f"prefill_chunk {self._chunk} must be a multiple of "
                    f"the SOI stride {cfg.soi.stride}")
            if self._chunk > max_len:
                raise ValueError(f"prefill_chunk {self._chunk} exceeds "
                                 f"max_len {max_len}")
        if self._paged:
            outer_len, mid_len = D.paged_group_lens(cfg, max_len)
            if not outer_len and not mid_len:
                raise ValueError("paged=True needs attention caches to page "
                                 f"(config '{cfg.name}' has none)")
            for name, ln in (("outer", outer_len), ("middle", mid_len)):
                if ln and ln % page_size:
                    raise ValueError(
                        f"page_size {page_size} must divide the {name} "
                        f"cache length {ln}")
            if n_pages is None:
                n_pages = max_concurrent_decodes * (outer_len // page_size) + 1
            if n_pages_mid is None:
                n_pages_mid = (max_concurrent_decodes
                               * (mid_len // page_size) + 1)
            self._outer_len, self._mid_len = outer_len, mid_len
            self._spec = PagedKV(page_size, max(n_pages, 2),
                                 max(n_pages_mid, 2))

        self._prefix_cache = bool(prefix_cache)
        self._prefix_index = PrefixIndex()
        self._pc_stats = {"hits": 0, "misses": 0, "tokens_skipped": 0,
                          "pages_shared": 0, "cow_copies": 0, "evictions": 0}
        if self._prefix_cache:
            if not self._paged:
                raise ValueError("prefix_cache=True requires paged=True "
                                 "(sharing maps pool pages across slots)")
            if self._chunk is None:
                raise ValueError(
                    "prefix_cache=True requires prefill_chunk: the prefill "
                    "skip fast-forwards the chunk loop past cached chunks")
            if not self._outer_len:
                raise ValueError("prefix_cache needs an outer attention "
                                 "cache group to share")
            align = math.lcm(self._chunk, self._spec.page_size)
            if cfg.soi is not None:
                # middle pages hold page_size *frames* = page_size*stride
                # tokens: boundaries must close a middle page exactly
                align = math.lcm(align,
                                 cfg.soi.stride * self._spec.page_size)
            if align > max_len:
                raise ValueError(
                    f"prefix-cache boundary alignment {align} "
                    f"(lcm of chunk, page size, stride*page size) exceeds "
                    f"max_len {max_len}: no prompt could ever hit")
            self._pc_align = align

        def _metrics(ds):
            # pre-step clocks: the phase histogram describes the step being
            # taken, not the state it leaves behind; None (a no-op in every
            # pytree) when telemetry is off, so the telemetry-off program
            # is byte-identical to the pre-telemetry engine
            if not self._telemetry:
                return None
            return step_metrics(ds["model"]["t"], ds["active"],
                                self._metrics_stride)

        def _gen(params, ds):
            self.compiles["gen"] += 1
            met = _metrics(ds)
            logits, ms = generate_step(params, cfg, ds["model"], ds["tokens"],
                                       active=ds["active"],
                                       constrain=constrain)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            data = jnp.stack([nxt, ds["active"].astype(jnp.int32),
                              ms["t"]], axis=1)
            return ({"model": ms, "tokens": nxt, "active": ds["active"]},
                    data, logits, met)

        def _specgen(params, ds, spec_mask):
            self.compiles["specgen"] += 1
            met = _metrics(ds)          # one sample per window (entry phase)
            ms, committed, n_acc, nxt, logits = speculative_window(
                params, cfg, ds["model"], ds["tokens"],
                k=self._speculate, active=ds["active"], spec=spec_mask,
                constrain=constrain)
            data = jnp.concatenate(
                [committed,
                 jnp.stack([ds["active"].astype(jnp.int32), ms["t"], n_acc],
                           axis=1)], axis=1)
            return ({"model": ms, "tokens": nxt, "active": ds["active"]},
                    data, logits, met)

        def _ins(ds, pstate, first_token, slot, page_rows):
            self.compiles["ins"] += 1
            model = insert_state(cfg, ds["model"], pstate, slot,
                                 page_rows=page_rows)
            return {"model": model,
                    "tokens": ds["tokens"].at[slot].set(first_token[0]),
                    "active": ds["active"].at[slot].set(True)}

        def _prefill(params, tokens, true_length, encoder_frames):
            self.compiles["prefill"] += 1   # one per bucket
            return D.prefill(params, cfg, tokens,
                             encoder_frames=encoder_frames,
                             max_len=max_len, true_length=true_length,
                             constrain=constrain)

        def _prefill_chunk(params, ms, tokens, offset, true_length):
            self.compiles["prefill_chunk"] += 1   # ONCE for all chunks
            return D.prefill_chunk(params, cfg, ms, tokens, offset,
                                   true_length, constrain=constrain)

        def _fresh_prefix_state(params):
            self.compiles["fresh_prefix"] += 1
            return D.init_decode_state(params, cfg, 1, max_len=max_len)

        def _cast_params(params):
            return split_soi_params(cast_params(params, cfg), cfg)

        def _scrub_model(m: dict, rows: dict) -> dict:
            m = dict(m)
            if cfg.soi is None:
                if "outer" in rows:
                    m["segments"] = _scrub_group(m["segments"], cfg.segments,
                                                 rows["outer"])
            else:
                pre, mid, post = soi_partition(cfg)
                if "outer" in rows:
                    m["pre"] = _scrub_group(m["pre"], pre, rows["outer"])
                    m["post"] = _scrub_group(m["post"], post, rows["outer"])
                if "mid" in rows:
                    m["mid"] = _scrub_group(m["mid"], mid, rows["mid"])
            return m

        def _release(ds, slot, rows):
            self.compiles["release"] += 1
            # ``rows`` indexes what gets scrubbed: released page rows in the
            # pools (paged) or the slot's own batch row (dense) — same
            # ``pos = -1`` hygiene either way, so a freed request's tokens
            # are unreadable even before the slot is re-inserted.
            return {"model": _scrub_model(ds["model"], rows),
                    "tokens": ds["tokens"],
                    "active": ds["active"].at[slot].set(False)}

        def _scrub_pages(ds, rows):
            self.compiles["scrub"] += 1
            # eviction path: scrub freed pages without touching any slot's
            # active bit (no slot is being released)
            return dict(ds, model=_scrub_model(ds["model"], rows))

        def _hydrate(ms, model, rows, n_tok, n_frames):
            self.compiles["hydrate"] += 1
            out = dict(ms)
            if cfg.soi is None:
                out["segments"] = _hydrate_groups(
                    ms["segments"], model["segments"], cfg.segments,
                    rows["outer"], n_tok)
            else:
                pre, mid, post = soi_partition(cfg)
                out["pre"] = _hydrate_groups(ms["pre"], model["pre"], pre,
                                             rows["outer"], n_tok)
                out["post"] = _hydrate_groups(ms["post"], model["post"], post,
                                              rows["outer"], n_tok)
                if "mid" in rows:
                    out["mid"] = _hydrate_groups(ms["mid"], model["mid"], mid,
                                                 rows["mid"], n_frames)
            return out

        has_mid = self._paged and bool(getattr(self, "_mid_len", 0))

        def _cow_batch(ds, srcs, dsts, m_srcs, m_dsts):
            # ONE dispatch covers the whole step's COW set across every
            # cache group: outer pairs hit the full-rate pools, mid pairs
            # the compressed-middle pools. Vectors are fixed-length and
            # (0, 0)-padded (null-page self-copies are no-ops), so one
            # compiled program serves every COW count.
            self.compiles["cow_batch"] += 1
            m = dict(ds["model"])
            if cfg.soi is None:
                m["segments"] = _copy_group_pages(m["segments"],
                                                  cfg.segments, srcs, dsts)
            else:
                pre, mid, post = soi_partition(cfg)
                m["pre"] = _copy_group_pages(m["pre"], pre, srcs, dsts)
                m["post"] = _copy_group_pages(m["post"], post, srcs, dsts)
                if has_mid:
                    m["mid"] = _copy_group_pages(m["mid"], mid, m_srcs,
                                                 m_dsts)
            return dict(ds, model=m)

        # donate the decode state: the per-slot KV caches dominate serving
        # HBM, and without donation every step double-buffers them.
        # checked_jit raises DroppedDonationError (instead of jax's
        # UserWarning) if XLA cannot honor a donation — a silent drop here
        # would double the serving footprint and add a copy per step.
        self._gen = checked_jit(_gen, donate_argnums=(1,))
        self._specgen = checked_jit(_specgen, donate_argnums=(1,))
        self._ins = checked_jit(_ins, donate_argnums=(0,))
        self._prefill_fn = checked_jit(_prefill)
        self._prefill_chunk_fn = checked_jit(_prefill_chunk,
                                             donate_argnums=(1,))
        self._fresh_prefix_fn = checked_jit(_fresh_prefix_state)
        self._cast_fn = jax.jit(_cast_params)
        self._release_fn = checked_jit(_release, donate_argnums=(0,))
        self._scrub_fn = checked_jit(_scrub_pages, donate_argnums=(0,))
        self._hydrate_fn = checked_jit(_hydrate, donate_argnums=(0,))
        self._cow_batch_fn = checked_jit(_cow_batch, donate_argnums=(0,))
        # COW pairs discovered while backing this step's writes, flushed as
        # ONE _cow_batch_fn dispatch right before the compiled step (or
        # before any eviction scrub, which could otherwise free-and-scrub a
        # pending source page first)
        self._cow_pending = {"outer": [], "mid": []}
        # PageTable.version of the last device upload per group: unchanged
        # maps ride along inside the decode state across steps, so
        # steady-state tokens skip the host->device map transfer
        self._pm_version = {"outer": -1, "mid": -1}

    def _resolve_buckets(self, policy):
        """Prefill bucket lengths: None (exact-length, one compile per
        distinct prompt length), "pow2" (powers of two up to max_len — the
        default), or an explicit iterable of lengths. Configs that can't
        honor true-length masking (recurrence/MoE) fall back to exact."""
        if policy is None or not self._masked_ok:
            return None
        if policy == "pow2":
            out, b = [], 16
            while b < self.max_len:
                out.append(b)
                b *= 2
            out.append(self.max_len)
            return tuple(out)
        buckets = sorted({int(x) for x in policy})
        if not buckets or buckets[0] < 1:
            raise ValueError(f"invalid prefill buckets {policy}")
        if buckets[-1] > self.max_len:
            raise ValueError(f"prefill bucket {buckets[-1]} exceeds "
                             f"max_len {self.max_len}")
        if buckets[-1] < self.max_len:
            buckets.append(self.max_len)   # every admissible prompt fits
        return tuple(buckets)

    @property
    def prefill_compiles(self) -> int:
        """Traces of the prefill programs: one per bucket, or exactly one
        chunk program."""
        return self.compiles["prefill"] + self.compiles["prefill_chunk"]

    @property
    def hydrate_compiles(self) -> int:
        """Traces of the prefix-cache hydration program (once, on the
        first hit)."""
        return self.compiles["hydrate"]

    @property
    def spec_compiles(self) -> int:
        """Traces of the speculative window (1 whatever K and the
        acceptance pattern)."""
        return self.compiles["specgen"]

    def _dispatch(self, program: str, fn, *args):
        """Call the jitted ``fn`` (``program`` of :data:`PROGRAMS`) under
        an ``engine.dispatch`` span, marked ``traced=1`` when the call
        traced the program anew (a compile, or a compile-cache load)."""
        before = self.compiles[program]
        with span("engine.dispatch", program=program) as sp:
            out = fn(*args)
            if self.compiles[program] != before:
                sp.set(traced=1)
        return out

    def _step_span(self) -> span:
        """The ``engine.generate`` span of the step about to run: its
        number, whether the compressed middle fires (``step_metrics``'s
        rule on the host's clocks: some occupied slot at phase 0; read
        before the clocks advance, from no device value) and the
        occupied slots."""
        occ = self._occupied
        mid = bool((self._clock[occ] % self._metrics_stride == 0).any())
        self._steps += 1
        return span("engine.generate", step=self._steps - 1, mid=int(mid),
                    active=int(occ.sum()))

    def _compute_params(self, params):
        """``params`` with its f32 leaves in the config's compute dtype
        and an SOI config's groups split apart (``split_soi_params``): the
        tree every jitted program takes, so none of them casts or slices.

        One entry, keyed on the leaves' identity: a call with the same leaf
        objects gets the tree cast before; another tree replaces the entry,
        and the old copy goes before the new one is made (two copies of a
        full-size model need not fit). The entry holds the source leaves
        weakly, so a tree the caller drops is freed. A tree with no f32
        leaf, or a float32 config, passes through as it is; so does a tree
        of tracers (an outer trace), where the programs cast inside. A
        tree of ``ShapeDtypeStruct`` maps to the structs of the cast tree,
        with nothing run."""
        leaves = jax.tree.leaves(params)
        src = self._cast_src
        if (src is not None and len(src) == len(leaves)
                and all(r() is p for r, p in zip(src, leaves))):
            return self._cast_out
        dt = _dtype(self.cfg)
        f32 = [p for p in leaves
               if getattr(p, "dtype", None) == jnp.float32]
        if (dt == jnp.float32 or not f32
                or any(isinstance(p, jax.core.Tracer) for p in leaves)):
            return params
        if any(isinstance(p, jax.ShapeDtypeStruct) for p in leaves):
            return jax.eval_shape(self._cast_fn, params)
        self._cast_src = self._cast_out = None
        nbytes = sum(p.size for p in f32) * jnp.dtype(dt).itemsize
        with span("engine.cast_params", bytes=nbytes):
            out = self._cast_fn(params)
        self._cast_src = [weakref.ref(p) for p in leaves]
        self._cast_out = out
        self.param_casts += 1
        return out

    @property
    def prefill_buckets(self):
        """Active bucket lengths (None = exact-length prefill)."""
        return self._buckets

    @property
    def prefill_chunk(self):
        """Active chunk size (None = whole-prompt prefill)."""
        return self._chunk

    @property
    def max_concurrent_decodes(self) -> int:
        return self._slots

    @property
    def prefix_cache_enabled(self) -> bool:
        return self._prefix_cache

    @property
    def live_decode_state(self):
        """The ONE live decode state this engine drives (paged engines
        stash it across calls; prefill hydration reads pool contents from
        it). Recovery handle: on a prefix-cache engine a failed ``insert``
        may already have LRU-evicted index entries — which scrubs pages
        through a donating jitted program — so the caller's own reference
        can be invalidated even though the insert raised; this property
        always points at the current buffers."""
        return self._live

    @property
    def prefix_cache_stats(self) -> dict:
        """Serving-visible prefix-cache counters: lookup hits/misses (+
        derived hit_rate), prompt tokens whose prefill compute was skipped,
        pages mapped by refcount instead of copy (never counts the null
        page), COW copies, and LRU evictions. Counters reset with
        ``init_decode_state`` (a fresh state starts a fresh serving
        session, like the index itself)."""
        s = dict(self._pc_stats)
        total = s["hits"] + s["misses"]
        s["hit_rate"] = s["hits"] / total if total else 0.0
        s["entries"] = len(self._prefix_index)
        return s

    def _page_maps(self) -> dict:
        maps = {}
        if self._pt_outer is not None:
            maps["outer"] = jnp.asarray(self._pt_outer.map)
            self._pm_version["outer"] = self._pt_outer.version
        if self._pt_mid is not None:
            maps["mid"] = jnp.asarray(self._pt_mid.map)
            self._pm_version["mid"] = self._pt_mid.version
        return maps

    def _refresh_page_maps(self, model: dict) -> dict:
        """Re-upload only the page-map matrices whose host table mutated
        since their last upload. Unchanged maps are already inside the
        decode state (the compiled step passes "pages" through, so the
        previous step handed them straight back) — a steady-state token
        costs zero host->device transfers here, which measured as ~0.5ms
        of the paged-vs-dense per-step gap on the CPU container."""
        with span("engine.refresh_page_maps") as sp:
            pages = dict(model["pages"])
            uploaded = 0
            for name, pt in (("outer", self._pt_outer),
                             ("mid", self._pt_mid)):
                if pt is not None and self._pm_version[name] != pt.version:
                    pages[name] = jnp.asarray(pt.map)
                    self._pm_version[name] = pt.version
                    uploaded += 1
            sp.set(maps=uploaded)
        return dict(model, pages=pages) if uploaded else model

    def _flush_cow(self, decode_state):
        """Dispatch every pending COW copy as one compiled call. Pair
        vectors are padded to a fixed multiple of the slot count so the
        program compiles once; overflow (speculative windows can COW
        several pages per slot) just dispatches again."""
        po, pm_ = self._cow_pending["outer"], self._cow_pending["mid"]
        if not po and not pm_:
            return decode_state
        self._cow_pending = {"outer": [], "mid": []}
        width = self._slots
        for i in range(0, max(len(po), len(pm_), 1), width):
            o, m = po[i:i + width], pm_[i:i + width]
            o_src = np.zeros(width, np.int32)
            o_dst = np.zeros(width, np.int32)
            m_src = np.zeros(width, np.int32)
            m_dst = np.zeros(width, np.int32)
            if o:
                o_src[:len(o)], o_dst[:len(o)] = zip(*o)
            if m:
                m_src[:len(m)], m_dst[:len(m)] = zip(*m)
            decode_state = self._dispatch(
                "cow_batch", self._cow_batch_fn, decode_state,
                jnp.asarray(o_src), jnp.asarray(o_dst), jnp.asarray(m_src),
                jnp.asarray(m_dst))
        self._live = decode_state
        return decode_state

    def init_decode_state(self, params):
        # cast the weights now, before a serving call needs them; the state
        # is built from the caller's tree, as the encoder cross-K/V's dtype
        # follows it
        self._compute_params(params)
        enc0 = None
        if self.cfg.encoder is not None:
            # per-slot encoder K/V buffers, zero until an insert fills them
            enc0 = jnp.zeros((self._slots, self.cfg.encoder.n_frames,
                              self.cfg.d_model), _dtype(self.cfg))
        ms = D.init_decode_state(params, self.cfg, self._slots,
                                 max_len=self.max_len, enc_out=enc0,
                                 paged=self._spec)
        if self._paged:
            p_sz = self._spec.page_size
            self._pt_outer = (PageTable(self._slots, self._outer_len, p_sz,
                                        self._spec.n_pages)
                              if self._outer_len else None)
            self._pt_mid = (PageTable(self._slots, self._mid_len, p_sz,
                                      self._spec.n_pages_mid)
                            if self._mid_len else None)
        self._occupied = np.zeros(self._slots, bool)
        self._clock = np.zeros(self._slots, np.int64)
        self._spec_slots = np.zeros(self._slots, bool)
        self._spec_pending = [[] for _ in range(self._slots)]
        self._cow_pending = {"outer": [], "mid": []}
        # a fresh decode state invalidates every resident page: the prefix
        # index — and the serving counters that describe it — restart with it
        self._prefix_index = PrefixIndex()
        self._pc_stats = {k: 0 for k in self._pc_stats}
        if self._paged:
            # attach the page maps from the start: generate_step passes
            # "pages" through the returned state, so a state WITHOUT the key
            # would give insert/release a second pytree structure (pre- vs
            # post-first-generate) and double their compile count
            ms = dict(ms)
            ms["pages"] = self._page_maps()
        state = {"model": ms,
                 "tokens": jnp.zeros((self._slots,), jnp.int32),
                 "active": jnp.zeros((self._slots,), bool)}
        self._live = state
        return state

    # -- prefix-cache host machinery -------------------------------------

    def _lookup_prefix(self, toks: np.ndarray, tl: int, keys: dict):
        """Longest registered boundary R (aligned, < tl by at least one
        chunk) whose tokens [0, R) are cached. ``keys`` is the prompt's
        already-computed block chain-key dict. Returns (R, key, entry) or
        None."""
        a = self._pc_align
        r_max = ((tl - 1) // self._chunk) * self._chunk
        r_max = (r_max // a) * a
        if r_max < a:
            return None
        for r in range(r_max, a - 1, -a):
            key = keys.get(r)
            if key is None:
                continue
            e = self._prefix_index.get(key, toks[:r])
            if e is not None and e.length == r:
                return r, key, e
        return None

    def _evict_entry(self, decode_state):
        """Drop the LRU prefix-index entry; scrub any page this was the
        last reference to."""
        with span("engine.evict"):
            # pending COW copies must land first: eviction can free (and
            # scrub) the last reference to a pending pair's SOURCE page,
            # and a flush after that would copy scrubbed garbage into the
            # new page
            decode_state = self._flush_cow(decode_state)
            e = self._prefix_index.pop_lru()
            if e is None:
                return decode_state
            self._pc_stats["evictions"] += 1
            freed_o = [pid for pid in e.outer_pages
                       if self._pt_outer.unpin(pid)]
            freed_m = []
            if self._pt_mid is not None:
                freed_m = [pid for pid in e.mid_pages
                           if self._pt_mid.unpin(pid)]
            if not freed_o and not freed_m:
                return decode_state
            rows = {"outer": self._pad_row(self._pt_outer, freed_o)}
            if self._pt_mid is not None:
                rows["mid"] = self._pad_row(self._pt_mid, freed_m)
            decode_state = self._dispatch("scrub", self._scrub_fn,
                                          decode_state, rows)
        self._live = decode_state
        return decode_state

    def _make_room(self, pt, n: int, decode_state):
        """Evict prefix-index entries (LRU) until ``pt`` has ``n`` free
        pages or the index is empty; allocation itself stays the authority
        on exhaustion."""
        while (pt.free_pages < n and self._prefix_cache
               and len(self._prefix_index)):
            decode_state = self._evict_entry(decode_state)
        return decode_state

    def _shared_plan(self, meta, true_len: int) -> tuple:
        """Resolve a prefill-time hit into {logical idx: pid} adoption maps
        against the *current* index (pages may have been evicted since the
        prefill; the hydrated dense state keeps the insert correct either
        way — sharing is purely the zero-copy optimization)."""
        if (not self._prefix_cache or not meta or not meta.get("hit")
                or self._pt_outer is None):
            return {}, {}
        R = meta["hit"]
        e = self._prefix_index.get(meta["hit_key"], meta["tokens"][:R])
        if e is None or e.length != R:
            return {}, {}
        p_sz = self._spec.page_size
        s_log = self._pt_outer.logical_len
        # windowed rings: suffix positions that wrapped onto prefix pages
        # already diverged in the dense prefill buffer — those pages must be
        # private fresh copies, not shared (the pool copy holds the PREFIX
        # ring state other sharers still read)
        over = set()
        if true_len > R:
            for p in range(max(R, true_len - s_log), true_len):
                over.add((p % s_log) // p_sz)
        shared_outer = {i: e.outer_pages[i] for i in range(R // p_sz)
                        if i not in over and e.outer_pages[i] > 0}
        shared_mid = {}
        if self._pt_mid is not None:
            # same wrap exclusion at frame granularity: suffix frames that
            # rang onto prefix middle pages diverged in the dense buffer
            st_ = self.cfg.soi.stride
            m_log = self._pt_mid.logical_len
            f_r, f_t = R // st_, -(-true_len // st_)
            over_m = set()
            if f_t > f_r:
                for fp in range(max(f_r, f_t - m_log), f_t):
                    over_m.add((fp % m_log) // p_sz)
            shared_mid = {i: e.mid_pages[i] for i in range(f_r // p_sz)
                          if i not in over_m and e.mid_pages[i] > 0}
        return shared_outer, shared_mid

    def _register_prefix(self, s_i: int, meta: dict, tl: int):
        """Pin + index the freshly inserted slot's full prefix pages at
        every aligned boundary, so later prompts sharing those token blocks
        hit. Skipped entirely when the prefill wrapped a ring (page contents
        are then a function of the whole length, not the prefix)."""
        pt_o, pt_m = self._pt_outer, self._pt_mid
        if pt_o is None or tl > pt_o.logical_len:
            return
        st_ = self.cfg.soi.stride if self.cfg.soi is not None else 1
        if pt_m is not None and -(-tl // st_) > pt_m.logical_len:
            return
        p_sz = self._spec.page_size
        soi = self.cfg.soi is not None
        for b in sorted(meta["keys"]):
            key = meta["keys"][b]
            if b > tl or key in self._prefix_index:
                continue
            if soi and b not in meta["snapshots"]:
                continue        # no carry snapshot: can't resume here
            outer = tuple(int(pt_o.map[s_i, j]) for j in range(b // p_sz))
            midp = ()
            if pt_m is not None:
                midp = tuple(int(pt_m.map[s_i, j])
                             for j in range((b // st_) // p_sz))
            if any(p <= 0 for p in outer) or any(p <= 0 for p in midp):
                continue        # never index the null page
            conv = queue = None
            if soi:
                conv, queue = meta["snapshots"][b]
            for p in outer:
                pt_o.pin(p)
            for p in midp:
                pt_m.pin(p)
            self._prefix_index.put(key, PrefixEntry(
                b, np.asarray(meta["tokens"][:b]).copy(), outer, midp,
                conv, queue))

    def _evictable_pages(self, pt, which: str) -> int:
        """Pages only the prefix index keeps alive (refs == pin count):
        eviction would free them."""
        if not self._prefix_cache or pt is None:
            return 0
        pins: dict = {}
        for e in self._prefix_index.entries():
            for pid in (e.outer_pages if which == "outer" else e.mid_pages):
                pins[pid] = pins.get(pid, 0) + 1
        return sum(1 for pid, c in pins.items() if pt.refs[pid] == c)

    # -- phase-aligned admission ------------------------------------------

    def batch_phase(self) -> int | None:
        """SOI phase class of the current batch: the modal value of
        ``clock % stride`` over active slots (ties break to the lowest
        phase). Slots advance together, so this class rotates by one per
        generate step but membership is fixed at insert. None when the
        config has no SOI schedule (every step fires the full stack) or no
        slot is active — the next insert then *defines* the class."""
        soi = self.cfg.soi
        if soi is None or soi.stride <= 1:
            return None
        occ = np.nonzero(self._occupied)[0]
        if len(occ) == 0:
            return None
        phases, counts = np.unique(self._clock[occ] % soi.stride,
                                   return_counts=True)
        return int(phases[np.argmax(counts)])

    def phase_gap(self, true_length: int) -> int:
        """Generate steps to wait before inserting a ``true_length``-token
        request so its slot lands in the batch's phase class. Inserting
        now starts the slot clock at ``true_length``; relative phases are
        frozen from then on (slots step together), so alignment must
        happen AT insert: wait ``(true_length - batch_phase) % stride``
        steps and the batch phase comes around to match. 0 when there is
        nothing to align with (no SOI middle, or no active slots)."""
        bp = self.batch_phase()
        if bp is None:
            return 0
        return int((int(true_length) - bp) % self.cfg.soi.stride)

    def can_insert(self, true_length: int, slot: int | None = None,
                   phase_align=False) -> bool:
        """Admission check for serving loops: can a prompt of
        ``true_length`` real tokens be backed right now — counting free
        pages, pages ``slot``'s eviction would release (if given and
        occupied), and pages LRU eviction of the prefix index would free?
        Conservative (a prefix hit only reduces the real need); ``insert``
        remains the authority.

        ``phase_align`` adds the scheduling half: defer an insert whose
        slot would land off the batch's SOI phase class, so the middle's
        ``lax.cond`` keeps skipping at high occupancy instead of firing
        for a lone misphased slot. ``True`` bounds the deferral by the
        worst-case gap (stride - 1 steps); an int is a tighter SLO bound —
        a request whose gap exceeds it is admitted misaligned NOW (waiting
        could not align it within the bound, so burning latency on a
        partial wait buys nothing). Deferral never deadlocks: with no
        active slots the gap is 0 by definition."""
        if phase_align:
            cap = (self.cfg.soi.stride - 1
                   if phase_align is True and self.cfg.soi is not None
                   else int(phase_align))
            if 0 < self.phase_gap(true_length) <= cap:
                return False
        if not self._paged or self._pt_outer is None:
            return True
        needs = [(self._pt_outer, "outer", true_length)]
        if self._pt_mid is not None:
            st_ = self.cfg.soi.stride
            needs.append((self._pt_mid, "mid", -(-true_length // st_)))
        for pt, which, n in needs:
            have = (pt.freeable_after_release(slot)
                    if slot is not None and self._occupied[slot]
                    else pt.free_pages)
            have += self._evictable_pages(pt, which)
            if have < pt.pages_needed(n):
                return False
        return True

    # -- prefill ----------------------------------------------------------

    def prefill(self, params, tokens, encoder_frames=None,
                true_length: int | None = None) -> Prefix:
        tokens = jnp.asarray(tokens)
        if tokens.ndim == 1:
            tokens = tokens[None]
        if tokens.shape[0] != 1:
            # insert() copies batch row 0 only; a multi-row prompt would be
            # silently truncated to its first request
            raise ValueError(f"prefill takes one request, got batch "
                             f"{tokens.shape[0]}")
        if tokens.shape[1] == 0:
            raise ValueError("prefill requires a non-empty prompt")
        if tokens.shape[1] > self.max_len:
            # the bulk cache fill would silently keep only the tail
            raise ValueError(
                f"prompt length {tokens.shape[1]} exceeds engine max_len "
                f"{self.max_len}")
        tl = int(true_length) if true_length is not None \
            else int(tokens.shape[1])
        if not 0 < tl <= tokens.shape[1]:
            raise ValueError(f"true_length {tl} outside (0, "
                             f"{tokens.shape[1]}]")
        if self._chunk is not None and encoder_frames is not None:
            raise ValueError("chunked prefill supports decoder-only "
                             "stacks (no encoder_frames)")
        params = self._compute_params(params)
        with span("engine.prefill", tokens=tl) as sp:
            if self._chunk is not None:
                return self._prefill_chunked(params, tokens, tl, sp)
            if self._buckets is not None:
                bucket = next(b for b in self._buckets if b >= tl)
                pad = bucket - int(tokens.shape[1])
                if pad > 0:
                    tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
                elif pad < 0:
                    tokens = tokens[:, :bucket]
                true_len = jnp.asarray(tl, jnp.int32)
            else:
                if tl != tokens.shape[1]:
                    tokens = tokens[:, :tl]   # exact length: drop the pad
                true_len = None
            logits, ms = self._dispatch("prefill", self._prefill_fn, params,
                                        tokens, true_len, encoder_frames)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return Prefix(state=ms, first_token=first, logits=logits,
                      length=tl, true_length=tl)

    def _prefill_chunked(self, params, tokens, tl: int, sp: span) -> Prefix:
        """Host loop over the ONE compiled chunk program: pad the prompt to
        a chunk multiple, append chunk by chunk at growing offsets, keep the
        logits of the chunk holding position true_length-1 (chunks past it
        would be all-pad no-ops and are skipped).

        With the prefix cache enabled, a hit at boundary R fast-forwards the
        loop: the cached pages are gathered into the fresh prefill buffer
        (hydration — bit-identical K/V, no recompute), the SOI conv window /
        extrapolation queue restore from the entry's host snapshots, and the
        loop starts at chunk R/C — prefill cost drops from O(prompt) to
        O(suffix). The final chunk (holding position true_length-1) always
        runs, so the returned logits/first token never come from the cache.
        ``sp`` is the caller's ``engine.prefill`` span (args ``chunks`` and
        ``hit`` are set here).
        """
        c = self._chunk
        n = (tl - 1) // c + 1
        pad = n * c - int(tokens.shape[1])
        if pad > 0:
            tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
        elif pad < 0:
            tokens = tokens[:, :n * c]   # trailing all-pad chunks: no-ops
        ms = self._dispatch("fresh_prefix", self._fresh_prefix_fn, params)
        i0 = 0
        meta = None
        soi = self.cfg.soi is not None
        if self._prefix_cache:
            with span("engine.prefix_lookup"):
                toks_np = np.asarray(tokens[0][:tl])
                block_keys = chain_keys(toks_np, self._spec.page_size)
                meta = {"hit": 0, "hit_key": None, "tokens": toks_np,
                        "keys": {b: k for b, k in block_keys.items()
                                 if b % self._pc_align == 0},
                        "snapshots": {}}
                hit = self._lookup_prefix(toks_np, tl, block_keys)
            if hit is not None:
                R, key, e = hit
                with span("engine.hydrate"):
                    rows = {"outer": self._pad_row(self._pt_outer,
                                                   e.outer_pages)}
                    if self._pt_mid is not None:
                        rows["mid"] = self._pad_row(self._pt_mid,
                                                    e.mid_pages)
                    n_frames = R // self.cfg.soi.stride if soi else 0
                    ms = self._dispatch(
                        "hydrate", self._hydrate_fn, ms, self._live["model"],
                        rows, jnp.asarray(R, jnp.int32),
                        jnp.asarray(n_frames, jnp.int32))
                    if soi:
                        ms = dict(ms)
                        ms["conv_buf"] = jnp.asarray(e.conv_buf)
                        ms["queue"] = jnp.asarray(e.queue)
                i0 = R // c
                meta["hit"], meta["hit_key"] = R, key
                self._pc_stats["hits"] += 1
                self._pc_stats["tokens_skipped"] += R
            else:
                self._pc_stats["misses"] += 1
        sp.set(chunks=n - i0, hit=i0 * c)
        tl_dev = jnp.asarray(tl, jnp.int32)
        logits = None
        for i in range(i0, n):
            with span("engine.prefill_chunk", index=i):
                logits, ms = self._dispatch(
                    "prefill_chunk", self._prefill_chunk_fn, params, ms,
                    tokens[:, i * c:(i + 1) * c],
                    jnp.asarray(i * c, jnp.int32), tl_dev)
            b = (i + 1) * c
            if (meta is not None and soi and b in meta["keys"]
                    and meta["keys"][b] not in self._prefix_index):
                # host snapshot of the SOI carries at this boundary: what a
                # resumed prefill needs beyond the paged caches
                with span("engine.snapshot"):
                    meta["snapshots"][b] = (np.asarray(ms["conv_buf"]),
                                            np.asarray(ms["queue"]))
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return Prefix(state=ms, first_token=first, logits=logits,
                      length=tl, true_length=tl, cache_meta=meta)

    @staticmethod
    def _pad_row(pt: PageTable, pids) -> jnp.ndarray:
        row = np.zeros(pt.pages_per_slot, np.int32)
        row[:len(pids)] = pids
        return jnp.asarray(row)

    # -- insert / generate / free ----------------------------------------

    def insert(self, prefix: Prefix, decode_state, slot: int,
               speculate: bool | None = None):
        """Install a prefilled request into ``slot``. ``speculate`` opts
        this request in/out of speculative windows on a speculative engine
        (default: in); opted-out slots commit exactly one token per window,
        so mixed batches serve both kinds at once."""
        if not 0 <= int(slot) < self._slots:
            # XLA drops out-of-bounds scatter updates silently
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self._slots})")
        s_i = int(slot)
        if speculate and self._speculate is None:
            raise ValueError("insert(speculate=True) needs an engine built "
                             "with speculate=K")
        self._spec_slots[s_i] = (self._speculate is not None
                                 if speculate is None else bool(speculate))
        with span("engine.insert", slot=s_i):
            return self._insert(prefix, decode_state, s_i)

    def _insert(self, prefix: Prefix, decode_state, s_i: int):
        if not self._paged:
            ds = self._dispatch("ins", self._ins, decode_state, prefix.state,
                                prefix.first_token,
                                jnp.asarray(s_i, jnp.int32), None)
            self._clock[s_i] = prefix.true_length
            self._occupied[s_i] = True
            self._live = ds
            return ds
        # pages cover the TRUE prompt only: a bucketed/chunked prefix's pad
        # rows map to the null page (masked on read, discarded on write)
        decode_state = self._flush_cow(decode_state)   # see free_slot
        true_len = prefix.true_length
        frames = (-(-true_len // self.cfg.soi.stride)
                  if self.cfg.soi is not None else 0)
        meta = prefix.cache_meta
        shared_outer, shared_mid = self._shared_plan(meta, true_len)
        # hold the shared pages across evictions/frees below: losing the
        # hit entry mid-insert must not free pages we are about to adopt
        temp_pins = ([(self._pt_outer, p) for p in shared_outer.values()]
                     + [(self._pt_mid, p) for p in shared_mid.values()])
        for pt, pid in temp_pins:
            pt.pin(pid)
        try:
            fresh = []
            if self._pt_outer is not None:
                fresh.append((self._pt_outer,
                              self._pt_outer.pages_needed(true_len)
                              - len(shared_outer)))
            if self._pt_mid is not None:
                fresh.append((self._pt_mid,
                              self._pt_mid.pages_needed(frames)
                              - len(shared_mid)))
            if self._occupied[s_i]:
                # Pre-check capacity BEFORE evicting: free_slot donates the
                # old decode state, so failing after it would strand the
                # caller with invalidated buffers and a half-released slot.
                for pt, need in fresh:
                    while (pt.freeable_after_release(s_i) < need
                           and self._prefix_cache
                           and len(self._prefix_index)):
                        decode_state = self._evict_entry(decode_state)
                    if pt.freeable_after_release(s_i) < need:
                        raise RuntimeError(
                            f"KV page pool exhausted: re-inserting into "
                            f"slot {s_i} needs {need} fresh pages but only "
                            f"{pt.free_pages} (+ the slot's own) are free")
                decode_state = self.free_slot(decode_state, s_i)
            for pt, need in fresh:
                decode_state = self._make_room(pt, need, decode_state)
            page_rows = {}
            try:
                if self._pt_outer is not None:
                    _, write = self._pt_outer.alloc_slot(s_i, true_len,
                                                         shared=shared_outer)
                    page_rows["outer"] = jnp.asarray(write)
                if self._pt_mid is not None:
                    _, write = self._pt_mid.alloc_slot(s_i, frames,
                                                       shared=shared_mid)
                    page_rows["mid"] = jnp.asarray(write)
                new_ds = self._dispatch(
                    "ins", self._ins, decode_state, prefix.state,
                    prefix.first_token, jnp.asarray(s_i, jnp.int32),
                    page_rows)
            except Exception:
                # transactional: a failed insert (pool exhausted mid-way,
                # mismatched prefix state) must not leak pages into an
                # unoccupied slot — never-written pages go straight back
                # (they were scrubbed when last freed) and adopted shared
                # pages drop their new reference
                for pt in (self._pt_outer, self._pt_mid):
                    if pt is not None:
                        pt.release(s_i)
                raise
        except Exception:
            # dropping the temp pins after a rollback can free a page whose
            # entry was evicted mid-insert — it still holds the old
            # prefix's K/V, and ensure() would hand it to another slot
            # unscrubbed, so scrub exactly like eviction does
            decode_state = self._unpin_scrubbed(temp_pins, decode_state)
            raise
        new_ds = self._unpin_scrubbed(temp_pins, new_ds)
        self._pc_stats["pages_shared"] += (
            sum(1 for p in shared_outer.values() if p > 0)
            + sum(1 for p in shared_mid.values() if p > 0))
        self._clock[s_i] = true_len
        self._occupied[s_i] = True
        if self._prefix_cache and meta:
            self._register_prefix(s_i, meta, true_len)
        self._live = new_ds
        return new_ds

    def _unpin_scrubbed(self, temp_pins, decode_state):
        """Drop insert-scoped temp pins; device-scrub any page that hit
        refcount zero (possible only when the hit entry was LRU-evicted
        while its pages were being adopted)."""
        freed_o, freed_m = [], []
        for pt, pid in temp_pins:
            if pt.unpin(pid):
                (freed_o if pt is self._pt_outer else freed_m).append(pid)
        if not freed_o and not freed_m:
            return decode_state
        rows = {"outer": self._pad_row(self._pt_outer, freed_o)}
        if self._pt_mid is not None:
            rows["mid"] = self._pad_row(self._pt_mid, freed_m)
        decode_state = self._dispatch("scrub", self._scrub_fn, decode_state,
                                      rows)
        self._live = decode_state
        return decode_state

    def _back_write_page(self, decode_state, pt: PageTable, slot: int,
                         pos: int, group: str):
        """Make the page this step's write lands on both *present* and
        *exclusive*: allocate on first touch (grow-by-one), copy-on-write
        when the page is shared (another slot or a prefix-index pin also
        references it — writes would leak across requests). Returns
        ``(decode_state, fresh_idx)`` — the page-map index of a first-touch
        allocation (the speculative path records these so a rejected
        position's page can be dropped), or None when the position was
        already backed / served by COW."""
        idx = (pos % pt.logical_len) // pt.page_size
        pid = int(pt.map[slot, idx])
        if pid == 0:
            decode_state = self._make_room(pt, 1, decode_state)
            pt.ensure(slot, pos)
            return decode_state, idx
        if pt.refs[pid] > 1:
            if pt.free_pages < 1:
                decode_state = self._make_room(pt, 1, decode_state)
            if pt.refs[pid] > 1:   # eviction may have just unshared it
                old, new = pt.cow(slot, idx)
                # deferred: the whole step's COW set flushes as ONE
                # _cow_batch_fn dispatch before the compiled step runs
                self._cow_pending[group].append((old, new))
                self._pc_stats["cow_copies"] += 1
        return decode_state, None

    def generate(self, params, decode_state):
        params = self._compute_params(params)
        if self._speculate is not None:
            return self._generate_spec(params, decode_state)
        with self._step_span() as sp:
            if self._paged:
                # back the cache row each live slot writes this step —
                # grow-by-one allocation plus COW off shared prefix pages —
                # then hand the updated maps to the compiled step as data
                cow0 = self._pc_stats["cow_copies"]
                with span("engine.back_pages"):
                    decode_state, pages = self._back_step(decode_state)
                sp.set(pages=pages,
                       cow=self._pc_stats["cow_copies"] - cow0)
                decode_state = self._upload_backing(decode_state)
            # the host mirror of every slot's decode clock advances for
            # paged AND dense engines: phase-aligned admission (phase_gap)
            # reads it, not just the paged backing loop above
            self._clock[self._occupied] += 1
            new_ds, data, logits, met = self._dispatch("gen", self._gen,
                                                       params, decode_state)
        self._live = new_ds
        return new_ds, ResultTokens(data=data, logits=logits, metrics=met)

    def _back_step(self, decode_state):
        """Back the position every occupied slot writes this step (and its
        middle frame on phase 0); returns the state and the pages newly
        allocated."""
        st = self.cfg.soi.stride if self.cfg.soi is not None else 0
        pages = 0
        for slot in np.nonzero(self._occupied)[0]:
            t = int(self._clock[slot])
            if self._pt_outer is not None:
                decode_state, fresh = self._back_write_page(
                    decode_state, self._pt_outer, slot, t, "outer")
                pages += fresh is not None
            if self._pt_mid is not None and t % st == 0:
                decode_state, fresh = self._back_write_page(
                    decode_state, self._pt_mid, slot, t // st, "mid")
                pages += fresh is not None
        return decode_state, pages

    def _upload_backing(self, decode_state):
        """Land the step's COW copies and hand the changed page maps to
        the compiled step."""
        with span("engine.flush_cow"):
            decode_state = self._flush_cow(decode_state)
        decode_state = dict(decode_state)
        decode_state["model"] = self._refresh_page_maps(decode_state["model"])
        return decode_state

    # -- speculative windows ---------------------------------------------

    def _drop_spec_pending(self, slot: int):
        """Release every still-pending speculative page of ``slot``.
        ``PageTable.drop`` is a no-op on entries already swept (free_slot's
        ``release`` zeroes the whole row), so this is safe to call in any
        order relative to a release. No device scrub: a dropped page was
        only ever a *write target of rejected positions*, and those writes
        were null-page-routed inside the window — its rows still hold the
        ``pos = -1`` hygiene pattern from the pool's last scrub."""
        for pt, idx, _pos in self._spec_pending[slot]:
            pt.drop(slot, idx)
        self._spec_pending[slot] = []

    def _back_spec_window(self, decode_state):
        """Back pages for every position a window MIGHT commit: K outer
        positions (1 for non-speculating slots) plus every middle frame a
        phase-0 crossing inside the window would write. Over-backing is
        rolled back after the window; COW copies are kept (the copy is
        needed the moment the slot's clock reaches that page, and the page
        already holds the right bytes)."""
        k = self._speculate
        st = self.cfg.soi.stride if self.cfg.soi is not None else 0
        for slot in np.nonzero(self._occupied)[0]:
            t0 = int(self._clock[slot])
            span = k if self._spec_slots[slot] else 1
            if self._pt_outer is not None:
                for pos in range(t0, t0 + span):
                    decode_state, fresh = self._back_write_page(
                        decode_state, self._pt_outer, slot, pos, "outer")
                    if fresh is not None:
                        self._spec_pending[slot].append(
                            (self._pt_outer, fresh, pos))
            if self._pt_mid is not None:
                for c in range(t0, t0 + span):
                    if c % st:
                        continue
                    decode_state, fresh = self._back_write_page(
                        decode_state, self._pt_mid, slot, c // st, "mid")
                    if fresh is not None:
                        self._spec_pending[slot].append(
                            (self._pt_mid, fresh, c // st))
        return decode_state

    def _rollback_spec_pages(self, n: np.ndarray):
        """Drop the fresh pages whose backed positions were all rejected.
        An outer page recorded at first-touch position ``pos`` held only
        positions >= pos of this window, so it survives iff ``pos`` itself
        committed; a middle page recorded at frame ``f`` survives iff some
        committed clock value crossed phase 0 at frame >= f."""
        st = self.cfg.soi.stride if self.cfg.soi is not None else 0
        for slot in np.nonzero(self._occupied)[0]:
            if not self._spec_pending[slot]:
                continue
            t0 = int(self._clock[slot])      # clock BEFORE the window
            last = t0 + int(n[slot]) - 1     # last committed clock value
            f_hi = last // st if st else -1  # last committed frame...
            if st and f_hi * st < t0:
                f_hi = -1                    # ...if any crossing committed
            for pt, idx, pos in self._spec_pending[slot]:
                committed = (pos <= last if pt is self._pt_outer
                             else 0 <= f_hi and pos <= f_hi)
                if not committed:
                    pt.drop(slot, idx)
            self._spec_pending[slot] = []
        # non-occupied slots can hold records only after an aborted window;
        # generate()'s except path already dropped those

    def _generate_spec(self, params, decode_state):
        with self._step_span() as sp:
            return self._spec_window(params, decode_state, sp)

    def _spec_window(self, params, decode_state, sp: span):
        k = self._speculate
        if self._paged:
            cow0 = self._pc_stats["cow_copies"]
            try:
                with span("engine.back_pages"):
                    decode_state = self._back_spec_window(decode_state)
            except Exception:
                # transactional: a failed backing (pool exhausted mid-loop)
                # must not leak the pages already grown for this window;
                # COW pairs already recorded still describe real map state,
                # so land their copies on the surviving live state
                for slot in range(self._slots):
                    self._drop_spec_pending(slot)
                self._live = self._flush_cow(self._live)
                raise
            sp.set(pages=sum(map(len, self._spec_pending)),
                   cow=self._pc_stats["cow_copies"] - cow0)
            decode_state = self._upload_backing(decode_state)
        spec_mask = jnp.asarray(self._spec_slots)
        new_ds, data, logits, met = self._dispatch(
            "specgen", self._specgen, params, decode_state, spec_mask)
        # the accepted counts gate host bookkeeping (clock advance, page
        # rollback), so every window syncs the result row to the host —
        # the same single device->host copy callers make to read tokens;
        # host_get keeps it the engine's ONE sanctioned explicit drain
        host = host_get(data)  # sync-ok: accepted counts gate page rollback
        n = host[:, k + 2]
        if self._paged:
            self._rollback_spec_pages(n)
        occ = self._occupied
        self._clock[occ] += n[occ]
        s = self.spec_stats
        s["windows"] += 1
        s["slot_windows"] += int(occ.sum())
        s["committed"] += int(n[occ].sum())
        spec_occ = occ & self._spec_slots
        s["draft_candidates"] += int(spec_occ.sum()) * (k - 1)
        s["draft_accepted"] += int((n[spec_occ] - 1).sum())
        self._live = new_ds
        return new_ds, ResultTokens(data=data, logits=logits, metrics=met,
                                    tokens_idx=(0, k),
                                    valid_idx=(k, k + 1),
                                    length_idx=(k + 1, k + 2),
                                    accepted_idx=(k + 2, k + 3))

    def spec_accept_stats(self) -> dict:
        """Accept-rate counters since engine construction: ``accept_rate``
        is the fraction of draft tokens the verifier kept;
        ``tokens_per_window`` the mean committed tokens per slot-window
        (upper bound K; 1.0 means speculation never paid off). Both report
        0.0 — never None/NaN — on an idle engine, so dashboards and BENCH
        files can always treat them as finite floats."""
        s = dict(self.spec_stats)
        s["speculate"] = self._speculate
        s["accept_rate"] = (s["draft_accepted"] / s["draft_candidates"]
                            if s["draft_candidates"] else 0.0)
        s["tokens_per_window"] = (s["committed"] / s["slot_windows"]
                                  if s["slot_windows"] else 0.0)
        return s

    def pool_stats(self) -> dict:
        """Page-pool residency per cache group (paged engines; {} dense):
        total real pages, currently free, currently used, and the
        lifetime high-water mark — the ``repro.obs`` pool gauges and the
        measured side of capacity planning."""
        out = {}
        for name, pt in (("outer", self._pt_outer), ("mid", self._pt_mid)):
            if pt is None:
                continue
            out[name] = {"n_pages": pt.n_pages - 1,
                         "free": pt.free_pages,
                         "used": pt.used_pages,
                         "high_water": pt.high_water}
        return out

    def free_slot(self, decode_state, slot: int):
        s_i = int(slot)
        if not 0 <= s_i < self._slots:
            raise ValueError(f"slot {slot} out of range [0, {self._slots})")
        if not self._occupied[s_i]:
            # refcounting turns a silent double-free into corruption (a
            # page freed twice lands on the free list twice and backs two
            # requests at once) — refuse loudly instead
            raise ValueError(
                f"free_slot({s_i}): slot is not occupied — it was never "
                f"inserted into, or already freed (double-free)")
        with span("engine.free_slot", slot=s_i):
            return self._free_slot(decode_state, s_i)

    def _free_slot(self, decode_state, s_i: int):
        # an aborted backing (pool exhausted mid-loop) can leave COW pairs
        # pending; land them before this release can recycle a pair's
        # destination page
        decode_state = self._flush_cow(decode_state)
        self._occupied[s_i] = False
        self._spec_slots[s_i] = False
        # a freed request's in-flight speculative window leaves nothing
        # behind: pending draft tokens die with the slot's active bit, and
        # the speculatively-grown pages are swept (and scrubbed) by the
        # release below — only the host-side records need clearing so a
        # later rollback can't double-free the page ids
        self._spec_pending[s_i] = []
        if not self._paged:
            # scrub the slot's cache positions like the paged path scrubs
            # released pages: a freed request's tokens must be unreadable —
            # the slot's rows keep absorbing (masked, garbage) writes while
            # free, and insert() rewrites them wholesale on reuse
            sl = jnp.asarray(s_i, jnp.int32)
            rows = {"outer": sl}
            if self.cfg.soi is not None:
                rows["mid"] = sl
            ds = self._dispatch("release", self._release_fn, decode_state,
                                sl, rows)
            self._live = ds
            return ds
        # released-page rows pad to the fixed pages_per_slot length (extra
        # entries land on the always-masked null page, whose pos lanes are
        # already -1): variable-length rows would retrace _release_fn once
        # per distinct freed-page count
        rows = {}
        if self._pt_outer is not None:
            rows["outer"] = self._pad_row(self._pt_outer,
                                          self._pt_outer.release(s_i))
        if self._pt_mid is not None:
            rows["mid"] = self._pad_row(self._pt_mid,
                                        self._pt_mid.release(s_i))
        self._clock[s_i] = 0
        ds = self._dispatch("release", self._release_fn, decode_state,
                            jnp.asarray(s_i, jnp.int32), rows)
        self._live = ds
        return ds

    # -- static-analysis hooks --------------------------------------------

    def analysis_entries(self, params) -> list:
        """Describe every jitted entry point for ``repro.analysis``.

        Returns ``JitEntry`` records pairing each entry with example
        arguments shaped exactly like live traffic (prefill-state examples
        are abstract ``ShapeDtypeStruct`` trees from ``jax.eval_shape``; the
        decode state is a real freshly initialized one). Analysis passes
        only ``lower``/``trace`` with these — nothing is executed, so no
        donation ever fires. Building the entries initializes a fresh
        decode state: use a dedicated engine instance, the ONE-live-state
        rule applies to analysis too. Tracing the prefill example bumps
        ``prefill_compiles`` (the counter counts traces); run compile-count
        measurements on counter *deltas*.
        """
        cfg = self.cfg
        params = self._compute_params(params)
        ro_params = ("params are shared by every call on the engine and "
                     "must never be donated")
        stride = cfg.soi.stride if cfg.soi is not None else 1
        ds = self.init_decode_state(params)
        slot = jnp.asarray(0, jnp.int32)
        first = jnp.zeros((1,), jnp.int32)
        entries = []
        if self._chunk is not None:
            tok_c = jnp.zeros((1, self._chunk), jnp.int32)
            off = jnp.asarray(0, jnp.int32)
            tl = jnp.asarray(self._chunk, jnp.int32)
            ms_ex = jax.eval_shape(self._fresh_prefix_fn, params)
            entries.append(JitEntry(
                "fresh_prefix", self._fresh_prefix_fn, (params,),
                readonly_ok={0: ro_params}))
            entries.append(JitEntry(
                "prefill_chunk", self._prefill_chunk_fn,
                (params, ms_ex, tok_c, off, tl), donate=(1,),
                state_args=(1,), readonly_ok={0: ro_params}, carry=(1, 1),
                cost={"role": "prefill_chunk", "tokens": self._chunk,
                      "batch": 1, "stride": stride}))
        else:
            length = self._buckets[0] if self._buckets else min(8,
                                                                self.max_len)
            tok = jnp.zeros((1, length), jnp.int32)
            tl = (jnp.asarray(length, jnp.int32) if self._buckets
                  else None)
            _, ms_ex = jax.eval_shape(self._prefill_fn, params, tok, tl,
                                      None)
            entries.append(JitEntry(
                "prefill", self._prefill_fn, (params, tok, tl, None),
                readonly_ok={0: ro_params},
                cost={"role": "prefill", "tokens": length, "batch": 1,
                      "stride": stride}))
        page_rows = None
        if self._paged:
            page_rows = {}
            if self._pt_outer is not None:
                page_rows["outer"] = jnp.zeros(
                    self._pt_outer.pages_per_slot, jnp.int32)
            if self._pt_mid is not None:
                page_rows["mid"] = jnp.zeros(
                    self._pt_mid.pages_per_slot, jnp.int32)
        entries.append(JitEntry(
            "insert", self._ins, (ds, ms_ex, first, slot, page_rows),
            donate=(0,), state_args=(0,),
            readonly_ok={1: "a Prefix is caller-owned and re-insertable "
                            "(one prefill may fan into several slots)"},
            carry=(0, None)))
        if self._speculate is None:
            entries.append(JitEntry(
                "generate", self._gen, (params, ds), donate=(1,),
                state_args=(1,), readonly_ok={0: ro_params}, carry=(1, 0),
                cost={"role": "generate", "stride": stride,
                      "batch": self._slots}))
        else:
            mask = jnp.asarray(self._spec_slots)
            entries.append(JitEntry(
                "speculative_window", self._specgen, (params, ds, mask),
                donate=(1,), state_args=(1,), readonly_ok={0: ro_params},
                carry=(1, 0),
                cost={"role": "spec_window", "stride": stride,
                      "k": self._speculate, "batch": self._slots}))
        if self._paged:
            rows = {k: jnp.zeros_like(v) for k, v in page_rows.items()}
        else:
            rows = {"outer": slot}
            if cfg.soi is not None:
                rows["mid"] = slot
        entries.append(JitEntry(
            "release", self._release_fn, (ds, slot, rows), donate=(0,),
            state_args=(0,), carry=(0, None)))
        if self._prefix_cache:
            entries.append(JitEntry(
                "scrub", self._scrub_fn, (ds, rows), donate=(0,),
                state_args=(0,), carry=(0, None)))
            n_tok = jnp.asarray(self._chunk, jnp.int32)
            n_fr = jnp.asarray(
                self._chunk // (cfg.soi.stride if cfg.soi else 1),
                jnp.int32)
            entries.append(JitEntry(
                "hydrate", self._hydrate_fn,
                (ms_ex, ds["model"], rows, n_tok, n_fr), donate=(0,),
                state_args=(0,),
                readonly_ok={1: "the LIVE pool state hydration gathers "
                                "from; it outlives the call"},
                carry=(0, None),
                cost={"role": "hydrate", "tokens": self._chunk,
                      "stride": stride}))
            pair = jnp.zeros(self._slots, jnp.int32)
            entries.append(JitEntry(
                "cow_batch", self._cow_batch_fn, (ds, pair, pair, pair,
                                                  pair),
                donate=(0,), state_args=(0,), carry=(0, None)))
        return entries
