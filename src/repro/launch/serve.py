"""Serving driver: slot-based continuous batching through ``repro.engine``.

``--smoke`` serves the reduced same-family config, for the CPU; without
it the model runs at its published width, which is for the chip
(``chip_smoke.py`` at the repo root serves qwen3-1.7b that way on one
TPU). Requests are prefilled individually (with
staggered prompt lengths, so slots sit at *different* SOI phases) and
inserted into engine slots; one jitted generate step then advances every
slot per iteration — the paper's scattered-recompute pattern is resolved
inside the compiled step from the per-slot clocks, not by cycling per-phase
programs on the host.

Prefill compiles O(1) programs under real (every-length-different) traffic:

* ``--bucket`` (default "pow2") pads each prompt to a bucket length and
  masks the pad by true length — one compiled prefill program per bucket,
  and the ``Prefix`` carries ``true_length`` so the decode clock, paged page
  allocation, and first-token logits ignore the pad;
* ``--chunk-size C`` switches to chunked prefill: ONE compiled program
  appends C tokens to the caches at a traced position offset, looped on the
  host;
* ``--bucket none`` restores exact-length prefill (one compile per distinct
  prompt length) for comparison.

``--prefix-cache`` (requires ``--paged`` and ``--chunk-size``) turns on the
copy-on-write prefix page cache: requests whose prompts share leading page
blocks (``--shared-prefix N`` makes every request share its first N tokens,
the system-prompt traffic shape) map the same KV + compressed-middle pages
by refcount and skip the prefill compute over the cached prefix. Admission
goes through ``engine.can_insert`` — a request the page pool cannot back
right now is deferred instead of crashing the pool mid-insert.

``--speculate K`` serves through self-speculative windows
(``repro.engine.speculative``): each engine call drafts K-1 tokens with
off-phase-forced SOI steps, verifies them against the true phase schedule in
the same compiled program, and commits the accepted prefix — up to K tokens
per call, greedy output token-for-token identical to per-token serving. The
tail line then adds the measured accept rate and mean committed
tokens/window. ``--mixed-spec`` opts every second request OUT of
speculation, demonstrating speculative and plain requests sharing a batch.

The tail line reports decode-phase throughput (prefill-produced first tokens
are excluded — the decode clock starts after insert), the prefill compile
count, and — with the prefix cache on — hit rate, pages shared, tokens
skipped, and COW copies, so recompile and cache regressions are visible
from the CLI. The hit-rate counters never count the null page.

``--trace-out trace.json`` (and/or ``--metrics-out metrics.json``) turns on
observability (``repro.obs``): the engine is built with ``telemetry=True``
(the per-step phase-occupancy/middle-skip vector rides the existing
deferred drain — no extra host sync), every request's lifecycle is traced
(queued → prefill → insert → first token → decode commits → done), and at
exit the Perfetto-openable Chrome trace and/or the flat metrics JSON
(registry snapshot + TTFT/ITL percentiles) are written. ``--trace-out``
also records the engine's own spans (``repro.obs.record_spans``:
``engine.generate`` and its parts, ``engine.prefill``, ...), written as
the trace's ``engine`` track. Interval timing uses the shared monotonic
clock ``repro.obs.now`` throughout.

``main`` returns a :class:`Served` record: the generated rows plus the
engine, config and parameters that produced them, so a caller (the chip
smoke test) can inspect the compiled programs and re-run a request.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib

import jax
import numpy as np

import repro.configs as configs
from repro.configs.base import ModelCfg
from repro.distributed.sharding import split_axes
from repro.engine import SOIEngine
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.obs import (EngineTelemetry, MetricsRegistry, Tracer, now,
                       record_spans, write_metrics, write_trace)


@dataclasses.dataclass
class Served:
    """One serving run. ``tokens`` maps every admitted slot to its
    generated ids (prefill's first token included); ``seqs`` stacks the
    admitted rows, cut to ``--gen-len``."""
    args: argparse.Namespace
    cfg: ModelCfg
    engine: SOIEngine
    params: dict
    prompt: jax.Array          # (batch, prompt_len) token ids
    plens: list                # true prompt length per slot
    tokens: dict
    seqs: np.ndarray


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=configs.ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--soi", default=None, choices=["pp", "fp"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=64)
    ap.add_argument("--stagger", type=int, default=1,
                    help="request i's prompt is shortened by i*stagger tokens "
                         "(mixed SOI phases in one batch; 0 = aligned)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV caches: shared page pools + per-slot page "
                         "lists instead of dense per-slot rings")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--bucket", default="pow2",
                    help="prefill bucket policy: 'pow2' (default), 'none' "
                         "(exact-length: one compile per distinct prompt "
                         "length), or comma-separated lengths")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="chunked prefill: ONE compiled program appends this "
                         "many tokens per host-loop iteration (overrides "
                         "--bucket)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="copy-on-write prefix page cache: share KV + "
                         "compressed-middle pages across requests with "
                         "common prompt prefixes and skip prefill over "
                         "cached prefixes (requires --paged --chunk-size)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="make every request share its first N prompt "
                         "tokens (system-prompt traffic; exercises "
                         "--prefix-cache)")
    ap.add_argument("--speculate", type=int, default=None, metavar="K",
                    help="self-speculative decoding: draft K-1 tokens with "
                         "off-phase SOI steps and verify them against the "
                         "true phase schedule in one compiled window — up "
                         "to K tokens commit per engine call, greedy output "
                         "identical to per-token serving; the tail line "
                         "reports accept rate and tokens/window")
    ap.add_argument("--mixed-spec", action="store_true",
                    help="with --speculate: opt every second request out of "
                         "speculation (mixed speculative/plain batch)")
    ap.add_argument("--phase-align", action="store_true",
                    help="phase-aligned admission: delay each insert (at "
                         "most stride-1 decode steps) until its slot lands "
                         "in the batch's t %% stride phase class, so the "
                         "compressed middle keeps skipping at high "
                         "occupancy instead of firing for a lone misphased "
                         "slot (engine.can_insert(..., phase_align=True))")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Perfetto-openable Chrome-trace JSON of "
                         "per-request lifecycle spans and the engine's own "
                         "spans; implies engine telemetry (repro.obs)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the flat metrics JSON (registry snapshot + "
                         "TTFT/ITL percentiles); implies engine telemetry")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def model_config(args) -> ModelCfg:
    mod = importlib.import_module(
        "repro.configs." + args.arch.replace("-", "_").replace(".", "_"))
    return (mod.smoke_config(soi=args.soi) if args.smoke
            else mod.config(soi=args.soi))


def make_engine(cfg: ModelCfg, args) -> SOIEngine:
    """The engine ``main`` serves through, built from parsed arguments."""
    if args.bucket == "pow2":
        buckets = "pow2"
    elif args.bucket == "none":
        buckets = None
    else:
        buckets = tuple(int(x) for x in args.bucket.split(","))
    return SOIEngine(cfg, max_concurrent_decodes=args.batch,
                     max_len=args.prompt_len + args.gen_len,
                     paged=args.paged, page_size=args.page_size,
                     prefill_buckets=buckets,
                     prefill_chunk=args.chunk_size,
                     prefix_cache=args.prefix_cache,
                     speculate=args.speculate,
                     telemetry=bool(args.trace_out or args.metrics_out))


def main(argv=None) -> Served:
    use_compile_cache()
    args = parse_args(argv)
    with (record_spans() if args.trace_out
          else contextlib.nullcontext()) as spans:
        return _serve(args, spans)


def _serve(args, spans) -> Served:
    """Serve the requests ``args`` describe; ``spans`` is the engine-span
    recorder of ``--trace-out`` (None without it)."""
    cfg = model_config(args)

    rng = jax.random.PRNGKey(args.seed)
    params, _ = split_axes(T.init(rng, cfg))
    b = args.batch
    prompt = jax.random.randint(jax.random.fold_in(rng, 1),
                                (b, args.prompt_len), 0, cfg.vocab)
    if args.shared_prefix:
        n = min(args.shared_prefix, args.prompt_len)
        prompt = prompt.at[:, :n].set(prompt[0, :n])
    plens = [max(1, args.prompt_len - i * args.stagger) for i in range(b)]

    obs_on = bool(args.trace_out or args.metrics_out)
    engine = make_engine(cfg, args)
    state = engine.init_decode_state(params)
    registry = MetricsRegistry()
    telemetry = EngineTelemetry(
        cfg.soi.stride if cfg.soi is not None else 1, registry=registry)
    tracer = Tracer()
    traces = {}

    t0 = now()
    admitted = []
    out = {}

    def admit(slot, state):
        tr = traces[slot]
        tr.mark_prefill_start(plens[slot])
        hits0 = (engine.prefix_cache_stats["hits"] if args.prefix_cache
                 else 0)
        prefix = engine.prefill(params, prompt[slot, :plens[slot]])
        tr.mark_prefill_end(
            cache_hit=(args.prefix_cache
                       and engine.prefix_cache_stats["hits"] > hits0),
            tokens_skipped=(prefix.cache_meta or {}).get("hit", 0))
        spec = (slot % 2 == 0 if args.speculate and args.mixed_spec
                else None)
        state = engine.insert(prefix, state, slot, speculate=spec)
        tr.mark_inserted()
        out[slot] = [int(prefix.first_token[0])]
        tr.mark_first_token()
        admitted.append(slot)
        return state

    pendq = []
    for slot in range(b):
        traces[slot] = tracer.request(slot, t_queued=t0)
        # admission: a request the page pool cannot back right now is
        # deferred, not crashed into a half-released slot mid-insert
        if not engine.can_insert(plens[slot], slot):
            print(f"request {slot} deferred: page pool cannot admit "
                  f"{plens[slot]} tokens (size --paged pools for the "
                  f"resident population)")
            continue
        pendq.append(slot)

    def admit_ready(state):
        # pick-slot scheduling: admit every pending request whose slot
        # would land in the batch's phase class right now; the rest wait
        # for the phase to come around (each decode step closes a gap by
        # one, so every request admits within stride-1 steps). Without
        # --phase-align this admits everything immediately.
        for slot in list(pendq):
            if args.phase_align and not engine.can_insert(
                    plens[slot], slot, phase_align=True):
                continue
            pendq.remove(slot)
            state = admit(slot, state)
        return state

    state = admit_ready(state)
    t_prefill = now() - t0
    if not admitted and not pendq:
        print(f"arch={cfg.name}: no request admitted — the paged pools "
              f"cannot back a single prompt; grow n_pages or shrink "
              f"--prompt-len")
        return Served(args, cfg, engine, params, prompt, plens, out,
                      np.zeros((0, args.gen_len), np.int64))

    n_steps = args.gen_len - 1   # every slot gains >= one token per call

    def drain(res, snapshot, state, done):
        # ONE batched explicit device->host copy per step (host_get under
        # convert_to_numpy); token extraction below runs on host numpy.
        # ``snapshot`` is the admitted set at dispatch: a slot admitted
        # AFTER this step ran was not active in it, and its result row is
        # garbage
        res = res.convert_to_numpy()
        if obs_on:
            telemetry.observe_result(res)
        for slot in snapshot:
            if len(out[slot]) < args.gen_len:
                sd = res.get_result_at_slot(slot)
                # per-token engines commit their one token; speculative
                # windows commit the accepted prefix of up to K
                n = 1 if sd.accepted is None else int(sd.accepted[0])
                room = args.gen_len - len(out[slot])
                got = min(n, room)
                out[slot].extend(int(x) for x in sd.tokens[:got])
                if got:
                    traces[slot].mark_decode(got)
                if len(out[slot]) == args.gen_len:
                    traces[slot].mark_done()
                    state = engine.free_slot(state, slot)
                    done += 1
        return state, done

    t0 = now()
    done = 0
    pending = None     # the previous step's (ResultTokens, admitted set)
    # phase-aligned admission can hold each request up to stride-1 extra
    # steps; bound the loop accordingly (it exits as soon as every
    # admitted request completes)
    stride = cfg.soi.stride if cfg.soi is not None else 1
    for _ in range(n_steps + (len(pendq) + 1) * stride):
        state = admit_ready(state)
        snapshot = list(admitted)
        state, result = engine.generate(params, state)
        # drain the PREVIOUS step's tokens while this step runs on device:
        # deferring the copy by one step overlaps host extraction with
        # dispatched compute instead of stalling the pipeline on a sync
        # (a finished slot is then freed one step late; its ring/page
        # writes stay confined to buffers the free will scrub)
        if pending is not None:
            state, done = drain(*pending, state, done)
            if done == len(admitted) and not pendq:
                pending = None
                break
        pending = (result, snapshot)
    if pending is not None:
        state, done = drain(*pending, state, done)
    for slot in pendq:
        # reachable only when clocks advanced past every alignment window
        # (e.g. speculative windows committing variable token counts)
        print(f"request {slot} not admitted within the phase-align "
              f"step budget")
    dt = now() - t0
    total = sum(len(v) for v in out.values())
    # each slot's FIRST token came from prefill (before the decode clock
    # started): counting it in the decode-phase rate overstated tok/s by
    # one per admitted slot — report decode-produced tokens vs decode time
    decoded = total - len(admitted)
    seqs = np.stack([np.asarray(out[s][:args.gen_len]) for s in admitted])
    print(f"arch={cfg.name} soi={args.soi or 'off'}  "
          f"prefill {len(admitted)}/{b} reqs (lens {plens}) in "
          f"{t_prefill:.2f}s "
          f"[{engine.prefill_compiles} prefill compile(s), "
          f"bucket={args.bucket if not args.chunk_size else '-'} "
          f"chunk={args.chunk_size or '-'}], "
          f"decoded {decoded} tok across {len(admitted)} slots in {dt:.2f}s "
          f"({decoded / max(dt, 1e-9):.1f} tok/s decode)")
    if args.speculate:
        sp = engine.spec_accept_stats()
        print(f"speculative: K={args.speculate}, {sp['windows']} windows, "
              f"{sp['committed']} tokens committed "
              f"({sp['tokens_per_window']:.2f} tokens/window), "
              f"draft accept rate {100 * sp['accept_rate']:.0f}% "
              f"({sp['draft_accepted']}/{sp['draft_candidates']})")
    if args.prefix_cache:
        pc = engine.prefix_cache_stats
        print(f"prefix-cache: {pc['hits']}/{pc['hits'] + pc['misses']} hits "
              f"({100 * pc['hit_rate']:.0f}%), "
              f"{pc['pages_shared']} pages shared, "
              f"{pc['tokens_skipped']} prompt tokens skipped, "
              f"{pc['cow_copies']} COW copies, "
              f"{pc['evictions']} evictions, {pc['entries']} entries")
    if obs_on:
        telemetry.snapshot_engine(engine)
        coh = telemetry.phase_coherence()
        print(f"phase coherence: {100 * coh['coherent_step_rate']:.0f}% of "
              f"active steps fully aligned (modal-bucket slot fraction "
              f"{coh['modal_fraction_mean']:.2f}; "
              f"--phase-align {'on' if args.phase_align else 'off'})")
        lat = tracer.summary()
        print(f"latency: TTFT p50 {1e3 * lat['ttft_p50_s']:.3f} ms, "
              f"p99 {1e3 * lat['ttft_p99_s']:.3f} ms; gap between tokens "
              f"p50 {1e3 * lat['itl_p50_s']:.3f} ms, "
              f"p99 {1e3 * lat['itl_p99_s']:.3f} ms")
        if args.trace_out:
            write_trace(tracer, args.trace_out, spans.records)
            print(f"trace written to {args.trace_out} "
                  f"(open in ui.perfetto.dev)")
        if args.metrics_out:
            write_metrics(args.metrics_out, registry=registry,
                          tracer=tracer)
            print(f"metrics written to {args.metrics_out}")
    print("sample:", seqs[0, :16].tolist())
    return Served(args, cfg, engine, params, prompt, plens, out, seqs)


if __name__ == "__main__":
    main()
