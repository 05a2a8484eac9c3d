"""One run of one cell: set-up, the measured window, the check."""

from __future__ import annotations

import gc
import pathlib
import shutil

import numpy as np

from soibench import check, metrics, model, serve, traffic, window


def warm(loop, traffic_, log) -> None:
    """Run every program shape the traffic uses, so nothing compiles in the
    window. Without the prefix cache: one prefill per chunk count, and one
    request served through insert, generate and free. With it: a request
    served at every prompt length (the cache's lookup slices each length),
    sharing a cached tenant prefix as the traffic's prompts do; their
    registered suffixes fill the pool until the cache evicts, as it does
    in the window."""
    rng = np.random.default_rng(0)
    lengths = sorted({p for p, _ in traffic_.sizes()})
    prefixes = traffic_.tenant_prompts()

    def prompt(p):
        head = prefixes[p % len(prefixes)] if prefixes else []
        own = rng.integers(0, traffic_.vocab, p - len(head))
        return np.concatenate([head, own]).astype(np.int32)

    if loop.engine.prefix_cache_enabled:
        for i, p in enumerate(lengths):
            serve_one(loop, prompt(p), -100 - i)
    else:
        by_chunks = {-(-p // loop.chunk): p for p in lengths}
        lengths = sorted(by_chunks.values())
        for p in lengths:
            int(loop.prefill(prompt(p)).first_token[0])
        serve_one(loop, prompt(lengths[0]), -1)
    log(f"warm-up: {len(lengths)} prompt shape(s); prefix cache "
        f"{loop.engine.prefix_cache_stats}")


def serve_one(loop, tokens: np.ndarray, rid: int) -> None:
    """Serve one short request through admission, generate, drain and
    free, outside the window (its result is not kept)."""
    loop.waiting.append(traffic.Request(rid=rid, client=-1, tokens=tokens,
                                        gen_len=3))
    while loop.waiting or loop.active:
        loop.admit()
        if loop.active:
            loop.step()
    loop.flush()
    loop.finished.clear()


def warm_prefixes(loop, traffic_, log) -> None:
    """Open loops with the prefix cache: serve one short request per tenant
    prefix, so the cache holds every prefix the window's requests share."""
    rng = np.random.default_rng(1)
    lo = traffic_.mix["prompt"]["lo"]
    for i, prefix in enumerate(traffic_.tenant_prompts()):
        own = rng.integers(0, traffic_.vocab, lo).astype(np.int32)
        serve_one(loop, np.concatenate([prefix, own]), -1 - i)
    log(f"prefix cache filled with {len(traffic_.tenant_prompts())} "
        f"tenant prefixes: {loop.engine.prefix_cache_stats}")


def prepare(cell, seed: int, seconds: float, log, engine=None):
    """The weights and the warmed, filled serving loop of one run.
    ``engine`` may be one built for this cell before (its programs are
    compiled once per process)."""
    import jax
    mix = cell.traffic
    fam = cell.family
    sizes = fam.sizes(cell.config)
    t0 = serve.clock()
    weights = model.make_weights(cell.config, model.seed_key(seed, 0))
    jax.block_until_ready(weights)
    t1 = serve.clock()
    if engine is None:
        engine = serve.make_engine(fam.program_config(cell.config), mix)
    tr = traffic.Traffic(mix, sizes["vocab"], seed)
    if max(p + o for p, o in tr.sizes()) > mix["engine"]["max_len"]:
        raise ValueError("the mix sends more tokens than max_len holds")
    if mix["loop"] == "closed":
        loop = serve.ClosedLoop(engine, weights, mix, tr, serve.clock())
    else:
        loop = serve.OpenLoop(engine, weights, mix, tr.schedule(seconds))
    if mix.get("prefix"):
        warm_prefixes(loop, tr, log)
    warm(loop, tr, log)
    t2 = serve.clock()
    if mix["loop"] == "closed":
        loop.preroll(mix["engine"]["slots"], mix["clients"])
    jax.block_until_ready(loop.state)
    log(f"set-up parts: weights {t1 - t0:.3f} s, engine and warm-up "
        f"{t2 - t1:.3f} s, pre-roll {serve.clock() - t2:.3f} s")
    return weights, loop


def measure(loop, mix: dict, seconds: float, tracer=None):
    """Serve the window; returns its (open, close) on ``serve.clock``."""
    if tracer:
        tracer.start()
    t_open = serve.clock()
    if mix["loop"] == "closed":
        loop.run(t_open, seconds)
    else:
        loop.run(t_open, seconds, float(mix.get("grace_s", 60)))
    if tracer:
        tracer.stop()
    return t_open, t_open + seconds


def run_cell(cell, device, peak: dict, *, seed: int, seconds: float,
             trace: bool, t_start: float, compiles, log) -> dict:
    import jax
    mix = cell.traffic
    closed = mix["loop"] == "closed"
    sizes = cell.family.sizes(cell.config)
    weights, loop = prepare(cell, seed, seconds, log)
    tracer = None
    if trace:
        from soibench import families, profile
        tracer = profile.Tracer(pathlib.Path("bench_trace").resolve(),
                                families.kernel_costs(cell.family))
    n_compiles = compiles.n
    t_open, t_close = measure(loop, mix, seconds, tracer)
    in_window = compiles.names[n_compiles:]
    stats = device.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    setup_s = t_open - t_start
    requests = _requests(loop)
    run = window.Run(cell=cell, sizes=sizes, seed=seed, t_open=t_open,
                     t_close=t_close, setup_s=setup_s, requests=requests,
                     loop=loop, peak=peak, memory_peak_bytes=mem_peak)
    log(f"set-up {setup_s:.3f} s; programs compiled or loaded in the "
        f"window: {len(in_window)} {sorted(set(in_window))}; "
        f"peak_bytes_in_use {mem_peak}; loop counts {dict(loop.counts)}")
    if not closed:
        late = np.asarray(loop.lateness)
        log(f"generator lateness: p50 {np.median(late) * 1e3:.3f} ms, "
            f"p99 {np.percentile(late, 99) * 1e3:.3f} ms, "
            f"max {late.max() * 1e3:.3f} ms over {len(late)} requests")
    if trace:
        run.trace = tracer.summary(run)
    names = cell.per_layer if trace else cell.end_to_end
    result_metrics = metrics.read_all(names, run)
    failed = sum(1 for r in requests if r.error is not None)
    if not closed:
        failed += sum(1 for r in run.due_in_window() if not r.times)
    log(f"requests attempted {len(requests)}, finished "
        f"{len(loop.finished)}, failed {failed}; share of token gaps "
        f"holding a prefill: {_stall_share(run):.4f}")
    # the check runs on the chip once the engine's state is gone
    sample = check.sample(loop.finished, int(mix["sample"]), seed)
    del loop, run.loop
    gc.collect()
    found = check.compare(cell.family, weights, sizes, sample,
                          mix["engine"]["max_len"])
    log(f"check: {found}")
    limit = cell.limits["max_gap"]
    correct = (bool(sample) and limit is not None
               and found["max_gap"] <= limit and not any(
                   r.error for r in requests))
    out = {"correct": correct, "attempted": len(requests), "failed": failed,
           "metrics": result_metrics,
           "device": {"platform": device.platform,
                      "kind": device.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": mem_peak}}
    if trace:
        out["device"].update(busy_s=run.trace.busy_s,
                             window_s=run.trace.window_s)
        out["breakdown"] = run.trace.breakdown
        shutil.rmtree(tracer.dir, ignore_errors=True)
    out["check"] = {"max_gap": {"value": found["max_gap"], "limit": limit}}
    return out


def _requests(loop) -> list:
    seen = {id(r): r for r in loop.finished}
    for r in list(loop.active.values()) + list(loop.waiting):
        seen[id(r)] = r
    for r in getattr(loop, "schedule", ()):
        seen[id(r)] = r
    return [r for r in seen.values() if r.rid >= 0]


def _stall_share(run) -> float:
    """Share of the window's token gaps during which a prefill began."""
    starts = np.sort([a for a, _ in run.spans("prefill")])
    gaps = n = 0
    for r in run.requests:
        t = np.asarray(r.times)
        if len(t) < 2:
            continue
        a, b = t[:-1], t[1:]
        keep = (b >= run.t_open) & (b < run.t_close)
        n += int(keep.sum())
        gaps += int((np.searchsorted(starts, b[keep])
                     > np.searchsorted(starts, a[keep], side="right")).sum())
    return gaps / n if n else 0.0
