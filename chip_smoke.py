"""Serve qwen3-1.7b at its published width on one TPU, through the Pallas
kernels, and check what comes out.

    python chip_smoke.py

Everything runs in this one process (a chip belongs to one process). The
serving driver ``repro.launch.serve.main`` runs twice, with random weights
from ``--seed 0``, at qwen3-1.7b's published width (28 layers, d_model 2048,
16 heads with 8 KV heads, vocab 151936) and SOI pp on:

  dense  dense KV rings, pow2-bucketed prefill
         (kernels: flash_attention, decode_attention)
  paged  paged KV pools, chunked prefill, prefix cache over a shared prompt
         prefix (kernels: chunk_attention, paged_decode_attention,
         copy_pages)

Each phase serves 4 requests with staggered prompt lengths (so slots sit at
different SOI phases) and checks that every request was admitted and got
all its tokens. It then lists the Pallas kernels (``tpu_custom_call``) in
each compiled engine program, and fails if one the phase needs is missing;
prints each program's memory analysis and the device's peak memory; and
compares the first-token and first-generate-step logits of the Pallas path
with the pure-XLA reference path (``kops.FORCE_MODE = "ref"``) on the same
chip, on one request, within a stated bf16 tolerance. Seconds printed are
smoke timings (compilation included), not benchmark numbers.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every phase and check passed. Without a TPU, or outside a checkout of
the repository, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

COMMON = ["--arch", "qwen3-1.7b", "--soi", "pp", "--batch", "4",
          "--prompt-len", "64", "--gen-len", "32", "--stagger", "3",
          "--seed", "0"]
PHASES = {
    "dense": (COMMON, {"prefill": "flash_attention",
                       "generate": "decode_attention"}),
    "paged": (COMMON + ["--paged", "--page-size", "16", "--chunk-size", "32",
                        "--prefix-cache", "--shared-prefix", "32"],
              {"prefill_chunk": "chunk_attention",
               "generate": "paged_decode_attention",
               "cow_batch": "copy_pages"}),
}
PUBLISHED = {"n_layers": 28, "d_model": 2048, "n_heads": 16, "n_kv": 8,
             "vocab": 151936}
# Pallas and reference attention differ by bf16 rounding of the attention
# output, compounded over 28 layers; a wrong head, mask or page lands at
# O(1) relative error
REL_L2_TOL = 5e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def kernels_in(hlo_text):
    """Names of the Pallas kernels (Mosaic custom-calls) in compiled HLO
    text."""
    from repro.analysis.hlo import kernel_name_in
    return sorted({kernel_name_in(line) or "unregistered"
                   for line in hlo_text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in line})


def list_programs(served):
    """Compile every engine entry point at its serving shapes and report
    its Pallas kernels and memory analysis."""
    programs = {}
    for entry in served.engine.analysis_entries(served.params):
        compiled = entry.jfn.lower(*entry.args).compile()
        mem = compiled.memory_analysis()
        programs[entry.name] = kernels_in(compiled.as_text())
        print(f"  program {entry.name:15s} kernels {programs[entry.name]}  "
              f"args {mem.argument_size_in_bytes:,} B  "
              f"outputs {mem.output_size_in_bytes:,} B  "
              f"temp {mem.temp_size_in_bytes:,} B  "
              f"aliased {mem.alias_size_in_bytes:,} B")
    return programs


def first_logits(engine, params, tokens):
    """Prefill one request, insert it into slot 0 and take one generate
    step: (first-token logits, generate-step logits of slot 0), as f32."""
    import numpy as np
    state = engine.init_decode_state(params)
    prefix = engine.prefill(params, tokens)
    state = engine.insert(prefix, state, 0)
    state, result = engine.generate(params, state)
    return (np.asarray(prefix.logits[0], np.float32),
            np.asarray(result.logits[0], np.float32))


def compare_with_reference(served):
    """Logits of the Pallas path vs the pure-XLA reference path on the
    same request, weights and chip."""
    import numpy as np
    from repro.kernels import ops as kops
    from repro.launch import serve
    tokens = served.prompt[1, :served.plens[1]]
    got = first_logits(served.engine, served.params, tokens)
    prev = kops.FORCE_MODE
    kops.FORCE_MODE = "ref"
    try:
        ref_engine = serve.make_engine(served.cfg, served.args)
        want = first_logits(ref_engine, served.params, tokens)
    finally:
        kops.FORCE_MODE = prev
    for what, g, w in zip(("first-token", "generate-step"), got, want):
        check(np.all(np.isfinite(g)) and np.all(np.isfinite(w)),
              f"{what} logits are not finite")
        rel = float(np.linalg.norm(g - w) / np.linalg.norm(w))
        print(f"  {what} logits, Pallas vs reference: rel L2 {rel:.3e} "
              f"(tolerance {REL_L2_TOL:g}), max |diff| "
              f"{float(np.max(np.abs(g - w))):.3e}, max |ref| "
              f"{float(np.max(np.abs(w))):.3e}, argmax "
              f"{int(np.argmax(g))} vs {int(np.argmax(w))}")
        check(rel <= REL_L2_TOL,
              f"{what} logits: Pallas path departs from the reference "
              f"(rel L2 {rel:.3e} > {REL_L2_TOL:g})")


def run_phase(name, argv, expected, device, clock):
    from repro.launch import serve
    print(f"== phase {name}: serve.main {' '.join(argv)}")
    c0, t0 = clock.seconds, time.perf_counter()
    served = serve.main(argv)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    cfg, args = served.cfg, served.args
    attn = cfg.segments[0].blocks[0].attn
    width = {"n_layers": sum(s.n_layers for s in cfg.segments),
             "d_model": cfg.d_model, "n_heads": attn.n_heads,
             "n_kv": attn.n_kv, "vocab": cfg.vocab}
    print(f"  model {cfg.name}: {width}, dtype {cfg.dtype}")
    check(width == PUBLISHED, f"not the published width: {width}")
    got = {s: len(v) for s, v in served.tokens.items()}
    print(f"  requests admitted {len(got)}/{args.batch}, tokens per request "
          f"{got} (want {args.gen_len}), prompt lengths {served.plens}")
    check(len(got) == args.batch, "a request was not admitted")
    check(all(n >= args.gen_len for n in got.values()),
          "a request did not get all its tokens")
    if args.prefix_cache:
        print(f"  prefix cache: {served.engine.prefix_cache_stats}")
    print(f"  smoke timing: serve {wall:.3f} s wall, of which "
          f"{compile_s:.3f} s tracing+compiling, {wall - compile_s:.3f} s "
          f"running")
    stats = device.memory_stats() or {}
    print(f"  device memory after serving: peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use')} (since process start), "
          f"bytes_in_use {stats.get('bytes_in_use')}, bytes_limit "
          f"{stats.get('bytes_limit')}")
    t0 = time.perf_counter()
    programs = list_programs(served)
    for prog, kernel in expected.items():
        check(kernel in programs.get(prog, ()),
              f"phase {name}: kernel {kernel} missing from the compiled "
              f"{prog} program (found {programs.get(prog)})")
    print(f"  expected kernels present: {expected}")
    compare_with_reference(served)
    stats = device.memory_stats() or {}
    print(f"  smoke timing: program listing and reference check "
          f"{time.perf_counter() - t0:.3f} s wall; peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use')} (since process start)")


def main():
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run this script "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device.platform} "
              f"({device.device_kind})", file=sys.stderr)
        return 1
    print(f"device {device.platform} {device.device_kind} x "
          f"{len(jax.devices())}; compilation cache {cache}")
    clock = CompileClock()
    for name, (argv, expected) in PHASES.items():
        run_phase(name, argv, expected, device, clock)
        gc.collect()    # the phase's f32 params must go before the next's
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
