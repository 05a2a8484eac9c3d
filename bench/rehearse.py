"""Compile a cell's engine programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload qwen3-chat-closed

Each program the cell's window drives (``prefill_chunk``, the fresh prefill
state, ``insert``, ``generate``, ``release``, and with the prefix cache
``hydrate``, ``scrub`` and ``cow_batch``) is lowered at the cell's slots,
``max_len`` and chunk with the Pallas kernels on, and compiled by the TPU
compiler for one chip of a described ``v5e:2x2``. Nothing runs and nothing
is allocated: weights and decode state are shapes. Prints the Pallas
kernels in each program and its memory analysis (argument, output, temp
and aliased bytes). A program the chip's compiler refuses raises here.
"""

from __future__ import annotations

import argparse
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.analysis.hlo import kernel_name_in
    from repro.kernels import ops as kops
    from soibench import model, serve, spec

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    cfg = cell.family.program_config(cell.config)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    kops.FORCE_MODE = "pallas"

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree)

    params = on_chip(model.weight_shapes(cell.config))
    engine = serve.make_engine(cfg, cell.traffic)
    total = 0
    refused = 0
    for name, jfn, ex in programs(engine, params):
        try:
            compiled = jfn.lower(*on_chip(ex)).compile()
        except Exception as e:      # the chip's compiler refused it
            refused += 1
            print(f"{args.workload} {name:14s} REFUSED "
                  f"{type(e).__name__}: {str(e)[:600]}", flush=True)
            continue
        mem = compiled.memory_analysis()
        kernels = sorted({kernel_name_in(line) or "unregistered"
                          for line in compiled.as_text().splitlines()
                          if 'custom_call_target="tpu_custom_call"' in line})
        total = max(total, mem.argument_size_in_bytes
                    + mem.temp_size_in_bytes)
        print(f"{args.workload} {name:14s} args {mem.argument_size_in_bytes:,}"
              f" B  outputs {mem.output_size_in_bytes:,} B  temp "
              f"{mem.temp_size_in_bytes:,} B  aliased "
              f"{mem.alias_size_in_bytes:,} B  kernels {kernels}",
              flush=True)
    print(f"{args.workload}: largest args + temp {total:,} B, "
          f"{refused} program(s) refused")
    return 1 if refused else 0


def programs(engine, params) -> list:
    """(name, jitted program, argument shapes) of every program a window
    of this engine drives, as ``SOIEngine.analysis_entries`` lists them,
    with the decode state as shapes (nothing is allocated)."""
    import jax
    import jax.numpy as jnp

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    ds = jax.eval_shape(engine.init_decode_state, params)
    ms = jax.eval_shape(engine._fresh_prefix_fn, params)
    rows = {"outer": sds((engine._pt_outer.pages_per_slot,)),
            "mid": sds((engine._pt_mid.pages_per_slot,))}
    one, slot = sds(()), sds(())
    out = [("fresh_prefix", engine._fresh_prefix_fn, (params,)),
           ("prefill_chunk", engine._prefill_chunk_fn,
            (params, ms, sds((1, engine.prefill_chunk)), one, one)),
           ("insert", engine._ins, (ds, ms, sds((1,)), slot, rows)),
           ("generate", engine._gen, (params, ds)),
           ("release", engine._release_fn, (ds, slot, rows))]
    if engine.prefix_cache_enabled:
        pair = sds((engine.max_concurrent_decodes,))
        out += [("hydrate", engine._hydrate_fn,
                 (ms, ds["model"], rows, one, one)),
                ("scrub", engine._scrub_fn, (ds, rows)),
                ("cow_batch", engine._cow_batch_fn,
                 (ds, pair, pair, pair, pair))]
    return out


if __name__ == "__main__":
    sys.exit(main())
