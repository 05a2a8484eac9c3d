"""Readings that a cell's correctness limit is set from, on the chip.

    python bench/control.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 20

For each seed, in one process (the engine compiles once): set-up as a run
of the cell does it, a window of ``--seconds`` at the cell's own load, and
the number a run compares (the widest reference gap of a served token over
the run's sample). For the seeds in ``--control-seeds`` it also reads the
control: the reference computed in float8 put in the program's place, on
the same prompts and served tokens. One JSON line per seed, then the
largest program reading and the smallest control reading. The limit in
``bench/limits/<cell>.json`` lies between them (``PERF.md`` gives the
readings of each cell).
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench_run.use_cache()
    from soibench import cell_run, check, serve, spec

    cell = spec.load_cell(args.workload)
    with open(bench_run.HERE / "peaks.json") as f:
        bench_run.chip(cell.chips, json.load(f))
    mix = cell.traffic
    fam = cell.family
    sizes = fam.sizes(cell.config)
    engine = serve.make_engine(fam.program_config(cell.config), mix)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    prog, ctl = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        weights, loop = cell_run.prepare(cell, seed, args.seconds,
                                         bench_run.log, engine=engine)
        cell_run.measure(loop, mix, args.seconds)
        sample = check.sample(loop.finished, int(mix["sample"]), seed)
        del loop
        gc.collect()
        found = check.compare(fam, weights, sizes, sample,
                              mix["engine"]["max_len"])
        line = {"seed": seed, "program": found}
        prog.append(found["max_gap"])
        if seed in controls:
            line["control"] = check.control(fam, weights, sizes, sample,
                                            mix["engine"]["max_len"])
            ctl.append(line["control"])
        print(json.dumps(line), flush=True)
        del weights
        gc.collect()
    print(json.dumps({"workload": args.workload, "seeds": len(prog),
                      "program_max": max(prog),
                      "control_min": min(ctl) if ctl else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
