"""Sweep an open-loop cell's offered rate, on the chip, to find its knee.

    python bench/knee.py --config qwen3-1.7b-soi-pp --traffic rag-open \\
        --rates 4,6,8,10 --seconds 20 --seed 1

For each rate, in one process: set-up as a run of the cell does it, a
window of ``--seconds`` at that rate, and one JSON line: requests due and
served, TTFT median and p95, the mean queue wait of the requests due in
the window's first and second halves, and the backlog (due but not yet
admitted) at the close. The knee is the highest rate whose second-half
wait is not above the first half's and whose backlog does not grow; the
open-loop cell of this mix runs at 0.8 x knee (``PERF.md`` keeps the
sweep).
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys

import numpy as np

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench_run.use_cache()
    from soibench import cell_run, serve, spec

    cell = spec.load_pair(args.config, args.traffic)
    with open(bench_run.HERE / "peaks.json") as f:
        bench_run.chip(cell.chips, json.load(f))
    engine = serve.make_engine(cell.family.program_config(cell.config),
                               cell.traffic)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_hz=rate)
        at = dataclasses.replace(cell, traffic=mix)
        weights, loop = cell_run.prepare(at, args.seed, args.seconds,
                                         bench_run.log, engine=engine)
        due = list(loop.schedule)
        t_open, _ = cell_run.measure(loop, mix, args.seconds)
        half = args.seconds / 2
        waits = [[r.t_prefill - (t_open + r.due) for r in due
                  if (r.due < half) == first and not math.isnan(r.t_prefill)]
                 for first in (True, False)]
        ttft = [r.times[0] - (t_open + r.due) for r in due if r.times]
        print(json.dumps({
            "rate_hz": rate, "due": len(due),
            "served": sum(1 for r in due if r.done),
            "backlog_at_close": loop.backlog_at_close,
            "ttft_p50_ms": 1e3 * float(np.median(ttft)),
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "wait_first_half_ms": 1e3 * float(np.mean(waits[0])),
            "wait_second_half_ms": 1e3 * float(np.mean(waits[1]))}),
            flush=True)
        del loop, weights
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
