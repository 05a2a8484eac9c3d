"""Every cell, end to end on the CPU at smoke sizes with the Pallas
kernels interpreted: set-up, the window, the metrics, the check."""

import json

import pytest
import smoke

from soibench import cell_run

CELLS = [w["name"] for w in json.loads(
    (smoke.BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]
CELLS += [c for c in smoke.EXTRA if c not in CELLS]
PEAK = json.loads((smoke.BENCH / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]
# smoke models in float32 serve the reference's own tokens (gaps of
# rounding, under 1e-4); a token altered or a step repeated lands about a
# logit spread (4) away, and the float8 control over 1
SMOKE_LIMIT = 1e-3


class Compiles:
    def __init__(self):
        self.names = []

    @property
    def n(self):
        return len(self.names)


def run(workload, seconds=2.0, seed=2 ** 31 + 5, **kw):
    import run as bench_run
    from repro.kernels import ops as kops
    kops.FORCE_MODE = "interpret"
    try:
        counter = bench_run.CompileCounter()
        out = cell_run.run_cell(
            smoke.cell(workload, SMOKE_LIMIT), smoke.FakeChip(), PEAK,
            seed=seed, seconds=seconds, trace=False, t_start=0.0,
            compiles=counter, log=lambda *a: None, **kw)
        return out, counter
    finally:
        kops.FORCE_MODE = None


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end(workload):
    out, counter = run(workload)
    cell = smoke.cell(workload)
    assert out["correct"] is True, out
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "setup_s" in out["metrics"]
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "check"
    assert out["check"]["max_gap"]["limit"] == SMOKE_LIMIT
