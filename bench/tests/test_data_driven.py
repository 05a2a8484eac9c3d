"""A later change adds a configuration, a traffic mix or a metric as new
files and BENCHMARK.json entries; the harness takes them by name, with no
edit to any file that is there."""

import json
import shutil

import smoke
import test_harness

from soibench import cell_run, spec

NEW_METRIC = '''"""Output tokens per finished request in the window."""


def read(run):
    done = [r for r in run.requests if r.done and r.times
            and run.inside(r.times[-1])]
    return sum(len(r.out) for r in done) / len(done) if done else None
'''


def checkout(tmp_path):
    """A checkout holding BENCHMARK.json and bench/ plus the additions."""
    root = tmp_path / "checkout"
    shutil.copytree(smoke.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((smoke.BENCH.parent / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/qwen3-1.7b-soi-pp.json")
                     .read_text())
    (root / "bench/configs/qwen3-1.7b-soi-pp-b.json").write_text(
        json.dumps(cfg))
    mix = smoke.smoke_mix("chat-closed")
    mix["clients"], mix["output"] = 4, {"lo": 4, "hi": 8}
    (root / "bench/traffic/chat-short.json").write_text(json.dumps(mix))
    (root / "bench/limits/qwen3-b-chat-short.json").write_text(
        json.dumps({"max_gap": test_harness.SMOKE_LIMIT}))
    (root / "bench/metrics/tokens_per_request.py").write_text(NEW_METRIC)
    bench["configs"].append(dict(bench["configs"][0],
                                 name="qwen3-1.7b-soi-pp-b",
                                 file="bench/configs/qwen3-1.7b-soi-pp-b.json"))
    bench["workloads"].append({"name": "qwen3-b-chat-short",
                               "config": "qwen3-1.7b-soi-pp-b",
                               "traffic": "chat-short", "chips": 1,
                               "why": "a cell added as data"})
    bench["end_to_end"].append({"name": "tokens_per_request", "unit": "tokens",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["qwen3-b-chat-short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_cell_added_as_files_runs(tmp_path):
    root = checkout(tmp_path)
    cell = spec.load_cell("qwen3-b-chat-short", root=root)
    assert cell.traffic["clients"] == 4
    assert "tokens_per_request" in {m["name"] for m in cell.end_to_end}
    # the configuration file is the one added; run it at smoke sizes
    cell = spec.Cell(**{**cell.__dict__,
                        "config": smoke.config("qwen3-1.7b-soi-pp")})
    from repro.kernels import ops as kops
    kops.FORCE_MODE = "interpret"
    try:
        out = cell_run.run_cell(cell, smoke.FakeChip(), test_harness.PEAK,
                                seed=3, seconds=2.0, trace=False,
                                t_start=0.0, compiles=test_harness.Compiles(),
                                log=lambda *a: None)
    finally:
        kops.FORCE_MODE = None
    assert out["correct"] is True
    assert 4 <= out["metrics"]["tokens_per_request"]["value"] <= 8
    # the cells that were there do not report the new metric
    assert "tokens_per_request" not in {
        m["name"] for m in spec.load_cell("qwen3-chat-closed",
                                          root=root).end_to_end}
