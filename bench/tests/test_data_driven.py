"""A later change adds a configuration, a traffic mix or a metric as new
files and BENCHMARK.json entries; the harness takes them by name, with no
edit to any file that is there."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest
import smoke
import test_harness

from soibench import cell_run, spec

NEW_METRIC = '''"""Output tokens per finished request in the window."""


def read(run):
    done = [r for r in run.requests if r.done and r.times
            and run.inside(r.times[-1])]
    return sum(len(r.out) for r in done) / len(done) if done else None
'''


# a model family added as a new file: dense GQA under another name, with a
# Pallas kernel of its own
NEW_FAMILY = '''"""Dense GQA under another name, with a kernel of its own."""

from soibench.families.dense_gqa import (head, hidden, leaf_specs,
                                         program_config, sizes, token_flops)


def toy_gather(out, ops):
    return {"flops": 0.0,
            "bytes": float(out.bytes + sum(o.bytes for o in ops))}


KERNEL_COSTS = {"toy_gather": toy_gather}
'''

# run in a checkout, with its own soibench: serve the new family's cell,
# and reduce a trace event of its kernel
IN_CHECKOUT = '''
import json, sys
sys.path[:0] = [sys.argv[1] + "/bench", sys.argv[2]]
from repro.kernels import ops as kops
from soibench import cell_run, families, profile, spec


class Chip:
    platform = device_kind = "cpu"

    def memory_stats(self):
        return {}


class Compiles:
    names = []
    n = 0


peak = json.loads(sys.argv[3])
cell = spec.load_cell("toy-chat-short")
kops.FORCE_MODE = "interpret"
out = cell_run.run_cell(cell, Chip(), peak, seed=3, seconds=2.0, trace=False,
                        t_start=0.0, compiles=Compiles(), log=lambda *a: None)
table = families.kernel_costs(cell.family)
kernel = profile.kernel_of("toy_gather.3", table)
# the shared table, which the other families' cells read, has no such kernel
other = profile.kernel_of("toy_gather.3")
ev = profile.Events(
    ops=[["toy_gather.3", 0, 100, kernel, [[[8, 128], 2], [[[8, 128], 2]]],
          False]],
    programs=[["jit__gen(1)", 0, 100]], spans=[])
calls, secs, least = profile.reduce(ev, 0, 100, [(0, 100)], peak,
                                    table).kernels[kernel]
print(json.dumps({"family": cell.family.__name__, "correct": out["correct"],
                  "metrics": sorted(out["metrics"]), "kernel": kernel,
                  "other": other,
                  "calls": calls, "least_s": least}))
'''


def _digests(root) -> dict:
    return {p.relative_to(root).as_posix():
            hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


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


def test_a_family_added_as_files_runs(tmp_path):
    """A configuration of a new model family: the family module, its
    configuration file, a traffic mix, a limit, a cell and a kernel cost,
    all new files in a checkout whose existing files stay as they are.
    The checkout's own harness loads the cell, serves it at smoke sizes,
    checks it against the family's reference, and finds the kernel."""
    root = tmp_path / "checkout"
    shutil.copytree(smoke.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = _digests(root / "bench")
    (root / "bench/soibench/families/toy_dense.py").write_text(NEW_FAMILY)
    cfg = dict(smoke.config("qwen3-1.7b-soi-pp"), family="toy_dense")
    (root / "bench/configs/toy-dense.json").write_text(json.dumps(cfg))
    mix = smoke.smoke_mix("chat-closed")
    mix["clients"], mix["output"] = 4, {"lo": 4, "hi": 8}
    (root / "bench/traffic/toy-short.json").write_text(json.dumps(mix))
    (root / "bench/limits/toy-chat-short.json").write_text(
        json.dumps({"max_gap": test_harness.SMOKE_LIMIT}))
    bench = json.loads((smoke.BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="toy-dense",
                                 file="bench/configs/toy-dense.json"))
    bench["workloads"].append({"name": "toy-chat-short",
                               "config": "toy-dense", "traffic": "toy-short",
                               "chips": 1, "why": "a family added as files"})
    for m in bench["end_to_end"]:
        if m["name"] in ("out_tok_s", "itl_p50_ms"):
            m["workloads"] = m.get("workloads", []) + ["toy-chat-short"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    p = subprocess.run(
        [sys.executable, "-c", IN_CHECKOUT, str(root),
         str(smoke.BENCH.parent / "src"), json.dumps(test_harness.PEAK)],
        cwd=root, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["family"] == "soibench.families.toy_dense"
    assert got["correct"] is True
    assert {"out_tok_s", "itl_p50_ms", "setup_s"} <= set(got["metrics"])
    assert got["kernel"] == "toy_gather" and got["other"] is None
    assert got["calls"] == 1 and got["least_s"] > 0
    after = _digests(root / "bench")
    assert {k: after[k] for k in before} == before


def test_a_config_without_its_family_module_is_refused(tmp_path):
    root = checkout(tmp_path)
    path = root / "bench/configs/qwen3-1.7b-soi-pp-b.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    family="no_such_family")))
    with pytest.raises(spec.SpecError, match="no_such_family"):
        spec.load_cell("qwen3-b-chat-short", root=root)
