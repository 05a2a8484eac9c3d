"""``bench/run.py`` prints no result without a chip, or without the
program beside it."""

import os
import shutil
import subprocess
import sys

import smoke

ARGS = ["--workload", "qwen3-chat-closed", "--seed", str(2 ** 31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = _run(smoke.BENCH.parent)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_alone_is_no_program(tmp_path):
    shutil.copytree(smoke.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(smoke.BENCH.parent / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
