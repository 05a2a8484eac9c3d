"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the weights on the chip from ``--seed``, builds the engine
for the cell's configuration and traffic mix, compiles (or loads from the
compile cache in ``.jax_cache``) and warms every program shape the window
uses, and fills the slots (closed loops) or the prefix cache (open loops).
The window then serves the mix for ``--seconds`` on the wall clock. After
it, the device's peak memory is read, the engine's state is freed, and a
sample of the finished requests is checked against the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window), ``device``, with ``--trace 1`` a ``breakdown``, and last
``check``, the number compared with its limit. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]
# the compile cache lives in the checkout, at a fixed path: the path is part
# of the cache's key, and nothing is shared with another checkout
CACHE = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    pass


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chip(want: int, peaks: dict):
    """The first device, if JAX finds at least ``want`` TPU chips whose
    kind the peak table knows."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < want:
        raise NoChip(f"needs {want} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s) ({devs[0].device_kind})")
    if devs[0].device_kind not in peaks["devices"]:
        raise NoChip(f"device kind {devs[0].device_kind!r} is not in "
                     f"bench/peaks.json")
    return devs[0]


class CompileCounter(logging.Handler):
    """Programs JAX compiled or loaded from its cache, by name, from the
    message it logs for each one."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = []
        log_ = logging.getLogger("jax._src.dispatch")
        log_.setLevel(logging.DEBUG)
        log_.propagate = False
        log_.addHandler(self)

    @property
    def n(self) -> int:
        return len(self.names)

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Finished XLA compilation of "):
            self.names.append(msg.split(" of ", 1)[1].rsplit(" in ", 1)[0])


def use_cache() -> None:
    """Keep every compiled program, however small, in ``.jax_cache``: only
    the first run of a cell in a checkout compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse_args(argv)
    use_cache()
    from soibench import cell_run
    with open(HERE / "peaks.json") as f:
        peaks = json.load(f)
    try:
        from soibench import spec
        cell = spec.load_cell(args.workload)
        device = chip(cell.chips, peaks)
    except (NoChip, spec.SpecError) as e:
        log(f"bench/run.py: {e}")
        return 2
    compiles = CompileCounter()
    result = cell_run.run_cell(cell, device, peaks["devices"][
        device.device_kind], seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, compiles=compiles,
        log=log)
    gc.collect()
    for name, c in result["check"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
