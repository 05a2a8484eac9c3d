"""The benchmark's tests run on the CPU at the program's smoke sizes."""

import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[1] / "src")]
