"""The chip benchmark of the SOI serving engine.

Everything here is the yardstick: traffic generation, the admission loop on
the wall clock, the plain reference that decides ``correct``, the reduction
from profiler traces to metrics, the peak table and the kernel cost
formulas. From the program it takes only the system under test
(``repro.engine.SOIEngine`` and the model configs it serves) and the names
its compiled programs and kernels carry in a trace.

A cell (``BENCHMARK.json`` ``workloads``) is one configuration file under
``bench/configs`` under one traffic file under ``bench/traffic``; the
configuration names its model family, a module of
``bench/soibench/families``; its correctness limit is
``bench/limits/<cell>.json`` and each per-layer metric is a reader
``bench/metrics/<metric>.py``. All of them are found by name.
"""
