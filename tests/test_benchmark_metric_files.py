"""The benchmark's guard on its metric files, as a tier-1 test.

A traced benchmark line that lacks a per-layer metric ``BENCHMARK.json``
lists for the cell is refused, and a metric goes missing when the
kernel, scope, span or event its file names is renamed in the program
(PR 25 renamed the kernels; PR 26 was refused for it, PERF.md §6).
``benchmark/tests/test_metric_files.py`` holds every metric file to the
catalogs of ``flexflow_tpu/obs/events.py`` and to the ops of its cells'
graphs, but the driver's test run collects ``tests/`` only.  This file
runs those cases here, so the rename fails in the PR that makes it.
Imports only: the cases and what they check stay the benchmark's.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark", "tests", "test_metric_files.py")
_spec = importlib.util.spec_from_file_location("benchmark_tests_metric_files", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update(
    {name: fn for name, fn in vars(_cases).items() if name.startswith("test_")}
)
