"""The benchmark's set-up metrics against the program's ``program_build``
records, as a tier-1 test.

``benchmark/reducers/program_builds.py`` reads ``setup_trace_lower_s``,
``setup_compile_s``, ``setup_cache_misses`` and ``setup_cost_probe_s``
from what ``runtime/telemetry.py::BuildLog`` folds out of jax's own
spans.  ``benchmark/tests/test_program_builds.py`` holds the reducer to a
recorded stream and runs a tiny cell whose traced line must carry the
four, but the driver's test run collects ``tests/`` only.  This file runs
those cases here: a PR that renames a field of the record, or stops
writing it, fails in its own run.  Imports only: the cases and what they
check stay the benchmark's.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark", "tests", "test_program_builds.py")
_spec = importlib.util.spec_from_file_location("benchmark_tests_program_builds", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update(
    {name: fn for name, fn in vars(_cases).items() if name.startswith("test_") or name == "copy"}
)
