"""The benchmark's request-phase reducer against the program's request
fold, as a tier-1 test.

``benchmark/reducers/request_phase.py`` reads ``serve_slot_wait_pct``,
``serve_token_gap_ms.p95`` and ``serve_superstep_gap_ms.p95`` from the
stamps ``Server.run`` writes, by a small fold of its own, so that an edit
of ``flexflow_tpu/obs/spans.py`` cannot move what the benchmark reads
unseen.  ``benchmark/tests/test_request_phase.py`` holds the two to the
same totals on a recorded stream, but the driver's test run collects
``tests/`` only.  This file runs those cases here: a PR that moves a
stamp, or what the program's fold makes of it, fails in its own run.
Imports only: the cases and what they check stay the benchmark's.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark", "tests", "test_request_phase.py")
_spec = importlib.util.spec_from_file_location("benchmark_tests_request_phase", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update(
    {name: fn for name, fn in vars(_cases).items() if name.startswith("test_")}
)
