"""Run analytics: the READ half of the telemetry subsystem.

``runtime/telemetry.py`` writes one JSONL stream per run
(OBSERVABILITY.md); this package is the one way those streams are
read back — the typed reader (``obs.reader``), the cross-run
comparator + paired measurement protocol (``obs.compare``), the
box-fingerprint/run registry (``obs.registry``), the device-time
attribution from a profiler trace (``obs.trace``), and the CLI
(``python -m flexflow_tpu.obs report|compare|history``).

Import discipline: nothing here imports jax at module load (the CLI
must work offline on any box holding the logs); ``registry.
box_fingerprint`` touches jax lazily inside the call.
"""

from flexflow_tpu.obs.events import (
    EVENT_CATALOG,
    EXIT_CLEAN,
    EXIT_PREEMPT,
    EXIT_TRUNCATED,
    exit_exception,
)
from flexflow_tpu.obs.reader import (
    Event,
    RunLog,
    latest_run,
    resolve_run,
    run_files,
)
from flexflow_tpu.obs.compare import (
    DEFAULT_THRESHOLDS,
    CompareResult,
    PairedResult,
    compare_paths,
    compare_runs,
    paired_measure,
)
from flexflow_tpu.obs.registry import (
    append_run,
    box_fingerprint,
    fingerprint_diff,
    history,
    index_record,
)

__all__ = [
    "EVENT_CATALOG", "EXIT_CLEAN", "EXIT_PREEMPT", "EXIT_TRUNCATED",
    "exit_exception",
    "Event", "RunLog", "latest_run", "resolve_run", "run_files",
    "DEFAULT_THRESHOLDS", "CompareResult", "PairedResult",
    "compare_paths", "compare_runs", "paired_measure",
    "append_run", "box_fingerprint", "fingerprint_diff", "history",
    "index_record",
]
