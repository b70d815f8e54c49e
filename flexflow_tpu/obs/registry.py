"""Run registry: box-state fingerprint + append-only run index.

The PIPELINE_OVERHEAD.md round-6 incident was a ~1.5x box-state drift
that silently invalidated every recorded number — nothing tied a run
to the machine state that produced it.  Two fixes live here:

- :func:`box_fingerprint` — git sha, jax/jaxlib versions, backend
  platform, device count, host — stamped onto every ``run_start``
  (``Telemetry.__init__``), so any two runs can be checked for
  same-box before their numbers are compared (``obs compare``).
- An **append-only index** (``runs.jsonl`` next to the run logs, one
  line per completed run: id, path, exit, fingerprint, headline
  summary numbers) appended by ``Telemetry.close`` — ``python -m
  flexflow_tpu.obs history`` reads it without opening every log.

The index name ``runs.jsonl`` deliberately does NOT match the
``run-*.jsonl`` per-run glob (no hyphen), so calibration's
latest-run selection never mistakes the index for a log.
"""

from __future__ import annotations

import functools
import json
import logging
import os
import socket
import subprocess
import time
from typing import Any, Dict, List, Optional

_log = logging.getLogger("ff.obs")

#: Index file name under the telemetry dir (append-only JSONL).
INDEX_NAME = "runs.jsonl"

#: Summary keys copied onto index rows (the compare headline metrics;
#: the serving block makes `obs history` answer "how did serving runs
#: trend" without opening each log — SERVING.md).
_INDEX_SUMMARY_KEYS = (
    "steps", "fences_per_step", "programs_per_step",
    "step_ms_p50", "step_ms_p95", "input_wait_ms_p50",
    "queue_wait_ms_p50", "queue_wait_ms_p99", "slo_attainment",
    "request_sheds", "request_preempts", "engine_restarts",
    "fleet_replicas", "fleet_dead_replicas",
)


@functools.lru_cache(maxsize=1)
def box_fingerprint() -> Dict[str, Any]:
    """The box-state identity of this process, cached per process
    (the git subprocess runs once, not once per Telemetry).  Every
    field degrades to ``None`` rather than raising — a fingerprint
    must never break the run it describes."""
    fp: Dict[str, Any] = {
        "git_sha": None, "jax": None, "jaxlib": None,
        "platform": None, "devices": None,
        "host": socket.gethostname(),
    }
    try:
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        )))
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=repo, capture_output=True, text=True, timeout=10,
        )
        if out.returncode == 0:
            fp["git_sha"] = out.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        pass
    try:
        import jax

        fp["jax"] = jax.__version__
        try:
            import jaxlib

            fp["jaxlib"] = jaxlib.__version__
        except Exception:
            pass
        # Backend identity: platform + device count.  This initializes
        # the backend if nothing has yet — its caller (Telemetry)
        # runs on a backend it already holds, so this never adds a
        # first touch of the device the run itself would not make.
        # The device's own word, not ``jax.default_backend()``: tests
        # that steer backend-sniffing code replace that function, and
        # this cache would keep their answer for the process's life.
        fp["platform"] = jax.devices()[0].platform
        fp["devices"] = jax.device_count()
        # World identity: which process of how many (1/1 single-host).
        # ``obs compare`` surfaces any delta via fingerprint_diff —
        # a world-size change between runs IS a box-state change
        # (elastic resize, RESILIENCE.md).
        fp["process_id"] = jax.process_index()
        fp["process_count"] = jax.process_count()
    except Exception as e:
        _log.warning("box_fingerprint: backend identity unavailable (%s)",
                     e)
    return fp


def fingerprint_diff(a: Dict[str, Any], b: Dict[str, Any]) -> List[str]:
    """Fields that differ between two fingerprints, as readable
    ``key: a -> b`` strings (empty = same box state)."""
    out = []
    for k in sorted(set(a) | set(b)):
        if a.get(k) != b.get(k):
            out.append(f"{k}: {a.get(k)!r} -> {b.get(k)!r}")
    return out


def index_path(directory: str) -> str:
    return os.path.join(directory, INDEX_NAME)


def append_run(directory: str, record: Dict[str, Any]) -> None:
    """Append one completed run's row to the index.  Append-only by
    contract (history is evidence); failures log and never propagate
    into the run being closed."""
    try:
        with open(index_path(directory), "a") as f:
            f.write(json.dumps(record, default=str) + "\n")
    except OSError as e:
        _log.warning("run registry: cannot append to %s: %s",
                     index_path(directory), e)


def index_record(tel) -> Dict[str, Any]:
    """Build the index row for a closing ``Telemetry`` (summary
    headline numbers + fingerprint + exit)."""
    summary = tel.step_summary()
    rec: Dict[str, Any] = {
        "ts": round(time.time(), 3),
        "run_id": tel.run_id,
        "path": os.path.basename(tel.path) if tel.path else None,
        "exit": getattr(tel, "exit_status", None),
        "fingerprint": getattr(tel, "fingerprint", None),
        "meta": getattr(tel, "meta", None) or None,
    }
    for k in _INDEX_SUMMARY_KEYS:
        if k in summary:
            rec[k] = summary[k]
    return rec


def history(directory: str) -> List[Dict[str, Any]]:
    """All index rows under ``directory``, oldest first; tolerant of a
    torn tail line exactly like the run-log reader."""
    rows: List[Dict[str, Any]] = []
    try:
        with open(index_path(directory)) as f:
            lines = f.read().splitlines()
    except OSError:
        return rows
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict):
            rows.append(rec)
    return rows


def format_history(rows: List[Dict[str, Any]]) -> str:
    """The ``obs history`` table."""
    if not rows:
        return "run registry: no runs recorded"
    hdr = (f"{'run_id':<26} {'exit':<20} {'steps':>6} {'p50 ms':>8} "
           f"{'fence/st':>8} {'qw p99':>8} {'slo':>6} {'git':>8}  app")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        fp = r.get("fingerprint") or {}
        meta = r.get("meta") or {}
        p50 = r.get("step_ms_p50")
        fps = r.get("fences_per_step")
        qw99 = r.get("queue_wait_ms_p99")
        slo = r.get("slo_attainment")
        lines.append(
            f"{str(r.get('run_id')):<26} {str(r.get('exit')):<20} "
            f"{str(r.get('steps', '')):>6} "
            f"{('' if p50 is None else format(p50, '.3f')):>8} "
            f"{('' if fps is None else format(fps, '.2f')):>8} "
            f"{('' if qw99 is None else format(qw99, '.2f')):>8} "
            f"{('' if slo is None else format(slo, '.3f')):>6} "
            f"{str(fp.get('git_sha') or ''):>8}  "
            f"{meta.get('app', '')}"
        )
    return "\n".join(lines)
