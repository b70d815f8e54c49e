"""A throw-away copy of the benchmark with tiny cells dropped into it.

The copy is the proof that a cell, a configuration, a mix, a per-layer
metric and a reducer are files plus entries: ``benchmark/`` is copied as
it is, the tiny files of ``tests/data`` are dropped beside the real ones,
and the real ``BENCHMARK.json`` with their entries added is written next
to the copy.  No file of the harness is edited.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from typing import Any, Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data")

#: Which end-to-end metric each tiny cell reports, beside ``setup_s``.
_REPORTS = {"tiny.gpt2.train": "train_tokens_per_s", "tiny.gpt2.serve": "serve_tokens_per_s",
            "tiny.dlrm.train": "train_samples_per_s", "tiny.dlrm.c4": "train_samples_per_s"}

_CELLS = [
    {"name": "tiny.gpt2.train", "config": "tiny-gpt2", "traffic": "tiny-train", "chips": 1, "why": "rehearsal"},
    {"name": "tiny.gpt2.serve", "config": "tiny-gpt2", "traffic": "tiny-closed", "chips": 1, "why": "rehearsal"},
    {"name": "tiny.dlrm.train", "config": "tiny-dlrm", "traffic": "tiny-dlrm", "chips": 1, "why": "rehearsal"},
    {"name": "tiny.dlrm.c4", "config": "tiny-dlrm", "traffic": "tiny-dlrm-c4", "chips": 4, "why": "rehearsal"},
]

_SUBDIR = {"config": "configs", "traffic": "traffic", "metric": "metrics", "reducer": "reducers"}


def make(dest: str) -> str:
    """Build the copy under ``dest``; returns its root.  What is added:
    two configurations, four mixes, four cells, one per-layer metric and
    the reducer it names, as files of their own plus entries."""
    root = os.path.join(dest, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "*.pyc"))
    for f in sorted(os.listdir(DATA)):
        kind, rest = f.split(".", 1)
        if kind in _SUBDIR:
            target = os.path.join(root, "benchmark", _SUBDIR[kind], rest)
            assert not os.path.exists(target), target
            shutil.copy(os.path.join(DATA, f), target)
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    bench["configs"] += [
        {"name": n, "source": "rehearsal", "file": f"benchmark/configs/{n}.json", "reduced": [],
         "why": "rehearsal"} for n in ("tiny-gpt2", "tiny-dlrm")]
    bench["workloads"] += _CELLS
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] += [c for c, e2e in _REPORTS.items() if e2e == m["name"]]
    bench["per_layer"].append({"name": "tiny_step_ms", "unit": "ms", "better": "lower",
                               "source": "program_span", "layer": "Executor / Trainer",
                               "moves": "train_tokens_per_s", "workloads": ["tiny.gpt2.train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root


def run_cell(root: str, cell: str, seed: int = 7, seconds: float = 1.0, trace: int = 0,
             devices: int = 1, env: Optional[Dict[str, str]] = None,
             platform: Optional[str] = "cpu") -> subprocess.CompletedProcess:
    """``run.py`` of the copy in a child process, the repository on its
    path for the program under test."""
    e = dict(os.environ)
    e.pop("JAX_PLATFORMS", None)
    if platform:
        e["JAX_PLATFORMS"] = platform
    e["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    e["PYTHONPATH"] = REPO
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", cell,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=root, env=e, capture_output=True, text=True, timeout=600)


def last_line(proc: subprocess.CompletedProcess) -> Dict[str, Any]:
    return json.loads(proc.stdout.strip().splitlines()[-1])
