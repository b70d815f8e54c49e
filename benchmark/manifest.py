"""``BENCHMARK.json``, read and checked before any run.

What a run relies on is held here: the character sets of names and
units, that a cell's configuration and every metric's cells exist, that
a per-layer metric moves an end-to-end metric which each of its cells
reports.  A manifest outside them is an error with no result line.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _fail(msg: str):
    raise SystemExit(f"BENCHMARK.json: {msg}")


def _name(what: str, value: Any) -> None:
    if not isinstance(value, str) or not NAME.match(value):
        _fail(f"{what} {value!r} is not a name (letters, digits, '_', '.', '-'; at most 64)")


def check(bench: Dict[str, Any]) -> Dict[str, Any]:
    configs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        _name("config", c["name"])
        for k in c["reduced"]:
            _name(f"reduced key of {c['name']}", k)
    for w in bench["workloads"]:
        for k in ("name", "config", "traffic"):
            _name(f"workload {k}", w[k])
        if w["config"] not in configs:
            _fail(f"workload {w['name']} names no configuration: {w['config']!r}")
        if w["chips"] not in (1, 4):
            _fail(f"workload {w['name']} asks for {w['chips']!r} chips")
    reports = {}  # end-to-end metric -> the cells that report it
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            _name(f"{kind} metric", m["name"])
            if not isinstance(m["unit"], str) or not UNIT.match(m["unit"]):
                _fail(f"metric {m['name']}: {m['unit']!r} is not a unit")
            if m["better"] not in ("lower", "higher") or m["source"] not in SOURCES:
                _fail(f"metric {m['name']}: better {m['better']!r}, source {m['source']!r}")
            listed = set(m.get("workloads", cells))
            if not listed <= cells:
                _fail(f"metric {m['name']} lists cells that do not exist: {sorted(listed - cells)}")
            if kind == "end_to_end":
                reports[m["name"]] = listed
            elif not listed <= reports.get(m["moves"], set()):
                _fail(f"metric {m['name']} moves {m['moves']!r}, which not all of its cells report")
    return bench


def entry(rows, name: str, what: str) -> Dict[str, Any]:
    """The entry called ``name`` of one of the manifest's lists."""
    for r in rows:
        if r["name"] == name:
            return r
    _fail(f"no {what} {name!r}")


def load(root: str) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return check(json.load(f))
