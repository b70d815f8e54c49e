"""Device-time attribution from the profiler's ``.xplane.pb``, by the
names of ``obs/events.py`` (OBSERVABILITY.md "Spans, kernels, scopes").

``--trace DIR --telemetry DIR2``: at trace stop the trainer folds
:func:`summarize_trace_dir` into ``run_end`` as ``trace_summary``: device
ms by Pallas kernel (``KERNEL_CATALOG``), by step phase (``SCOPE_CATALOG``),
and idle ms by the ``ff/`` host span over each gap's middle.

What a TPU trace holds (read on the chip, PERF.md PR 25): a device event
is named by its HLO text, so a kernel is ``%ff_flash_fwd.24 = ...
custom-call(``; its scope is the ``tf_op`` stat of its metadata record,
which ``jax.profiler.ProfileData`` (imported in the call, nothing here
needs jax to load) does not surface, so ``_op_scopes`` reads those
records from the file's wire format.  A CPU run has no device plane.
"""

from __future__ import annotations

import bisect
import glob
import logging
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Tuple

from flexflow_tpu.obs.events import KERNEL_CATALOG, SCOPE_CATALOG

_log = logging.getLogger("ff.obs")
_KERNEL = re.compile(r"^%(\w+?)(?:\.\d+)? = ")
_CONTAINER = re.compile(r" (while|conditional|call)\(")
_DEVICE = "/device:TPU:0"


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """``(field number, int or memoryview)`` of one protobuf message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            size, i = (8, i) if kind == 1 else (4, i) if kind == 5 else _varint(buf, i)
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """``{plane: {event name: scope path}}`` (``XSpace``: plane 1; in it
    name 2, event metadata 4, stat metadata 5, each a map entry with its
    value at 2; a record's name 2, stats 5; a stat's metadata id 1,
    string 5, or reference 7 to a stat metadata's name)."""
    text = lambda v: bytes(v).decode("utf-8", "replace")
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, records, stat_names = "", [], {}
        for num, value in _fields(plane):
            if num == 2:
                name = text(value)
            elif num in (4, 5):
                rec = list(_fields(dict(_fields(value))[2]))
                if num == 4:
                    records.append(rec)
                else:
                    stat_names[dict(rec).get(1, 0)] = text(dict(rec).get(2, b""))
        scopes = {}
        for rec in records:
            for st in (dict(_fields(v)) for n, v in rec if n == 5):
                if stat_names.get(st.get(1)) == "tf_op":
                    scope = text(st[5]) if 5 in st else stat_names.get(st.get(7), "")
                    scopes[text(dict(rec).get(2, b""))] = scope.rstrip(":")
        if scopes:
            out[name] = scopes
    return out


def _innermost(spans) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces: at each instant the span
    that started last and has not ended."""
    pieces, stack, cur = [], [], 0.0
    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])) + [(float("inf"), 0.0, "")]:
        while stack and stack[-1][0] <= a:
            if stack[-1][0] > cur:
                pieces.append((cur, stack[-1][0], stack[-1][1]))
            cur = max(cur, stack.pop()[0])
        if stack and a > cur:
            pieces.append((cur, a, stack[-1][1]))
        cur = max(cur, a)
        stack.append((b, name))
    return pieces


def summarize_trace(path: str) -> Dict[str, Any]:
    """One ``.xplane.pb`` into the ``trace_summary`` block (ms, 3 dp)."""
    from jax.profiler import ProfileData

    ops, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name == _DEVICE and line.name == "XLA Ops":
                ops = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events)
            elif plane.name.startswith("/host:"):
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events if e.name.startswith("ff/")]
    scope_of = _op_scopes(path).get(_DEVICE, {})
    pieces = _innermost(spans)
    kernels, scopes, idle = {}, {}, {}
    busy, cur = 0.0, ops[0][0] if ops else 0.0
    for a, b, name in ops:
        if a > cur:  # a gap: to the innermost span over its middle
            mid = 0.5 * (cur + a)
            i = bisect.bisect_right(pieces, (mid, float("inf"), "")) - 1
            owner = pieces[i][2] if i >= 0 and pieces[i][1] >= mid else "<none>"
            idle[owner] = idle.get(owner, 0.0) + (a - cur) * 1e-6
        busy += max(0.0, b - max(a, cur)) * 1e-6
        cur = max(cur, b)
        if _CONTAINER.search(name):
            continue  # its children are events too
        m = _KERNEL.match(name)
        if m and m.group(1) in KERNEL_CATALOG:
            k = kernels.setdefault(m.group(1), {"device_ms": 0.0, "count": 0})
            k["device_ms"] += (b - a) * 1e-6
            k["count"] += 1
        for part in set(re.split(r"[/();]", scope_of.get(name, ""))) & SCOPE_CATALOG:
            scopes[part] = scopes.get(part, 0.0) + (b - a) * 1e-6
    return {
        "trace_file": path,
        "device_ms_total": round(busy, 3),
        "window_ms": round((cur - ops[0][0]) * 1e-6, 3) if ops else 0.0,
        "kernels": {k: {"device_ms": round(v["device_ms"], 3), "count": v["count"]}
                    for k, v in kernels.items()},
        "scopes": {k: round(v, 3) for k, v in scopes.items()},
        "idle_ms_by_span": {k: round(v, 3) for k, v in idle.items()},
    }


def summarize_trace_dir(log_dir: str) -> Optional[Dict[str, Any]]:
    """The trainer's entry point: the newest trace under the XProf dir,
    summarized, or None with one warning — attribution must never fail
    the run that produced it."""
    try:
        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
        if paths:
            return summarize_trace(max(paths, key=os.path.getmtime))
        _log.warning("trace summary: no .xplane.pb under %s", log_dir)
    except (OSError, RuntimeError, ValueError, KeyError, IndexError) as e:
        _log.warning("trace summary: cannot read the trace under %s: %s", log_dir, e)
    return None
