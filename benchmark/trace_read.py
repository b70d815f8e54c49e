"""From the profiler's ``.xplane.pb`` to device operations, busy time and
idle gaps.  Read with nothing but jax (``jax.profiler.ProfileData``).

A device operation is an event of the line ``XLA Ops`` on a plane
``/device:TPU:<n>``.  On the CPU (a rehearsal, never a device number)
the host's XLA events, those that carry an ``hlo_op`` stat, stand in as
device 0 so that the same reduction runs end to end.  The harness's own
host spans are the ``bench/...`` events (``jax.profiler.TraceAnnotation``).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, NamedTuple, Tuple

_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


class Op(NamedTuple):
    name: str          # on the TPU the event's whole HLO text, shapes and all
    start_ns: float
    dur_ns: float


class Trace(NamedTuple):
    devices: Dict[int, List[Op]]      # device index -> its operations
    host_spans: List[Op]              # bench/... annotations
    path: str


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List[Op]] = {}
    host_spans: List[Op] = []
    cpu_ops: List[Op] = []
    for plane in data.planes:
        m = _TPU_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                if line.name != OPS_LINE:
                    continue
                ops = devices.setdefault(int(m.group(1)), [])
                for e in line.events:
                    ops.append(Op(e.name, e.start_ns, e.duration_ns))
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith("bench/"):
                        host_spans.append(Op(e.name, e.start_ns, e.duration_ns))
                    elif e.duration_ns > 0 and "hlo_op" in dict(e.stats):
                        cpu_ops.append(Op(e.name, e.start_ns, e.duration_ns))
    if not devices and cpu_ops:
        devices[0] = cpu_ops
    for ops in devices.values():
        ops.sort(key=lambda o: o.start_ns)
    return Trace(devices, host_spans, path)


def busy_intervals(ops: List[Op]) -> List[Tuple[float, float]]:
    """Union of the operations' intervals, sorted, in ns."""
    out: List[List[float]] = []
    for o in sorted(ops, key=lambda o: o.start_ns):
        end = o.start_ns + o.dur_ns
        if out and o.start_ns <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([o.start_ns, end])
    return [(a, b) for a, b in out]


def busy_seconds(ops: List[Op]) -> float:
    return sum(b - a for a, b in busy_intervals(ops)) * 1e-9


def window_ns(trace: Trace) -> Tuple[float, float]:
    """The traced window: the ``bench/window`` span where the harness
    wrote one, else first to last device operation."""
    for s in trace.host_spans:
        if s.name == "bench/window":
            return s.start_ns, s.start_ns + s.dur_ns
    starts = [o.start_ns for ops in trace.devices.values() for o in ops]
    ends = [o.start_ns + o.dur_ns for ops in trace.devices.values() for o in ops]
    return min(starts), max(ends)


def clip(ops: List[Op], lo: float, hi: float) -> List[Op]:
    """Operations cut to ``[lo, hi]``."""
    out = []
    for o in ops:
        a, b = max(o.start_ns, lo), min(o.start_ns + o.dur_ns, hi)
        if b > a:
            out.append(Op(o.name, a, b - a))
    return out


def op_seconds(ops: List[Op], patterns: List[str]) -> Tuple[float, int]:
    """Summed duration and count of the operations whose name matches
    any pattern (regular expressions, searched)."""
    rx = [re.compile(p) for p in patterns]
    total, n = 0.0, 0
    for o in ops:
        if any(r.search(o.name) for r in rx):
            total += o.dur_ns
            n += 1
    return total * 1e-9, n


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
#: Operations that only hold others (their children are events too).
_CONTAINERS = ("while", "conditional", "call")


def label(name: str) -> Tuple[str, str]:
    """``(opcode, label)`` of a device event.  The TPU's events are
    named by their HLO text, ``%name.12 = type opcode(operands)``; the
    label is ``opcode`` or ``opcode:name`` with every number dropped, so
    that the calls of one kernel, fusion family or layer add up."""
    head, sep, rest = name.partition(" = ")
    short = re.sub(r"\d+", "N", re.sub(r"\.\d+$", "", head.lstrip("%")))
    if not sep:
        return short, short
    m = _OPCODE.search(" " + rest)
    op = m.group(1) if m else short
    return op, (op if short.startswith(op) else f"{op}:{short}")


def top_ops(ops: List[Op], k: int = 10) -> List[List[Any]]:
    """``[[label, seconds], ...]``: the operations that took most time,
    summed by ``label``; containers (``while`` and the like) are left
    out, since their children are counted."""
    acc: Dict[str, float] = {}
    for o in ops:
        op, key = label(o.name)
        if op in _CONTAINERS:
            continue
        acc[key] = acc.get(key, 0.0) + o.dur_ns * 1e-9
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(ops: List[Op], spans: List[Op], lo: float, hi: float, k: int = 10) -> List[List[Any]]:
    """``[[what the host was doing, seconds], ...]``: the device's idle
    time inside ``[lo, hi]``, each gap given to the innermost harness
    span that covers its middle, summed by span name."""
    gaps = []
    cur = lo
    for a, b in busy_intervals(clip(ops, lo, hi)):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    inner = sorted((s for s in spans if s.name != "bench/window"), key=lambda s: s.dur_ns)
    acc: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        owner = next((s.name for s in inner if s.start_ns <= mid <= s.start_ns + s.dur_ns),
                     "bench/between_calls")
        acc[owner] = acc.get(owner, 0.0) + (b - a) * 1e-9
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def describe(path: str, limit: int = 12) -> str:
    """Planes, lines and the heaviest event names with their stats: what
    to look at by hand before a pattern goes into a metric file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = [f"trace {path}"]
    for plane in data.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            acc: Dict[str, List[float]] = {}
            stat: Dict[str, Dict[str, Any]] = {}
            for e in evs:
                a = acc.setdefault(e.name, [0, 0.0])
                a[0] += 1
                a[1] += e.duration_ns
                if e.name not in stat:
                    stat[e.name] = dict(e.stats)
            out.append(f"  LINE {line.name}: {len(evs)} events, {len(acc)} names")
            for name, (cnt, dur) in sorted(acc.items(), key=lambda kv: -kv[1][1])[:limit]:
                s = {k: (v if not isinstance(v, str) else v[:160]) for k, v in stat[name].items()}
                out.append(f"    {dur * 1e-6:12.3f} ms  x{cnt:<6d} {name[:100]}  {s}")
            if line.name == OPS_LINE:
                # Every custom call (a Pallas kernel is one), by label,
                # with one full name each: the patterns are written from these.
                calls: Dict[str, List[Any]] = {}
                for name, (cnt, dur) in acc.items():
                    op, key = label(name)
                    if op == "custom-call":
                        c = calls.setdefault(key, [0, 0.0, name])
                        c[0] += cnt
                        c[1] += dur
                for key, (cnt, dur, name) in sorted(calls.items(), key=lambda kv: -kv[1][1]):
                    out.append(f"    CUSTOM-CALL {dur * 1e-6:10.3f} ms x{cnt:<6d} {key}  e.g. {name[:260]}")
    return "\n".join(out)
