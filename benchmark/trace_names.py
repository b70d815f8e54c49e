"""What PR 25's metrics read from a trace beside ``trace_read``: the
program's own ``ff/`` host spans (``flexflow_tpu/obs/events.py``
``SPAN_CATALOG``) with the instants each is the innermost of, and the
scope every device operation ran under (``jax.named_scope``).

``trace_read.load`` keeps only the harness's ``bench/`` spans, so this
opens the ``.xplane.pb`` itself (once a process, however many metrics
read it).  A program without the spans gives an empty list and the
metrics that read them find nothing, which a traced run reports by
leaving them out.

The scope is not in an event: on the TPU a device event's name is its
HLO text without ``metadata={...}`` and its own stats are three numbers
(read on the chip, PR 25).  The ``op_name`` the compiler kept is the
``tf_op`` stat of the event's *metadata* record
(``jit(train_step)/transpose(jvp(ff_loss))/softmax/mul:``), which
``jax.profiler.ProfileData`` does not surface.  ``op_scopes`` reads
just those records from the file's wire format (``XSpace`` of
``tsl/profiler/protobuf/xplane.proto``: plane 1, name 2, event metadata
4, stat metadata 5; event metadata: name 2, stats 5; stat: metadata id
1, string 5, reference 7).
"""

from __future__ import annotations

import bisect
import functools
import re
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark.trace_read import Op

SPAN_PREFIX = "ff/"


@functools.lru_cache(maxsize=2)
def host_spans(path: str) -> Tuple[Op, ...]:
    """Every ``ff/`` event of the host planes."""
    from jax.profiler import ProfileData

    out: List[Op] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append(Op(e.name, e.start_ns, e.duration_ns))
    return tuple(out)


def innermost(spans) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces, sorted: at each instant
    the span that started last and has not ended (spans of one thread
    nest, so that is the innermost)."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []  # (end, name)
    cur = 0.0

    def upto(t: float) -> None:
        nonlocal cur
        if stack and t > cur:
            pieces.append((cur, t, stack[-1][1]))
        cur = max(cur, t)

    for s in sorted(spans, key=lambda s: (s.start_ns, -s.dur_ns)):
        while stack and stack[-1][0] <= s.start_ns:
            upto(stack[-1][0])
            stack.pop()
        upto(s.start_ns)
        stack.append((s.start_ns + s.dur_ns, s.name))
    while stack:
        upto(stack[-1][0])
        stack.pop()
    return pieces


def owner(pieces, t: float) -> Optional[str]:
    """The innermost span over instant ``t``, or nothing."""
    i = bisect.bisect_right(pieces, (t, float("inf"), "")) - 1
    return pieces[i][2] if i >= 0 and t <= pieces[i][1] else None


# -- the scope of a device operation ---------------------------------------------

SCOPE_STAT = "tf_op"


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a ``memoryview`` for a length-delimited or fixed field."""
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


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


@functools.lru_cache(maxsize=2)
def op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """``{plane name: {event name: scope path}}`` for every event
    metadata record that carries a ``tf_op`` stat; the path as the
    compiler kept it, without the trailing ``:``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, records, stat_names = "", [], {}
        for num, value in _fields(plane):
            if num == 2:
                name = _text(value)
            elif num in (4, 5):  # a map entry: its value, a record, is field 2
                record = list(_fields(dict(_fields(value))[2]))
                if num == 4:
                    records.append(record)
                else:
                    stat_names[dict(record).get(1, 0)] = _text(dict(record).get(2, b""))
        scopes = {}
        for record in records:
            for stat in (dict(_fields(v)) for n, v in record if n == 5):
                if stat_names.get(stat.get(1)) == SCOPE_STAT:
                    scope = _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
                    if scope:
                        scopes[_text(dict(record).get(2, b""))] = scope.rstrip(":")
        if scopes:
            out[name] = scopes
    return out


def under(scope_path: str, names) -> bool:
    """Whether any of ``names`` is a component of the scope path: under
    whatever autodiff wrapped round it (``transpose(jvp(ff_loss))``), in
    any of the paths of a merged operation (``a/b;a/c``), or as the
    parameter the compiler names on a copy it made of one
    (``params['embeddings']['tables']``)."""
    parts = set(re.split(r"[/();\[\]']", scope_path))
    return any(n in parts for n in names)
