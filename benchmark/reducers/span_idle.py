"""Device idle time owned by the program's host spans, as a share of
the traced window in %.

A gap is a stretch of the window in which no operation runs on device
``device`` (default 0).  It belongs to the innermost ``ff/`` span over
its middle (``benchmark/trace_names.py``); args ``spans`` lists the
names whose gaps are summed.  A gap under no ``ff/`` span belongs to
none.  A trace without such spans (a program from before them) gives
nothing to read.
"""

from benchmark import trace_names, trace_read


def gaps(ops, lo, hi):
    """The idle stretches of ``[lo, hi]``, in ns."""
    out, cur = [], lo
    for a, b in trace_read.busy_intervals(trace_read.clip(ops, lo, hi)):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


def idle_by_span(ops, spans, lo, hi):
    """``{span name or None: idle ns}`` over ``[lo, hi]``."""
    pieces = trace_names.innermost(spans)
    acc = {}
    for a, b in gaps(ops, lo, hi):
        name = trace_names.owner(pieces, 0.5 * (a + b))
        acc[name] = acc.get(name, 0.0) + (b - a)
    return acc


def reduce(args, rctx):
    if rctx["platform"] != "tpu":
        return None  # a rehearsal's number is never a device metric
    trace = rctx["trace"]
    ops = trace.devices.get(int(args.get("device", 0)))
    spans = trace_names.host_spans(trace.path)
    if not ops or not spans:
        return None
    lo, hi = rctx["window_ns"]
    acc = idle_by_span(ops, spans, lo, hi)
    return 100.0 * sum(acc.get(n, 0.0) for n in args["spans"]) / (hi - lo)
