"""Device time of the operations whose name matches
``patterns``, on device ``device`` (default 0), as a share of the traced
window in %."""

from benchmark import trace_read


def reduce(args, rctx):
    if rctx["platform"] != "tpu":
        return None  # a rehearsal's number is never a device metric
    trace = rctx["trace"]
    ops = trace.devices.get(int(args.get("device", 0)))
    if not ops:
        return None
    lo, hi = rctx["window_ns"]
    secs, n = trace_read.op_seconds(trace_read.clip(ops, lo, hi), args["patterns"])
    if not n:
        return None
    return 100.0 * secs / ((hi - lo) * 1e-9)
