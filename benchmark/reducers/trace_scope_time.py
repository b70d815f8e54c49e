"""Device time of the operations that ran under any of the scopes
``scopes`` (``jax.named_scope`` names: ``ff_loss``, ``ff_opt``, an
op's own name), on device ``device`` (default 0), as a share of the
traced window in %.  The scope of an operation is what
``benchmark/trace_names.py::op_scopes`` finds for it; operations that
only hold others (``while`` and the like) are left out, since their
children are counted.  No operation under such a scope (a program from
before the scope existed) gives nothing to read.
"""

from benchmark import trace_names, trace_read


def scope_seconds(ops, scopes, names):
    """``(seconds, count)`` of the operations of ``ops`` under ``names``."""
    wanted = {op for op, path in scopes.items() if trace_names.under(path, names)
              and trace_read.label(op)[0] not in trace_read._CONTAINERS}
    took = [o.dur_ns for o in ops if o.name in wanted]
    return sum(took) * 1e-9, len(took)


def reduce(args, rctx):
    if rctx["platform"] != "tpu":
        return None  # a rehearsal's number is never a device metric
    trace = rctx["trace"]
    device = int(args.get("device", 0))
    ops = trace.devices.get(device)
    scopes = trace_names.op_scopes(trace.path).get(f"/device:TPU:{device}")
    if not ops or not scopes:
        return None
    lo, hi = rctx["window_ns"]
    secs, n = scope_seconds(trace_read.clip(ops, lo, hi), scopes, args["scopes"])
    if not n:
        return None
    return 100.0 * secs / ((hi - lo) * 1e-9)
