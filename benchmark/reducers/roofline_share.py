"""A kernel family's share of its roofline, in %: the least time the
chip could take for the calls the trace shows (the larger of operations
over peak FLOP/s and bytes over peak bytes/s, from
``costs/<costs>.py::kernel_cost``) over the device time those calls took.

args: ``costs``; ``kernels``: ``[{"cost": kind, "patterns": [...]}]``;
``device`` (default 0).  A kernel the trace does not show contributes
nothing; none shown returns nothing.
"""

from benchmark import common, trace_read
from benchmark.costs import peaks


def reduce(args, rctx):
    if rctx["platform"] != "tpu":
        return None  # a rehearsal's number is never a device metric
    trace = rctx["trace"]
    ops = trace.devices.get(int(args.get("device", 0)))
    if not ops:
        return None
    lo, hi = rctx["window_ns"]
    ops = trace_read.clip(ops, lo, hi)
    peak = peaks.peak(rctx["device_kind"])
    costs = common.load_module("costs", args["costs"])
    least = took = 0.0
    for k in args["kernels"]:
        secs, n = trace_read.op_seconds(ops, k["patterns"])
        if not n:
            continue
        flops, byts = costs.kernel_cost(k["cost"], rctx, n)
        t_f, t_b = flops / peak["bf16_flops"], byts / peak["hbm_bytes_per_s"]
        common.say(f"[roofline] {k['cost']}: {n} calls, {secs * 1e3:.3f} ms on the device, "
                   f"least {max(t_f, t_b) * 1e3:.3f} ms "
                   f"({'compute' if t_f > t_b else 'memory'} bound: {flops:.4g} flops, {byts:.4g} bytes)")
        least += max(t_f, t_b)
        took += secs
    if took <= 0:
        return None
    return 100.0 * least / took
