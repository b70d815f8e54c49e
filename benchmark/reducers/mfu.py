"""Model FLOP/s utilisation in %: the window's items a second times
``costs/<costs>.py::train_flops_per_item`` over the chips' bf16 peak."""

from benchmark import common
from benchmark.costs import peaks


def reduce(args, rctx):
    if rctx["platform"] != "tpu":
        return None  # a rehearsal's number is never a device metric
    costs = common.load_module("costs", args["costs"])
    per_item = costs.train_flops_per_item(rctx["config"], rctx["traffic"])
    rate = rctx["result"]["quantities"]["items_per_s"]
    peak = peaks.peak(rctx["device_kind"])["bf16_flops"] * rctx["cell"]["chips"]
    return 100.0 * rate * per_item / peak
