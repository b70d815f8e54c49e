"""A statistic of one field of one kind of telemetry event in the window.

args: ``event``; ``field``; ``per`` (optional field to divide by, event
by event); ``stat`` (``p50``, ``p95``, ``mean``, ``sum``,
``sum_over_window_pct``: the sum over the window's wall time, in %);
``scale`` (optional multiplier, e.g. 1000 for ms).
"""

import numpy as np


def reduce(args, rctx):
    vals = [e[args["field"]] / (e[args["per"]] if "per" in args else 1.0)
            for e in rctx["events"] if e["ev"] == args["event"] and args["field"] in e]
    if not vals:
        return None
    stat = args["stat"]
    if stat == "sum_over_window_pct":
        out = 100.0 * sum(vals) / rctx["result"]["window_s"]
    elif stat == "sum":
        out = float(sum(vals))
    elif stat == "mean":
        out = float(np.mean(vals))
    elif stat.startswith("p"):
        out = float(np.percentile(vals, float(stat[1:])))
    else:
        raise KeyError(stat)
    return out * args.get("scale", 1.0)
