"""A quantity the cell's runner already took over the window (args:
``name``), reported as a per-layer metric: a statistic that stands
beside an end-to-end metric without a bound of its own."""


def reduce(args, rctx):
    return rctx["result"]["quantities"].get(args["name"])
