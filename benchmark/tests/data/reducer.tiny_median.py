"""The median of one field of one kind of telemetry event in the window
(args: ``event``, ``field``, ``scale``): a reducer a later PR might add."""


def reduce(args, rctx):
    vals = sorted(e[args["field"]] for e in rctx["events"]
                  if e["ev"] == args["event"] and args["field"] in e)
    return vals[len(vals) // 2] * args.get("scale", 1.0) if vals else None
