"""The mean over the window's events of one kind of one field over
another, event by event (args: ``event``, ``field``, ``over``, optional
``scale``).  Only events that carry both are read, so a program from
before a field existed gives nothing to read and does not fail:
``telemetry_stat``'s ``per`` takes the divisor for granted.
"""


def reduce(args, rctx):
    vals = [e[args["field"]] / e[args["over"]] for e in rctx["events"]
            if e["ev"] == args["event"] and args["field"] in e and e.get(args["over"])]
    if not vals:
        return None
    return args.get("scale", 1.0) * sum(vals) / len(vals)
