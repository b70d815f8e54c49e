"""Where a served request's time in its slot went, and what a caller and
the loop waited between rounds, from the stamps ``Server.run`` puts on
the events it writes (the run's own clock, ``time.perf_counter`` since
its start, in ms to three decimals):

- ``request_start.t_ms``: the admission; ``request_end.t_ms``: the finish;
- ``prefill.t_ms``: the return of the request's own prefill's fence;
- ``decode_superstep.t0_ms`` and ``.t_ms`` (``spec_verify`` alike): both
  edges of a round, from the engine starting on the program's arguments
  to its fence's return; ``.slots``: the ids of the requests it decoded.

args ``stat``, over the window's events:

- ``slot_wait_pct``: over the requests that start and end clean in the
  window, the time in the slot that was neither the request's own
  prefill nor a round it decoded in (other slots' admissions, installs,
  pack, bookkeeping), over admission to finish, in %;
- ``token_gap_ms``: a request's longest stretch from the end of one
  round it decoded in to the end of the next (its own prefill's end
  opens the first), the ``pct`` percentile (default 95) over those
  requests: the stutter a streaming caller sees;
- ``superstep_gap_ms``: from one round's end to the next round's start,
  the ``pct`` percentile over the window: what every occupied slot pays
  each time the loop leaves decode.

A small fold of its own, in integer microseconds, not the program's
(``flexflow_tpu/obs/spans.py``), so that an edit there cannot move what
the benchmark reads; ``benchmark/tests/test_request_phase.py`` holds the
two to the same totals on a recorded stream.  A stream without the
stamps (a program from before them) gives nothing to read.
"""

import numpy as np

ROUNDS = ("decode_superstep", "spec_verify")


def us(ms) -> int:
    return int(round(float(ms) * 1000.0))


def fold(events):
    """``(requests, rounds)``: ``id -> {"start", "prefill", "end",
    "ends": [a round's end, ...], "decode": us}`` for the requests that
    start and end clean among ``events``, and ``[(t0, t1), ...]`` of the
    rounds, in stream order, all in integer microseconds."""
    reqs, rounds = {}, []
    for e in events:
        ev = e["ev"]
        if e.get("t_ms") is None:
            continue
        t = us(e["t_ms"])
        if ev == "request_start":
            reqs[e["id"]] = {"start": t, "ends": [], "decode": 0}
        elif ev == "prefill" and e["id"] in reqs:
            reqs[e["id"]]["prefill"] = t
        elif ev in ROUNDS and e.get("t0_ms") is not None:
            t0 = us(e["t0_ms"])
            rounds.append((t0, t))
            for rid in e.get("slots", ()):
                if rid in reqs:
                    reqs[rid]["ends"].append(t)
                    reqs[rid]["decode"] += t - t0
        elif ev == "request_end" and e["id"] in reqs:
            reqs[e["id"]].update(end=t, error=e.get("error"))
    clean = {i: r for i, r in reqs.items()
             if "end" in r and r["error"] is None and "prefill" in r}
    return clean, rounds


def slot_wait_us(r) -> int:
    """Admission to finish, less the own prefill and the rounds decoded in."""
    return (r["end"] - r["start"]) - (r["prefill"] - r["start"]) - r["decode"]


def reduce(args, rctx):
    reqs, rounds = fold(rctx["events"])
    stat, pct = args["stat"], float(args.get("pct", 95))
    if stat == "slot_wait_pct":
        life = sum(r["end"] - r["start"] for r in reqs.values())
        return 100.0 * sum(slot_wait_us(r) for r in reqs.values()) / life if life else None
    if stat == "token_gap_ms":
        gaps = [max(np.diff([r["prefill"]] + r["ends"])) for r in reqs.values() if r["ends"]]
    elif stat == "superstep_gap_ms":
        gaps = [b[0] - a[1] for a, b in zip(rounds, rounds[1:])]
    else:
        raise KeyError(stat)
    return float(np.percentile(gaps, pct)) / 1000.0 if gaps else None
