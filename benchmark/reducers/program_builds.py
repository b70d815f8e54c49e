"""What set-up spent building programs, from the ``program_build``
records the program's telemetry folds out of jax's own trace, lowering
and compile spans (``flexflow_tpu/runtime/telemetry.py::BuildLog``), and
what its cost probes took (``program_cost.wall_s``).

Read from the WHOLE stream (``result["telemetry_path"]``): the harness's
``events`` hold the window only, and a build belongs to set-up.  Set-up
is every record with ``backlog`` true (made before the stream opened:
a training cell's stream opens at the window) plus every record that
ended (``t1``; a ``program_cost``'s ``ts``) at or before the stream's
LAST ``serve_run`` line, where it has one: the window's run, with the
warm-up's builds before it.  Both are ``time.time()``, the clock of
``setup_s``.

args ``stat``:

- ``trace_lower_s``: ``trace_s + wall_s`` of the ``lower`` records,
  ``wall_s`` of the ``trace`` records, ``trace_lower_s`` of the
  ``small`` lines;
- ``compile_s``: ``wall_s`` of the ``compile`` records (the backend's
  compile on a miss, retrieval and load on a hit) and ``compile_s`` of
  the ``small`` lines;
- ``cache_misses``: ``compile`` records whose ``cache`` is not ``hit``,
  plus ``misses`` of the ``small`` lines;
- ``cost_probe_s``: ``wall_s`` of the ``program_cost`` events.

A stream without a ``program_build`` (a program from before the event)
gives nothing to read; any other stream gives a number, 0.0 included.
"""

from benchmark import common


def setup_events(events):
    """``(builds, probes)`` of set-up: the ``program_build`` and the
    ``program_cost`` events before the window's run."""
    runs = [e["ts"] for e in events if e["ev"] == "serve_run"]
    cut = runs[-1] if runs else float("-inf")
    builds = [e for e in events if e["ev"] == "program_build"
              and (e.get("backlog") or e["t1"] <= cut)]
    probes = [e for e in events if e["ev"] == "program_cost" and e["ts"] <= cut]
    return builds, probes


def reduce(args, rctx):
    path = rctx["result"].get("telemetry_path")
    events = common.read_events(path) if path else []
    if not any(e["ev"] == "program_build" for e in events):
        return None
    builds, probes = setup_events(events)
    by = {p: [b for b in builds if b["phase"] == p]
          for p in ("lower", "trace", "compile", "small")}
    stat = args["stat"]
    if stat == "trace_lower_s":
        return float(sum(b["wall_s"] + b["trace_s"] for b in by["lower"])
                     + sum(b["wall_s"] for b in by["trace"])
                     + sum(b["trace_lower_s"] for b in by["small"]))
    if stat == "compile_s":
        return float(sum(b["wall_s"] for b in by["compile"])
                     + sum(b["compile_s"] for b in by["small"]))
    if stat == "cache_misses":
        return float(sum(b["cache"] != "hit" for b in by["compile"])
                     + sum(b["misses"] for b in by["small"]))
    if stat == "cost_probe_s":
        return float(sum(p.get("wall_s", 0.0) for p in probes))
    raise KeyError(stat)
