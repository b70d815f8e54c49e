#!/usr/bin/env python3
"""One cell of the benchmark, once, in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its
configuration (``configs/<config>.json``), its traffic mix
(``traffic/<traffic>.json``), the runner the mix's ``kind`` names
(``runners/<kind>.py``) and, in a traced run, every per-layer metric
that lists the cell (``metrics/<metric>.json`` and the reducer it names,
``reducers/<reducer>.py``) are files found by name: adding a cell, a
configuration, a mix or a metric adds files and one entry and edits
nothing here.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics) and ``device``.  No TPU, or
fewer chips than the cell asks for, is exit 2 and no result line; only
``JAX_PLATFORMS=cpu``, by name, makes a rehearsal on the CPU, and its
line says ``"platform": "cpu"``.

``setup_s`` runs from ``T_UP``, the instant ``jax.devices()`` has
returned, to the first instant of the measured window.  Before ``T_UP``
nothing of the program and nothing of the benchmark is imported: what
lies there (the interpreter, ``import jax``, the TPU runtime's start) no
PR to this repository can move, and on the chip machine it swings by
seconds from run to run.  It is printed as ``boot_s`` on an earlier
line, with ``import_s`` (the part of ``setup_s`` spent importing the
benchmark and the program) and ``process_setup_s = boot_s + setup_s``;
none of the three is a metric.
"""

import time

T_PROC = time.time()  # process start, as near as Python lets this file see it
import jax  # noqa: E402

try:
    jax.devices()
except RuntimeError as e:  # no backend at all: no result line
    raise SystemExit(f"benchmark: jax found no device: {e}")
T_UP = time.time()  # the set-up clock starts here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Context:
    """What a runner is given, and where it leaves the window's marks."""

    def __init__(self, cell, config, traffic, family, seed, seconds, trace, compiles, scratch):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.family = family
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.chips = int(cell["chips"])
        self.compiles = compiles
        self.telemetry_dir = os.path.join(scratch, "telemetry")
        self.trace_dir = os.path.join(scratch, "trace", cell["name"])
        self.setup_s = None
        self.window_wall0 = None
        #: Built programs, kept by a caller that runs several seeds in
        #: one process (tests/chip_limits.py); a benchmark run has one.
        self.reuse = {}
        #: Also read the lower-precision control (never in a benchmark run).
        self.control = False

    def window_seconds(self) -> float:
        """A traced run measures a short window of its own."""
        if self.trace:
            return min(self.seconds, float(self.traffic.get("trace_seconds", 5)))
        return self.seconds

    def mark_window_start(self) -> None:
        self.window_wall0 = time.time()
        self.setup_s = self.window_wall0 - T_UP

    @contextlib.contextmanager
    def profile(self):
        if not self.trace:
            yield
            return
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        try:
            with jax.profiler.TraceAnnotation("bench/window"):
                yield
        finally:
            jax.profiler.stop_trace()


def _applies(metric, cell_name) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def _end_to_end(bench, ctx, res) -> dict:
    """The cell's end-to-end metrics: ``setup_s`` and the quantities of
    the runner's window that the mix's ``end_to_end`` table names."""
    metrics = {}
    for m in bench["end_to_end"]:
        if _applies(m, ctx.cell["name"]):
            value = ctx.setup_s if m["name"] == "setup_s" else \
                res["quantities"][ctx.traffic["end_to_end"][m["name"]]]
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def _per_layer(bench, ctx, res, device) -> tuple:
    """``(metrics, breakdown)`` of a traced run; fills the device's
    ``busy_s`` and ``window_s``.  Each metric is read by the reducer its
    own file names; one that finds nothing to read is left out."""
    from benchmark import common, trace_read

    trace = trace_read.load(trace_read.find_xplane(ctx.trace_dir))
    lo, hi = trace_read.window_ns(trace)
    window_s = (hi - lo) * 1e-9
    shares = {i: trace_read.busy_seconds(trace_read.clip(ops, lo, hi))
              for i, ops in sorted(trace.devices.items())}
    for i, b in shares.items():
        common.say(f"[trace] device {i}: busy {b:.4f} s of {window_s:.4f} s, "
                   f"idle share {100 * (1 - b / window_s):.2f}%")
    if not shares or max(shares.values()) <= 0:
        raise SystemExit("the trace holds no device operation")
    device["busy_s"] = sum(shares.values()) / len(shares)
    device["window_s"] = window_s
    first = trace.devices[min(trace.devices)]
    breakdown = {
        "device_ops": trace_read.top_ops(trace_read.clip(first, lo, hi)),
        "idle_gaps": trace_read.idle_gaps(first, trace.host_spans, lo, hi),
    }
    events = [e for e in common.read_events(res["telemetry_path"])
              if e["ts"] >= ctx.window_wall0]
    rctx = {"cell": ctx.cell, "config": ctx.config, "traffic": ctx.traffic, "result": res,
            "events": events, "trace": trace, "window_ns": (lo, hi),
            "device_kind": device["kind"], "platform": device["platform"],
            "window_compiles": ctx.compiles.count}
    metrics = {}
    for m in bench["per_layer"]:
        if not _applies(m, ctx.cell["name"]):
            continue
        spec = common.load_json(HERE, "metrics", m["name"] + ".json")
        value = common.load_module("reducers", spec["reducer"]).reduce(spec.get("args", {}), rctx)
        if value is None:
            common.say(f"[metric] {m['name']}: nothing to read")
            continue
        common.say(f"[metric] {m['name']} = {value} {m['unit']}")
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # Everything imported: the benchmark's modules and, through the
    # runner and the family, the program's.  Timed as ``import_s``.
    t_import = time.time()
    from benchmark import common, manifest

    bench = manifest.load(ROOT)
    cell, config, traffic, runner, family = common.load_cell(bench, args.workload)
    import_s = time.time() - t_import

    cache = common.enable_compile_cache()
    device = common.require_device(int(cell["chips"]))
    common.say(f"[run] cell {cell['name']} seed {args.seed} seconds {args.seconds} "
               f"trace {args.trace} on {device} cache {cache}")
    os.makedirs(common.SCRATCH, exist_ok=True)
    ctx = Context(cell, config, traffic, family, args.seed, args.seconds, bool(args.trace),
                  common.CompileCounter(), common.SCRATCH)
    res = runner.run(ctx)

    device["memory_peak_bytes"] = int(res["peak_bytes"])
    boot_s = T_UP - T_PROC
    common.say(f"[run] boot_s {boot_s:.3f} import_s {import_s:.3f} setup_s {ctx.setup_s:.3f} "
               f"process_setup_s {boot_s + ctx.setup_s:.3f} peak_bytes {res['peak_bytes']} "
               f"window_compiles {ctx.compiles.count}")
    out = {"correct": bool(res["correct"]), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "device": device}
    if args.trace:
        out["metrics"], out["breakdown"] = _per_layer(bench, ctx, res, device)
    else:
        out["metrics"] = _end_to_end(bench, ctx, res)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
