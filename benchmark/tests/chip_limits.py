#!/usr/bin/env python3
"""The readings a limit is set from: sound runs of the program and the
lower-precision control, on the chip, at the cell's own size, many seeds
in one process (set-up is paid once).

    python3 benchmark/tests/chip_limits.py --workload <cell> --seeds 11,12,13 \
        [--control-seeds 3] [--seconds 2]

Prints, seed by seed, each number compared ([check] lines) and, for the
first ``--control-seeds`` seeds, the control's ([control] lines).  Not a
benchmark run: nothing here is a metric.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()

    from benchmark import common, manifest, run

    bench = manifest.load(ROOT)
    cell, config, traffic, runner, family = common.load_cell(bench, args.workload)
    common.enable_compile_cache()
    common.require_device(int(cell["chips"]))
    reuse = {}
    compiles = common.CompileCounter()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = run.Context(cell, config, traffic, family, seed, args.seconds, False, compiles, common.SCRATCH)
        ctx.reuse = reuse
        ctx.control = i < args.control_seeds
        common.say(f"=== seed {seed} control {ctx.control}")
        res = runner.run(ctx)
        common.say(f"=== seed {seed} correct {res['correct']} quantities {res['quantities']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
