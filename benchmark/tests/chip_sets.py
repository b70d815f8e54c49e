#!/usr/bin/env python3
"""Sets of runs of one cell, each run a process of its own, and the
spread a bound is set from.

    python3 benchmark/tests/chip_sets.py --workload <cell> --seeds 11,12,13,14,15,16 \
        [--sets A,B] [--seconds 30] [--traced-seed 17] [--out chiprun_out/sets]

Every set runs the same seeds.  The very first run of the call compiles
(its ``setup_s`` is printed apart and left out of the medians).  For each
end-to-end metric and each set: the median and the spread, the distance
between the first and third quartile (``statistics.quantiles(n=4)``) over
the median; and ``boot_s`` / ``import_s`` of every run beside them.  This
process never touches jax: a chip belongs to the run it starts.  The
compile cache is the checkout's own (``JAX_COMPILATION_CACHE_DIR`` is
taken out of the runs' environment), so the second run finds the first
one's programs whatever cap the machine's own cache has.
"""

import argparse
import json
import os
import re
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CLOCK = re.compile(r"^\[run\] boot_s (\S+) import_s (\S+) setup_s (\S+) process_setup_s (\S+) ", re.M)


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one_run(cell, seed, seconds, trace, stem):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    with open(stem + ".out", "w") as out, open(stem + ".err", "w") as err:
        rc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", cell,
             "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
            cwd=ROOT, env=env, stdout=out, stderr=err).returncode
    text = open(stem + ".out").read()
    if rc != 0:
        print(f"rc {rc}: {stem}\n{text[-1500:]}\n{open(stem + '.err').read()[-3000:]}", flush=True)
        raise SystemExit(rc)
    line = json.loads(text.strip().splitlines()[-1])
    clock = [float(x) for x in CLOCK.search(text).groups()]
    return line, clock, text


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", default="A,B")
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--traced-seed", type=int)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "sets"))
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    first = True
    table = {}
    for label in args.sets.split(","):
        for seed in seeds:
            stem = os.path.join(args.out, f"{args.workload}.{label}.{seed}")
            line, (boot_s, import_s, _, _), text = one_run(args.workload, seed, args.seconds, 0, stem)
            vals = {k: m["value"] for k, m in line["metrics"].items()}
            longest = re.search(r"longest (?:step|superstep) (\S+) ms", text)
            print(f"{label} {seed} correct {line['correct']} "
                  + " ".join(f"{k} {v:.6g}" for k, v in vals.items())
                  + f" boot_s {boot_s:.3f} import_s {import_s:.3f}"
                  + (f" longest {longest.group(1)} ms" if longest else "")
                  + (" (first run of the call: compiles)" if first else ""), flush=True)
            for k, v in vals.items():
                if not (first and k == "setup_s"):
                    table.setdefault(k, {}).setdefault(label, []).append(v)
            for k, v in (("boot_s", boot_s), ("import_s", import_s)):
                if not first:
                    table.setdefault(k, {}).setdefault(label, []).append(v)
            first = False
    for k, by_set in table.items():
        for label, vals in by_set.items():
            sp = f"{spread(vals):.5f}" if len(vals) >= 2 else "n/a"
            print(f"[sets] {k} set {label}: median {statistics.median(vals):.6g} spread {sp} "
                  f"min {min(vals):.6g} max {max(vals):.6g} n {len(vals)}", flush=True)
    if args.traced_seed is not None:
        stem = os.path.join(args.out, f"{args.workload}.T.{args.traced_seed}")
        line, _, _ = one_run(args.workload, args.traced_seed, args.seconds, 1, stem)
        print("[traced] " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
