#!/usr/bin/env python3
"""A wide sample of seeds for a DLRM cell's limits, without the chip.

    JAX_PLATFORMS=cpu python3 benchmark/tests/emulate_dlrm.py \
        --config benchmark/configs/dlrm-random.json \
        --traffic benchmark/traffic/random.b1024.json --seeds 40 --base 3200000000

The plain reference follows the first steps twice: at ``highest``, and
with the inputs of every matrix product, forward and backward, rounded
to bfloat16 — what a TPU does to a float32 product at the default
precision, so it stands in for the program.  Seed by seed it prints the
first loss and the three numbers a training cell compares, for that
sound stand-in, for the stand-in fed half of every batch (a part of the
batch left out), and for the bfloat16 control.  It is how PR 23 found
that a bias drawn near zero under the sigmoid leaves no comparison
conditioned (PERF.md section 4 (c)).  The chip's readings
(``chip_limits.py``) stay the ones a limit is held to: on the seeds both
ran, the two read within twice of each other.  Nothing of the program is
run.
"""

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def one_pass_bf16(jnp, jax):
    """``matmul(x, w)`` as one bfloat16 pass with float32 accumulation,
    in both directions."""
    r = lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    dot = lambda a, b: jnp.matmul(a, b, precision="highest")

    @jax.custom_vjp
    def mm(x, w):
        return dot(r(x), r(w))

    mm.defvjp(lambda x, w: (mm(x, w), (x, w)),
              lambda res, g: (dot(r(g), r(res[1]).T), dot(r(res[0]).T, r(g))))
    return mm


def gaps(common, got, want):
    return (max(abs(g - w) / abs(w) for g, w in zip(got["losses"], want["losses"])),
            common.worst_leaf_gap(got["grad_norms"], want["grad_norms"]),
            common.worst_leaf_gap(got["delta_norms"], want["delta_norms"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--base", type=int, default=3200000000)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark import common
    from benchmark.families import dlrm as family
    from benchmark.references import dlrm as reference

    common.say = lambda _msg: None
    cfg, traffic = common.load_json(args.config), common.load_json(args.traffic)
    batch = int(traffic["flags"][traffic["flags"].index("-b") + 1])
    mm = one_pass_bf16(jnp, jax)
    stand_in = types.SimpleNamespace(**{k: getattr(jnp, k) for k in dir(jnp) if not k.startswith("__")})
    stand_in.matmul = lambda a, b, precision=None: mm(a, b)

    def follow(seed, batches, as_program=False, quant=False):
        reference.jnp = stand_in if as_program else jnp
        try:
            return reference.train(cfg, traffic, seed, batches, quant=quant)
        finally:
            reference.jnp = jnp

    print("seed first_loss | sound: loss grad param | half batch: loss grad param | control: loss grad param")
    rows = []
    for seed in range(args.base, args.base + args.seeds):
        host = family.host_batches(cfg, traffic, seed, 3, batch)
        want = follow(seed, host)
        row = [seed, want["losses"][0]]
        row += gaps(common, follow(seed, host, as_program=True), want)
        row += gaps(common, follow(seed, [{k: v[: batch // 2] for k, v in b.items()} for b in host],
                                   as_program=True), want)
        row += gaps(common, follow(seed, host, quant=True), want)
        rows.append(row)
        print("%d %.4f | %.3e %.3e %.3e | %.3e %.3e %.3e | %.3e %.3e %.3e" % tuple(row), flush=True)
    print(json.dumps({"seeds": len(rows),
                      "sound_largest": [max(r[i] for r in rows) for i in (2, 3, 4)],
                      "half_batch_smallest": [min(r[i] for r in rows) for i in (5, 6, 7)],
                      "control_smallest": [min(r[i] for r in rows) for i in (8, 9, 10)]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
