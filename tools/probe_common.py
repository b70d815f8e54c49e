"""Shared scaffolding for the live-TPU kernel probes.

The two-point jitted-chain slope timer: a per-call timing carries the
host's dispatch and fence cost, so a probe times ONE dispatch of an
N-long dependent chain, min-of-3 per chain length (host delays are
one-sided additive noise), and reports the (N2-N1) slope, retrying
once and emitting NaN when noise still swamps the signal.
"""

import sys
import time

import jax


def parse_dims_blocks(argv, default_dims=(16, 8, 2048, 64),
                      default_blocks=(256, 512)):
    """``[b h t hd] [--blocks 256,512]`` with both flag forms; unknown
    flags are an error (a typo must not silently measure defaults)."""
    blocks = list(default_blocks)
    rest = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a.startswith("--blocks"):
            if "=" in a:
                val = a.split("=", 1)[1]
            elif i + 1 < len(argv):
                i += 1
                val = argv[i]
            else:
                sys.exit("--blocks expects a comma-separated list")
            blocks = [int(x) for x in val.split(",")]
        elif a.startswith("--"):
            sys.exit(f"unknown flag {a!r} (only --blocks is supported)")
        else:
            rest.append(a)
        i += 1
    if rest and len(rest) != 4:
        sys.exit(f"expected 4 positional dims (b h t hd), got {rest}")
    dims = tuple(int(x) for x in rest) if len(rest) == 4 else default_dims
    return dims, blocks


def chain_slope_ms(make_run, x0, n1, n2, reps=3):
    """Per-iteration ms from the slope between two chain lengths.

    ``make_run(n)`` returns a jitted callable of one argument that
    executes n dependent iterations; x0 seeds the chain.  Retries once
    on a non-positive slope, then returns NaN rather than garbage.
    """
    def timed(n):
        run = make_run(n)
        y = run(x0)
        jax.device_get(y.ravel()[:1])  # compile+warm fence
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            y = run(x0)
            jax.device_get(y.ravel()[:1])
            best = min(best, time.perf_counter() - t0)
        return best

    for _ in range(2):
        ms = (timed(n2) - timed(n1)) / (n2 - n1) * 1e3
        if ms > 0:
            return ms
    return float("nan")
