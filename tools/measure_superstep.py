"""Superstep A/B: per-step wall time of Trainer.fit at k steps/dispatch.

ISSUE 1 acceptance harness: at a dispatch-bound shape (a model whose
step compute is far below the per-dispatch host cost) the superstep
path (``Executor.build_superstep``: K train steps fused into one jitted
``lax.scan`` with one host-readback fence per call) must show per-step
wall time strictly decreasing from k=1 to k=8.  On CPU the per-dispatch
overhead is ~100 us; on the chip it is not measured yet (ROADMAP A2).

One process.  Runs on the CPU mesh by default; pass --tpu to skip the
CPU pin and measure the attached chip instead.
Prints per-arm lines on stderr and ONE JSON summary line on stdout.
"""

import json
import os
import sys

if "--tpu" not in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"
    # The 8-device virtual mesh (the repo's test environment): each
    # dispatch launches the executable on 8 virtual devices of ONE
    # core, putting the per-dispatch host cost near 1 ms.
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax
import numpy as np


def build_executor():
    """Dispatch-bound shape: a 2-layer b=32 MLP whose whole step is
    tens of microseconds of compute — per-step time is dominated by
    dispatch + fence, exactly what supersteps amortize."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.graph import FFModel
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.runtime.executor import Executor

    batch = 32
    ff = FFModel(FFConfig(batch_size=batch, seed=3))
    x = ff.create_tensor((batch, 64), name="x")
    lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
    t = ff.dense(x, 64, activation="relu", name="fc1")
    t = ff.dense(t, 8, name="fc2")
    ff.softmax(t, lbl, name="softmax")
    return Executor(ff, optimizer=SGDOptimizer(lr=0.01, momentum=0.9))


def main():
    import contextlib

    from flexflow_tpu.runtime.trainer import Trainer

    ks = (1, 2, 4, 8, 16)
    iters = 64  # divisible by every k: no remainder recompile
    reps = 3
    best_ms = {}
    ex = build_executor()
    # Interleaved rounds (ABAB) split host drift from the k effect;
    # per-k jit caches live on the executor, so later rounds re-time
    # the same compiled program.  Trainer.fit prints its reference
    # timing lines on stdout — route them to stderr so stdout stays
    # one JSON line.
    for rep in range(reps):
        for k in ks:
            with contextlib.redirect_stdout(sys.stderr):
                stats = Trainer(ex).fit(iterations=iters, warmup=1,
                                        steps_per_call=k)
            ms = stats["elapsed_s"] / iters * 1e3
            best_ms[k] = min(best_ms.get(k, float("inf")), ms)
            print(f"rep {rep} k={k:2d}: {ms:8.3f} ms/step",
                  file=sys.stderr)
    k1 = best_ms[1]
    summary = {
        "metric": "superstep_ms_per_step",
        "platform": jax.default_backend(),
        "batch_size": 32,
        "iterations": iters,
        "ms_per_step": {f"k{k}": round(best_ms[k], 4) for k in ks},
        "amortization_vs_k1": {
            f"k{k}": round(k1 / best_ms[k], 3) for k in ks if k > 1
        },
        "strictly_decreasing_to_k8": best_ms[1] > best_ms[2] > best_ms[4]
        > best_ms[8],
    }
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
