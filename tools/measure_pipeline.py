"""Pipeline dispatch-overhead measurement (PIPELINE_OVERHEAD.md rows).

Round 6 (ISSUE 3) additions on top of the round-3/5 table: a CHUNK
sweep (``--pipeline-chunk`` c folds each stage's per-microbatch fwd/bwd
into one scanned program — host programs per step drop from ``2*S*m``
to ``2*S*ceil(m/c)``, printed from the actual ``last_schedule`` event
count) and a SUPERSTEP A/B (k pipeline steps dispatched back-to-back
under ONE ``jax.device_get`` fence, ``Trainer._fit_superstep_pipeline``
semantics timed inline).  Acceptance: S=4 mb=8 c=mb 1f1b beats the
round-5 1f1b number (981 ms) by >= 1.2x on the 8-dev virtual CPU mesh.

Round 7 (ISSUE 5) adds the COMPILED whole-step rows (``--pipeline-
compiled``: the entire multi-stage step as ONE jitted program on the
shared stage mesh, 1 host program per step) and the FUSED pipeline
superstep A/B (``build_superstep(k)``: one dispatch + one fence per k
steps, 1/k programs per step) — both same-day against the unchanged
host path per the round-6 box-drift caveat.  Acceptance: compiled
beats the chunked host path per-step in the dispatch-bound regime
(``--batch 64 --width 256``, S=4 mb=8).

The virtual mesh multiplexes ONE core, so these numbers isolate host
dispatch + boundary transfer cost, exactly as in rounds 3/5.

Usage: python tools/measure_pipeline.py [--width 1024 --batch 512]
"""
import argparse
import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"


def build(batch, width, depth=8, classes=32):
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.graph import FFModel
    import jax.numpy as jnp

    ff = FFModel(FFConfig(batch_size=batch))
    x = ff.create_tensor((batch, width), name="x")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="label")
    t = x
    for i in range(depth):
        t = ff.dense(t, width, activation="relu", name=f"fc{i}")
    t = ff.dense(t, classes, name="head")
    ff.softmax(t, lbl, name="softmax")
    return ff


def time_step(ex, batch, iters=30, warmup=5):
    import jax

    params, opt_state, state = ex.init(seed=0)
    placed = ex.shard_batch(batch)
    for _ in range(warmup):
        params, opt_state, state, m = ex.train_step(
            params, opt_state, state, placed)
    jax.device_get(m)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, state, m = ex.train_step(
            params, opt_state, state, placed)
    jax.device_get(m)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def time_superstep(ex, batch, k, iters=32, warmup=4):
    """k steps dispatched back-to-back, ONE device_get of all k
    metrics per superstep — the pipeline-superstep fence pattern."""
    import jax

    params, opt_state, state = ex.init(seed=0)
    placed = ex.shard_batch(batch)
    ms = []
    for _ in range(warmup):
        params, opt_state, state, m = ex.train_step(
            params, opt_state, state, placed)
        ms.append(m)
    jax.device_get(ms)
    t0 = time.perf_counter()
    done = 0
    while done < iters:
        n = min(k, iters - done)
        ms = []
        for _ in range(n):
            params, opt_state, state, m = ex.train_step(
                params, opt_state, state, placed)
            ms.append(m)
        jax.device_get(ms)
        done += n
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def time_fused_superstep(pipe, batch, k, iters=32, warmup=1):
    """k whole pipeline steps as ONE compiled dispatch + ONE fence
    (``PipelineExecutor.build_superstep`` on the compiled-step path)."""
    import jax

    from flexflow_tpu.runtime.trainer import clamp_fused_steps

    k = clamp_fused_steps(k)
    params, opt_state, state = pipe.init(seed=0)
    fn = pipe.build_superstep(k)
    stacked = pipe.stack_steps([batch] * k)
    for _ in range(warmup):
        params, opt_state, state, ms = fn(params, opt_state, state, stacked)
    jax.device_get(ms)
    t0 = time.perf_counter()
    done = 0
    while done < iters:
        params, opt_state, state, ms = fn(params, opt_state, state, stacked)
        jax.device_get(ms)
        done += k
    return (time.perf_counter() - t0) / done * 1e3  # ms/step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()

    import jax
    import numpy as np

    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.pipeline import PipelineExecutor

    nd = len(jax.devices())
    assert nd == 8, f"expected 8 virtual devices, got {nd}"
    ff = build(args.batch, args.width)
    rng = np.random.default_rng(0)
    batch = {
        "x": rng.standard_normal((args.batch, args.width)).astype(np.float32),
        "label": rng.integers(0, 32, size=(args.batch,)).astype(np.int32),
    }
    opt = lambda: SGDOptimizer(lr=0.01, momentum=0.9)

    plain = Executor(ff, strategy=StrategyStore.data_parallel(nd),
                     optimizer=opt())
    t_plain = time_step(plain, batch, args.iters)
    print(f"plain DP x{nd}: {t_plain:.1f} ms", flush=True)

    def pipe_store(S):
        store = StrategyStore(nd)
        per = nd // S
        ops = [f"fc{i}" for i in range(8)] + ["head", "softmax"]
        for i, name in enumerate(ops):
            si = min(i * S // len(ops), S - 1)
            ids = tuple(range(si * per, (si + 1) * per))
            store.set(name, ParallelConfig(n=per, device_ids=ids))
        return store

    def make_pipe(S, mb, sched, c, compiled=False):
        return PipelineExecutor(
            ff, pipe_store(S), optimizer=opt(),
            microbatches=mb, schedule=sched, chunk=c, compiled=compiled,
        )

    for S in (2, 4):
        for mb in (1, 4, 8):
            # Both schedules at c=1 (round-3/5 comparability), then the
            # chunk sweep on 1f1b: c in {2, mb}, then the compiled
            # whole-step row (ONE program; schedule is moot — the
            # trace sequences stages by data dependency).
            chunks = [1] if mb == 1 else [1, 2, mb]
            for sched in ("gpipe", "1f1b"):
                for c in (chunks if sched == "1f1b" else [1]):
                    pipe = make_pipe(S, mb, sched, c)
                    t = time_step(pipe, batch, args.iters)
                    progs = len(pipe.last_schedule)
                    flag = " <= plain" if t <= t_plain else ""
                    print(
                        f"pipeline S={S} mb={mb} c={c} {sched}: "
                        f"{t:.1f} ms  ({progs} programs/step){flag}",
                        flush=True,
                    )
            pipe = make_pipe(S, mb, "1f1b", 1, compiled=True)
            t = time_step(pipe, batch, args.iters)
            flag = " <= plain" if t <= t_plain else ""
            print(
                f"pipeline S={S} mb={mb} compiled: {t:.1f} ms  "
                f"(1 program/step){flag}",
                flush=True,
            )

    # Superstep-over-pipeline A/B: one fence per k=8 steps at the
    # dispatch-minimal chunk (and at c=1 for the fence-only delta),
    # then the FUSED compiled superstep (one dispatch + one fence per
    # k steps — 1/k programs per step).
    for c in (1, 8):
        pipe = make_pipe(4, 8, "1f1b", c)
        t1 = time_superstep(pipe, batch, k=1, iters=args.iters)
        t8 = time_superstep(pipe, batch, k=8, iters=args.iters)
        print(
            f"superstep S=4 mb=8 c={c} 1f1b: k=1 {t1:.1f} ms -> "
            f"k=8 {t8:.1f} ms/step ({t1 / t8:.2f}x)",
            flush=True,
        )
    pipe = make_pipe(4, 8, "1f1b", 1, compiled=True)
    t1 = time_superstep(pipe, batch, k=1, iters=args.iters)
    t8 = time_fused_superstep(pipe, batch, k=8, iters=args.iters)
    print(
        f"superstep S=4 mb=8 compiled: k=1 {t1:.1f} ms -> "
        f"k=8 fused {t8:.1f} ms/step ({t1 / t8:.2f}x)",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
