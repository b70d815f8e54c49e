#!/usr/bin/env python
"""Telemetry overhead A/B: enabled-vs-off per-step cost on the 8-dev
virtual CPU mesh (the OBSERVABILITY.md acceptance table; bar < 2%).

Each regime trains the dispatch-bound MLP twice — telemetry OFF, then
telemetry ON writing a real JSONL stream to a temp dir (the honest
cost: event serialization + flush + heartbeat touch per step/fence) —
and reports ms/step for both plus the overhead.  Regimes:

- ``k1``: the per-step loop (one `step` event + heartbeat per step;
  the unfenced regime, so wall times are dispatch times).
- ``k8``: fused supersteps (`superstep` + 8 `step` events per fence).
- ``pipeline``: S=2 x mb=4 c=4 layer-wise (adds the programs/step
  counter fold per step).
- ``sched_serving``: the SLO scheduler's real-engine loop (request
  lifecycle events incl. the per-superstep ``slots`` occupancy field
  — OBSERVABILITY.md "Reading a request"; row is ms/RUN, one bursty
  24-request workload per leg).

CPU wall noise at these sizes is a few percent between *identical*
runs AND drifts over a session (an A/A test on this box reads 1-15%
"overhead" from ordering alone), so the protocol is paired: each rep
runs the two variants back to back (order alternating between reps)
and the statistic is the MEDIAN OF PER-PAIR RELATIVE DELTAS — drift
cancels to first order inside a pair, and the median rejects the
box's occasional 2x outlier runs.  An ``a_a_pct`` control column runs
the same protocol on two OFF variants; read the overhead against it.

Usage: env PYTHONPATH=/root/repo python tools/measure_telemetry.py
       [--reps N] [--iters N] [--tpu]
(One process.  Without --tpu it pins the 8-device virtual CPU mesh
before jax initializes (``analysis.program_audit.ensure_cpu_mesh``);
with --tpu it leaves the environment alone and runs on the attached
chip.)
"""

import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _arg(argv, flag, default):
    if flag in argv:
        return int(argv[argv.index(flag) + 1])
    return default


def run(argv):
    # The off legs must be genuinely off: an inherited
    # FF_TELEMETRY_DIR would install file-backed telemetry on them via
    # Trainer.fit's maybe_run and corrupt the A/B.
    os.environ.pop("FF_TELEMETRY_DIR", None)
    import jax
    import numpy as np

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.graph import FFModel
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.pipeline import PipelineExecutor
    from flexflow_tpu.runtime.telemetry import Telemetry
    from flexflow_tpu.runtime.trainer import Trainer

    reps = _arg(argv, "--reps", 9)
    iters = _arg(argv, "--iters", 256)
    batch, width = 32, 64
    nd = len(jax.devices())

    def mlp():
        ff = FFModel(FFConfig(batch_size=batch, seed=7))
        x = ff.create_tensor((batch, width), name="x")
        lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
        t = ff.dense(x, width, activation="relu", name="fc1")
        t = ff.dense(t, 8, name="fc2")
        ff.softmax(t, lbl, name="softmax")
        return ff

    # ONE executor (= one set of compiled programs) per regime, warmed
    # before timing, shared by the off and on legs: rebuilding and
    # re-jitting per rep was measured to swamp the telemetry cost by
    # an order of magnitude (allocator/compile-cache churn).
    def full_mesh(k):
        ex = Executor(mlp(), optimizer=SGDOptimizer(lr=0.01, momentum=0.9))
        tr = Trainer(ex)
        tr.fit(iterations=2 * k, warmup=k, steps_per_call=k)  # warm jits

        def run(tel_dir):
            if tel_dir is None:
                return tr.fit(iterations=iters, warmup=1, steps_per_call=k)
            with Telemetry(tel_dir, stall_deadline_s=300.0):
                return tr.fit(iterations=iters, warmup=1, steps_per_call=k)
        return run

    def pipeline():
        ff = FFModel(FFConfig(batch_size=batch, seed=7))
        x = ff.create_tensor((batch, width), name="x")
        lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
        t = ff.dense(x, width, activation="relu", name="fc0")
        t = ff.dense(t, 8, name="head")
        ff.softmax(t, lbl, name="softmax")
        per = nd // 2
        st = StrategyStore(nd)
        st.set("fc0", ParallelConfig(n=per, device_ids=tuple(range(per))))
        for name in ("head", "softmax"):
            st.set(name, ParallelConfig(
                n=per, device_ids=tuple(range(per, 2 * per))))
        pipe = PipelineExecutor(
            ff, st, optimizer=SGDOptimizer(lr=0.01, momentum=0.9),
            microbatches=4, chunk=4,
        )
        tr = Trainer(pipe)
        tr.fit(iterations=2, warmup=1)  # warm jits

        def run(tel_dir):
            if tel_dir is None:
                return tr.fit(iterations=iters, warmup=1)
            with Telemetry(tel_dir, stall_deadline_s=300.0):
                return tr.fit(iterations=iters, warmup=1)
        return run

    def sched_serving():
        # The serving scheduler's real-engine loop: telemetry ON adds
        # the request-lifecycle events (request_start/prefill/
        # sched_decision+slots/decode_superstep/request_end) — the
        # span-layer instrumentation measured under the same < 2% bar.
        from flexflow_tpu.models.transformer import build_transformer_lm
        from flexflow_tpu.runtime.serving import ServingExecutor
        from flexflow_tpu.serving import (
            ScheduledServer,
            SchedulerPolicy,
            WorkloadSpec,
            make_workload,
        )

        # Sized so one decode superstep carries real compute on the
        # CPU mesh (~ms-scale dispatches): the per-dispatch event cost
        # is fixed (~3 emits + a heartbeat touch), so a toy model
        # would over-weight it against any real dispatch.
        max_batch, max_seq = 2, 64
        ffs = build_transformer_lm(
            batch_size=max_batch, seq_len=max_seq, vocab_size=64,
            d_model=64, num_heads=4, num_layers=2,
            config=FFConfig(batch_size=max_batch),
        )
        sexm = ServingExecutor(ffs, max_batch=max_batch,
                               max_seq=max_seq, buckets=(8,))
        p, s = sexm.init(seed=0)
        srv = ScheduledServer(sexm, p, s, decode_steps=8,
                              policy=SchedulerPolicy(name="slo"))

        def reqs():
            return make_workload(WorkloadSpec(
                n_requests=24, vocab=64, prompt_len=(3, 6),
                max_new=(2, 12), mean_gap_ms=1.0, burst=12,
                priorities=3, slo_ms=60.0, seed=13,
            ))

        srv.run(reqs())  # warm jits

        def run(tel_dir):
            if tel_dir is None:
                _, stats = srv.run(reqs())
            else:
                with Telemetry(tel_dir):
                    # First telemetered run pays the one-time
                    # program_cost attribution (Lowered.cost_analysis
                    # is deduped PER TELEMETRY instance, ~1 ms/program
                    # lowering) — a documented first-build cost, like
                    # jit warmup.  The row measures the steady state:
                    # the per-event serialization incl. `slots`.
                    srv.run(reqs())
                    _, stats = srv.run(reqs())
            return stats
        return run

    regimes = [("k1", full_mesh(1), iters), ("k8", full_mesh(8), iters)]
    if nd >= 2:
        regimes.append(("pipeline", pipeline(), iters))
    else:
        print(f"pipeline regime skipped: {nd} device(s)", file=sys.stderr)
    # Serving row normalizes per RUN, not per step (one workload = one
    # "iteration"); the overhead % is normalization-free either way.
    regimes.append(("sched_serving", sched_serving(), 1))

    # The paired-median + A/A-control protocol now lives in
    # obs.compare.paired_measure (this tool's local copy, promoted);
    # ``a`` is the OFF leg, ``b`` the ON leg, the control runs two OFF
    # legs under the same alternation.
    from flexflow_tpu.obs.compare import paired_measure

    print(f"{'regime':<14} {'off ms/step':>12} {'on ms/step':>12} "
          f"{'overhead':>9} {'a_a_pct':>8}   (median of {reps} paired "
          f"A/B deltas, {iters} iters, {nd} devices; "
          f"sched_serving row is ms/run)")
    for name, run, norm in regimes:
        with tempfile.TemporaryDirectory(prefix="tel_ab_") as d:
            res = paired_measure(
                make_a=lambda r, run=run, norm=norm:
                    run(None)["elapsed_s"] / norm * 1e3,
                make_b=lambda r, run=run, norm=norm, name=name: run(
                    os.path.join(d, f"{name}_{r}")
                )["elapsed_s"] / norm * 1e3,
                reps=reps,
                control=lambda r, run=run, norm=norm:
                    run(None)["elapsed_s"] / norm * 1e3,
            )
        print(f"{name:<14} {res.median_a:>12.3f} "
              f"{res.median_b:>12.3f} "
              f"{res.median_delta_pct:>8.2f}% "
              f"{res.median_aa_pct:>7.2f}%")

    # Deterministic accounting: this box's A/B wall clock swings more
    # between identical sessions than the cost being measured, so the
    # primary number is the added per-step host work itself — a tight
    # loop over the exact file-backed calls the instrumented loops
    # make, immune to scheduler noise.  Overhead = this / step time.
    with tempfile.TemporaryDirectory(prefix="tel_micro_") as d:
        tel = Telemetry(os.path.join(d, "micro"))
        N = 20000
        t0 = time.perf_counter()
        for i in range(N):
            tel.record_step(i, loss=1.5, wall_s=0.001)
        us = (time.perf_counter() - t0) / N * 1e6
        t0 = time.perf_counter()
        for i in range(N):
            tel.emit("superstep", k=8, mode="fused", wall_s=0.004,
                     first_step=i)
        emit_us = (time.perf_counter() - t0) / N * 1e6
        t0 = time.perf_counter()
        for i in range(N):
            tel.emit("sched_decision", vclock_ms=float(i),
                     admitted=[i], k=8, slots=[0, 1, 2, 3])
        slots_us = (time.perf_counter() - t0) / N * 1e6
        tel.close()
    print(f"deterministic: record_step+heartbeat = {us:.1f} us/step, "
          f"generic emit = {emit_us:.1f} us, "
          f"sched_decision+slots = {slots_us:.1f} us "
          f"(k1 adds 1 record_step/step; k8 adds 8 record_steps + "
          f"2 emits per 8-step superstep; a serving decode dispatch "
          f"adds ~3 emits incl. the slots occupancy list; the "
          f"dispatch cost they ride on is not measured on the chip)")
    return 0


def main():
    argv = sys.argv[1:]
    sys.path.insert(0, REPO)
    if "--tpu" not in argv:
        from flexflow_tpu.analysis.program_audit import ensure_cpu_mesh

        ensure_cpu_mesh()  # before jax initializes: the flags parse once
    return run(argv)


if __name__ == "__main__":
    sys.exit(main())
