"""Training-loop driver with the reference's measurement protocol.

The reference times N iterations between an execution fence and a
TimingLauncher and prints ``tp = iters*batch/elapsed`` images/s
(``cnn.cc:122-129``) / ``THROUGHPUT = samples/s`` (``dlrm.cc:159-166``).
Here the fence is ``block_until_ready`` and the formulas are identical,
so relative numbers are comparable.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Any, Callable, Dict, Iterable, Optional

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation

from flexflow_tpu.metrics import PerfMetrics
from flexflow_tpu.runtime import telemetry as _telemetry
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.pipeline import PipelineExecutor

_log = logging.getLogger("ff.trainer")

#: Bound on the steps fused into one dispatch (``steps_per_call``,
#: decode K, draft depth): the host sees nothing — no loss, no fault,
#: no preemption — until the fence at the end of the chain.  20 is a
#: plain bound carried over from earlier rounds, not a measured
#: optimum; the dispatch and fence floor it trades against is not
#: measured on the chip yet (ROADMAP A2).
MAX_STEPS_PER_CALL = 20


def clamp_fused_steps(k: int, what: str = "steps_per_call",
                      log: logging.Logger = _log) -> int:
    """THE fused-step bound: clamp a fused-dispatch step count to
    ``MAX_STEPS_PER_CALL`` with a loud warning.  Every
    ``build_superstep``/``build_decode_superstep`` feed must pass
    through here (fflint FF006 flags scan builds in modules that
    don't), so the bound has one owner instead of N copied clamps."""
    k = int(k)
    if k > MAX_STEPS_PER_CALL:
        log.warning(
            "%s=%d exceeds the fused-step bound; clamping to %d "
            "(one fence per fused chain: keep chains short)",
            what, k, MAX_STEPS_PER_CALL,
        )
        return MAX_STEPS_PER_CALL
    return max(1, k)


class Trainer:
    def __init__(self, executor: Executor):
        self.ex = executor
        self.metrics = PerfMetrics()

    def _synthetic_host_batch(self, seed: int = 0) -> Dict[str, np.ndarray]:
        """Host-side synthetic inputs keyed by input-tensor name."""
        from flexflow_tpu.data.loader import synthetic_host_batch

        return synthetic_host_batch(self.ex.model, np.random.default_rng(seed))

    def synthetic_batch(self, seed: int = 0) -> Dict[str, jax.Array]:
        """Device-resident synthetic inputs (reference: syntheticInput,
        ``config.h:73``; DLRM loads random data once, ``dlrm.cc:144-150``)."""
        return self.ex.shard_batch(self._synthetic_host_batch(seed))

    def _batch_source(self, batches, total: int, prefetch: int):
        """Per-step batch plumbing shared by :meth:`fit` and the
        pipeline superstep loop: a fixed synthetic batch when
        ``batches`` is None (infinite), a caller-owned
        ``PrefetchLoader`` as-is (already device-placing), otherwise an
        owned ``PrefetchLoader`` — bounded to exactly the ``total``
        batches this run consumes, so the worker never pulls ahead
        past the run and a caller-reused iterator loses nothing (the
        synchronous path's contract) — or, with ``prefetch=0``, a
        synchronous ``shard_batch`` generator.  Returns
        ``(iterator, owned_prefetch_or_None)``; the caller closes the
        owned loader."""
        from flexflow_tpu.data.loader import PrefetchLoader

        ex = self.ex
        if batches is None:
            fixed = self.synthetic_batch()
            return iter(lambda: fixed, None), None
        if isinstance(batches, PrefetchLoader):
            return batches, None
        if prefetch > 0:
            import itertools

            owned = PrefetchLoader(
                itertools.islice(iter(batches), total),
                ex.shard_batch, depth=prefetch,
            )
            return owned, owned
        return (ex.shard_batch(b) for b in iter(batches)), None

    def fit(
        self,
        iterations: int,
        batches: Optional[Iterable[Dict[str, Any]]] = None,
        warmup: int = 1,
        log_every: int = 0,
        checkpoint=None,
        save_every: int = 0,
        resume: bool = True,
        accum_steps: int = 1,
        prefetch: int = 2,
        steps_per_call: int = 1,
    ) -> Dict[str, float]:
        """Run ``iterations`` steps; returns throughput stats computed
        with the reference formula.

        ``steps_per_call > 1`` switches to superstep execution
        (``Executor.build_superstep``): K train steps fused into one
        compiled ``lax.scan`` dispatch, fencing with ``jax.device_get``
        once per superstep — the dispatch-overhead amortization path
        (full-mesh strategies only; capped at ``MAX_STEPS_PER_CALL``).

        User-supplied ``batches`` are double-buffered by default: a
        background thread runs the host path (decode/gather) and the
        H2D ``shard_batch`` for batch i+1 while step i executes on
        device — the reference's zero-copy staging + in-trace gather
        overlap (``dlrm.cu:20-50``, ``dlrm.cc:151-156``).  ``prefetch``
        sets the queue depth (0 restores the synchronous path; a
        ``PrefetchLoader`` passed in is used as-is, caller-owned).

        With ``checkpoint`` (a ``CheckpointManager``) the run resumes
        from the latest saved step when ``resume`` and saves every
        ``save_every`` steps plus once at the end — the crash-recovery
        subsystem the reference lacks entirely (SURVEY.md §5).

        With ``config.telemetry_dir`` (``--telemetry DIR``) the run
        writes a JSONL event stream — per-step/superstep wall time,
        fences, losses, checkpoint I/O — and a telemetry summary
        (fences/step, step-time p50/p95/max) folds into the returned
        stats under ``"telemetry"`` (OBSERVABILITY.md).  Off = zero
        overhead, stats and numerics bit-identical."""
        with _telemetry.maybe_run(self.ex.config):
            if isinstance(self.ex, PipelineExecutor) and accum_steps > 1:
                # Pipeline gradient accumulation is lowered at executor
                # construction (accum groups x m microbatches == a*m
                # microbatches); the trainer must not stack again.
                if accum_steps != self.ex.accum_steps:
                    raise ValueError(
                        f"accum_steps={accum_steps} on a layer-wise "
                        f"strategy must be lowered at construction: "
                        f"build the PipelineExecutor (or make_executor) "
                        f"with accum_steps={accum_steps} (this one has "
                        f"accum_steps={self.ex.accum_steps})"
                    )
                accum_steps = 1
            if steps_per_call > 1:
                if (isinstance(self.ex, PipelineExecutor)
                        and not self.ex.superstep_fused):
                    # Host-driven layer-wise strategies cannot FUSE k
                    # steps into one scan (per-stage host dispatch), but
                    # the host fence amortizes the same way: k steps
                    # dispatch back-to-back with ONE device_get per
                    # superstep.  The compiled pipeline step
                    # (--pipeline-compiled) takes the fused path below
                    # instead.
                    return self._fit_superstep_pipeline(
                        iterations, batches, warmup, log_every, checkpoint,
                        save_every, resume, accum_steps, prefetch,
                        steps_per_call,
                    )
                return self._fit_superstep(
                    iterations, batches, warmup, log_every, checkpoint,
                    save_every, resume, accum_steps, prefetch,
                    steps_per_call,
                )
            return self._fit_plain(
                iterations, batches, warmup, log_every, checkpoint,
                save_every, resume, accum_steps, prefetch,
            )

    def _fit_plain(
        self,
        iterations: int,
        batches,
        warmup: int,
        log_every: int,
        checkpoint,
        save_every: int,
        resume: bool,
        accum_steps: int,
        prefetch: int,
    ) -> Dict[str, float]:
        """The per-step (k=1) training loop — see :meth:`fit`."""
        tel = _telemetry.current()
        ex = self.ex
        if accum_steps > 1:
            accum_fn = ex.accum_train_step(accum_steps)
            step_fn = lambda p, o, s, b: accum_fn(
                p, o, s, ex.stack_microbatches(b, accum_steps)
            )
        else:
            step_fn = ex.train_step
        params, opt_state, state = ex.init()
        start_step = 0
        if checkpoint is not None and resume:
            if checkpoint.latest_step() is not None:
                start_step, params, opt_state, state = checkpoint.restore(
                    templates=(params, opt_state, state)
                )
                print(f"resumed from step {start_step}")
        batches, owned_prefetch = self._batch_source(
            batches, warmup + iterations, prefetch
        )
        # Input-starvation gauges (PrefetchLoader h2d queue + any nested
        # StreamingLoader reader queue); None for synthetic/sync paths.
        depth_fn = getattr(batches, "queue_depths", None)

        # Preemption (SIGTERM/SIGINT) with a checkpoint attached: finish
        # the in-flight step, save at the boundary, exit cleanly so a
        # restarted run resumes (resilience.PreemptionHandler; imported
        # lazily — resilience imports this module for the fence cap).
        from flexflow_tpu.runtime.resilience import PreemptionHandler

        preempt = PreemptionHandler(install=checkpoint is not None).__enter__()
        try:
            # Warmup (compile) outside the timed region — the reference's
            # init_layers()+first-iteration cuDNN algo search equivalent.
            # Warmup steps are REAL optimizer updates (train_step donates its
            # inputs, so they can't be discarded); count them in the step
            # numbering so checkpoint steps always equal applied updates.
            m = None
            for _ in range(warmup):
                batch = next(batches)
                params, opt_state, state, m = step_fn(params, opt_state, state, batch)
            start_step += warmup
            if m is not None:
                # host readback: the fence (waits for the program)
                tel.fence(m, "warmup")

            assert iterations > 0, "fit() needs at least one iteration"
            trace_ctx = contextlib.nullcontext()
            if ex.config.trace_dir:
                # --trace DIR: XProf capture of the timed loop (the fused
                # step as XLA runs it — the observability the reference's
                # per-task cudaEvent prints could not give).
                from flexflow_tpu.runtime.profiler import trace

                trace_ctx = trace(ex.config.trace_dir)
            ckpt_s = 0.0  # checkpoint I/O time, excluded from throughput
            with trace_ctx:
                # Both timestamps live INSIDE the trace context so neither
                # start_trace spin-up nor stop_trace serialization is
                # billed to the timed loop.
                start = time.perf_counter()
                t_prev = start
                for it in range(iterations):
                    if tel.enabled:
                        # Steady-state input wait: how long this pull
                        # blocked the loop (0 when the prefetch queue
                        # had a batch staged).  Host-side timing only —
                        # no fence, zero cost when telemetry is off.
                        t_in = time.perf_counter()
                        batch = next(batches)
                        tel.record_input_wait(
                            start_step + it, time.perf_counter() - t_in,
                            **(depth_fn() if depth_fn else {}))
                    else:
                        batch = next(batches)
                    if it == 0 and tel.enabled:
                        # program_cost at first (timed) dispatch: XLA's
                        # static flops/bytes for the step program —
                        # lowering only, the args are not consumed.
                        if accum_steps > 1:
                            tel.program_cost(
                                "accum_step", accum_fn,
                                (params, opt_state, state,
                                 ex.stack_microbatches(batch, accum_steps)),
                                accum_steps=accum_steps)
                        else:
                            tel.program_cost(
                                "train_step", step_fn,
                                (params, opt_state, state, batch))
                    # StepTraceAnnotation: XProf device timelines group
                    # by train step, so --trace captures correlate with
                    # the telemetry JSONL's step events (no-op unless a
                    # profiler trace is active).
                    with StepTraceAnnotation("train",
                                             step_num=start_step + it):
                        params, opt_state, state, m = step_fn(
                            params, opt_state, state, batch
                        )
                    if tel.enabled:
                        # Host-side per-step wall time: in this unfenced
                        # regime it is the DISPATCH time (the loop never
                        # blocks on the device) — the percentile feed,
                        # no extra device_get.
                        now = time.perf_counter()
                        tel.record_step(start_step + it, wall_s=now - t_prev)
                        t_prev = now
                    if log_every and (it + 1) % log_every == 0:
                        self.metrics.update(tel.fence(m, "log"))
                        print(f"iter {it+1}: {self.metrics.report()}")
                        t_prev = time.perf_counter()  # drain not a step time
                    if checkpoint is not None and save_every and (it + 1) % save_every == 0:
                        # fence: don't bill queued compute to I/O
                        tel.fence(m, "pre_save")
                        t0 = time.perf_counter()
                        checkpoint.save(start_step + it + 1, params, opt_state, state)
                        ckpt_s += time.perf_counter() - t0
                        t_prev = time.perf_counter()  # I/O not a step time
                    if preempt.triggered:
                        break  # emergency save below, then clean exit
                completed = it + 1
                # The execution fence (dlrm.cc:159-162): a host readback of
                # the final step's metrics; the step chain serializes
                # through params.  elapsed is taken here, INSIDE the trace
                # context, so stop_trace's xplane serialization is not
                # billed to the timed loop.
                final_m = tel.fence(m, "final")
                elapsed = time.perf_counter() - start - ckpt_s

            if ex.config.trace_dir and tel.enabled:
                # Device-time attribution: read the .xplane.pb the block
                # above just wrote into run_end's trace_summary.
                tel.attach_trace_summary(ex.config.trace_dir)
            self.metrics.update(final_m)
            if checkpoint is not None:
                checkpoint.save(start_step + completed, params, opt_state, state)
                if hasattr(checkpoint, "wait_until_finished"):
                    checkpoint.wait_until_finished()  # durable before exit
                if preempt.triggered:
                    print(f"preempted: emergency checkpoint at step "
                          f"{start_step + completed}, exiting cleanly")
            if ex.config.profiling:
                # --profiling: per-op breakdown, the reference's per-task
                # cudaEvent timings (conv_2d.cu:515-546).
                if isinstance(ex, Executor):
                    from flexflow_tpu.runtime.profiler import profile_ops, report

                    print(report(profile_ops(ex, params, state, batch)))
                else:
                    print("profiling: per-op breakdown unavailable for "
                          "pipeline executors")
            batch_size = ex.model.input_tensors[0].shape[0]
            throughput = completed * batch_size / elapsed
            # Reference printout formulas (cnn.cc:128-129, dlrm.cc:165-166).
            print(f"time = {elapsed:.4f}s")
            print(f"tp = {throughput:.2f} samples/s")
            #: Public contract: the trained (params, opt_state, state) of
            #: the run that just finished — for post-training evaluation
            #: or manual checkpointing.
            self.final = (params, opt_state, state)
            stats = {
                "elapsed_s": elapsed,
                "samples_per_s": throughput,
                "iterations": completed,
                "batch_size": batch_size,
                "loss": float(self.metrics.avg_loss),
            }
            if preempt.triggered:
                tel.emit("preempt", step=start_step + completed,
                         signum=preempt.signum)
                stats["preempted"] = True
                stats["checkpoint_step"] = start_step + completed
            return tel.fold_stats(stats)
        finally:
            preempt.__exit__(None, None, None)
            if owned_prefetch is not None:
                owned_prefetch.close()

    def _fit_superstep(
        self,
        iterations: int,
        batches,
        warmup: int,
        log_every: int,
        checkpoint,
        save_every: int,
        resume: bool,
        accum_steps: int,
        prefetch: int,
        k: int,
    ) -> Dict[str, float]:
        """Superstep training loop: K steps per compiled dispatch.

        The measurement protocol is :meth:`fit`'s (fenced timed region,
        checkpoint I/O excluded), but the fence granularity is one host
        readback of the stacked per-step metrics PER SUPERSTEP — the
        amortization win, bounded by ``MAX_STEPS_PER_CALL``.
        The next stacked batch double-buffers through ``PrefetchLoader``
        while the current superstep runs on device.

        Accounting deviation from the k=1 path, by design: warmup
        ROUNDS UP to whole supersteps — ``ceil(warmup/k)`` calls of the
        SAME compiled k-program, i.e. ``ceil(warmup/k)*k`` real updates
        and batches — because the warmup call is what keeps the timed
        program's compile outside the timed region (a warmup-sized scan
        would compile a different program and leave the k-program's
        compile inside the measurement).  Checkpoint step numbers still
        equal applied updates.  Finite ``batches`` iterables must be
        sized for this contract; exhaustion raises a ValueError naming
        the required count instead of dying mid-loop.  A non-divisible
        ``iterations`` tail runs as one shorter superstep (a second
        compile — prefer ``iterations % k == 0``).
        """
        tel = _telemetry.current()
        ex = self.ex
        if not getattr(ex, "superstep_fused", False):
            raise ValueError(
                "fused steps_per_call > 1 requires the full-mesh "
                "Executor or the compiled pipeline step "
                "(--pipeline-compiled); host-driven layer-wise "
                "strategies dispatch per-stage programs the superstep "
                "scan cannot fuse — they take the fence-amortized path"
            )
        assert iterations > 0, "fit() needs at least one iteration"
        k = clamp_fused_steps(k)
        step_fns = {k: ex.build_superstep(k, accum_steps)}
        params, opt_state, state = ex.init()
        start_step = 0
        if checkpoint is not None and resume:
            if checkpoint.latest_step() is not None:
                start_step, params, opt_state, state = checkpoint.restore(
                    templates=(params, opt_state, state)
                )
                print(f"resumed from step {start_step}")

        warm_calls = -(-warmup // k) if warmup > 0 else 0
        if warm_calls and warm_calls * k != warmup:
            _log.info(
                "steps_per_call=%d: warmup rounded up from %d to %d steps "
                "(%d supersteps)", k, warmup, warm_calls * k, warm_calls,
            )
        plan = [k] * (warm_calls + iterations // k)
        if iterations % k:
            plan.append(iterations % k)
        total_steps = sum(plan)

        from flexflow_tpu.data.loader import PrefetchLoader

        owned_prefetch = None
        # Captured before the grouping wrappers below hide the source;
        # an owned loader overrides it further down.
        depth_fn = getattr(batches, "queue_depths", None)
        if batches is None:
            host = self._synthetic_host_batch()
            fixed: Dict[int, Any] = {}

            def synth():
                for n in plan:
                    if n not in fixed:
                        fixed[n] = ex.stack_steps([host] * n, accum_steps)
                    yield fixed[n]

            batches = synth()
        else:
            src = iter(batches)

            def groups():
                done = 0
                for n in plan:
                    g = []
                    for _ in range(n):
                        try:
                            g.append(next(src))
                        except StopIteration:
                            raise ValueError(
                                f"batches exhausted after {done} steps; "
                                f"steps_per_call={k} needs "
                                f"ceil(warmup/k)*k + iterations = "
                                f"{total_steps} batches (warmup rounds "
                                f"up to whole supersteps)"
                            ) from None
                        done += 1
                    yield g

            place = lambda g: ex.stack_steps(g, accum_steps)
            if isinstance(batches, PrefetchLoader):
                # Caller-owned loader: it already overlaps host work +
                # placement on its own thread; stack device-to-device
                # synchronously rather than spinning a second loader
                # thread that would re-place every batch.
                batches = (place(g) for g in groups())
            elif prefetch > 0:
                owned_prefetch = PrefetchLoader(groups(), place, depth=prefetch)
                batches = iter(owned_prefetch)
                depth_fn = owned_prefetch.queue_depths
            else:
                batches = (place(g) for g in groups())

        from flexflow_tpu.runtime.resilience import PreemptionHandler

        preempt = PreemptionHandler(install=checkpoint is not None).__enter__()
        try:
            ms = None
            for _ in range(warm_calls):
                superbatch = next(batches)
                params, opt_state, state, ms = step_fns[k](
                    params, opt_state, state, superbatch
                )
                if isinstance(ex, PipelineExecutor):
                    ex.note_fused_dispatch(k)
            start_step += warm_calls * k
            if ms is not None:
                tel.fence(ms, "warmup")  # compile outside the timed loop

            trace_ctx = contextlib.nullcontext()
            if ex.config.trace_dir:
                from flexflow_tpu.runtime.profiler import trace

                trace_ctx = trace(ex.config.trace_dir)
            ckpt_s = 0.0
            timed = plan[warm_calls:]
            steps_done = 0
            superbatch = None
            with trace_ctx:
                start = time.perf_counter()
                for n in timed:
                    if n not in step_fns:
                        step_fns[n] = ex.build_superstep(n, accum_steps)
                    t_call = time.perf_counter()
                    if tel.enabled:
                        superbatch = next(batches)
                        tel.record_input_wait(
                            start_step + steps_done,
                            time.perf_counter() - t_call,
                            **(depth_fn() if depth_fn else {}))
                    else:
                        superbatch = next(batches)
                    if steps_done == 0 and tel.enabled:
                        tel.program_cost(
                            "superstep", step_fns[n],
                            (params, opt_state, state, superbatch), k=n)
                    with StepTraceAnnotation("superstep",
                                             step_num=start_step + steps_done):
                        params, opt_state, state, ms = step_fns[n](
                            params, opt_state, state, superbatch
                        )
                        # ONE host readback per superstep: the execution
                        # fence AND the stacked per-step metrics,
                        # unstacked so the loss curve is bit-identical
                        # to k=1.
                        host_ms = tel.fence(ms, "superstep")
                    wall = time.perf_counter() - t_call
                    if isinstance(ex, PipelineExecutor):
                        # Compiled pipeline: ONE host program covered n
                        # steps — programs/step honestly reads 1/k.
                        ex.note_fused_dispatch(n)
                    if tel.enabled:
                        tel.emit("superstep", k=n, mode="fused",
                                 wall_s=round(wall, 6),
                                 first_step=start_step + steps_done)
                    for j in range(n):
                        row = Executor.metrics_row(host_ms, j)
                        if tel.enabled:
                            loss = row.get("train_loss")
                            tel.record_step(
                                start_step + steps_done,
                                loss=None if loss is None else float(loss),
                                wall_s=wall / n,
                            )
                        self.metrics.update(row)
                        steps_done += 1
                        if log_every and steps_done % log_every == 0:
                            print(f"iter {steps_done}: {self.metrics.report()}")
                    if (
                        checkpoint is not None and save_every
                        and steps_done // save_every
                        > (steps_done - n) // save_every
                    ):
                        # Superstep granularity: save at the first
                        # boundary past each save_every multiple.
                        t0 = time.perf_counter()
                        checkpoint.save(
                            start_step + steps_done, params, opt_state, state
                        )
                        ckpt_s += time.perf_counter() - t0
                    if preempt.triggered:
                        break  # emergency save at this superstep boundary
                elapsed = time.perf_counter() - start - ckpt_s

            if ex.config.trace_dir and tel.enabled:
                tel.attach_trace_summary(ex.config.trace_dir)
            if checkpoint is not None:
                checkpoint.save(start_step + steps_done, params, opt_state, state)
                if hasattr(checkpoint, "wait_until_finished"):
                    checkpoint.wait_until_finished()  # durable before exit
                if preempt.triggered:
                    print(f"preempted: emergency checkpoint at step "
                          f"{start_step + steps_done}, exiting cleanly")
            if ex.config.profiling:
                if isinstance(ex, PipelineExecutor):
                    print("profiling: per-op breakdown unavailable for "
                          "pipeline executors")
                else:
                    from flexflow_tpu.runtime.profiler import (
                        profile_ops,
                        report,
                    )

                    one = {
                        key: (
                            v[0].reshape((-1,) + v.shape[3:])
                            if accum_steps > 1 else v[0]
                        )
                        for key, v in superbatch.items()
                    }
                    print(report(profile_ops(ex, params, state, one)))
            batch_size = ex.model.input_tensors[0].shape[0]
            throughput = steps_done * batch_size / elapsed
            print(f"time = {elapsed:.4f}s")
            print(f"tp = {throughput:.2f} samples/s")
            self.final = (params, opt_state, state)
            stats = {
                "elapsed_s": elapsed,
                "samples_per_s": throughput,
                "iterations": steps_done,
                "batch_size": batch_size,
                "loss": float(self.metrics.avg_loss),
                "steps_per_call": k,
                "supersteps": len(timed),
            }
            if preempt.triggered:
                tel.emit("preempt", step=start_step + steps_done,
                         signum=preempt.signum)
                stats["preempted"] = True
                stats["checkpoint_step"] = start_step + steps_done
            return tel.fold_stats(stats)
        finally:
            preempt.__exit__(None, None, None)
            if owned_prefetch is not None:
                owned_prefetch.close()

    def _fit_superstep_pipeline(
        self,
        iterations: int,
        batches,
        warmup: int,
        log_every: int,
        checkpoint,
        save_every: int,
        resume: bool,
        accum_steps: int,
        prefetch: int,
        k: int,
    ) -> Dict[str, float]:
        """Fence-amortized supersteps over the layer-wise pipeline.

        The full-mesh superstep fuses K steps into ONE compiled scan;
        the pipeline's step is host-orchestrated per-stage dispatch and
        cannot fuse (``StrategyStore.superstep_mode() == "amortized"``)
        — but the HOST FENCE amortizes identically: K ``train_step``
        dispatches run back-to-back and their per-step metrics come
        back in ONE ``jax.device_get`` per superstep — the round trip
        being amortized (its cost is not measured on the chip yet,
        ROADMAP A2).  The dependent program chain between fences is
        ``k`` steps long (each ``2*S*ceil(m/c)`` programs), so the
        ``MAX_STEPS_PER_CALL`` bound applies unchanged — pair a large
        ``k`` with a pipeline ``chunk`` to keep the chain short.

        Honest limit: with ``clip_norm > 0`` the global-norm fetch
        inside ``train_step`` is a per-step fence — the floor is one
        fence per STEP, not per superstep, and a loud warning says so
        rather than silently serializing.

        Unlike the fused path, warmup needs NO rounding (there is no
        k-sized compiled program whose compile must stay outside the
        timed region), so finite ``batches`` keep the k=1 contract:
        ``warmup + iterations`` batches.
        """
        tel = _telemetry.current()
        ex = self.ex
        assert iterations > 0, "fit() needs at least one iteration"
        if accum_steps > 1:
            raise ValueError(
                "accum_steps composes with full-mesh strategies only; "
                "pipeline strategies microbatch via microbatches="
            )
        k = clamp_fused_steps(k)
        if ex.config.clip_norm > 0.0:
            _log.warning(
                "steps_per_call=%d with clip_norm=%g: the global-norm "
                "fetch is a per-step fence, so dispatch amortizes but "
                "the fence does not (one-fence-per-step floor)",
                k, ex.config.clip_norm,
            )
        params, opt_state, state = ex.init()
        start_step = 0
        if checkpoint is not None and resume:
            if checkpoint.latest_step() is not None:
                start_step, params, opt_state, state = checkpoint.restore(
                    templates=(params, opt_state, state)
                )
                print(f"resumed from step {start_step}")

        batches, owned_prefetch = self._batch_source(
            batches, warmup + iterations, prefetch
        )
        depth_fn = getattr(batches, "queue_depths", None)

        from flexflow_tpu.runtime.resilience import PreemptionHandler

        preempt = PreemptionHandler(install=checkpoint is not None).__enter__()
        try:
            m = None
            for _ in range(warmup):
                batch = next(batches)
                params, opt_state, state, m = ex.train_step(
                    params, opt_state, state, batch
                )
            start_step += warmup
            if m is not None:
                tel.fence(m, "warmup")  # compiles outside the timed loop

            trace_ctx = contextlib.nullcontext()
            if ex.config.trace_dir:
                from flexflow_tpu.runtime.profiler import trace

                trace_ctx = trace(ex.config.trace_dir)
            ckpt_s = 0.0
            steps_done = 0
            supersteps = 0
            with trace_ctx:
                start = time.perf_counter()
                while steps_done < iterations:
                    n = min(k, iterations - steps_done)
                    t_call = time.perf_counter()
                    ms = []
                    walls = []
                    for i in range(n):
                        t_disp = time.perf_counter()
                        if tel.enabled:
                            batch = next(batches)
                            tel.record_input_wait(
                                start_step + steps_done + i,
                                time.perf_counter() - t_disp,
                                **(depth_fn() if depth_fn else {}))
                        else:
                            batch = next(batches)
                        with StepTraceAnnotation(
                            "train", step_num=start_step + steps_done + i
                        ):
                            params, opt_state, state, m = ex.train_step(
                                params, opt_state, state, batch
                            )
                        walls.append(time.perf_counter() - t_disp)
                        ms.append(m)
                    # ONE host readback per superstep: all n steps'
                    # metrics — the fence AND the amortization.
                    host_ms = tel.fence(ms, "superstep")
                    if tel.enabled:
                        tel.emit("superstep", k=n, mode="amortized",
                                 wall_s=round(time.perf_counter() - t_call, 6),
                                 first_step=start_step + steps_done,
                                 programs_per_step=len(ex.last_schedule))
                    supersteps += 1
                    # Read the preemption flag AFTER the fence, so a
                    # signal landing mid-superstep still exits at THIS
                    # boundary.
                    trig = preempt.triggered
                    for i, hm in enumerate(host_ms):
                        if tel.enabled:
                            loss = hm.get("train_loss")
                            tel.record_step(
                                start_step + steps_done,
                                loss=None if loss is None else float(loss),
                                wall_s=walls[i],
                            )
                        self.metrics.update(hm)
                        steps_done += 1
                        if log_every and steps_done % log_every == 0:
                            print(f"iter {steps_done}: "
                                  f"{self.metrics.report()}")
                    if (
                        checkpoint is not None and save_every
                        and steps_done // save_every
                        > (steps_done - n) // save_every
                    ):
                        t0 = time.perf_counter()
                        checkpoint.save(
                            start_step + steps_done, params, opt_state,
                            state,
                        )
                        ckpt_s += time.perf_counter() - t0
                    if trig:
                        break  # emergency save at this boundary
                elapsed = time.perf_counter() - start - ckpt_s

            if ex.config.trace_dir and tel.enabled:
                tel.attach_trace_summary(ex.config.trace_dir)
            if checkpoint is not None:
                checkpoint.save(
                    start_step + steps_done, params, opt_state, state
                )
                if hasattr(checkpoint, "wait_until_finished"):
                    checkpoint.wait_until_finished()
                if preempt.triggered:
                    print(f"preempted: emergency checkpoint at step "
                          f"{start_step + steps_done}, exiting cleanly")
            if ex.config.profiling:
                print("profiling: per-op breakdown unavailable for "
                      "pipeline executors")
            batch_size = ex.model.input_tensors[0].shape[0]
            throughput = steps_done * batch_size / elapsed
            print(f"time = {elapsed:.4f}s")
            print(f"tp = {throughput:.2f} samples/s")
            self.final = (params, opt_state, state)
            stats = {
                "elapsed_s": elapsed,
                "samples_per_s": throughput,
                "iterations": steps_done,
                "batch_size": batch_size,
                "loss": float(self.metrics.avg_loss),
                "steps_per_call": k,
                "supersteps": supersteps,
            }
            if preempt.triggered:
                tel.emit("preempt", step=start_step + steps_done,
                         signum=preempt.signum)
                stats["preempted"] = True
                stats["checkpoint_step"] = start_step + steps_done
            return tel.fold_stats(stats)
        finally:
            preempt.__exit__(None, None, None)
            if owned_prefetch is not None:
                owned_prefetch.close()

    def evaluate(
        self,
        params,
        state,
        batches: Iterable[Dict[str, Any]],
        iterations: Optional[int] = None,
    ) -> Dict[str, float]:
        """Held-out evaluation over ``batches`` (host or device dicts);
        returns mean loss and accuracy.  The reference computes metrics
        only inside the training backward (``mse_loss.cu:61-112``); a
        read-only eval pass is this rebuild's addition."""
        ex = self.ex
        pm = PerfMetrics()
        for it, batch in enumerate(batches):
            if iterations is not None and it >= iterations:
                break
            _, m = ex.eval_step(params, state, ex.shard_batch(batch))
            pm.update(jax.device_get(m))
        return {
            "loss": pm.avg_loss,
            "accuracy": pm.accuracy,
            "batches": pm.steps,
        }
