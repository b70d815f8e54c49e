"""Failure detection and elastic recovery.

The reference has NO failure handling: ``FatalError`` aborts the whole
process (``cuda_helper.h:5-11``), there is no retry and no
checkpoint-restart (SURVEY.md §5).  This subsystem is built from
scratch for the TPU rebuild (failure model + recovery decision matrix:
RESILIENCE.md):

- **Failure detection** — three classes: *raised* failures
  (device/runtime errors escaping the jitted step), *silent* failures
  (non-finite loss: divergence, bad batch, flipped bits), and
  *preemption* (SIGTERM/SIGINT from the scheduler).
- **Recovery** — restore the latest checkpoint through
  :class:`~flexflow_tpu.runtime.checkpoint.CheckpointManager` (whose
  restores are sharding-portable and tolerate torn snapshots),
  optionally rebuild the executor via a user factory (fresh mesh/
  compile after a backend fault), and resume; a restart budget bounds
  crash loops.  Batches come from ``batch_fn(step)``, so replayed
  steps are deterministic and the recovered loss trajectory is
  bit-identical to an unfaulted run.
- **Superstep composition** — ``fit(steps_per_call=k)`` drives
  :meth:`Executor.build_superstep`: K steps per compiled dispatch, ONE
  host fence per superstep, and the stacked per-step metrics scanned
  at that fence for the first non-finite step (max loss on rollback =
  the steps since the last save, never more than one fence's worth of
  undetected divergence).
- **Fault injection** — :class:`FaultInjector`, a first-class chaos
  harness: scheduled raised faults, NaN-in-batch, NaN-in-loss,
  self-preemption, and checkpoint corruption, mirroring how the
  reference's DISABLE_COMPUTATION builds exercise machinery without
  compute (bare ``callable(step)`` hooks are still accepted).
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
import shutil
import signal
import time
from typing import Any, Callable, Dict, Iterable, Optional, Union

import jax
import numpy as np

from flexflow_tpu.runtime import telemetry as _telemetry
from flexflow_tpu.runtime.checkpoint import CheckpointManager
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.trainer import (
    MAX_STEPS_PER_CALL,
    clamp_fused_steps,
)

logger = logging.getLogger("ff.resilience")


@dataclasses.dataclass
class FailurePolicy:
    """What counts as a failure and how hard to try to recover."""

    max_restarts: int = 3
    rollback_on_nonfinite: bool = True
    backoff_s: float = 0.0
    # Exception types treated as recoverable; everything else re-raises.
    # Deliberately narrow: ValueError/TypeError/KeyError/AssertionError
    # are programmer errors (bad shapes, wrong keys, broken configs) —
    # replaying them from a checkpoint reproduces the same crash until
    # the restart budget is exhausted, which buries the actual
    # traceback under max_restarts replays.  Those must surface
    # immediately (pinned by tests/test_resilience.py).
    recoverable: tuple = (RuntimeError, OSError)
    # Classifier for failures that are recoverable BY TYPE but cannot
    # be recovered in-process: a True verdict re-raises immediately
    # instead of burning the restart budget on doomed replays.  The
    # elastic rig installs ``elastic.classify_world_failure`` here — a
    # gloo peer-loss surfaces as XlaRuntimeError (a RuntimeError), yet
    # every in-process retry re-enters the same dead world; the
    # SUPERVISOR must resize, so the process's job is to exit fast
    # (RESILIENCE.md "Host loss & elastic resize").
    fatal: Optional[Callable[[BaseException], bool]] = None


class StepFailure(RuntimeError):
    """A detected silent failure (e.g. non-finite loss)."""


class PreemptionHandler:
    """SIGTERM/SIGINT → a flag the train loop checks at step/superstep
    boundaries (the analogue of a cloud scheduler's grace window): the
    loop finishes the in-flight dispatch, validates it, writes an
    emergency checkpoint, and exits cleanly so the restarted job
    resumes exactly where it stopped.

    A second SIGINT restores default handling (an impatient ^C^C still
    kills).  Installing handlers is only possible on the main thread;
    elsewhere the handler degrades to never-triggered.
    """

    def __init__(self, install: bool = True,
                 signals: Iterable[int] = (signal.SIGTERM, signal.SIGINT)):
        self._install = install
        self._signals = tuple(signals)
        self._previous: Dict[int, Any] = {}
        self.triggered = False
        self.signum: Optional[int] = None

    def _on_signal(self, signum, frame):
        if self.triggered and signum == signal.SIGINT:
            self._restore()
            raise KeyboardInterrupt
        self.triggered = True
        self.signum = signum
        logger.warning(
            "received signal %d: emergency checkpoint at the next "
            "step/superstep boundary, then clean exit", signum,
        )

    def __enter__(self) -> "PreemptionHandler":
        if self._install:
            try:
                for s in self._signals:
                    self._previous[s] = signal.signal(s, self._on_signal)
            except ValueError:  # not the main thread
                logger.info("signal handlers unavailable off the main "
                            "thread; preemption handling disabled")
                self._previous = {}
        return self

    def _restore(self) -> None:
        for s, h in self._previous.items():
            signal.signal(s, h)
        self._previous = {}

    def __exit__(self, *exc) -> None:
        self._restore()


class FaultInjector:
    """First-class scheduled chaos for tests and ``tools/chaos_smoke.py``.

    Every mode is one-shot per scheduled step — the fault fires on the
    first visit and disarms — so the deterministic replay after a
    rollback sees a clean step and the recovered trajectory can be
    compared bit-for-bit against an unfaulted run.

    Modes (all keyed by global step index):

    - ``raise_at``: ``{step: exception}`` (or an iterable of steps,
      raising ``RuntimeError``) raised host-side before the step runs —
      the raised-failure class (device faults, preempted workers).
    - ``nan_batch_at``: every float input of that step's batch becomes
      NaN — a silent failure detected at the loss fence.
    - ``nan_loss_at``: the host-read loss of that step is replaced with
      NaN — silent divergence without touching device numerics.
    - ``preempt_at``: SIGTERM to the own process before the step —
      drives the emergency-save path end to end.
    - ``corrupt_checkpoint_at``: after the first save at/after that
      step, the newest snapshot's payload is destroyed — the
      torn-checkpoint fallback class.
    """

    def __init__(
        self,
        raise_at: Union[Dict[int, BaseException], Iterable[int], None] = None,
        nan_batch_at: Iterable[int] = (),
        nan_loss_at: Iterable[int] = (),
        preempt_at: Iterable[int] = (),
        corrupt_checkpoint_at: Iterable[int] = (),
    ):
        if raise_at is None:
            raise_at = {}
        elif not isinstance(raise_at, dict):
            raise_at = {
                s: RuntimeError(f"injected fault at step {s}") for s in raise_at
            }
        self.raise_at = dict(raise_at)
        self.nan_batch_at = set(nan_batch_at)
        self.nan_loss_at = set(nan_loss_at)
        self.preempt_at = set(preempt_at)
        self.corrupt_checkpoint_at = set(corrupt_checkpoint_at)
        #: Log of (mode, step) pairs actually fired, for assertions.
        self.fired = []

    def _fire(self, mode: str, step: int) -> None:
        """Record one fired fault — and report it to run telemetry, so
        a chaos run's JSONL carries fault→rollback→replay in order."""
        self.fired.append((mode, step))
        _telemetry.current().emit("fault", mode=mode, step=int(step))

    # -- hooks the resilient loop drives -----------------------------------

    def before_step(self, step: int) -> None:
        """Host-side, before the step's batch is assembled."""
        if step in self.preempt_at:
            self.preempt_at.discard(step)
            self._fire("preempt", step)
            os.kill(os.getpid(), signal.SIGTERM)
        if step in self.raise_at:
            exc = self.raise_at.pop(step)
            self._fire("raise", step)
            raise exc

    def poison_batch(self, step: int, batch: Dict[str, Any]) -> Dict[str, Any]:
        if step not in self.nan_batch_at:
            return batch
        self.nan_batch_at.discard(step)
        self._fire("nan_batch", step)
        return {
            k: np.full_like(v, np.nan)
            if isinstance(v, np.ndarray) and np.issubdtype(v.dtype, np.floating)
            else v
            for k, v in batch.items()
        }

    def poison_loss(self, step: int, loss: float) -> float:
        if step not in self.nan_loss_at:
            return loss
        self.nan_loss_at.discard(step)
        self._fire("nan_loss", step)
        return float("nan")

    def after_save(self, step: int, checkpoint: CheckpointManager) -> None:
        """Called after each periodic save completes (scheduling-wise;
        the save itself may still be flushing asynchronously)."""
        due = {s for s in self.corrupt_checkpoint_at if s <= step}
        if not due:
            return
        self.corrupt_checkpoint_at -= due
        self._fire("corrupt", step)
        self.corrupt(checkpoint)

    @staticmethod
    def corrupt(checkpoint: CheckpointManager) -> None:
        """Destroy the newest snapshot's payload in place (local
        directories only) — the torn/half-deleted directory the restore
        fallback must survive."""
        checkpoint.wait_until_finished()
        step = checkpoint.latest_step()
        if step is None or "://" in checkpoint.directory:
            return
        payload = os.path.join(checkpoint.directory, str(step), "params")
        if os.path.isdir(payload):
            shutil.rmtree(payload)
            logger.warning("chaos: corrupted checkpoint step %d", step)
        checkpoint.reload()  # drop the manager's cached metadata

    @classmethod
    def wrap(cls, obj) -> "FaultInjector":
        """Normalize the ``fault_injector`` argument: None → inert
        injector, FaultInjector → itself, bare ``callable(step)`` →
        adapter firing it in :meth:`before_step` (the seed API)."""
        if obj is None:
            return cls()
        if isinstance(obj, cls):
            return obj
        return _CallableInjector(obj)


class _CallableInjector(FaultInjector):
    def __init__(self, fn: Callable[[int], None]):
        super().__init__()
        self._fn = fn

    def before_step(self, step: int) -> None:
        self._fn(step)


class ResilientTrainer:
    """Checkpointed train loop that survives step failures and
    preemption, on both the per-step and the superstep execution path.

    ``executor_factory`` rebuilds the Executor after a raised failure
    (a fresh factory call re-jits against a healthy backend); plain
    rollbacks reuse the existing executor.
    """

    def __init__(
        self,
        executor_factory: Callable[[], Executor],
        checkpoint: CheckpointManager,
        policy: Optional[FailurePolicy] = None,
        fault_injector: Union[FaultInjector, Callable[[int], None], None] = None,
    ):
        self.executor_factory = executor_factory
        self.checkpoint = checkpoint
        self.policy = policy or FailurePolicy()
        self.fault_injector = fault_injector
        # restarts = consecutive failures since the last durable
        # progress (the crash-loop budget); total_restarts = lifetime.
        self.restarts = 0
        self.total_restarts = 0
        #: The executor of the finished (or failed) fit, for post-run
        #: evaluation against the returned params/state.
        self.executor: Optional[Executor] = None

    # -- internals ---------------------------------------------------------

    def _fresh_state(self, ex: Executor, seed: int, loader=None,
                     initial: bool = False):
        params, opt_state, state = ex.init(seed=seed)
        try:
            if loader is not None:
                from flexflow_tpu.data.stream import loader_state_template

                step, params, opt_state_r, state_r, ls = (
                    self.checkpoint.restore(
                        templates=(params, opt_state, state),
                        loader_template=loader_state_template(),
                    )
                )
                if ls is not None:
                    # Rewind the streaming loader to the snapshot's
                    # cursor: replayed steps re-pull the exact batches
                    # (deterministic replay through the data plane).
                    loader.load_state_dict(ls)
                else:
                    logger.warning(
                        "checkpoint step %d carries no loader item "
                        "(pre-streaming snapshot); rewinding the "
                        "streaming loader to its start — replayed "
                        "batches may differ from the original run", step,
                    )
                    loader.load_state_dict(self._loader_origin)
            else:
                step, params, opt_state_r, state_r = self.checkpoint.restore(
                    templates=(params, opt_state, state)
                )
            logger.info("resumed from checkpoint step %d", step)
            return step, params, (
                opt_state_r if opt_state_r is not None else opt_state
            ), (state_r or state)
        except FileNotFoundError:
            if loader is not None and not initial:
                # No snapshot yet: recovery replays from step 0, so the
                # loader rewinds to its construction-time cursor.  The
                # INITIAL call skips this — the loader is already there,
                # and rewinding would pointlessly tear down its reader
                # thread (discarding prefetched windows).
                loader.load_state_dict(self._loader_origin)
            return 0, params, opt_state, state

    def _recover(self, ex: Optional[Executor], seed: int, why: BaseException,
                 loader=None):
        self.restarts += 1
        self.total_restarts += 1
        if self.restarts > self.policy.max_restarts:
            raise RuntimeError(
                f"restart budget ({self.policy.max_restarts}) exhausted"
            ) from why
        logger.warning(
            "step failure (%s); restart %d/%d",
            why, self.restarts, self.policy.max_restarts,
        )
        _telemetry.current().emit(
            "rollback", restart=self.restarts,
            reason=f"{type(why).__name__}: {why}",
            rebuild_executor=ex is None or not isinstance(why, StepFailure),
        )
        if self.policy.backoff_s:
            time.sleep(self.policy.backoff_s * self.restarts)
        # A silent failure (bad loss) leaves the backend healthy: keep
        # the compiled executor and just roll the state back.  Raised
        # runtime faults get a fresh executor (new mesh/jit) instead.
        if ex is None or not isinstance(why, StepFailure):
            ex = self.executor_factory()
        step, params, opt_state, state = self._fresh_state(ex, seed, loader)
        _telemetry.current().emit("replay", from_step=int(step))
        return ex, step, params, opt_state, state

    # -- the loop ----------------------------------------------------------

    def fit(
        self,
        iterations: int,
        batch_fn: Optional[Callable[[int], Dict[str, Any]]] = None,
        save_every: int = 10,
        seed: int = 0,
        steps_per_call: int = 1,
        check_every: Optional[int] = None,
        loader=None,
    ) -> Dict[str, Any]:
        """Run ``iterations`` steps with detection + recovery.

        ``batch_fn(step)`` supplies the batch for a step, so replayed
        steps after a rollback see the same data (deterministic resume,
        which the reference cannot do at all) — the recovered loss
        trajectory is bit-identical to an unfaulted run's.

        ``loader`` (instead of ``batch_fn``) drives the run from a
        ``StreamingLoader``: each step pulls ``next(loader)``, every
        checkpoint carries the loader cursor+rng as a ``loader`` item,
        and a rollback rewinds the loader with ``load_state_dict``
        before replaying — so the replayed steps re-pull bit-identical
        batches straight from the out-of-core source (the reader
        thread's raw reads are deterministic; see DATA.md).  The
        loader is driven directly, NOT through a ``PrefetchLoader``
        (disk overlap still comes from its reader thread): the
        consumer-side cursor then matches the step count exactly.

        ``steps_per_call=k > 1`` fuses K steps into one compiled
        superstep dispatch (``Executor.build_superstep``): the stacked
        per-step metrics come back in ONE host fence per superstep and
        are scanned there for the first non-finite step.  ``k=1`` keeps
        per-step dispatch but amortizes the finiteness fence too:
        device-side losses accumulate and are validated in one batched
        readback every ``check_every`` steps (default: ``save_every``)
        — no blocking fence every iteration (what a fence costs on the
        chip is not measured yet, ROADMAP A2).  Detection latency is bounded
        by the fence period either way, and a save never covers
        unvalidated steps (the fence always runs first).

        On SIGTERM/SIGINT the loop finishes + validates the in-flight
        step/superstep, force-saves, flushes, and returns with
        ``preempted=True`` (callers exit 0; a restarted job resumes
        from that emergency snapshot automatically).

        Returns step/restarts/params/opt_state/state/loss as before,
        plus ``losses`` — ``{step: validated host loss}`` for every
        step this process ran — and ``preempted``.

        Like ``Trainer.fit``, the run self-installs telemetry from the
        executor's config (``telemetry_dir`` / ``FF_TELEMETRY_DIR``)
        when no run telemetry is already current, so a direct
        ``ResilientTrainer(...).fit()`` gets the same JSONL stream as
        an app-routed one.
        """
        if batch_fn is None and loader is None:
            raise ValueError("ResilientTrainer.fit needs batch_fn or loader")
        if batch_fn is not None and loader is not None:
            raise ValueError(
                "ResilientTrainer.fit takes batch_fn OR loader, not both"
            )
        ex = self.executor_factory()
        with _telemetry.maybe_run(getattr(ex, "config", None)):
            return self._fit(ex, iterations, batch_fn, save_every, seed,
                             steps_per_call, check_every, loader)

    def _fit(
        self,
        ex,
        iterations: int,
        batch_fn: Optional[Callable[[int], Dict[str, Any]]],
        save_every: int,
        seed: int,
        steps_per_call: int,
        check_every: Optional[int],
        loader=None,
    ) -> Dict[str, Any]:
        injector = FaultInjector.wrap(self.fault_injector)
        # Rewind target for recoveries that land before the first save
        # (and for pre-streaming checkpoints without a loader item).
        self._loader_origin = (
            loader.state_dict() if loader is not None else None
        )
        k = clamp_fused_steps(steps_per_call, log=logger)
        # The k=1 fence period is the same quantity as the superstep
        # length (an unfenced dependent dispatch chain): clamp it to
        # the same bound.
        check_every = min(check_every or save_every or 1, MAX_STEPS_PER_CALL)
        if k > 1 and not getattr(ex, "superstep_fused", False):
            # Host-driven layer-wise (pipeline) executors have no fused
            # superstep; the k=1 path composes fully (per-stage
            # {si: ...} trees checkpoint/restore through orbax like any
            # pytree).  The COMPILED pipeline step has one — its
            # stacked per-step metrics come back at the single
            # superstep fence, so the same first-non-finite-step scan +
            # rollback/replay machinery applies unchanged.
            raise ValueError(
                "steps_per_call > 1 in ResilientTrainer requires a "
                "fused superstep (the full-mesh Executor, or a "
                "PipelineExecutor on the compiled-step path: "
                "--pipeline-compiled); host-driven layer-wise "
                "strategies compose with resilience at steps_per_call=1"
            )
        step, params, opt_state, state = self._fresh_state(
            ex, seed, loader, initial=True
        )
        if step >= iterations:
            # A restarted job whose checkpoint already reached the
            # target (e.g. preempted on the final step): nothing to
            # run; the returned losses dict is empty.
            logger.info(
                "resumed at step %d >= iterations %d: already complete",
                step, iterations,
            )
        losses: Dict[int, float] = {}
        sstep_fns: Dict[int, Any] = {}
        pending = []  # k=1: (step, device loss) awaiting the batched fence
        preempted = False

        def validate_pending():
            """ONE host readback for all pending per-step losses; record
            the finite prefix, raise StepFailure at the first bad one."""
            nonlocal pending
            if not pending:
                return
            host = _telemetry.current().fence(
                [m for _, m in pending], "validate"
            )
            todo, pending = pending, []
            for (s, _), v in zip(todo, host):
                self._record(losses, injector, s, float(v))

        with PreemptionHandler() as preempt:
            while step < iterations:
                try:
                    if k == 1:
                        injector.before_step(step)
                        raw = (next(loader) if loader is not None
                               else batch_fn(step))
                        batch = ex.shard_batch(
                            injector.poison_batch(step, raw)
                        )
                        params, opt_state, state, metrics = ex.train_step(
                            params, opt_state, state, batch
                        )
                        pending.append((step, metrics["train_loss"]))
                        step += 1
                        trig = preempt.triggered
                        at_save = bool(save_every) and step % save_every == 0
                        if (len(pending) >= check_every or at_save
                                or step >= iterations or trig):
                            validate_pending()
                            if at_save:
                                self.checkpoint.save(
                                    step, params, opt_state, state,
                                    loader=(loader.state_dict()
                                            if loader is not None else None),
                                )
                                injector.after_save(step, self.checkpoint)
                                # Durable forward progress: the budget
                                # bounds crash *loops*, not total faults
                                # over the job lifetime.
                                self.restarts = 0
                    else:
                        n = min(k, iterations - step)
                        group = []
                        for i in range(n):
                            injector.before_step(step + i)
                            raw = (next(loader) if loader is not None
                                   else batch_fn(step + i))
                            group.append(injector.poison_batch(step + i, raw))
                        fn = sstep_fns.get(n)
                        if fn is None:
                            fn = sstep_fns[n] = ex.build_superstep(n)
                        stacked = ex.stack_steps(group)
                        params, opt_state, state, ms = fn(
                            params, opt_state, state, stacked
                        )
                        # ONE host fence per superstep: the stacked
                        # per-step metrics, scanned for the first
                        # non-finite step.
                        host = _telemetry.current().fence(
                            ms["train_loss"], "superstep"
                        )
                        # Read the preemption flag AFTER the fence —
                        # nearly all wall time is inside the dispatch,
                        # so a signal landing there still exits at THIS
                        # boundary, not one superstep later.
                        trig = preempt.triggered
                        for j in range(n):
                            self._record(
                                losses, injector, step + j, float(host[j]),
                                f" (superstep offset {j} of {n})",
                            )
                        prev, step = step, step + n
                        if save_every and step // save_every > prev // save_every:
                            # Superstep granularity: save at the first
                            # boundary past each save_every multiple.
                            self.checkpoint.save(
                                step, params, opt_state, state,
                                loader=(loader.state_dict()
                                        if loader is not None else None),
                            )
                            injector.after_save(step, self.checkpoint)
                            self.restarts = 0
                    if trig:
                        preempted = True
                        _telemetry.current().emit(
                            "preempt", step=int(step), signum=preempt.signum
                        )
                        logger.warning(
                            "preempted: emergency checkpoint at step %d, "
                            "exiting cleanly", step,
                        )
                        break
                except self.policy.recoverable as e:  # noqa: PERF203
                    if self.policy.fatal is not None and self.policy.fatal(e):
                        # World-level failure: in-process recovery would
                        # replay into the same dead collective; surface
                        # to the supervising launcher for a resize.
                        raise
                    pending = []
                    new_ex, step, params, opt_state, state = self._recover(
                        ex, seed, e, loader
                    )
                    if new_ex is not ex:
                        ex, sstep_fns = new_ex, {}  # stale jits died with it
        # Final (or emergency) save: if the step was already saved
        # periodically it is this very state (same trajectory since the
        # last restore) — skip; a fresh step force-saves past orbax's
        # save-interval gating (force-replace is crash-safe now).  The
        # flush fence makes it durable before the process exits.
        if step not in self.checkpoint.all_steps():
            self.checkpoint.save(
                step, params, opt_state, state, force=True,
                loader=(loader.state_dict()
                        if loader is not None else None),
            )
        self.checkpoint.wait_until_finished()
        self.executor = ex
        return _telemetry.current().fold_stats({
            "step": step,
            "restarts": self.total_restarts,
            "params": params,
            "opt_state": opt_state,
            "state": state,
            "loss": losses.get(step - 1, math.nan),
            "losses": losses,
            "preempted": preempted,
        })

    def _record(self, losses, injector, s: int, v: float, where: str = ""):
        """Validate one host loss at the fence; record it or raise."""
        v = injector.poison_loss(s, v)
        if self.policy.rollback_on_nonfinite and not math.isfinite(v):
            raise StepFailure(f"non-finite loss at step {s}{where}: {v}")
        losses[s] = v
        _telemetry.current().record_step(s, loss=v)
