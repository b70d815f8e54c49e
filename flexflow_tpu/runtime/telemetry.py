"""Structured run telemetry: JSONL event stream, dispatch/fence
counters, step-time percentiles, and a stall watchdog.

The reference could always answer "where did this step's time go" —
per-task cudaEvent timing under ``--profiling`` plus Legion trace
capture (``conv_2d.cu:515-546``, ``dlrm.cc:151-163``).  This rebuild
has grown three dispatch regimes (per-step, fused superstep,
fence-amortized pipeline) and a resilience layer whose behavior used
to be visible only through scattered prints; the PIPELINE_OVERHEAD.md
round-6 incident (an unexplained ~1.5x box-state drift untangled by
hand-rerun A/Bs) is exactly what a durable, structured per-run record
exists to prevent.

Design (OBSERVABILITY.md has the full event schema):

- ONE :class:`Telemetry` object per run; components report into
  :func:`current` (installed by the context manager), so the trainer,
  executors, checkpoint manager and resilience layer all write into
  the same stream without threading a handle through every call.
- Events are JSON lines ``{"ts": wall-clock s, "seq": n, "ev": type,
  ...}``.  Rare events (fences, checkpoints, faults, rollbacks,
  stalls) flush immediately; high-rate ``step`` events buffer and
  flush at the next rare event or after ``FLUSH_EVERY_S`` — so a
  crashed run's log is complete to within a flush interval of the
  instant it died, and the per-step cost stays a buffered ``write``,
  not a syscall (the < 2% overhead bar, OBSERVABILITY.md).
- **Zero overhead when off**: the :data:`NULL` singleton's hooks are
  no-op attribute calls and :meth:`_NullTelemetry.fence` is *exactly*
  ``jax.device_get`` — instrumentation wraps the fences the trainer
  already had and NEVER adds one (fences/step is pinned unchanged by
  tests/test_telemetry.py; trainer numerics and stats are bit-identical
  with telemetry off).
- The **stall watchdog** is a daemon thread fed by in-process
  heartbeats (every completed step and both edges of every fence); a
  gap exceeding the deadline logs ONE loud last-known-event warning —
  a ``device_get`` that never returns is otherwise silent — and emits
  a ``stall`` event.  Observe-and-warn only: it never kills the
  process (whether to kill is the supervisor's call, and the run may
  only be compiling).  Heartbeats also touch a file
  (``DIR/heartbeat``, or ``FF_HEARTBEAT_FILE``) so an external
  supervisor shares the same liveness signal as the in-process
  monitor.
"""

from __future__ import annotations

import itertools
import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax

_log = logging.getLogger("ff.telemetry")

#: The run-scoped telemetry components report into (None = disabled).
_current: Optional["Telemetry"] = None

#: Watchdog deadline (s) used when a config carries no override.
DEFAULT_STALL_DEADLINE_S = 300.0

#: Max age of buffered ``step`` events before a time-based flush.
FLUSH_EVERY_S = 0.5

#: Min spacing of heartbeat-FILE touches (the in-process timestamp
#: updates on every beat; the file is for the external watcher, whose
#: liveness resolution is seconds — syscalls per step are not).
HEARTBEAT_FILE_EVERY_S = 1.0

#: High-rate event types that may buffer; everything else flushes
#: immediately (fences, checkpoints, faults, rollbacks, stalls are
#: exactly the events a postmortem cannot afford to lose).
_BUFFERED_EVENTS = frozenset({"step", "input_wait"})

#: Fence labels excluded from fence_ms calibration fitting: ``warmup``
#: fences include the first-call compile, ``final`` drains the whole
#: queued run — neither is a per-step round trip.  The ONE exclusion
#: rule shared by :meth:`Telemetry.calibration_summary` (in-memory fit)
#: and ``search.cost_model.Calibration.from_events`` (JSONL re-derive)
#: — the two fitters must agree or fence_ms means different things
#: depending on which path fed it.
CALIBRATION_FENCE_EXCLUDE = frozenset({"warmup", "final"})

#: Per-process run counter: strftime has one-second resolution, so two
#: quick fits in one process would otherwise append-interleave into the
#: same JSONL file (breaking the one-file-per-run contract).
_RUN_COUNTER = itertools.count()


class _NullTelemetry:
    """The disabled singleton: every hook is a no-op, and ``fence`` is
    exactly ``jax.device_get`` — so instrumentation sites stay
    unconditional with zero measurable cost and zero extra fences."""

    enabled = False
    path = None

    def fence(self, value, label: str = "fence"):
        return jax.device_get(value)

    def emit(self, ev: str, **fields) -> None:
        pass

    def record_step(self, step, loss=None, wall_s=None, **fields) -> None:
        pass

    def record_steps(self, n: int, wall_s: float) -> None:
        pass

    def record_input_wait(self, step, wall_s, **depths) -> None:
        pass

    def add_programs(self, n: int, steps: int = 1) -> None:
        pass

    def program_cost(self, kind, fn, args=(), **meta) -> None:
        pass

    def attach_trace_summary(self, log_dir) -> None:
        pass

    def heartbeat(self, label: str = "beat") -> None:
        pass

    def note_summary(self, **fields) -> None:
        pass

    def step_summary(self) -> Dict[str, Any]:
        return {}

    def fold_stats(self, stats: Dict[str, Any]) -> Dict[str, Any]:
        return stats

    def close(self) -> None:
        pass

    def __enter__(self) -> "_NullTelemetry":
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL = _NullTelemetry()


def current():
    """The active run's :class:`Telemetry`, or :data:`NULL` when no run
    telemetry is installed."""
    return _current if _current is not None else NULL


#: ``span(name, **kw)`` opens a host span named from
#: ``obs/events.py::SPAN_CATALOG``: a ``jax.profiler.TraceAnnotation``,
#: so it lands in the profiler's ``.xplane.pb`` beside the device
#: operations, on their clock, and costs a check of the profiler's flag
#: when no profile is running.  ``kw`` (``id``, ``bucket``,
#: ``superstep``, ``active``) go on as the annotation's keywords and
#: join the trace to the JSONL stream.  The class itself, not a
#: function around it: the call sits between one span's end and the
#: next one's start, where a traced run counts it as no span's.  Not a
#: :class:`Telemetry` method: a span exists in a traced run whether or
#: not a stream is open.
span = jax.profiler.TraceAnnotation


def process_tag() -> str:
    """``-p<N>`` when this process is one of a multi-host world
    (``JAX_PROCESS_ID`` set — the elastic rig, TPU pods), else empty:
    N processes sharing one ``--telemetry DIR`` get per-process run
    JSONL and heartbeat files instead of clobbering each other."""
    p = os.environ.get("JAX_PROCESS_ID", "")
    return f"-p{int(p)}" if p.isdigit() else ""


def maybe_run(config=None, meta: Optional[Dict[str, Any]] = None):
    """Context manager for an optionally-telemetered run: a fresh
    :class:`Telemetry` when ``config.telemetry_dir`` (or the
    ``FF_TELEMETRY_DIR`` environment variable) names a directory AND no
    run telemetry is already installed; otherwise :data:`NULL` (which
    leaves an enclosing run's telemetry in place — nested ``fit`` calls
    report into the outer stream)."""
    if current().enabled:
        return NULL
    d = getattr(config, "telemetry_dir", None) or os.environ.get(
        "FF_TELEMETRY_DIR"
    )
    if not d:
        return NULL
    deadline = getattr(config, "stall_deadline_s", DEFAULT_STALL_DEADLINE_S)
    notify = getattr(config, "stall_notify_pid", 0)
    if not notify:
        try:
            notify = int(os.environ.get("FF_STALL_NOTIFY_PID", "0") or 0)
        except ValueError:
            # Junk in the environment must not abort a run that never
            # asked for escalation; warn and run without it.
            _log.warning(
                "FF_STALL_NOTIFY_PID=%r is not an integer; stall "
                "escalation disabled",
                os.environ.get("FF_STALL_NOTIFY_PID"),
            )
            notify = 0
    return Telemetry(d, stall_deadline_s=deadline, meta=meta,
                     notify_pid=notify)


def _json_default(o):
    try:
        return float(o)
    except (TypeError, ValueError):
        return str(o)


def _jnum(v: float) -> str:
    """JSON fragment for one float: repr round-trips finite values
    exactly; non-finite spell NaN/Infinity the way json.dumps does
    (json.loads accepts both)."""
    v = float(v)
    if v == v and v not in (float("inf"), float("-inf")):
        return repr(v)
    return json.dumps(v)


class Telemetry:
    """Run-scoped telemetry collector.

    ``directory=None`` keeps everything in-process (counters +
    percentiles + watchdog, no JSONL) — what the program audit
    (``analysis/program_audit.py``) counts a live pipeline step's host
    programs with, without touching disk.

    As a context manager it installs itself as :func:`current` so every
    runtime component (trainer fences, pipeline program counters,
    checkpoint I/O, resilience faults/rollbacks) reports into this run.
    """

    enabled = True

    def __init__(
        self,
        directory: Optional[str] = None,
        run_id: Optional[str] = None,
        heartbeat_path: Optional[str] = None,
        stall_deadline_s: float = 0.0,
        meta: Optional[Dict[str, Any]] = None,
        notify_pid: int = 0,
    ):
        self.run_id = run_id or (
            time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
            + f"-{os.getpid()}-{next(_RUN_COUNTER)}"
        )
        self._lock = threading.Lock()
        self._seq = 0
        self._f = None
        self.path: Optional[str] = None
        self._dir = directory
        self.meta: Dict[str, Any] = dict(meta or {})
        if directory:
            os.makedirs(directory, exist_ok=True)
            self.path = os.path.join(
                directory, f"run-{self.run_id}{process_tag()}.jsonl"
            )
            self._f = open(self.path, "a")
        #: Box-state identity stamped onto run_start and the run index
        #: (the round-6 drift attribution; cached per process —
        #: obs/registry.py).  Lazy import: obs must stay loadable
        #: without the runtime stack and vice versa.
        from flexflow_tpu.obs.registry import box_fingerprint

        self.fingerprint: Dict[str, Any] = box_fingerprint()
        #: Dispatch/fence counters: ``fences`` and ``steps`` feed
        #: fences/step; ``host_programs``/``program_steps`` hold the
        #: pipeline's folded ``last_schedule`` lengths (programs/step).
        self.counts: Dict[str, int] = {
            "fences": 0, "steps": 0, "host_programs": 0, "program_steps": 0,
        }
        #: Host-side per-step wall times (s) — percentile source.  In
        #: the unfenced per-step regime these are DISPATCH times (the
        #: loop never blocks on the device); on fenced paths
        #: (superstep) they include device execution.  Either way they
        #: are measured host-side and add no ``device_get``.
        self.step_times: List[float] = []
        #: Per-step input-starvation waits (s): time the training loop
        #: blocked on ``next(batches)`` in steady state (warmup pulls
        #: excluded).  Feeds the input_wait percentiles in
        #: :meth:`step_summary`; populated ONLY by instrumented batch
        #: pulls, so synthetic fixed-batch runs carry no block at all.
        self.input_waits: List[float] = []
        #: (label, wall_s) of every fence — the calibration feed for
        #: the execution autotuner's fence_ms constant (the MINIMUM
        #: non-warmup/final fence is the round-trip floor estimate;
        #: search/cost_model.Calibration).
        self.fence_times: List[tuple] = []
        #: Subsystem-noted summary rows (:meth:`note_summary`), merged
        #: into :meth:`step_summary` last so the serving scheduler's
        #: virtual-clock metrics ride the run_end summary block.
        self._extra_summary: Dict[str, Any] = {}
        self._hb_path = (
            heartbeat_path
            or os.environ.get("FF_HEARTBEAT_FILE")
            or (os.path.join(directory, "heartbeat" + process_tag())
                if directory else None)
        )
        self._hb_warned = False
        self._hb_created = False
        self._last_flush = time.monotonic()
        self._last_file_touch = time.monotonic()
        self._last_beat = time.monotonic()
        self._last_label = "run_start"
        self._stall_deadline = float(stall_deadline_s or 0.0)
        #: Stall-escalation hook: an EXTERNAL supervisor pid notified
        #: with SIGUSR1 when a stall fires (0 = off).  Never the own
        #: pid — the watchdog must not signal the process it watches
        #: (even a handled signal interrupting a blocked device_get is
        #: territory the observe-and-warn contract stays out of).
        self._notify_pid = int(notify_pid or 0)
        if self._notify_pid < 0:
            # A negative pid makes os.kill signal a whole PROCESS
            # GROUP — potentially including this process, whose
            # default SIGUSR1 disposition is termination: the exact
            # kill-a-TPU-claim-holder hazard the watchdog exists to
            # avoid.
            _log.warning(
                "stall_notify_pid=%d is negative (a process group); "
                "refusing — escalation notifies exactly one external "
                "pid or nothing", self._notify_pid,
            )
            self._notify_pid = 0
        if self._notify_pid == os.getpid():
            _log.warning(
                "stall_notify_pid=%d is THIS process; refusing "
                "(the watchdog never signals the process it watches) "
                "— escalation disabled", self._notify_pid,
            )
            self._notify_pid = 0
        self._stalled = False
        self._closed = False
        self._stop = threading.Event()
        self._watchdog: Optional[threading.Thread] = None
        self._prev_current: Optional[Telemetry] = None
        #: run_end.exit bookkeeping: a recorded ``preempt`` event makes
        #: the whole run's outcome ``preempt`` (the SIGTERM emergency
        #: path exits by exception, but the preemption IS the cause).
        self._preempted = False
        self.exit_status: Optional[str] = None
        #: program_cost dedup: one event per (kind, program identity).
        self._cost_seen: set = set()
        self._trace_summary: Optional[Dict[str, Any]] = None
        if self._hb_path:
            self._touch_heartbeat()
        self.emit("run_start", run_id=self.run_id, pid=os.getpid(),
                  fingerprint=self.fingerprint, **(meta or {}))
        if self._stall_deadline > 0:
            self._watchdog = threading.Thread(
                target=self._watch, name="ff-telemetry-watchdog", daemon=True
            )
            self._watchdog.start()

    # -- event stream -------------------------------------------------------

    def emit(self, ev: str, **fields) -> None:
        """Append one event to the JSONL stream.  ``step`` events
        buffer (flushed at the next rare event or ``FLUSH_EVERY_S``);
        everything else flushes immediately."""
        with self._lock:
            self._seq += 1
            rec: Dict[str, Any] = {
                "ts": round(time.time(), 6), "seq": self._seq, "ev": ev,
            }
            rec.update(fields)
            if self._f is not None and not self._closed:
                self._f.write(json.dumps(rec, default=_json_default) + "\n")
                now = time.monotonic()
                if (ev not in _BUFFERED_EVENTS
                        or now - self._last_flush >= FLUSH_EVERY_S):
                    self._f.flush()
                    self._last_flush = now
            self._last_label = ev
            if ev == "preempt":
                self._preempted = True

    def record_step(self, step, loss=None, wall_s=None, **fields) -> None:
        """One completed training step: a ``step`` event plus the
        counters/percentile feed, plus a heartbeat.  On a rollback
        replay the same step index is recorded again — reconstruction
        takes the LAST event per index (OBSERVABILITY.md).

        This is the per-step hot path (the whole point is < 2%
        overhead on dispatch-bound steps), so the JSON line is built by
        hand instead of ``json.dumps`` — measured ~2x faster."""
        step = int(step)
        self.counts["steps"] += 1
        if wall_s is not None:
            self.step_times.append(float(wall_s))
        with self._lock:
            self._seq += 1
            if self._f is not None and not self._closed:
                line = (f'{{"ts": {time.time():.6f}, "seq": {self._seq}, '
                        f'"ev": "step", "step": {step}')
                if wall_s is not None:
                    line += f', "wall_s": {float(wall_s):.6f}'
                if loss is not None:
                    line += f', "loss": {_jnum(loss)}'
                for k, v in fields.items():
                    line += f', {json.dumps(k)}: ' \
                            f'{json.dumps(v, default=_json_default)}'
                self._f.write(line + "}\n")
                now = time.monotonic()
                if now - self._last_flush >= FLUSH_EVERY_S:
                    self._f.flush()
                    self._last_flush = now
            self._last_label = "step"
        self.heartbeat(f"step:{step}")

    def record_steps(self, n: int, wall_s: float) -> None:
        """``n`` steps of ``wall_s`` each that one fused serving round
        covered: the counters and the percentile feed of ``n``
        :meth:`record_step` calls, and no line.  The round's own event
        (``decode_superstep`` / ``spec_verify``: ``wall_s``, ``k`` or
        ``d``, ``superstep``) is the stream's record of them, and
        ``RunLog.reconstruct_summary`` divides it the same way."""
        self.counts["steps"] += int(n)
        self.step_times.extend([float(wall_s)] * int(n))
        self.heartbeat("steps")

    def record_input_wait(self, step, wall_s, **depths) -> None:
        """Input starvation: the wall time one steady-state
        ``next(batches)`` blocked the training loop, plus queue-depth
        gauges at the moment of the pull (``h2d`` = staged device
        batches in the PrefetchLoader, ``reader`` = raw windows in the
        StreamingLoader's queue — both edges of the pipeline, DATA.md).
        High-rate and host-side only: buffers like ``step`` events,
        never fences.  A starving run reads as rising input_wait with
        both gauges pinned at 0."""
        # The accumulator stores the SAME rounded value the event
        # carries, so the summary's input_wait_s_total reconciles with
        # the event stream exactly (the accounting audit).
        w = round(float(wall_s), 6)
        self.input_waits.append(w)
        self.emit("input_wait", step=int(step), wall_s=w, **depths)

    def fence(self, value, label: str = "fence"):
        """Host-readback fence: heartbeats on both edges (so the
        watchdog knows a fence is in flight while ``device_get``
        blocks), times it, emits a ``fence`` event, and returns the
        host value.  This WRAPS the fences the trainer already had —
        it never adds a ``device_get`` the un-telemetered path lacks."""
        self.heartbeat(f"fence:{label}:in-flight")
        t0 = time.perf_counter()
        host = jax.device_get(value)
        dt = time.perf_counter() - t0
        self.counts["fences"] += 1
        self.fence_times.append((label, dt))
        self.emit("fence", label=label, wall_s=round(dt, 6))
        self.heartbeat(f"fence:{label}:done")
        return host

    def add_programs(self, n: int, steps: int = 1) -> None:
        """Fold ``n`` host programs covering ``steps`` train steps into
        the programs/step counter: the host-driven pipeline reports one
        step's ``len(last_schedule)`` per call (``steps=1``); the fused
        compiled-pipeline superstep reports ONE program covering k
        steps (``n=1, steps=k``), so programs/step honestly reads
        ``1/k``."""
        self.counts["host_programs"] += int(n)
        self.counts["program_steps"] += int(steps)

    def program_cost(self, kind: str, fn, args=(), **meta) -> None:
        """One ``program_cost`` event per compiled program at first
        build: XLA's static flops/bytes estimate from
        ``Lowered.cost_analysis()`` — device-side attribution that
        exists even without a trace (OBSERVABILITY.md).

        ``Lowered`` (not ``Compiled``): probing this jaxlib showed
        ``lowered.compile()`` performs a genuine SECOND XLA compile
        (~36 ms, not shared with the jit call's cache) while
        ``lower()`` after a warm call is ~1 ms and its cost_analysis
        reports the same flops — the < 2% overhead bar decides.
        Deduped per (kind, program identity); never raises — cost
        attribution must not break the program it describes."""
        key = (kind, id(fn))
        if key in self._cost_seen:
            return
        self._cost_seen.add(key)
        try:
            lower = getattr(fn, "lower", None)
            if lower is None:
                return
            ca = lower(*args).cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if not isinstance(ca, dict):
                return
            self.emit(
                "program_cost", kind=kind,
                flops=float(ca.get("flops", 0.0)),
                bytes_accessed=float(ca.get("bytes accessed", 0.0)),
                transcendentals=float(ca.get("transcendentals", 0.0)),
                **meta,
            )
        except Exception as e:
            _log.debug("program_cost(%s): cost analysis unavailable: %s",
                       kind, e)

    def attach_trace_summary(self, log_dir: str) -> None:
        """Fold device-time attribution from an XProf trace's
        ``.xplane.pb`` (``--trace DIR`` + telemetry together) into the
        coming ``run_end``.  Reading failures warn and attach
        nothing."""
        from flexflow_tpu.obs.trace import summarize_trace_dir

        summary = summarize_trace_dir(log_dir)
        if summary is not None:
            self._trace_summary = summary

    # -- heartbeat / watchdog ----------------------------------------------

    def heartbeat(self, label: str = "beat") -> None:
        now = time.monotonic()
        self._last_beat = now
        self._last_label = label
        if self._stalled:
            self._stalled = False
            _log.warning(
                "telemetry watchdog: heartbeat resumed (%s) — the stall "
                "cleared on its own", label,
            )
            self.emit("stall_recovered", last=label)
        if self._hb_path and (
            now - self._last_file_touch >= HEARTBEAT_FILE_EVERY_S
        ):
            self._last_file_touch = now
            self._touch_heartbeat()

    def _touch_heartbeat(self) -> None:
        # utime-only on the hot path (one syscall per beat); the file
        # is created once here, re-created if something removes it.
        try:
            if self._hb_created:
                try:
                    os.utime(self._hb_path, None)
                    return
                except FileNotFoundError:
                    pass
            with open(self._hb_path, "a"):
                pass
            os.utime(self._hb_path, None)
            self._hb_created = True
        except OSError as e:
            if not self._hb_warned:
                self._hb_warned = True
                _log.warning("cannot touch heartbeat file %s: %s",
                             self._hb_path, e)

    def _watch(self) -> None:
        period = min(max(self._stall_deadline / 4.0, 0.05), 30.0)
        while not self._stop.wait(period):
            idle = time.monotonic() - self._last_beat
            if idle >= self._stall_deadline and not self._stalled:
                self._stalled = True
                _log.warning(
                    "telemetry watchdog: NO heartbeat for %.1fs (deadline "
                    "%.1fs); last known event: %s.  If that event is a "
                    "fence in flight, a device_get is not returning — "
                    "a hung device, or a long first-call compile.  "
                    "Observe-and-warn only: NOT killing anything.",
                    idle, self._stall_deadline, self._last_label,
                )
                notified = self._notify_supervisor()
                self.emit("stall", idle_s=round(idle, 1),
                          deadline_s=self._stall_deadline,
                          last=self._last_label,
                          notified_pid=notified)

    def _notify_supervisor(self) -> int:
        """Stall escalation: SIGUSR1 to the configured EXTERNAL
        supervisor pid (``--stall-notify-pid`` / FF_STALL_NOTIFY_PID).
        Observe-and-warn stays the in-process contract — this never
        touches the watched process itself; a dead/invalid supervisor
        is logged and ignored.  Returns the pid notified (0 = none)."""
        if not self._notify_pid:
            return 0
        import signal

        try:
            os.kill(self._notify_pid, signal.SIGUSR1)
            _log.warning(
                "telemetry watchdog: notified supervisor pid %d "
                "(SIGUSR1) of the stall", self._notify_pid,
            )
            return self._notify_pid
        except (OSError, ProcessLookupError) as e:
            _log.warning(
                "telemetry watchdog: could not notify supervisor "
                "pid %d: %s", self._notify_pid, e,
            )
            return 0

    # -- summaries ----------------------------------------------------------

    def note_summary(self, **fields) -> None:
        """Stash subsystem-computed summary rows (the serving
        scheduler's queue-wait percentiles / SLO attainment,
        SERVING.md) to be merged into :meth:`step_summary` — and so
        into the ``run_end`` summary block, where ``RunLog.summary``
        reads them.  Values must already carry their final rounding:
        ``reconstruct_summary`` recomputes them from raw events and
        the two must match bit-for-bit."""
        self._extra_summary.update(fields)

    def step_summary(self) -> Dict[str, Any]:
        """Counters + host-side step-time percentiles (p50/p95/max ms,
        nearest-rank) — the block folded into fit stats, ``run_end``
        and the run index (``obs/registry.py``)."""
        out: Dict[str, Any] = {
            "steps": self.counts["steps"],
            "fences": self.counts["fences"],
        }
        steps = max(self.counts["steps"], 1)
        out["fences_per_step"] = round(self.counts["fences"] / steps, 4)
        if self.counts["program_steps"]:
            out["programs_per_step"] = round(
                self.counts["host_programs"] / self.counts["program_steps"], 4
            )
        if self.step_times:
            ts = sorted(self.step_times)

            def pct(p: float) -> float:
                return ts[min(len(ts) - 1, int(round(p * (len(ts) - 1))))]

            out["step_ms_p50"] = round(pct(0.50) * 1e3, 3)
            out["step_ms_p95"] = round(pct(0.95) * 1e3, 3)
            out["step_ms_max"] = round(ts[-1] * 1e3, 3)
        if self.input_waits:
            ws = sorted(self.input_waits)

            def wpct(p: float) -> float:
                return ws[min(len(ws) - 1, int(round(p * (len(ws) - 1))))]

            # input_wait_s_total is the accounting hook: it must equal
            # the sum of the run's input_wait event wall_s exactly
            # (audited like programs/step, tests/test_data_stream.py).
            out["input_wait_ms_p50"] = round(wpct(0.50) * 1e3, 3)
            out["input_wait_ms_p95"] = round(wpct(0.95) * 1e3, 3)
            out["input_waits"] = len(ws)
            out["input_wait_s_total"] = round(sum(ws), 6)
        out.update(self._extra_summary)
        return out

    def fold_stats(self, stats: Dict[str, Any]) -> Dict[str, Any]:
        """Fold the telemetry summary into a fit stats dict (under the
        ``"telemetry"`` key, so the existing keys stay bit-identical)."""
        stats["telemetry"] = self.step_summary()
        return stats

    def calibration_summary(self) -> Dict[str, Any]:
        """Everything the execution autotuner's :class:`~flexflow_tpu.
        search.cost_model.Calibration` needs, from ONE run: the
        per-program dispatch cost estimate (step p50 / programs-per-step
        when the run was dispatch-audited at >= 2 programs/step), the
        fence round-trip floor (MINIMUM non-warmup/final fence wall —
        every fence also drains queued compute, so the cheapest one
        bounds the round trip), and the source counts.  Folded into the
        ``run_end`` event as its ``calibration`` block
        (OBSERVABILITY.md)."""
        ss = self.step_summary()
        floors = [
            dt for lbl, dt in self.fence_times
            if lbl not in CALIBRATION_FENCE_EXCLUDE
        ]
        out: Dict[str, Any] = {
            "steps": ss["steps"],
            # STEADY-STATE fences per step: the excluded warmup/final
            # fences happen once per RUN, not per step — counting them
            # here would charge the cost model a per-step fence a long
            # run never pays (the fit multiplies this by fence_ms,
            # which is fitted over the same exclusion).
            "fences_per_step": round(
                len(floors) / max(ss["steps"], 1), 4
            ),
        }
        pps = ss.get("programs_per_step")
        if pps is not None:
            out["programs_per_step"] = pps
        p50 = ss.get("step_ms_p50")
        if p50 is not None:
            out["step_ms_p50"] = p50
            if pps is not None and pps >= 2.0:
                out["dispatch_ms_per_program"] = round(p50 / pps, 4)
        if floors:
            out["fence_ms"] = round(max(min(floors) * 1e3, 1e-3), 4)
            out["fence_samples"] = len(floors)
        return out

    # -- lifecycle ----------------------------------------------------------

    def close(self, exc_type=None) -> None:
        """End the run: classify the outcome (``clean`` /
        ``exception:<type>`` / ``preempt`` — a crashed run is now
        distinguishable from a truncated log), emit ``run_end`` with
        the summary/calibration blocks (+ ``trace_summary`` when
        attribution was attached), and append the run to the registry
        index (obs/registry.py)."""
        if self._closed:
            return
        self._stop.set()
        if self._watchdog is not None:
            self._watchdog.join(timeout=2.0)
        from flexflow_tpu.obs.events import (
            EXIT_CLEAN,
            EXIT_PREEMPT,
            exit_exception,
        )

        if self._preempted:
            self.exit_status = EXIT_PREEMPT
        elif exc_type is not None:
            self.exit_status = exit_exception(
                getattr(exc_type, "__name__", str(exc_type))
            )
        else:
            self.exit_status = EXIT_CLEAN
        end_fields: Dict[str, Any] = {
            "summary": self.step_summary(),
            "calibration": self.calibration_summary(),
            "exit": self.exit_status,
        }
        if self._trace_summary is not None:
            end_fields["trace_summary"] = self._trace_summary
        self.emit("run_end", **end_fields)
        with self._lock:
            self._closed = True
            if self._f is not None:
                self._f.close()
                self._f = None
        if self._dir:
            from flexflow_tpu.obs.registry import append_run, index_record

            append_run(self._dir, index_record(self))

    def __enter__(self) -> "Telemetry":
        global _current
        self._prev_current = _current
        _current = self
        return self

    def __exit__(self, exc_type=None, exc=None, tb=None) -> None:
        global _current
        if _current is self:
            _current = self._prev_current
        self._prev_current = None
        self.close(exc_type)
