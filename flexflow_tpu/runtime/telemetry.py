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
- A process-level **build log** (:class:`BuildLog`, OBSERVABILITY.md
  "Program builds"): jax times its own trace, lowering and backend
  compile of every program and hands the spans to any
  ``jax.monitoring`` listener; this module registers the listeners
  once, at import, and folds the spans into ``program_build`` records.
  The log is the process's, not a stream's — programs are built before
  a stream opens — and a stream writes what the log holds when it
  opens, then each record as it is made.  A listener fires only while
  jax traces, lowers or compiles, never in a cached dispatch.
"""

from __future__ import annotations

import collections
import itertools
import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.monitoring

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


#: jax's own timers of a build's three phases
#: (``jax._src.dispatch.LogElapsedTimeContextManager``: both edges are
#: ``time.time()``, the clock of every event's ``ts``; ``fun_name`` is
#: ``f`` on the trace and ``jit(f)`` on the other two).
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: What the persistent cache says of the compile request in flight.
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
    "/jax/compilation_cache/compile_requests_use_cache": "asked",
}
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class BuildLog:
    """Every program the process builds, folded from jax's own spans
    into ``program_build`` records (OBSERVABILITY.md "Program builds").

    The three listeners are the whole write side.  Spans arrive at
    their END, so whatever a span contains has arrived before it:

    - a **trace** span drops the pending trace spans it contains (the
      nested jits and ``jnp`` helpers traced inside a program) and
      waits as pending; its seconds, like a lowering's, are its span
      less any build made inside it, so that each second is booked
      once;
    - a **lower** span of ``jit(f)`` takes the pending trace of ``f``
      as its ``trace_s``; any other pending trace of
      :attr:`TRACE_MIN_S` or more is a program of its own (an
      ``eval_shape``, a re-trace whose lowering was cached), the rest
      (a cached trace looked up again) are dropped.  The record stays
      open for its compile;
    - a **compile** span labels itself from the cache events since the
      previous compile span and closes the open record of its ``fun``.

    A program whose records sum under :attr:`SMALL_S` (the eager
    ``jit(convert_element_type)`` of set-up code) is counted into a
    running ``small`` record, written as one line before the next
    listed record and by :meth:`flush`.  At most :attr:`MAX_RECORDS`
    records are kept, the newest; ``dropped`` on a ``small`` line
    counts what a late stream no longer finds.

    One building thread is what the fold assumes (top-level spans of
    one thread cannot overlap); builders on several threads are all
    counted, and a span may then be attributed to the wrong program.
    A listener never raises: a fault is logged at debug and the span
    dropped.
    """

    MAX_RECORDS = 1024
    MAX_PENDING = 65536
    SMALL_S = 0.010
    TRACE_MIN_S = 0.001

    def __init__(self):
        self._lock = threading.RLock()
        #: The newest records, in the order they were made.
        self.records: collections.deque = collections.deque(
            maxlen=self.MAX_RECORDS)
        #: Records ever made; ``made - len(records)`` were dropped.
        self.made = 0
        #: Top-level trace spans no lowering has claimed:
        #: ``(fun, t0, t1, own seconds)``.
        self._pending: List[tuple] = []
        #: ``(t0, seconds)`` of the newest booked spans: what a trace
        #: span that contains them takes off its own seconds.
        self._booked: collections.deque = collections.deque(maxlen=64)
        self._open: Optional[Dict[str, Any]] = None
        self._cache: Dict[str, Any] = {}
        self._small: Optional[Dict[str, Any]] = None

    # -- the listeners ------------------------------------------------------

    def on_span(self, event: str, t0: float, t1: float, fun_name="",
                **_kw) -> None:
        try:
            with self._lock:
                if event == _TRACE_EVENT:
                    # Nine spans in ten are a jnp helper traced inside
                    # a program (42,000 of them in one serving cell's
                    # set-up): this branch calls nothing.
                    pending = self._pending
                    while pending and pending[-1][1] >= t0:
                        pending.pop()  # what this span contains
                    own = t1 - t0
                    if self._booked and self._booked[-1][0] >= t0:
                        own = self._own(t0, t1)
                    # What a lowering traces itself waits here until
                    # the lowering's own span drops it.
                    if own >= self.TRACE_MIN_S \
                            or len(pending) < self.MAX_PENDING:
                        pending.append((fun_name, t0, t1, own))
                elif event == _LOWER_EVENT:
                    self._lower(str(fun_name), t0, t1)
                elif event == _COMPILE_EVENT:
                    self._compile(str(fun_name), t0, t1)
        except Exception as e:
            _log.debug("build log: dropped %s: %r", event, e)

    def on_event(self, event: str, **_kw) -> None:
        kind = _CACHE_EVENTS.get(event)
        if kind is not None:
            self._cache[kind] = True

    def on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == _RETRIEVAL_EVENT:
            self._cache["retrieval_s"] = secs

    # -- the fold -------------------------------------------------------------

    def _drop_contained(self, t0: float) -> None:
        while self._pending and self._pending[-1][1] >= t0:
            self._pending.pop()

    def _own(self, t0: float, t1: float) -> float:
        """The span's seconds less those booked inside it (an eager
        program built while an outer one is traced or lowered)."""
        own = t1 - t0
        for b0, secs in reversed(self._booked):
            if b0 < t0:
                break
            own -= secs
        return max(own, 0.0)

    def _strays(self) -> None:
        for fun, t0, t1, own in self._pending:
            if own >= self.TRACE_MIN_S:
                self._booked.append((t0, own))
                self._program([{"fun": str(fun), "phase": "trace",
                                "wall_s": own, "t0": t0, "t1": t1}])
        self._pending.clear()

    def _lower(self, fun: str, t0: float, t1: float) -> None:
        self._close_open()
        self._drop_contained(t0)  # what the lowering traced itself
        traced = fun[fun.find("(") + 1:-1] if fun.endswith(")") else fun
        trace_s = 0.0
        for i in range(len(self._pending) - 1, -1, -1):
            if self._pending[i][0] == traced:
                _, p0, _, trace_s = self._pending.pop(i)
                self._booked.append((p0, trace_s))
                break
        self._strays()
        own = self._own(t0, t1)
        self._booked.append((t0, own))
        self._open = {"fun": fun, "phase": "lower", "wall_s": own,
                      "trace_s": trace_s, "t0": t0, "t1": t1}

    def _compile(self, fun: str, t0: float, t1: float) -> None:
        said, self._cache = self._cache, {}
        if "hit" in said:
            cache = "hit"
        elif "miss" in said or (
                "asked" in said and jax.config.jax_compilation_cache_dir):
            # asked of a cache that is there, not found, and perhaps
            # not written (under the cache's own thresholds): a miss
            cache = "miss"
        else:
            cache = "off"
        rec = {"fun": fun, "phase": "compile", "wall_s": t1 - t0,
               "cache": cache, "t0": t0, "t1": t1}
        if "retrieval_s" in said:
            rec["retrieval_s"] = said["retrieval_s"]
        self._drop_contained(t0)
        self._strays()
        self._booked.append((t0, t1 - t0))
        if self._open is not None and self._open["fun"] != fun:
            self._close_open()
        lowered, self._open = self._open, None
        self._program([rec] if lowered is None else [lowered, rec])

    def _close_open(self) -> None:
        lowered, self._open = self._open, None
        if lowered is not None:
            self._program([lowered])

    def _program(self, recs: List[Dict[str, Any]]) -> None:
        """One program's records: listed, or counted into ``small``."""
        compile_s = sum(r["wall_s"] for r in recs if r["phase"] == "compile")
        trace_lower_s = sum(r["wall_s"] + r.get("trace_s", 0.0)
                            for r in recs if r["phase"] != "compile")
        if trace_lower_s + compile_s >= self.SMALL_S:
            self._write_small()
            for r in recs:
                self._deliver(r)
            return
        if self._small is None:
            self._small = {"phase": "small", "n": 0, "trace_lower_s": 0.0,
                           "compile_s": 0.0, "misses": 0,
                           "t0": recs[0]["t0"]}
        s = self._small
        s["n"] += 1
        s["trace_lower_s"] += trace_lower_s
        s["compile_s"] += compile_s
        s["misses"] += sum(r.get("cache", "hit") != "hit" for r in recs)
        s["t1"] = recs[-1]["t1"]

    def _write_small(self) -> None:
        s, self._small = self._small, None
        if s is not None:
            s["wall_s"] = s["trace_lower_s"] + s["compile_s"]
            if self.made > len(self.records):
                s["dropped"] = self.made - len(self.records)
            self._deliver(s)

    def _deliver(self, rec: Dict[str, Any]) -> None:
        for k, v in rec.items():
            if isinstance(v, float):
                rec[k] = round(v, 6)
        self.records.append(rec)
        self.made += 1
        tel = _current
        if tel is not None:
            tel._write_builds()

    # -- the read side --------------------------------------------------------

    def flush(self) -> None:
        """Close what is open: the record waiting for a compile, the
        pending traces, the running ``small`` line."""
        try:
            with self._lock:
                self._close_open()
                self._strays()
                self._write_small()
        except Exception as e:
            _log.debug("build log: flush failed: %r", e)

    def since(self, at: int) -> List[Dict[str, Any]]:
        """The kept records from the ``at``-th ever made on."""
        with self._lock:
            new = min(self.made - at, len(self.records))
            if new <= 0:
                return []
            if new == 1:
                return [self.records[-1]]
            return list(self.records)[-new:]


#: The process's build log.  Registered once, here: a listener costs a
#: dict store a span and runs only while jax traces, lowers or compiles.
BUILD_LOG = BuildLog()
jax.monitoring.register_event_time_span_listener(BUILD_LOG.on_span)
jax.monitoring.register_event_listener(BUILD_LOG.on_event)
jax.monitoring.register_event_duration_secs_listener(BUILD_LOG.on_duration)


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
        #: How many of the build log's records (counted from the first
        #: ever made) this stream has written: what the log holds now
        #: goes in as the backlog, each later record as it is made.
        self._builds_at = 0
        BUILD_LOG.flush()
        self._write_builds(backlog=True)
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

    def _write_builds(self, backlog: bool = False) -> None:
        """One ``program_build`` event for every record of the build
        log this stream has not written, in ``t1`` order; ``backlog``
        marks those made before the stream opened."""
        recs = BUILD_LOG.since(self._builds_at)
        self._builds_at = BUILD_LOG.made
        mark = {"backlog": True} if backlog else {}
        for rec in sorted(recs, key=lambda r: r["t1"]):
            self.emit("program_build", **rec, **mark)

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
        sight: XLA's static flops/bytes estimate from
        ``Lowered.cost_analysis()`` — device-side attribution that
        exists even without a trace (OBSERVABILITY.md) — and
        ``wall_s``, what the probe itself took.

        ``Lowered`` (not ``Compiled``): ``lowered.compile()`` performs
        a genuine SECOND XLA compile, not shared with the jit call's
        cache.  ``lower()`` shares the jit's own trace and lowering
        caches: called BEFORE the program's first call, as the serving
        engine does, it IS the program's trace and lowering (0.9–2.7 s a
        program of ``gpt2m.serve`` on the chip's host, PERF.md §5 (4))
        and the call that follows reuses both; called after, it is two
        cache lookups.  Where the backend gives no analysis (the TPU's
        ``Lowered.cost_analysis()`` is ``None``) the event carries
        ``wall_s`` and no estimate.  Deduped per (kind, program
        identity); never raises — cost attribution must not break the
        program it describes."""
        key = (kind, id(fn))
        if key in self._cost_seen:
            return
        self._cost_seen.add(key)
        try:
            lower = getattr(fn, "lower", None)
            if lower is None:
                return
            t0 = time.perf_counter()
            ca = lower(*args).cost_analysis()
            fields = {"wall_s": round(time.perf_counter() - t0, 6)}
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if isinstance(ca, dict):
                fields.update(
                    flops=float(ca.get("flops", 0.0)),
                    bytes_accessed=float(ca.get("bytes accessed", 0.0)),
                    transcendentals=float(ca.get("transcendentals", 0.0)))
            self.emit("program_cost", kind=kind, **fields, **meta)
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
        BUILD_LOG.flush()
        self._write_builds()
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
        self._write_builds()  # made since it opened, installed nowhere
        return self

    def __exit__(self, exc_type=None, exc=None, tb=None) -> None:
        global _current
        if _current is self:
            _current = self._prev_current
        self._prev_current = None
        self.close(exc_type)
