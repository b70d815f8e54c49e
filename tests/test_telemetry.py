"""Structured run telemetry (runtime/telemetry.py; OBSERVABILITY.md).

Pins the observability layer's four contracts:

- **Event schema**: a run's JSONL stream opens with ``run_start``,
  closes with ``run_end``, every event carries ``ts``/``seq``/``ev``,
  ``seq`` is strictly increasing and ``ts`` non-decreasing.
- **Dispatch audit**: the pipeline's host-programs-per-step counter
  equals ``len(last_schedule)`` across chunk settings.
- **Chaos reconstruction**: a resilient run's log contains
  fault → rollback → replay (and checkpoint save/restore) in order,
  and replaying the step events yields the same step count and final
  loss as the live run's stats dict.
- **Off-path purity**: telemetry off leaves trainer numerics and the
  stats dict bit-identical (and enabled telemetry adds no fences —
  fences/step is exactly the un-telemetered ``device_get`` count).
- **Program builds**: every program the process builds leaves
  ``program_build`` records folded from jax's own trace, lowering and
  compile spans; a cached call leaves none.
"""

import json
import logging
import os
import time

import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime import telemetry
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.pipeline import PipelineExecutor
from flexflow_tpu.runtime.telemetry import NULL, Telemetry
from flexflow_tpu.runtime.trainer import Trainer


def _model(batch=8, depth=2, seed=11):
    ff = FFModel(FFConfig(batch_size=batch, seed=seed))
    x = ff.create_tensor((batch, 16), name="x")
    lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
    t = x
    for i in range(depth):
        t = ff.dense(t, 32, activation="relu", name=f"fc{i}")
    t = ff.dense(t, 4, name="head")
    ff.softmax(t, lbl, name="softmax")
    return ff


def _executor(seed=11):
    return Executor(_model(seed=seed), optimizer=SGDOptimizer(lr=0.1))


def _events(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


def _batch(rng, batch=8):
    return {
        "x": rng.standard_normal((batch, 16)).astype(np.float32),
        "label": rng.integers(0, 4, size=(batch,)).astype(np.int32),
    }


# -- event schema ----------------------------------------------------------


def test_event_schema_golden(tmp_path):
    with Telemetry(str(tmp_path)) as tel:
        stats = Trainer(_executor()).fit(iterations=4, warmup=1, log_every=2)
    events = _events(tel.path)
    assert events[0]["ev"] == "run_start"
    assert events[-1]["ev"] == "run_end"
    for e in events:
        assert {"ts", "seq", "ev"} <= set(e)
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    tss = [e["ts"] for e in events]
    assert tss == sorted(tss)  # monotonic timestamps
    steps = [e for e in events if e["ev"] == "step"]
    assert [e["step"] for e in steps] == [1, 2, 3, 4]  # warmup offsets
    assert all(e["wall_s"] > 0 for e in steps)
    fences = [e for e in events if e["ev"] == "fence"]
    # The k=1 loop's real fences, wrapped not added: warmup, the two
    # log_every readbacks, and the final execution fence.
    assert [e["label"] for e in fences] == ["warmup", "log", "log", "final"]
    # run_end embeds the same summary fit folded into its stats.
    assert events[-1]["summary"] == stats["telemetry"]
    assert stats["telemetry"]["fences_per_step"] == 1.0
    assert (stats["telemetry"]["step_ms_p50"]
            <= stats["telemetry"]["step_ms_p95"]
            <= stats["telemetry"]["step_ms_max"])


def test_run_end_calibration_block(tmp_path):
    """ISSUE 6: ``run_end`` carries a ``calibration`` block — the
    dispatch/fence constants the execution autotuner
    (search/cost_model.Calibration) fits from ONE ``--telemetry`` run
    (OBSERVABILITY.md schema)."""
    with Telemetry(str(tmp_path)) as tel:
        Trainer(_executor()).fit(iterations=4, warmup=1, log_every=2)
    cal = _events(tel.path)[-1]["calibration"]
    assert cal["steps"] == 4
    # STEADY-STATE fences/step: the 2 log_every readbacks over 4 steps;
    # the once-per-run warmup/final fences are excluded (they are also
    # excluded from fence_ms — the fit multiplies the two together).
    assert cal["fences_per_step"] == 0.5
    assert cal["step_ms_p50"] > 0
    # fence_ms = the MINIMUM non-warmup/final fence (round-trip floor);
    # the compile-inclusive warmup and run-draining final are excluded.
    assert cal["fence_samples"] == 2  # the two log_every readbacks
    log_walls = [e["wall_s"] * 1e3 for e in _events(tel.path)
                 if e["ev"] == "fence" and e["label"] == "log"]
    assert cal["fence_ms"] == pytest.approx(min(log_walls), abs=2e-3)
    # The loader round-trips the block into calibrated constants.
    from flexflow_tpu.search import Calibration

    loaded = Calibration.from_jsonl(tel.path)
    assert loaded.calibrated
    assert loaded.fence_ms == cal["fence_ms"]
    assert loaded.step_ms_p50 == cal["step_ms_p50"]
    assert Calibration.from_telemetry(tel).fence_ms == cal["fence_ms"]


def test_superstep_one_fence_per_superstep(tmp_path):
    with Telemetry(str(tmp_path)) as tel:
        stats = Trainer(_executor()).fit(iterations=8, warmup=2,
                                         steps_per_call=4)
    events = _events(tel.path)
    ss = [e for e in events if e["ev"] == "superstep"]
    assert len(ss) == 2 and all(e["k"] == 4 and e["mode"] == "fused"
                                for e in ss)
    timed_fences = [e for e in events
                    if e["ev"] == "fence" and e["label"] == "superstep"]
    assert len(timed_fences) == 2  # the amortization, visible in the log
    steps = [e for e in events if e["ev"] == "step"]
    assert len(steps) == 8 and all("loss" in e for e in steps)
    assert stats["telemetry"]["steps"] == 8


# -- pipeline dispatch audit ----------------------------------------------


@pytest.mark.parametrize("chunk", [1, 2, 4])
def test_programs_per_step_equals_last_schedule(chunk):
    import jax

    ff = _model(batch=16, depth=2)
    st = StrategyStore(8)
    st.set("fc0", ParallelConfig(n=4, device_ids=(0, 1, 2, 3)))
    for name in ("fc1", "head", "softmax"):
        st.set(name, ParallelConfig(n=4, device_ids=(4, 5, 6, 7)))
    pipe = PipelineExecutor(
        ff, st, optimizer=SGDOptimizer(lr=0.1), microbatches=4, chunk=chunk,
    )
    params, opt_state, state = pipe.init()
    batch = pipe.shard_batch(_batch(np.random.default_rng(0), batch=16))
    with Telemetry() as tel:
        for _ in range(2):
            params, opt_state, state, m = pipe.train_step(
                params, opt_state, state, batch
            )
        jax.device_get(m)
    expected = 2 * 2 * -(-4 // chunk)  # 2*S*ceil(m/c)
    assert len(pipe.last_schedule) == expected
    assert tel.counts["host_programs"] == 2 * expected
    assert tel.step_summary()["programs_per_step"] == expected


# -- chaos reconstruction --------------------------------------------------


def test_chaos_log_reconstructs_run(tmp_path):
    from flexflow_tpu.runtime.chaos import chaos_batch_fn, tiny_factory
    from flexflow_tpu.runtime.checkpoint import CheckpointManager
    from flexflow_tpu.runtime.resilience import (
        FailurePolicy,
        FaultInjector,
        ResilientTrainer,
    )

    iters = 16
    with Telemetry(str(tmp_path / "tel")) as tel:
        with CheckpointManager(str(tmp_path / "ck"), async_save=True) as ck:
            rt = ResilientTrainer(
                tiny_factory(), ck, policy=FailurePolicy(max_restarts=3),
                fault_injector=FaultInjector(nan_loss_at=(11,)),
            )
            out = rt.fit(iterations=iters, batch_fn=chaos_batch_fn,
                         save_every=8, steps_per_call=8)
    assert out["restarts"] == 1
    # The chaos log is read back through THE log reader (obs.reader):
    # schema-validated events, replay-aware step reconstruction.
    from flexflow_tpu.obs.reader import RunLog

    log = RunLog.load(tel.path)
    assert log.complete and log.exit == "clean"
    assert not log.malformed and not log.unknown_events
    events = list(log.iter_raw())
    tss = [e["ts"] for e in events]
    assert tss == sorted(tss)  # monotonic across fault/rollback/replay
    kinds = [e["ev"] for e in events]
    # fault -> rollback -> (restore) -> replay, in order.
    i_fault = kinds.index("fault")
    i_roll = kinds.index("rollback")
    i_replay = kinds.index("replay")
    assert i_fault < i_roll < i_replay
    assert events[i_fault]["mode"] == "nan_loss"
    assert events[i_fault]["step"] == 11
    assert events[i_roll]["restart"] == 1
    assert "StepFailure" in events[i_roll]["reason"]
    # The rollback restored the step-8 snapshot and replayed from it.
    restores = [e for e in events if e["ev"] == "ckpt_restore"]
    assert any(e["step"] == 8 for e in restores)
    assert events[i_replay]["from_step"] == 8
    saves = [e for e in events if e["ev"] == "ckpt_save"]
    assert {e["step"] for e in saves} >= {8, 16}
    assert all(e["io_s"] >= 0 for e in saves + restores)
    assert all(e["async"] for e in saves)
    # Replaying the log alone reproduces the live run: last step event
    # per index IS the validated loss (replays overwrite) — the exact
    # semantics of RunLog.losses().
    replayed = log.losses()
    assert sorted(replayed) == list(range(iters))
    assert replayed == out["losses"]
    assert replayed[iters - 1] == out["loss"]
    assert out["telemetry"]["steps"] == len(
        [e for e in events if e["ev"] == "step"]
    )


# -- off-path purity -------------------------------------------------------


def test_telemetry_off_is_bit_identical():
    stats_off = Trainer(_executor(seed=3)).fit(iterations=4, warmup=1)
    with Telemetry() as tel:
        stats_on = Trainer(_executor(seed=3)).fit(iterations=4, warmup=1)
    # Off: the pre-PR stats surface, nothing folded in.
    assert sorted(stats_off) == [
        "batch_size", "elapsed_s", "iterations", "loss", "samples_per_s",
    ]
    # Numerics identical bit for bit; only the "telemetry" key differs.
    assert stats_on["loss"] == stats_off["loss"]
    assert stats_on["iterations"] == stats_off["iterations"]
    assert "telemetry" in stats_on
    # The enabled run added NO fences: one warmup + one final readback,
    # exactly the device_get count the un-telemetered loop performs.
    assert tel.counts["fences"] == 2


@pytest.fixture(scope="module")
def tiny_server():
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.runtime.serving import Server, ServingExecutor
    from flexflow_tpu.serving import uniform_workload

    lm = build_transformer_lm(batch_size=2, seq_len=16, vocab_size=64,
                              d_model=32, num_heads=2, num_layers=2,
                              config=FFConfig(batch_size=2))
    sex = ServingExecutor(lm, max_batch=2, max_seq=16, buckets=(8, 16),
                          decode_kernel=False)
    params, state = sex.init(seed=0)
    reqs = uniform_workload(5, 64, prompt_len=(3, 6), max_new_tokens=6,
                            seed=5)
    srv = Server(sex, params, state, decode_steps=4)
    # The baseline: no stream, no profile (and every program built).
    res, st = srv.run(reqs)
    return (srv, reqs, {i: r.tokens for i, r in res.items()},
            st["prefills"] + st["decode_supersteps"])


@pytest.mark.parametrize("stream,profile", [
    (False, False), (True, False), (False, True), (True, True)])
def test_serving_spans_add_no_fence_and_change_no_token(
        tiny_server, stream, profile, tmp_path, monkeypatch):
    """The same pin for the serving loop and its ``ff/serve/*`` spans:
    with a profile running or not, with a stream open or not, the
    tokens are the same bit for bit and the loop fences exactly once
    an admission and once a superstep."""
    import contextlib

    import jax

    srv, reqs, want_tokens, want_fences = tiny_server
    real, calls = jax.device_get, []
    monkeypatch.setattr(jax, "device_get",
                        lambda v: (calls.append(1), real(v))[1])
    tel = Telemetry(str(tmp_path / "tel")) if stream \
        else contextlib.nullcontext()
    if profile:
        jax.profiler.start_trace(str(tmp_path / "xprof"))
    try:
        with tel:
            res, st = srv.run(reqs)
    finally:
        if profile:
            jax.profiler.stop_trace()
    assert {i: r.tokens for i, r in res.items()} == want_tokens
    assert len(calls) == want_fences
    assert st["prefills"] + st["decode_supersteps"] == want_fences
    if stream:
        assert st["telemetry"]["fences"] == want_fences
        sup = [e for e in _events(tel.path)
               if e["ev"] == "decode_superstep"]
        assert sup and all(e["capacity"] == 2 >= e["active"] for e in sup)
    else:
        assert "telemetry" not in st


def test_null_telemetry_fence_is_device_get():
    import jax.numpy as jnp

    assert telemetry.current() is NULL
    host = NULL.fence({"a": jnp.float32(2.0)}, "anything")
    assert float(host["a"]) == 2.0
    NULL.record_step(0, loss=1.0)
    NULL.emit("x", y=1)
    NULL.add_programs(3)
    assert NULL.fold_stats({"k": 1}) == {"k": 1}


# -- watchdog / heartbeat --------------------------------------------------


def test_watchdog_warns_and_recovers(caplog):
    with caplog.at_level(logging.WARNING, logger="ff.telemetry"):
        with Telemetry(stall_deadline_s=0.1) as tel:
            time.sleep(0.45)
            assert tel._stalled  # fired while no heartbeats arrived
            tel.heartbeat("step:0")  # the stall clears on its own
            assert not tel._stalled
    msgs = [r.message for r in caplog.records]
    assert any("NO heartbeat" in m and "NOT killing" in m for m in msgs)
    assert any("resumed" in m for m in msgs)


def test_watchdog_warns_once_per_stall(caplog):
    with caplog.at_level(logging.WARNING, logger="ff.telemetry"):
        with Telemetry(stall_deadline_s=0.1):
            time.sleep(0.6)
    stalls = [r for r in caplog.records if "NO heartbeat" in r.message]
    assert len(stalls) == 1  # loud once, not a warning storm


def test_watchdog_notifies_external_supervisor(tmp_path):
    """Stall escalation (--stall-notify-pid): the watchdog SIGUSR1s an
    EXTERNAL supervisor process on stall — and still kills nothing
    (the child observes the signal and exits cleanly on its own).  The
    watchdog is armed only once the child says its handler is in: a
    SIGUSR1 that finds the default disposition kills it (seen once
    under six workers, where starting an interpreter outlasted the
    0.1 s deadline)."""
    import subprocess
    import sys

    child = subprocess.Popen(
        [sys.executable, "-c", (
            "import signal, sys, time\n"
            "got = []\n"
            "signal.signal(signal.SIGUSR1, lambda s, f: got.append(s))\n"
            "print('READY', flush=True)\n"
            "deadline = time.monotonic() + 15\n"
            "while not got and time.monotonic() < deadline:\n"
            "    time.sleep(0.02)\n"
            "print('NOTIFIED' if got else 'TIMEOUT')\n"
        )],
        stdout=subprocess.PIPE, text=True,
    )
    try:
        assert child.stdout.readline().strip() == "READY"
        with Telemetry(str(tmp_path), stall_deadline_s=0.1,
                       notify_pid=child.pid) as tel:
            time.sleep(0.5)
            path = tel.path
        out, _ = child.communicate(timeout=20)
    finally:
        if child.poll() is None:
            child.kill()
    assert "NOTIFIED" in out
    events = [json.loads(l) for l in open(path)]
    stalls = [e for e in events if e["ev"] == "stall"]
    assert stalls and stalls[0]["notified_pid"] == child.pid


def test_watchdog_refuses_self_notification():
    """The escalation hook never signals the process it watches
    (the observe-and-warn contract)."""
    with Telemetry(stall_deadline_s=0.0, notify_pid=os.getpid()) as tel:
        assert tel._notify_pid == 0


def test_heartbeat_file(tmp_path, monkeypatch):
    hb = tmp_path / "heartbeat"
    with Telemetry(str(tmp_path)) as tel:
        assert hb.exists()
        t0 = hb.stat().st_mtime
        time.sleep(0.02)
        tel.heartbeat()
        assert hb.stat().st_mtime >= t0
    # FF_HEARTBEAT_FILE relocates it (an external supervisor's wiring).
    alt = tmp_path / "alt_beat"
    monkeypatch.setenv("FF_HEARTBEAT_FILE", str(alt))
    with Telemetry():
        pass
    assert alt.exists()


# -- config / flags --------------------------------------------------------


def test_resilient_trainer_self_installs_from_config(tmp_path):
    from flexflow_tpu.runtime.chaos import chaos_batch_fn, tiny_factory
    from flexflow_tpu.runtime.checkpoint import CheckpointManager
    from flexflow_tpu.runtime.resilience import ResilientTrainer

    make = tiny_factory()

    def factory():
        ex = make()
        ex.config.telemetry_dir = str(tmp_path / "tel")
        ex.config.stall_deadline_s = 0.0
        return ex

    with CheckpointManager(str(tmp_path / "ck")) as ck:
        out = ResilientTrainer(factory, ck).fit(
            iterations=4, batch_fn=chaos_batch_fn, save_every=4,
        )
    assert "telemetry" in out and out["telemetry"]["steps"] == 4
    # ONE run log; the registry index (runs.jsonl, obs/registry.py)
    # rides alongside and deliberately misses the run-*.jsonl glob.
    logs = [p for p in os.listdir(tmp_path / "tel") if p.startswith("run-")]
    assert len(logs) == 1
    assert os.path.exists(tmp_path / "tel" / "runs.jsonl")


def test_pipeline_clip_norm_fence_is_instrumented():
    import jax

    ff = _model(batch=16, depth=2)
    ff.config.clip_norm = 1.0
    st = StrategyStore(8)
    st.set("fc0", ParallelConfig(n=4, device_ids=(0, 1, 2, 3)))
    for name in ("fc1", "head", "softmax"):
        st.set(name, ParallelConfig(n=4, device_ids=(4, 5, 6, 7)))
    pipe = PipelineExecutor(ff, st, optimizer=SGDOptimizer(lr=0.1),
                            microbatches=2)
    params, opt_state, state = pipe.init()
    batch = pipe.shard_batch(_batch(np.random.default_rng(0), batch=16))
    with Telemetry() as tel:
        params, opt_state, state, m = pipe.train_step(
            params, opt_state, state, batch
        )
        jax.device_get(m)
        # The per-step clip-norm device_get is a REAL fence; the
        # watchdog/counters must see it.
        assert tel.counts["fences"] == 1


def test_two_runs_same_second_get_distinct_files(tmp_path):
    # strftime has 1 s resolution; the per-process run counter keeps
    # back-to-back fits from append-interleaving into one JSONL file.
    with Telemetry(str(tmp_path)) as a:
        pass
    with Telemetry(str(tmp_path)) as b:
        pass
    assert a.path != b.path
    assert len([p for p in os.listdir(tmp_path) if p.startswith("run-")]) == 2


def test_cli_flags(tmp_path):
    cfg = FFConfig.parse_args(
        ["--telemetry", str(tmp_path), "--stall-deadline", "7.5"]
    )
    assert cfg.telemetry_dir == str(tmp_path)
    assert cfg.stall_deadline_s == 7.5
    assert FFConfig().telemetry_dir is None  # off by default


def test_config_wires_trainer(tmp_path):
    ex = _executor()
    ex.config.telemetry_dir = str(tmp_path)
    ex.config.stall_deadline_s = 0.0
    stats = Trainer(ex).fit(iterations=2, warmup=1)
    assert "telemetry" in stats
    logs = [p for p in os.listdir(tmp_path) if p.startswith("run-")]
    assert len(logs) == 1
    events = _events(os.path.join(str(tmp_path), logs[0]))
    assert events[0]["ev"] == "run_start" and events[-1]["ev"] == "run_end"


def test_nested_fit_reports_into_outer_run(tmp_path):
    ex = _executor()
    ex.config.telemetry_dir = str(tmp_path)  # would self-install...
    with Telemetry() as outer:  # ...but an installed run wins
        Trainer(ex).fit(iterations=2, warmup=1)
    assert outer.counts["steps"] == 2
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".jsonl")]


# -- PerfMetrics extras (satellite) ---------------------------------------


def test_perfmetrics_extras_and_report():
    from flexflow_tpu.metrics import PerfMetrics

    pm = PerfMetrics()
    pm.update({"train_loss": 1.0, "train_correct": 3, "train_all": 4})
    base = pm.report()
    assert base == "[Metrics] loss=1.000000 accuracy=75.00% (3/4)"
    pm2 = PerfMetrics()
    pm2.update({"train_loss": 1.0, "train_correct": 3, "train_all": 4,
                "grad_norm": 2.0})
    pm2.update({"train_loss": 1.0, "train_correct": 3, "train_all": 4,
                "grad_norm": 4.0})
    assert pm2.avg_extra("grad_norm") == 3.0
    # Reference-format prefix bit-identical; extras append after it.
    assert pm2.report().startswith(
        "[Metrics] loss=1.000000 accuracy=75.00% (6/8)"
    )
    assert "grad_norm=3.000000" in pm2.report()


# -- program builds (OBSERVABILITY.md "Program builds") --------------------

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from flexflow_tpu.runtime.telemetry import BUILD_LOG, BuildLog  # noqa: E402

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def every_build_listed(monkeypatch):
    """A CPU compile of a few operations may fall under the 10 ms that
    separate a listed program from the ``small`` line: list them all."""
    monkeypatch.setattr(BUILD_LOG, "SMALL_S", 0.0)


@pytest.fixture
def compile_cache(tmp_path):
    """jax's persistent cache on, in a directory of the test's own,
    writing every program (the suite runs with it off: conftest.py)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = {"jax_enable_compilation_cache": True,
            "jax_compilation_cache_dir": str(tmp_path / "jax_cache"),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1}
    old = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    cc.reset_cache()
    try:
        yield
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _program(name):
    """A fresh jitted function called ``name``: nothing of it is in any
    of jax's caches, and ``jit(name)`` finds its records."""
    def f(x):
        return jnp.where(x > 0, jnp.sin(x) @ x, 0.0).sum()
    f.__name__ = f.__qualname__ = name
    return jax.jit(f)


def _builds(path_or_events, fun=None):
    events = path_or_events if isinstance(path_or_events, list) \
        else _events(path_or_events)
    return [e for e in events if e["ev"] == "program_build"
            and (fun is None or e.get("fun") == fun)]


def test_build_before_the_stream_arrives_as_backlog(tmp_path,
                                                    every_build_listed):
    f = _program("built_before")
    f(jnp.ones((8, 8)))
    with Telemetry(str(tmp_path)) as tel:
        pass
    events = _events(tel.path)
    assert events[0]["ev"] == "run_start" and events[-1]["ev"] == "run_end"
    lower, comp = _builds(events, "jit(built_before)")
    assert lower["backlog"] is True and comp["backlog"] is True
    assert lower["phase"] == "lower" and lower["trace_s"] > 0
    assert lower["wall_s"] > 0 and "cache" not in lower
    assert comp["phase"] == "compile" and comp["wall_s"] > 0
    assert "trace_s" not in comp
    # the backlog follows run_start, in t1 order, before anything else
    back = [e for e in events if e.get("backlog")]
    assert events[1:1 + len(back)] == back
    assert [e["t1"] for e in back] == sorted(e["t1"] for e in back)


def test_build_inside_the_stream_arrives_live(tmp_path, every_build_listed):
    f = _program("built_inside")
    with Telemetry(str(tmp_path)) as tel:
        tel.emit("analysis", clean=True, violations=[])
        f(jnp.ones((8, 8)))
    events = _events(tel.path)
    lower, comp = _builds(events, "jit(built_inside)")
    assert not lower.get("backlog") and not comp.get("backlog")
    assert (lower["phase"], comp["phase"]) == ("lower", "compile")
    mark = next(e["seq"] for e in events if e["ev"] == "analysis")
    assert mark < lower["seq"] < comp["seq"]


def test_cached_call_writes_no_event_and_the_log_does_not_grow(
        tmp_path, every_build_listed):
    """The zero-hot-path pin: a listener runs only while jax traces,
    lowers or compiles."""
    before, inside = _program("warm_before"), _program("warm_inside")
    x = jnp.ones((8, 8))
    before(x)
    with Telemetry(str(tmp_path)) as tel:
        inside(x)
        BUILD_LOG.flush()
        made, n = BUILD_LOG.made, len(_events(tel.path))
        for _ in range(3):
            before(x).block_until_ready()
            inside(x).block_until_ready()
        BUILD_LOG.flush()
        assert BUILD_LOG.made == made
        assert len(_events(tel.path)) == n


def test_nested_jit_leaves_one_top_level_trace(tmp_path,
                                               every_build_listed):
    inner = _program("nested_inner")

    def outer(x):
        return inner(x) + inner(2 * x)
    outer.__name__ = outer.__qualname__ = "nested_outer"
    x = jnp.ones((8, 8))
    with Telemetry(str(tmp_path)) as tel:
        jax.jit(outer)(x)
    builds = [b for b in _builds(tel.path) if not b.get("backlog")]
    assert [b["phase"] for b in builds
            if b.get("fun") == "jit(nested_outer)"] == ["lower", "compile"]
    # the inner jit was traced inside the outer's trace span: no record
    # of its own, and no stray ``trace`` record of any jnp helper
    assert not [b for b in builds if "nested_inner" in str(b.get("fun"))]
    assert not [b for b in builds if b["phase"] == "trace"]


def test_cache_miss_then_hit_with_retrieval_s(tmp_path, compile_cache,
                                              every_build_listed):
    f = _program("cached_twice")
    x = jnp.ones((8, 8))
    with Telemetry(str(tmp_path / "tel")) as tel:
        f(x)
        jax.clear_caches()
        f(x)
    first, second = [b for b in _builds(tel.path, "jit(cached_twice)")
                     if b["phase"] == "compile"]
    assert first["cache"] == "miss" and "retrieval_s" not in first
    assert second["cache"] == "hit" and second["retrieval_s"] > 0
    assert second["retrieval_s"] <= second["wall_s"]


def test_no_cache_directory_reads_off(tmp_path, every_build_listed):
    assert not jax.config.jax_compilation_cache_dir
    with Telemetry(str(tmp_path)) as tel:
        _program("no_cache")(jnp.ones((8, 8)))
    (comp,) = [b for b in _builds(tel.path, "jit(no_cache)")
               if b["phase"] == "compile"]
    assert comp["cache"] == "off" and "retrieval_s" not in comp


def _feed(log, fun, t0, trace=0.0, lower=0.0, compile_s=None, events=()):
    """One program's spans as jax hands them over: each at its end."""
    t = t0
    log.on_span(_TRACE, t, t + trace, fun_name=fun)
    t += trace
    log.on_span(_LOWER, t, t + lower, fun_name=f"jit({fun})")
    t += lower
    if compile_s is not None:
        for ev in events:
            log.on_event(f"/jax/compilation_cache/{ev}")
        log.on_span(_COMPILE, t, t + compile_s, fun_name=f"jit({fun})")
        t += compile_s
    return t


def test_a_microsecond_eager_primitive_is_in_the_small_line():
    log = BuildLog()
    t = _feed(log, "convert_element_type", 100.0, 1e-6, 1e-6, 1e-6,
              events=("compile_requests_use_cache", "cache_hits"))
    t = _feed(log, "broadcast_in_dim", t, 1e-3, 2e-3, 3e-3)
    assert not log.records and log.made == 0  # counted, not listed
    t = _feed(log, "train_step", t, 0.5, 0.25, 2.0)
    small, lower, comp = log.records
    assert small["phase"] == "small" and small["n"] == 2
    assert small["trace_lower_s"] == pytest.approx(3e-3 + 2e-6)
    assert small["compile_s"] == pytest.approx(3e-3 + 1e-6)
    assert small["wall_s"] == pytest.approx(6e-3 + 3e-6)
    assert small["misses"] == 1  # the hit is not one
    # from the first program's lowering (its own edge) to the last's end
    assert (small["t0"], small["t1"]) == (100.000001, 100.006003)
    assert (lower["fun"], lower["phase"]) == ("jit(train_step)", "lower")
    assert (lower["trace_s"], lower["wall_s"]) == (0.5, 0.25)
    assert (comp["phase"], comp["wall_s"], comp["cache"]) == \
        ("compile", 2.0, "off")
    _feed(log, "add", t, 1e-6, 1e-6, 1e-6)
    log.flush()  # the running line is written at close
    assert log.records[-1]["phase"] == "small" and log.records[-1]["n"] == 1
    assert log.made == 4


def test_the_probe_lowers_and_the_call_compiles_one_program():
    """``fn.lower()`` before the first call (``program_cost``): the
    lowering waits for its compile, a cached trace looked up in between
    is no record, and a second lowering of the same ``fun`` shows as a
    second ``lower`` record."""
    log = BuildLog()
    t = _feed(log, "prefill", 10.0, 3.0, 1.0)          # the probe
    log.on_span(_TRACE, t, t + 2e-5, fun_name="prefill")  # the call
    log.on_span(_COMPILE, t + 1e-3, t + 5.0, fun_name="jit(prefill)")
    assert [(r["phase"], r["wall_s"]) for r in log.records] == \
        [("lower", 1.0), ("compile", pytest.approx(4.999))]
    assert log.records[0]["trace_s"] == 3.0
    t = _feed(log, "prefill", 20.0, 3.0, 1.0)          # lowered again,
    _feed(log, "superstep", t, 0.5, 0.5, 1.0)          # never compiled
    assert [(r["fun"], r["phase"]) for r in log.records][2:] == [
        ("jit(prefill)", "lower"), ("jit(superstep)", "lower"),
        ("jit(superstep)", "compile")]


def test_a_trace_no_lowering_claims_is_a_record_of_its_own():
    log = BuildLog()
    log.on_span(_TRACE, 1.0, 1.5, fun_name="init")       # an eval_shape
    log.on_span(_TRACE, 2.0, 2.00002, fun_name="step")   # a cache lookup
    _feed(log, "step", 3.0, 0.1, 0.1, 0.1)
    assert [(r["fun"], r["phase"], r["wall_s"]) for r in log.records] == [
        ("init", "trace", 0.5), ("jit(step)", "lower", 0.1),
        ("jit(step)", "compile", 0.1)]


def test_a_build_inside_a_trace_is_not_counted_twice():
    """An eager program built while an outer function is traced lies
    inside the outer's trace span: the outer's ``trace_s`` is its span
    less what was booked inside it."""
    log = BuildLog()
    _feed(log, "eager", 1.0, 0.1, 0.2, 0.3)              # 1.0 .. 1.6
    log.on_span(_TRACE, 0.5, 2.0, fun_name="outer")
    log.on_span(_LOWER, 2.0, 2.5, fun_name="jit(outer)")
    log.flush()
    outer = [r for r in log.records if r["fun"] == "jit(outer)"][0]
    assert outer["trace_s"] == pytest.approx(1.5 - 0.6)
    total = sum(r["wall_s"] + r.get("trace_s", 0.0) for r in log.records)
    assert total == pytest.approx(2.0)  # 0.5 .. 2.5, each second once


def test_the_log_keeps_the_newest_records_and_counts_the_rest():
    log = BuildLog()
    t = 0.0
    for i in range(BuildLog.MAX_RECORDS // 2 + 3):
        t = _feed(log, f"f{i}", t, 1.0, 1.0, 1.0)
    assert len(log.records) == BuildLog.MAX_RECORDS
    assert log.made - len(log.records) == 6
    assert log.records[-1]["fun"] == f"jit(f{BuildLog.MAX_RECORDS // 2 + 2})"
    _feed(log, "add", t, 1e-6, 1e-6, 1e-6)
    log.flush()
    assert log.records[-1]["dropped"] == 6  # before the line itself
    assert [r["phase"] for r in log.since(log.made - 2)] == \
        ["compile", "small"]
    assert log.since(log.made) == [] and len(log.since(0)) == len(log.records)


def test_a_listener_never_raises(caplog):
    log = BuildLog()
    with caplog.at_level(logging.DEBUG, logger="ff.telemetry"):
        log.on_span(_LOWER, "not a time", None, fun_name=object())
        log.on_span(_COMPILE, 1.0, 2.0)           # no fun_name at all
        log.on_span("/some/other/span", 1.0, 2.0, fun_name="x")
        log.on_event("/some/other/event", a=1)
        log.on_duration("/some/other/duration", 1.0)
    assert "dropped" in caplog.text
    log.flush()
    assert [r["phase"] for r in log.records] == ["compile"]


def test_build_edges_lie_between_the_wall_clock_stamps(tmp_path,
                                                       every_build_listed):
    with Telemetry(str(tmp_path)) as tel:
        before = time.time()
        _program("edges")(jnp.ones((8, 8)))
        after = time.time()
    lower, comp = _builds(tel.path, "jit(edges)")
    for rec in (lower, comp):
        assert before <= rec["t0"] <= rec["t1"] <= after
        assert rec["wall_s"] == pytest.approx(rec["t1"] - rec["t0"], abs=2e-6)
        assert rec["t1"] <= rec["ts"]  # the line is written at the span's end
    assert lower["t1"] <= comp["t0"]


def test_two_streams_each_get_the_backlog_once(tmp_path,
                                               every_build_listed):
    _program("seen_by_both")(jnp.ones((8, 8)))
    paths = []
    for d in ("a", "b"):
        with Telemetry(str(tmp_path / d)) as tel:
            _program(f"only_{d}")(jnp.ones((8, 8)))
        paths.append(tel.path)
    a, b = (_builds(p) for p in paths)
    for recs in (a, b):
        both = [r for r in recs if r.get("fun") == "jit(seen_by_both)"]
        assert [r["phase"] for r in both] == ["lower", "compile"]
        assert all(r["backlog"] for r in both)
    assert not _builds(a, "jit(only_b)")
    live, again = _builds(a, "jit(only_a)"), _builds(b, "jit(only_a)")
    assert not any(r.get("backlog") for r in live)
    assert [r["phase"] for r in again] == ["lower", "compile"]
    assert all(r["backlog"] for r in again)
    assert [(r["t0"], r["t1"]) for r in live] == \
        [(r["t0"], r["t1"]) for r in again]


def test_a_stream_with_no_file_takes_builds_as_any_event(
        every_build_listed):
    with Telemetry(directory=None) as tel:
        seq = tel._seq
        _program("no_file")(jnp.ones((8, 8)))
        assert tel._seq == seq + 2 and tel.path is None


def test_program_cost_carries_wall_s(tmp_path, every_build_listed):
    """The probe before the first call IS the program's lowering: one
    ``lower`` record, and the call that follows only compiles."""
    f = _program("probed")
    x = jnp.ones((8, 8))
    with Telemetry(str(tmp_path)) as tel:
        tel.program_cost("train_step", f, (x,))
        f(x)
        tel.program_cost("train_step", f, (x,))  # deduped: no second event
    events = _events(tel.path)
    (cost,) = [e for e in events if e["ev"] == "program_cost"]
    assert cost["wall_s"] > 0 and cost["flops"] > 0
    lower, comp = _builds(events, "jit(probed)")
    assert (lower["phase"], comp["phase"]) == ("lower", "compile")
    assert lower["trace_s"] + lower["wall_s"] <= cost["wall_s"]


def test_program_cost_without_an_analysis_still_says_what_it_took(tmp_path):
    """The TPU's ``Lowered.cost_analysis()`` is ``None``."""
    class NoAnalysis:
        def lower(self, *args):
            return self

        def cost_analysis(self):
            return None

    with Telemetry(str(tmp_path)) as tel:
        tel.program_cost("prefill", NoAnalysis(), (), bucket=512)
    (cost,) = [e for e in _events(tel.path) if e["ev"] == "program_cost"]
    assert cost["wall_s"] >= 0 and cost["bucket"] == 512
    assert "flops" not in cost


def test_obs_report_prints_builds_and_flags_steady_state(
        tmp_path, capsys, every_build_listed):
    from flexflow_tpu.obs.__main__ import main as obs_main
    from flexflow_tpu.obs.reader import RunLog

    x, x4 = jnp.ones((8, 8)), jnp.ones((4, 4))

    with Telemetry(str(tmp_path)) as tel:
        tel.emit("serve_run", requests=1, capacity=1, k=1)  # the warm-up
        tel.emit("serving_program", kind="prefill", bucket=64)
        _program("prefill")(x)
        tel.emit("serving_program", kind="decode", k=4)
        _program("superstep")(x)
        tel.emit("decode_superstep", k=4, active=1, slots=[0], wall_s=0.01)
        tel.emit("serve_run", requests=1, capacity=1, k=1)  # the window
        tel.emit("decode_superstep", k=4, active=1, slots=[0], wall_s=0.01)
        time.sleep(0.002)
        tel.emit("serving_program", kind="prefill", bucket=128)
        _program("prefill")(x4)
    log = RunLog.load(tel.path)
    assert not log.unknown_events
    folded = log.program_builds()
    rows = {(r["fun"], r["shape"]): r for r in folded["rows"]}
    assert rows[("jit(prefill)", "bucket=64")]["lowered"] == 1
    assert rows[("jit(prefill)", "bucket=128")]["compiled"] == 1
    assert rows[("jit(superstep)", "k=4")]["compile_s"] > 0
    assert rows[("jit(superstep)", "k=4")]["misses"] == 1
    # only the build after the window's first round is in steady state
    assert {(b["fun"], b["phase"]) for b in folded["steady"]} == {
        ("jit(prefill)", "lower"), ("jit(prefill)", "compile")}
    assert obs_main(["report", tel.path]) == 0
    out = capsys.readouterr().out
    assert "program builds" in out
    assert "jit(superstep) [k=4]" in out and "lowered x1 compiled x1" in out
    assert out.count("BUILD IN STEADY STATE: jit(prefill)") == 2
