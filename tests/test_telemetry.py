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
