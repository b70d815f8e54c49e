"""Failure detection / elastic recovery (a subsystem the reference
lacks entirely — FatalError aborts, SURVEY.md §5)."""

import numpy as np
import pytest

import jax

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime.checkpoint import CheckpointManager
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.resilience import (
    FailurePolicy,
    FaultInjector,
    ResilientTrainer,
)


def _factory():
    def make():
        ff = FFModel(FFConfig(batch_size=8))
        x = ff.create_tensor((8, 16), name="x")
        lbl = ff.create_tensor((8,), dtype=np.int32, name="label")
        t = ff.dense(x, 32, activation="relu", name="fc1")
        t = ff.dense(t, 4, name="fc2")
        ff.softmax(t, lbl, name="softmax")
        store = StrategyStore(8, {"fc1": ParallelConfig(n=2, c=4)})
        return Executor(ff, strategy=store, optimizer=SGDOptimizer(lr=0.1))

    return make


def _batch_fn(step):
    rng = np.random.default_rng(step)  # deterministic per step
    return {
        "x": rng.standard_normal((8, 16)).astype(np.float32),
        "label": rng.integers(0, 4, size=(8,)).astype(np.int32),
    }


def test_trains_to_completion_and_checkpoints(tmp_path):
    with CheckpointManager(str(tmp_path / "ck")) as ck:
        rt = ResilientTrainer(_factory(), ck)
        out = rt.fit(iterations=7, batch_fn=_batch_fn, save_every=3)
        assert out["step"] == 7 and out["restarts"] == 0
        assert np.isfinite(out["loss"])
        assert ck.latest_step() == 7


def test_recovers_from_injected_fault(tmp_path):
    fails = {"left": 2}

    def inject(step):
        if step == 5 and fails["left"] > 0:
            fails["left"] -= 1
            raise RuntimeError("injected device failure")

    with CheckpointManager(str(tmp_path / "ck")) as ck:
        rt = ResilientTrainer(_factory(), ck, fault_injector=inject)
        out = rt.fit(iterations=8, batch_fn=_batch_fn, save_every=2)
        assert out["step"] == 8
        assert out["restarts"] == 2
        assert np.isfinite(out["loss"])


def test_nonfinite_loss_rolls_back(tmp_path):
    poisoned = {"armed": True}

    def batch_fn(step):
        b = _batch_fn(step)
        if step == 4 and poisoned["armed"]:
            poisoned["armed"] = False  # only the first visit is bad
            b["x"] = np.full_like(b["x"], np.nan)
        return b

    with CheckpointManager(str(tmp_path / "ck")) as ck:
        rt = ResilientTrainer(_factory(), ck)
        out = rt.fit(iterations=6, batch_fn=batch_fn, save_every=2)
        assert out["step"] == 6
        assert out["restarts"] == 1
        assert np.isfinite(out["loss"])


def test_restart_budget_exhausted_raises(tmp_path):
    def inject(step):
        raise RuntimeError("permanently broken")

    with CheckpointManager(str(tmp_path / "ck")) as ck:
        rt = ResilientTrainer(
            _factory(), ck, policy=FailurePolicy(max_restarts=2),
            fault_injector=inject,
        )
        with pytest.raises(RuntimeError, match="restart budget"):
            rt.fit(iterations=3, batch_fn=_batch_fn)
        assert rt.restarts == 3  # 2 allowed + the one that exceeded


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_budget_resets_on_durable_progress(tmp_path):
    """Isolated transient faults spread over a long run must not
    accumulate against the crash-loop budget."""
    def inject(step):
        # One fault after every checkpoint: 6 faults total with budget 3.
        if step % 3 == 2 and inject.seen.get(step, 0) == 0:
            inject.seen[step] = 1
            raise RuntimeError(f"transient at {step}")
    inject.seen = {}

    with CheckpointManager(str(tmp_path / "ck")) as ck:
        rt = ResilientTrainer(
            _factory(), ck, policy=FailurePolicy(max_restarts=3),
            fault_injector=inject,
        )
        out = rt.fit(iterations=18, batch_fn=_batch_fn, save_every=3)
        assert out["step"] == 18
        assert out["restarts"] == 6          # lifetime count
        assert rt.restarts == 0              # budget counter reset


def test_unrecoverable_exception_propagates(tmp_path):
    class Fatal(BaseException):
        pass

    def inject(step):
        raise Fatal("not in recoverable tuple")

    with CheckpointManager(str(tmp_path / "ck")) as ck:
        rt = ResilientTrainer(_factory(), ck, fault_injector=inject)
        with pytest.raises(Fatal):
            rt.fit(iterations=2, batch_fn=_batch_fn)
        assert rt.restarts == 0


def test_programmer_errors_surface_immediately(tmp_path):
    """Regression for the over-broad recoverable default: ValueError is
    a programmer error (bad shapes, wrong keys, broken configs) —
    replaying it from a checkpoint reproduces the same crash until the
    restart budget is exhausted and buries the traceback.  It must
    propagate on the FIRST occurrence, with zero restarts."""
    def inject(step):
        raise ValueError("shape bug: expected (8, 16), got (8, 17)")

    with CheckpointManager(str(tmp_path / "ck")) as ck:
        rt = ResilientTrainer(_factory(), ck, fault_injector=inject)
        with pytest.raises(ValueError, match="shape bug"):
            rt.fit(iterations=4, batch_fn=_batch_fn)
        assert rt.restarts == 0 and rt.total_restarts == 0


def test_real_shape_bug_surfaces_immediately(tmp_path):
    """A batch_fn emitting the wrong feature width must crash on first
    contact (the executor's input assert), not spin the restart loop."""
    def bad_batch(step):
        b = _batch_fn(step)
        b["x"] = np.zeros((8, 17), np.float32)  # model declares (8, 16)
        return b

    with CheckpointManager(str(tmp_path / "ck")) as ck:
        rt = ResilientTrainer(_factory(), ck)
        with pytest.raises((AssertionError, TypeError, ValueError)):
            rt.fit(iterations=4, batch_fn=bad_batch)
        assert rt.restarts == 0


def _trajectory(out, iters):
    return np.array([out["losses"][i] for i in range(iters)])


def test_superstep_trajectory_matches_per_step(tmp_path):
    """fit(steps_per_call=4) must reproduce the per-step resilient
    loop's loss trajectory bit-for-bit (the superstep scan invariant of
    tests/test_superstep.py, now through the resilient loop)."""
    with CheckpointManager(str(tmp_path / "a")) as ck:
        out1 = ResilientTrainer(_factory(), ck).fit(
            iterations=8, batch_fn=_batch_fn, save_every=4)
    with CheckpointManager(str(tmp_path / "b")) as ck:
        out4 = ResilientTrainer(_factory(), ck).fit(
            iterations=8, batch_fn=_batch_fn, save_every=4, steps_per_call=4)
    np.testing.assert_array_equal(_trajectory(out1, 8), _trajectory(out4, 8))


def test_superstep_rollback_replays_bit_identical(tmp_path):
    """A raised fault inside a k=4 superstep: rollback to the last
    boundary checkpoint, deterministic replay, trajectory identical to
    the unfaulted superstep run."""
    with CheckpointManager(str(tmp_path / "ref")) as ck:
        ref = ResilientTrainer(_factory(), ck).fit(
            iterations=12, batch_fn=_batch_fn, save_every=4, steps_per_call=4)
    inj = FaultInjector(raise_at=(9,))
    with CheckpointManager(str(tmp_path / "ck")) as ck:
        out = ResilientTrainer(_factory(), ck, fault_injector=inj).fit(
            iterations=12, batch_fn=_batch_fn, save_every=4, steps_per_call=4)
    assert out["restarts"] == 1 and inj.fired == [("raise", 9)]
    np.testing.assert_array_equal(_trajectory(ref, 12), _trajectory(out, 12))


def test_nan_loss_injection_rolls_back(tmp_path):
    """NaN-in-loss mode: silent divergence surfaced at the batched
    fence without touching device numerics; one-shot, so the replay is
    clean and the final trajectory matches the unfaulted run."""
    with CheckpointManager(str(tmp_path / "ref")) as ck:
        ref = ResilientTrainer(_factory(), ck).fit(
            iterations=6, batch_fn=_batch_fn, save_every=2)
    inj = FaultInjector(nan_loss_at=(4,))
    with CheckpointManager(str(tmp_path / "ck")) as ck:
        out = ResilientTrainer(_factory(), ck, fault_injector=inj).fit(
            iterations=6, batch_fn=_batch_fn, save_every=2)
    assert out["restarts"] == 1 and inj.fired == [("nan_loss", 4)]
    np.testing.assert_array_equal(_trajectory(ref, 6), _trajectory(out, 6))


def test_per_step_fence_is_amortized(tmp_path, monkeypatch):
    """Satellite: the per-step path must not host-fence the loss every
    iteration — one batched readback
    per check_every window."""
    fences = []
    real = jax.device_get

    def counting(x):
        if isinstance(x, list):
            fences.append(len(x))
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    with CheckpointManager(str(tmp_path / "ck")) as ck:
        out = ResilientTrainer(_factory(), ck).fit(
            iterations=12, batch_fn=_batch_fn, save_every=0, check_every=4)
    assert out["step"] == 12
    # 12 steps / check_every=4 → exactly 3 batched fences of 4 losses.
    assert fences == [4, 4, 4]


def test_check_every_clamped_to_fused_steps_cap(tmp_path, monkeypatch):
    """check_every is the same unfenced dependent chain as
    steps_per_call: it must clamp to MAX_STEPS_PER_CALL too."""
    from flexflow_tpu.runtime.trainer import MAX_STEPS_PER_CALL

    fences = []
    real = jax.device_get

    def counting(x):
        if isinstance(x, list):
            fences.append(len(x))
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    with CheckpointManager(str(tmp_path / "ck")) as ck:
        out = ResilientTrainer(_factory(), ck).fit(
            iterations=25, batch_fn=_batch_fn, save_every=0, check_every=50)
    assert out["step"] == 25
    assert fences and max(fences) <= MAX_STEPS_PER_CALL


def test_preemption_emergency_save_and_resume(tmp_path):
    """SIGTERM mid-run: validate the in-flight window, emergency-save,
    return preempted=True; a restarted trainer resumes from the
    emergency snapshot and the concatenated trajectory is bit-identical
    to an unfaulted run."""
    with CheckpointManager(str(tmp_path / "ref")) as ck:
        ref = ResilientTrainer(_factory(), ck).fit(
            iterations=9, batch_fn=_batch_fn, save_every=3)
    ckdir = str(tmp_path / "ck")
    inj = FaultInjector(preempt_at=(4,))
    with CheckpointManager(ckdir) as ck:
        first = ResilientTrainer(_factory(), ck, fault_injector=inj).fit(
            iterations=9, batch_fn=_batch_fn, save_every=3)
    assert first["preempted"] and 0 < first["step"] < 9
    assert first["step"] in (5, 6)  # next boundary after the signal
    with CheckpointManager(ckdir) as ck:
        second = ResilientTrainer(_factory(), ck).fit(
            iterations=9, batch_fn=_batch_fn, save_every=3)
    assert not second["preempted"] and second["step"] == 9
    merged = {**first["losses"], **second["losses"]}
    np.testing.assert_array_equal(
        _trajectory(ref, 9), np.array([merged[i] for i in range(9)])
    )


def test_bare_callable_injector_still_works(tmp_path):
    """The seed API — fault_injector as a bare callable(step) — keeps
    working through the FaultInjector.wrap adapter."""
    calls = []

    def inject(step):
        calls.append(step)

    with CheckpointManager(str(tmp_path / "ck")) as ck:
        out = ResilientTrainer(_factory(), ck, fault_injector=inject).fit(
            iterations=3, batch_fn=_batch_fn, save_every=2)
    assert out["step"] == 3 and calls == [0, 1, 2]


# -- layer-wise (pipeline) executors through the resilient loop (ISSUE 3) ----


def _pipeline_factory():
    """Executor factory yielding a PipelineExecutor (enc on devices
    0-3, dec on 4-7) — the {si: params}/{si: opt_state} per-stage trees
    exercise checkpoint save/restore of int-keyed stage dicts."""
    from flexflow_tpu.runtime.pipeline import PipelineExecutor

    def make():
        ff = FFModel(FFConfig(batch_size=8))
        x = ff.create_tensor((8, 16), name="x")
        lbl = ff.create_tensor((8,), dtype=np.int32, name="label")
        t = ff.dense(x, 32, activation="relu", name="fc1")
        t = ff.dense(t, 4, name="fc2")
        ff.softmax(t, lbl, name="softmax")
        store = StrategyStore(8)
        store.set("fc1", ParallelConfig(n=4, device_ids=(0, 1, 2, 3)))
        for n in ("fc2", "softmax"):
            store.set(n, ParallelConfig(n=4, device_ids=(4, 5, 6, 7)))
        return PipelineExecutor(ff, store, optimizer=SGDOptimizer(lr=0.1),
                                microbatches=2, chunk=2)

    return make


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_pipeline_fault_recovery_matches_unfaulted(tmp_path):
    """The k=1 resilient loop composes with PipelineExecutor.  A raised
    fault mid-run restores the per-stage {si: params}/{si: opt_state}
    trees from the checkpoint and replays deterministically — the
    recovered loss trajectory is bit-identical to an unfaulted pipeline
    run (restore-then-train-on == uninterrupted)."""
    with CheckpointManager(str(tmp_path / "ref")) as ck:
        ref = ResilientTrainer(_pipeline_factory(), ck).fit(
            iterations=8, batch_fn=_batch_fn, save_every=2)
        assert ref["step"] == 8 and ref["restarts"] == 0
        assert ck.latest_step() == 8
        assert sorted(ref["params"].keys()) == [0, 1]  # per-stage trees
    inj = FaultInjector(raise_at=(5,))
    with CheckpointManager(str(tmp_path / "ck")) as ck:
        out = ResilientTrainer(_pipeline_factory(), ck,
                               fault_injector=inj).fit(
            iterations=8, batch_fn=_batch_fn, save_every=2)
    assert out["restarts"] == 1 and inj.fired == [("raise", 5)]
    np.testing.assert_array_equal(_trajectory(ref, 8), _trajectory(out, 8))
    for a, b in zip(jax.tree.leaves(ref["params"]),
                    jax.tree.leaves(out["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipeline_nonfinite_loss_rolls_back(tmp_path):
    """Silent-failure detection reads the pipeline's merged last-stage
    metrics at the batched fence — a NaN batch rolls back and replays."""
    inj = FaultInjector(nan_batch_at=(4,))
    with CheckpointManager(str(tmp_path / "ck")) as ck:
        rt = ResilientTrainer(_pipeline_factory(), ck, fault_injector=inj)
        out = rt.fit(iterations=6, batch_fn=_batch_fn, save_every=2)
    assert out["step"] == 6 and out["restarts"] == 1
    assert np.isfinite(out["loss"])
