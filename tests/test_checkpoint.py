"""Checkpoint/resume subsystem.

The reference has no save/load path at all (SURVEY.md §5); these tests
pin the from-scratch subsystem's core guarantees: exact-resume
numerics, strategy-portable restore, and retention.
"""

import numpy as np
import pytest

import jax

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime import CheckpointManager, Executor, Trainer


def _tiny_model(batch=8):
    ff = FFModel(FFConfig(batch_size=batch))
    x = ff.create_tensor((batch, 12), name="x")
    lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
    t = ff.dense(x, 16, activation="relu", name="fc1")
    t = ff.dense(t, 4, name="fc2")
    ff.softmax(t, lbl, name="softmax")
    return ff


def _batch(ex, seed=0, batch=8):
    rng = np.random.default_rng(seed)
    return ex.shard_batch({
        "x": rng.standard_normal((batch, 12)).astype(np.float32),
        "label": rng.integers(0, 4, size=(batch,)).astype(np.int32),
    })


def _run_steps(ex, params, opt_state, state, batches):
    for b in batches:
        params, opt_state, state, m = ex.train_step(params, opt_state, state, b)
    jax.block_until_ready(m)
    return params, opt_state, state


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestCheckpointRoundtrip:
    def test_resume_matches_uninterrupted_run(self, tmp_path):
        """Train 4 steps straight vs 2 + save + restore + 2: identical
        params AND momentum buffers (SGD momentum must round-trip)."""
        ff = _tiny_model()
        opt = SGDOptimizer(lr=0.05, momentum=0.9)
        ex = Executor(ff, optimizer=opt)
        batches = [_batch(ex, seed=s) for s in range(4)]

        p, o, s = ex.init(seed=7)
        p_ref, o_ref, s_ref = _run_steps(ex, p, o, s, batches)

        p, o, s = ex.init(seed=7)
        p, o, s = _run_steps(ex, p, o, s, batches[:2])
        with CheckpointManager(str(tmp_path / "ck")) as ck:
            ck.save(2, p, o, s)
            p0, o0, s0 = ex.init(seed=0)  # fresh (different) init
            step, p2, o2, s2 = ck.restore(templates=(p0, o0, s0))
        assert step == 2
        p2, o2, s2 = _run_steps(ex, p2, o2, s2, batches[2:])
        _assert_trees_equal(p_ref, p2)
        _assert_trees_equal(o_ref, o2)

    def test_restore_under_different_strategy(self, tmp_path):
        """A checkpoint saved under DP restores into a TP executor and
        produces identical forward numerics — strategy-portable
        checkpoints (impossible in the reference, where weights live in
        strategy-shaped Legion regions)."""
        ff = _tiny_model()
        ex_dp = Executor(ff, optimizer=SGDOptimizer(lr=0.05))
        p, o, s = ex_dp.init(seed=3)
        b = _batch(ex_dp, seed=0)
        p, o, s = _run_steps(ex_dp, p, o, s, [b])
        loss_dp, _ = ex_dp.eval_step(p, s, b)

        with CheckpointManager(str(tmp_path / "ck")) as ck:
            ck.save(1, p, o, s)
            store = StrategyStore(8)
            store.set("fc1", ParallelConfig(n=2, c=4))
            store.set("fc2", ParallelConfig(c=2))
            ex_tp = Executor(ff, strategy=store, optimizer=SGDOptimizer(lr=0.05))
            templates = ex_tp.init(seed=0)
            _, p2, o2, s2 = ck.restore(templates=templates)
        loss_tp, _ = ex_tp.eval_step(p2, s2, _batch(ex_tp, seed=0))
        np.testing.assert_allclose(
            float(loss_dp), float(loss_tp), rtol=1e-5
        )

    def test_latest_step_and_retention(self, tmp_path):
        ff = _tiny_model()
        ex = Executor(ff, optimizer=SGDOptimizer(lr=0.05, momentum=0.9))
        p, o, s = ex.init()
        with CheckpointManager(str(tmp_path / "ck"), max_to_keep=2) as ck:
            assert ck.latest_step() is None
            for step in (1, 2, 3):
                ck.save(step, p, o, s)
            assert ck.latest_step() == 3
            assert ck.all_steps() == [2, 3]  # max_to_keep pruned step 1

    def test_restore_without_checkpoint_raises(self, tmp_path):
        ff = _tiny_model()
        ex = Executor(ff)
        with CheckpointManager(str(tmp_path / "empty")) as ck:
            with pytest.raises(FileNotFoundError):
                ck.restore(templates=ex.init())

    def test_momentumless_and_stateless_roundtrip(self, tmp_path):
        """opt_state=None (no momentum) and empty op-state must survive
        the trip as None/empty, not crash."""
        ff = _tiny_model()
        ex = Executor(ff, optimizer=SGDOptimizer(lr=0.05, momentum=0.0))
        p, o, s = ex.init()
        assert o is None
        with CheckpointManager(str(tmp_path / "ck")) as ck:
            ck.save(1, p, o, s)
            step, p2, o2, s2 = ck.restore(templates=(p, o, s))
        assert step == 1 and o2 is None
        _assert_trees_equal(p, p2)


class TestTrainerIntegration:
    def test_fit_saves_and_resumes(self, tmp_path):
        """Checkpoint step numbers count every applied update, warmup
        included (warmup steps are real updates — train_step donates)."""
        ff = _tiny_model()
        ex = Executor(ff, optimizer=SGDOptimizer(lr=0.05, momentum=0.9))
        trainer = Trainer(ex)
        with CheckpointManager(str(tmp_path / "ck")) as ck:
            trainer.fit(iterations=3, warmup=1, checkpoint=ck, save_every=2)
            # 1 warmup + 3 iterations = 4 updates; periodic save at
            # update 3 (it==2), final at 4.
            assert ck.latest_step() == 4
        # A new trainer resumes from step 4: +1 warmup +2 iters = 7.
        ex2 = Executor(ff, optimizer=SGDOptimizer(lr=0.05, momentum=0.9))
        with CheckpointManager(str(tmp_path / "ck")) as ck:
            Trainer(ex2).fit(iterations=2, warmup=1, checkpoint=ck)
            assert ck.latest_step() == 7


class TestDurability:
    """Async saves, crash-safe force-replace, torn-snapshot fallback
    (the checkpoint half of the resilience tentpole; RESILIENCE.md)."""

    def test_async_save_roundtrip(self, tmp_path):
        """async_save: non-blocking saves; restore fences on pending
        writes, so the round trip is exact regardless of flush timing."""
        ff = _tiny_model()
        ex = Executor(ff, optimizer=SGDOptimizer(lr=0.05, momentum=0.9))
        p, o, s = ex.init(seed=3)
        p1, o1, s1 = _run_steps(ex, p, o, s, [_batch(ex, seed=0)])
        with CheckpointManager(str(tmp_path / "ck"), async_save=True) as ck:
            ck.save(1, p1, o1, s1)
            step, p2, o2, s2 = ck.restore(templates=ex.init(seed=0))
            assert step == 1
            _assert_trees_equal(p1, p2)
            _assert_trees_equal(o1, o2)
        # close() flushed: a fresh manager still sees a durable step.
        with CheckpointManager(str(tmp_path / "ck")) as ck:
            assert ck.latest_step() == 1

    def test_force_replace_is_atomic_and_leaves_no_staging(self, tmp_path):
        """force=True on an existing step: write-new-then-retire — the
        replacement lands, nothing of the staging snapshot remains."""
        import os

        ff = _tiny_model()
        ex = Executor(ff, optimizer=SGDOptimizer(lr=0.05))
        p, o, s = ex.init(seed=1)
        p2 = jax.tree.map(lambda x: x + 1.0, p)
        with CheckpointManager(str(tmp_path / "ck")) as ck:
            ck.save(1, p, o, s)
            assert ck.save(1, p2, o, s, force=True)
            step, pr, _, _ = ck.restore(templates=(p, o, s))
            assert step == 1
            _assert_trees_equal(p2, pr)
            assert ck.all_steps() == [1]
        assert not any(
            ".force-tmp" in n for n in os.listdir(tmp_path / "ck")
        )

    def test_kill_between_force_save_phases_always_restorable(self, tmp_path):
        """Simulated kills at each force-replace phase boundary: a
        fresh manager must always find a restorable checkpoint — the
        old snapshot before the staged one commits, the new after."""
        import os
        import shutil

        d = str(tmp_path / "ck")
        ff = _tiny_model()
        ex = Executor(ff, optimizer=SGDOptimizer(lr=0.05))
        p, o, s = ex.init(seed=1)
        p_new = jax.tree.map(lambda x: x + 1.0, p)

        def restored():
            with CheckpointManager(d) as ck:
                _, pr, _, _ = ck.restore(templates=(p, o, s))
            return pr

        with CheckpointManager(d) as ck:
            ck.save(1, p, o, s)
        # Kill mid-write (phase 1): only orbax's internal staging tmp
        # exists — recovery discards it, the old snapshot survives.
        os.makedirs(os.path.join(
            d, "1.force-tmp.orbax-checkpoint-tmp-0", "params"))
        _assert_trees_equal(p, restored())
        # Kill after the staged snapshot committed but before retire.
        with CheckpointManager(d) as ck:
            ck._write_force_tmp(1, ck._items(p_new, o, s))
        _assert_trees_equal(p_new, restored())
        # Kill mid-retire: staged snapshot + half-deleted old dir.
        with CheckpointManager(d) as ck:
            ck._write_force_tmp(1, ck._items(p_new, o, s))
            shutil.rmtree(os.path.join(d, "1", "params"))
        _assert_trees_equal(p_new, restored())

    def test_restore_falls_back_past_torn_step(self, tmp_path):
        """A half-deleted latest step (crash mid-delete / corruption)
        must not strand the job: latest-restore skips it and restores
        the previous intact step."""
        import os
        import shutil

        ff = _tiny_model()
        ex = Executor(ff, optimizer=SGDOptimizer(lr=0.05))
        p, o, s = ex.init(seed=1)
        p2 = jax.tree.map(lambda x: x + 1.0, p)
        d = str(tmp_path / "ck")
        with CheckpointManager(d) as ck:
            ck.save(1, p, o, s)
            ck.save(2, p2, o, s)
        shutil.rmtree(os.path.join(d, "2", "params"))  # tear the latest
        with CheckpointManager(d) as ck:
            step, pr, _, _ = ck.restore(templates=(p, o, s))
        assert step == 1
        _assert_trees_equal(p, pr)

    def test_all_steps_torn_raises_instead_of_fresh_start(self, tmp_path):
        """Snapshots exist but none is readable: restore must raise
        TornCheckpointError, NOT FileNotFoundError — resilience's
        _fresh_state treats the latter as 'no checkpoint yet' and would
        silently restart from step 0 over a damaged run."""
        import os
        import shutil

        from flexflow_tpu.runtime.checkpoint import TornCheckpointError

        ff = _tiny_model()
        ex = Executor(ff, optimizer=SGDOptimizer(lr=0.05))
        p, o, s = ex.init(seed=1)
        d = str(tmp_path / "ck")
        with CheckpointManager(d) as ck:
            ck.save(1, p, o, s)
        shutil.rmtree(os.path.join(d, "1", "params"))
        with CheckpointManager(d) as ck:
            with pytest.raises(TornCheckpointError):
                ck.restore(templates=(p, o, s))

    def test_template_mismatch_propagates_not_fallback(self, tmp_path):
        """A template whose tree structure doesn't match the snapshot
        (a changed/renamed layer) is a programmer error: restore must
        raise it, not 'fall back' through every intact step and report
        no checkpoint found (which resilience would treat as a fresh
        start and overwrite the run)."""
        ff = _tiny_model()
        ex = Executor(ff, optimizer=SGDOptimizer(lr=0.05))
        p, o, s = ex.init(seed=1)
        with CheckpointManager(str(tmp_path / "ck")) as ck:
            ck.save(1, p, o, s)
            bad = {("fc1_renamed" if k == "fc1" else k): v
                   for k, v in p.items()}
            # Matched on the offending key, not on orbax's wording: the
            # installed orbax (0.11.32) says "tree structures do not
            # match" where older ones said "key mismatch"; what this
            # pins is that orbax's own ValueError surfaces.
            with pytest.raises(ValueError, match="fc1_renamed"):
                ck.restore(templates=(bad, o, s))

    def test_periodic_save_replaces_torn_step(self, tmp_path):
        """A non-force save landing on a torn step dir (a replayed run
        crossing the same boundary) must replace it, not skip it."""
        import os
        import shutil

        ff = _tiny_model()
        ex = Executor(ff, optimizer=SGDOptimizer(lr=0.05))
        p, o, s = ex.init(seed=1)
        d = str(tmp_path / "ck")
        with CheckpointManager(d) as ck:
            ck.save(1, p, o, s)
            shutil.rmtree(os.path.join(d, "1", "params"))
            ck.reload()
            assert ck.save(1, p, o, s)  # replaced, not skipped
            step, pr, _, _ = ck.restore(templates=(p, o, s))
        assert step == 1
        _assert_trees_equal(p, pr)


def test_zero_sharded_opt_state_portable_restore(tmp_path):
    """Satellite: ZeRO-sharded optimizer moments (Adam m/v split over
    the DP mesh axes, --zero-opt) must restore exactly AND be
    strategy-portable — saved under a hybrid n2c4 strategy, restored
    into a pure-DP executor, then trained, matching the uninterrupted
    hybrid run (the DP≡strategy invariant extended through a
    checkpoint boundary; the seed suite only covered dense params)."""
    from flexflow_tpu.optim import AdamOptimizer

    def model():
        ff = FFModel(FFConfig(batch_size=8, zero_sharded_optimizer=True))
        x = ff.create_tensor((8, 12), name="x")
        lbl = ff.create_tensor((8,), dtype=np.int32, name="label")
        t = ff.dense(x, 16, activation="relu", name="fc1")
        t = ff.dense(t, 4, name="fc2")
        ff.softmax(t, lbl, name="softmax")
        return ff

    store_a = StrategyStore(8, {"fc1": ParallelConfig(n=2, c=4),
                                "fc2": ParallelConfig(c=2)})
    hosts = []
    for seed in range(4):
        rng = np.random.default_rng(seed)
        hosts.append({
            "x": rng.standard_normal((8, 12)).astype(np.float32),
            "label": rng.integers(0, 4, size=(8,)).astype(np.int32),
        })

    # Uninterrupted reference: 4 steps under the hybrid strategy.
    ex_ref = Executor(model(), strategy=store_a,
                      optimizer=AdamOptimizer(lr=0.01))
    p, o, s = ex_ref.init(seed=7)
    p_ref, o_ref, _ = _run_steps(
        ex_ref, p, o, s, [ex_ref.shard_batch(h) for h in hosts])

    # 2 steps under hybrid, save, restore into pure-DP ZeRO, 2 more.
    ex_a = Executor(model(), strategy=store_a,
                    optimizer=AdamOptimizer(lr=0.01))
    p, o, s = ex_a.init(seed=7)
    p2, o2, s2 = _run_steps(
        ex_a, p, o, s, [ex_a.shard_batch(h) for h in hosts[:2]])
    with CheckpointManager(str(tmp_path / "ck")) as ck:
        ck.save(2, p2, o2, s2)
        ex_b = Executor(model(), optimizer=AdamOptimizer(lr=0.01))  # DP
        step, pr, orr, sr = ck.restore(templates=ex_b.init(seed=0))
    assert step == 2
    # The ZeRO-sharded moment buffers round-trip exactly (values; the
    # shardings are now ex_b's — that resharding IS the portability).
    _assert_trees_equal(o2, orr)
    p_b, o_b, _ = _run_steps(
        ex_b, pr, orr, sr, [ex_b.shard_batch(h) for h in hosts[2:]])
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_b)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_dropout_rng_state_resumes_exactly(tmp_path):
    """Dropout's PRNG key is op STATE: a restore must continue the
    mask stream exactly where the run left off (4 straight steps ==
    2 steps + save/restore + 2 steps, bit-for-bit)."""
    def model():
        ff = FFModel(FFConfig(batch_size=8, seed=9))
        x = ff.create_tensor((8, 12), name="x")
        lbl = ff.create_tensor((8,), dtype=np.int32, name="label")
        t = ff.dense(x, 16, activation="relu", name="fc1")
        t = ff.dropout(t, 0.5, name="drop")
        t = ff.dense(t, 4, name="fc2")
        ff.softmax(t, lbl, name="softmax")
        return ff

    ex = Executor(model(), optimizer=SGDOptimizer(lr=0.05))
    batches = [_batch(ex, seed=s) for s in range(4)]

    p, o, s = ex.init()
    p4, o4, s4 = _run_steps(ex, p, o, s, batches)

    ex2 = Executor(model(), optimizer=SGDOptimizer(lr=0.05))
    p, o, s = ex2.init()
    p2, o2, s2 = _run_steps(ex2, p, o, s, batches[:2])
    with CheckpointManager(str(tmp_path / "ck")) as ck:
        ck.save(2, p2, o2, s2)
        ex3 = Executor(model(), optimizer=SGDOptimizer(lr=0.05))
        pr, orr, sr = ex3.init()
        _, pr, orr, sr = ck.restore(templates=(pr, orr, sr))
    pr4, _, sr4 = _run_steps(ex3, pr, orr, sr, batches[2:])

    for a, b in zip(jax.tree.leaves(p4), jax.tree.leaves(pr4)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(s4["drop"]["rng"]), np.asarray(sr4["drop"]["rng"])
    )


class TestPipelineCheckpoint:
    """Per-stage {si: params}/{si: opt_state} trees through the manager
    (ISSUE 3): layer-wise executors checkpoint like any pytree."""

    _shared = None

    def _pipe(self, fresh=False):
        from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
        from flexflow_tpu.runtime.pipeline import PipelineExecutor

        if not fresh and type(self)._shared is not None:
            return type(self)._shared  # executors are call-stateless
        ff = _tiny_model()
        store = StrategyStore(8)
        store.set("fc1", ParallelConfig(n=4, device_ids=(0, 1, 2, 3)))
        for n in ("fc2", "softmax"):
            store.set(n, ParallelConfig(n=4, device_ids=(4, 5, 6, 7)))
        pipe = PipelineExecutor(
            ff, store, optimizer=SGDOptimizer(lr=0.05, momentum=0.9),
            microbatches=2, chunk=2,
        )
        if not fresh:
            type(self)._shared = pipe
        return pipe

    def test_restore_then_train_on_matches_uninterrupted(self, tmp_path):
        """Train 4 pipeline steps straight vs 2 + save + restore into a
        FRESH executor + 2: identical per-stage params AND momentum."""
        ex = self._pipe()
        batches = [_batch(ex, seed=s) for s in range(4)]
        p, o, s = ex.init(seed=0)
        p4, o4, s4 = _run_steps(ex, p, o, s, batches)

        ex2 = self._pipe()
        p, o, s = ex2.init(seed=0)
        p2, o2, s2 = _run_steps(ex2, p, o, s, batches[:2])
        with CheckpointManager(str(tmp_path / "ck")) as ck:
            ck.save(2, p2, o2, s2)
            ex3 = self._pipe(fresh=True)
            pr, orr, sr = ex3.init(seed=1)  # different init: restore wins
            step, pr, orr, sr = ck.restore(templates=(pr, orr, sr))
        assert step == 2
        pr4, or4, _ = _run_steps(ex3, pr, orr, sr, batches[2:])
        _assert_trees_equal(p4, pr4)
        _assert_trees_equal(o4, or4)  # momentum buffers round-trip

    def test_trainer_fit_saves_and_resumes_pipeline(self, tmp_path):
        """Trainer.fit(checkpoint=...) on a PipelineExecutor: periodic
        saves + resume, including through the superstep path."""
        ex = self._pipe()
        with CheckpointManager(str(tmp_path / "ck")) as ck:
            Trainer(ex).fit(iterations=4, warmup=1, save_every=2,
                            checkpoint=ck, steps_per_call=2)
            assert ck.latest_step() == 5  # warmup counts as an update
        with CheckpointManager(str(tmp_path / "ck")) as ck:
            stats = Trainer(ex).fit(iterations=2, warmup=1,
                                    checkpoint=ck, steps_per_call=2)
        assert stats["iterations"] == 2
