"""Superstep execution (ISSUE 1): K train steps fused into one
compiled ``lax.scan`` dispatch (``Executor.build_superstep``).

The invariants pinned here extend the strategy-equivalence family
(``test_sharding_equivalence.py``): superstep(k) must be BIT-IDENTICAL
to k sequential ``train_step`` calls — per-step losses and final params
— for DP and non-DP strategies; the donated (params, opt_state, state)
carry must survive consecutive supersteps composed with gradient
accumulation and ZeRO optimizer sharding; and pipeline (layer-wise)
strategies must refuse loudly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.optim import AdamOptimizer, SGDOptimizer
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.trainer import Trainer


def _model(batch=16, zero=False, dropout=0.0):
    ff = FFModel(FFConfig(batch_size=batch, seed=4,
                          zero_sharded_optimizer=zero))
    x = ff.create_tensor((batch, 16), name="x")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="lbl")
    t = ff.dense(x, 32, activation="relu", name="fc1")
    if dropout > 0.0:
        t = ff.dropout(t, rate=dropout, name="drop")
    t = ff.dense(t, 4, name="fc2")
    ff.softmax(t, lbl, name="softmax")
    return ff


def _host_batches(n, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {
            "x": rng.standard_normal((batch, 16)).astype(np.float32),
            "lbl": rng.integers(0, 4, size=(batch,)).astype(np.int32),
        }
        for _ in range(n)
    ]


def _executor(table=None, zero=False, optimizer=None, dropout=0.0):
    ff = _model(zero=zero, dropout=dropout)
    return Executor(
        ff,
        strategy=StrategyStore(8, table or {}),
        optimizer=optimizer or SGDOptimizer(lr=0.05, momentum=0.9),
        devices=jax.devices()[:8],
    )


def _run_sequential(ex, batches):
    params, opt_state, state = ex.init()
    losses = []
    for b in batches:
        params, opt_state, state, m = ex.train_step(
            params, opt_state, state, ex.shard_batch(b)
        )
        losses.append(jax.device_get(m["train_loss"]))
    return np.array(losses), jax.device_get(params)


def _run_superstep(ex, batches, k):
    params, opt_state, state = ex.init()
    fn = ex.build_superstep(k)
    losses = []
    for i in range(0, len(batches), k):
        sb = ex.stack_steps(batches[i:i + k])
        params, opt_state, state, ms = fn(params, opt_state, state, sb)
        losses.extend(np.asarray(jax.device_get(ms["train_loss"])))
    return np.array(losses), jax.device_get(params)


def _assert_bit_identical(run_a, run_b):
    losses_a, params_a = run_a
    losses_b, params_b = run_b
    np.testing.assert_array_equal(losses_a, losses_b)
    for a, b in zip(jax.tree.leaves(params_a), jax.tree.leaves(params_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_superstep_bit_identical_dp():
    batches = _host_batches(6)
    seq = _run_sequential(_executor(), batches)
    sup = _run_superstep(_executor(), batches, k=3)
    _assert_bit_identical(seq, sup)


def test_superstep_bit_identical_tp():
    """Non-DP strategy: hybrid n x c tensor parallelism."""
    table = {
        "fc1": ParallelConfig(n=2, c=4),
        "fc2": ParallelConfig(n=2, c=2),
    }
    batches = _host_batches(6)
    seq = _run_sequential(_executor(table), batches)
    sup = _run_superstep(_executor(table), batches, k=3)
    _assert_bit_identical(seq, sup)


def test_superstep_dropout_rng_chain():
    """The op-state carry threads the dropout RNG through the scan:
    stochastic layers must advance exactly as in sequential steps."""
    batches = _host_batches(4)
    seq = _run_sequential(_executor(dropout=0.5), batches)
    sup = _run_superstep(_executor(dropout=0.5), batches, k=2)
    _assert_bit_identical(seq, sup)


def test_superstep_accum_zero_consecutive_calls():
    """Donation safety: superstep x accum x ZeRO runs two consecutive
    supersteps on the 8-dev mesh without use-after-donate, and matches
    sequential accum_train_step calls bit-for-bit."""
    batches = _host_batches(4, seed=7)

    ex = _executor(zero=True, optimizer=AdamOptimizer(lr=0.01))
    params, opt_state, state = ex.init()
    accum_fn = ex.accum_train_step(2)
    seq_losses = []
    for b in batches:
        stacked = ex.stack_microbatches(ex.shard_batch(b), 2)
        params, opt_state, state, m = accum_fn(params, opt_state, state, stacked)
        seq_losses.append(jax.device_get(m["train_loss"]))
    seq_params = jax.device_get(params)

    ex2 = _executor(zero=True, optimizer=AdamOptimizer(lr=0.01))
    p, o, s = ex2.init()
    fn = ex2.build_superstep(2, accum_steps=2)
    sup_losses = []
    for i in (0, 2):  # two consecutive supersteps: donated carry reused
        sb = ex2.stack_steps(batches[i:i + 2], accum_steps=2)
        p, o, s, ms = fn(p, o, s, sb)
        sup_losses.extend(np.asarray(jax.device_get(ms["train_loss"])))
    np.testing.assert_array_equal(np.array(seq_losses), np.array(sup_losses))
    # Params: the Adam update fuses differently inside the scan body
    # than in the standalone jitted step (rsqrt/mul ordering), so the
    # weakest link is 1-ULP f32 drift — the loss trajectory above is
    # still exactly equal, which is the invariant that matters.
    for a, b in zip(jax.tree.leaves(seq_params), jax.tree.leaves(jax.device_get(p))):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-6, atol=1e-8
        )
    # ZeRO invariant: moments stayed sharded on their leading dim.
    spec = o["m"]["fc1"]["kernel"].sharding.spec
    assert spec and spec[0], f"expected ZeRO-sharded moments, got {spec}"


def test_superstep_metrics_stacked_per_step():
    ex = _executor()
    params, opt_state, state = ex.init()
    fn = ex.build_superstep(4)
    sb = ex.stack_steps(_host_batches(4))
    _, _, _, ms = fn(params, opt_state, state, sb)
    assert all(v.shape[:1] == (4,) for v in jax.tree.leaves(ms))


def test_trainer_fit_superstep_remainder_and_stats():
    """iterations not divisible by k: the tail runs as one shorter
    superstep; stats account every step exactly once."""
    ex = _executor()
    stats = Trainer(ex).fit(iterations=5, warmup=2, steps_per_call=2)
    assert stats["iterations"] == 5
    assert stats["steps_per_call"] == 2
    assert stats["supersteps"] == 3  # 2 + 2 + 1
    assert stats["samples_per_s"] > 0


def test_trainer_fit_superstep_user_batches_prefetch():
    ex = _executor()
    stats = Trainer(ex).fit(
        iterations=4, warmup=2, steps_per_call=2,
        batches=iter(_host_batches(8)), prefetch=2,
    )
    assert stats["iterations"] == 4 and stats["supersteps"] == 2


def test_trainer_fit_superstep_exhausted_batches_error():
    """A finite iterable sized for the k=1 contract (warmup +
    iterations) fails LOUDLY with the required count, not with a
    PEP 479 crash mid-loop (warmup rounds up to whole supersteps)."""
    ex = _executor()
    with pytest.raises(ValueError, match="batches exhausted"):
        # needs ceil(1/4)*4 + 4 = 8 batches; 5 provided
        Trainer(ex).fit(iterations=4, warmup=1, steps_per_call=4,
                        batches=iter(_host_batches(5)), prefetch=0)


def test_trainer_clamps_steps_per_call(caplog):
    """The fused-step bound: k above MAX_STEPS_PER_CALL clamps with a
    loud warning."""
    import logging

    from flexflow_tpu.runtime.trainer import MAX_STEPS_PER_CALL

    ex = _executor()
    with caplog.at_level(logging.WARNING, logger="ff.trainer"):
        stats = Trainer(ex).fit(
            iterations=MAX_STEPS_PER_CALL, warmup=0,
            steps_per_call=MAX_STEPS_PER_CALL + 5,
        )
    assert stats["steps_per_call"] == MAX_STEPS_PER_CALL
    assert any("clamping" in r.message for r in caplog.records)


def test_superstep_pipeline_strategies_amortize():
    """Layer-wise (device-subset) strategies cannot FUSE k steps into
    one scan (``superstep_mode() == "amortized"``, ``build_superstep``
    unavailable), but ``Trainer.fit(steps_per_call=k)`` now runs them
    through the fence-amortized pipeline superstep path instead of
    refusing: k per-stage-dispatched steps share ONE ``device_get``."""
    from flexflow_tpu.runtime.pipeline import PipelineExecutor, make_executor

    ff = _model(batch=8)
    st = StrategyStore(8)
    st.set("fc1", ParallelConfig(n=4, device_ids=(0, 1, 2, 3)))
    st.set("fc2", ParallelConfig(n=4, device_ids=(4, 5, 6, 7)))
    assert not st.superstep_capable()
    assert st.superstep_mode() == "amortized"
    ex = make_executor(ff, st, devices=jax.devices()[:8])
    assert isinstance(ex, PipelineExecutor)
    stats = Trainer(ex).fit(iterations=4, warmup=1, steps_per_call=2)
    assert stats["iterations"] == 4
    assert stats["steps_per_call"] == 2 and stats["supersteps"] == 2
    # The FUSED superstep stays Executor-only: ResilientTrainer's k>1
    # path drives build_superstep and must refuse loudly.
    from flexflow_tpu.runtime.checkpoint import CheckpointManager
    from flexflow_tpu.runtime.resilience import ResilientTrainer
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        with CheckpointManager(d) as ck:
            rt = ResilientTrainer(lambda: ex, ck)
            with pytest.raises(ValueError, match="steps_per_call"):
                rt.fit(iterations=2,
                       batch_fn=lambda s: _host_batches(1, batch=8)[0],
                       steps_per_call=2)


def test_superstep_capable_full_mesh():
    st = StrategyStore(8)
    st.set("fc1", ParallelConfig(n=2, c=4))
    assert st.superstep_capable()
    # device_ids spanning the FULL mesh stay capable (placement-
    # equivalent to mesh coordinates, make_executor's warning path).
    st.set("fc2", ParallelConfig(n=8, device_ids=tuple(range(8))))
    assert st.superstep_capable()


def test_steps_per_call_cli():
    assert FFConfig.parse_args(["--steps-per-call", "4"]).steps_per_call == 4
    assert FFConfig.parse_args([]).steps_per_call == 1
    with pytest.raises(SystemExit):
        FFConfig.parse_args(["--steps-per-call", "0"])


@pytest.mark.slow  # ~42s app e2e; tier1_smoke runs it unfiltered
def test_steps_per_call_app_end_to_end():
    """The shared app harness drives the superstep path (the
    test_zero_opt CLI-flag pattern)."""
    from flexflow_tpu.apps import alexnet

    assert alexnet.main([
        "-b", "8", "-i", "4", "-ll:tpu", "8", "--image-size", "67",
        "--steps-per-call", "2",
    ]) == 0
