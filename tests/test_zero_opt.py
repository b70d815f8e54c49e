"""ZeRO-1 optimizer-state sharding (--zero-opt): moments shard their
leading dim over the data-parallel mesh axes instead of replicating;
numerics must be bit-compatible with the replicated layout."""

import jax
import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.optim import AdamOptimizer, SGDOptimizer
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime.executor import Executor


def _model(zero: bool, batch=16):
    ff = FFModel(FFConfig(batch_size=batch, seed=4,
                          zero_sharded_optimizer=zero))
    x = ff.create_tensor((batch, 32), name="x")
    lbl = ff.create_tensor((batch,), dtype=np.int32, name="lbl")
    t = ff.dense(x, 64, activation="relu", name="fc1")
    t = ff.dense(t, 64, activation="relu", name="fc2")
    t = ff.dense(t, 4, name="fc3")
    ff.softmax(t, lbl, name="softmax")
    return ff


def _train(zero, optimizer, table=None, steps=3, n_devices=8):
    rng = np.random.default_rng(12)
    ff = _model(zero)
    ex = Executor(
        ff,
        strategy=StrategyStore(n_devices, table or {}),
        optimizer=optimizer(),
        devices=jax.devices()[:n_devices],
    )
    params, opt_state, state = ex.init()
    for _ in range(steps):
        batch = ex.shard_batch({
            "x": rng.standard_normal((16, 32)).astype(np.float32),
            "lbl": rng.integers(0, 4, size=(16,)).astype(np.int32),
        })
        params, opt_state, state, m = ex.train_step(
            params, opt_state, state, batch
        )
    jax.block_until_ready(m)
    return ex, params, opt_state, float(m["train_loss"])


@pytest.mark.parametrize("optimizer", [
    lambda: AdamOptimizer(lr=0.01),
    lambda: SGDOptimizer(lr=0.05, momentum=0.9),
])
def test_zero_opt_matches_replicated(optimizer):
    _, p_rep, _, l_rep = _train(False, optimizer)
    _, p_z, _, l_z = _train(True, optimizer)
    np.testing.assert_allclose(l_rep, l_z, rtol=2e-4, atol=1e-6)
    for a, b in zip(jax.tree.leaves(p_rep), jax.tree.leaves(p_z)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
        )


def test_zero_opt_moments_actually_sharded():
    """Adam m/v leaves carry a leading-dim shard over the DP axes
    (8-way DP mesh: fc1 kernel (32, 64) -> dim0 split 8 ways)."""
    ex, _, opt_state, _ = _train(True, lambda: AdamOptimizer(lr=0.01))
    m_fc1 = opt_state["m"]["fc1"]["kernel"]
    spec = m_fc1.sharding.spec
    assert spec and spec[0], f"expected dim0 sharded, got {spec}"
    n_axes = ex.plan.assign(ex._pc(ex.model.layers[0])).get("n", ())
    entry = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
    assert set(entry) <= set(ex.plan.axis_names)
    assert set(n_axes) & set(entry), (n_axes, spec)
    # Replicated layout keeps dim0 unsharded.
    _, _, opt_rep, _ = _train(False, lambda: AdamOptimizer(lr=0.01))
    rep_spec = opt_rep["m"]["fc1"]["kernel"].sharding.spec
    assert not rep_spec or not rep_spec[0]


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_zero_opt_composes_with_tp():
    """Under hybrid n x c: a c-sharded weight's moments keep the c
    shard AND gain the DP split on the free leading dim; numerics
    still match the replicated layout."""
    table = {
        "fc1": ParallelConfig(n=2, c=4),
        "fc2": ParallelConfig(n=2, c=2),
    }
    _, p_rep, _, _ = _train(False, lambda: AdamOptimizer(lr=0.01), table)
    ex, p_z, opt_z, _ = _train(True, lambda: AdamOptimizer(lr=0.01), table)
    for a, b in zip(jax.tree.leaves(p_rep), jax.tree.leaves(p_z)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
        )
    spec = opt_z["m"]["fc1"]["kernel"].sharding.spec
    assert spec and spec[0], spec  # dim0 gained the DP axis


@pytest.mark.slow  # ~19s app e2e (targeted suite: test_zero_opt)
def test_zero_opt_cli_flag():
    assert FFConfig.parse_args(["--zero-opt"]).zero_sharded_optimizer
    from flexflow_tpu.apps import alexnet

    assert alexnet.main([
        "-b", "8", "-i", "1", "-ll:tpu", "8", "--image-size", "67",
        "--zero-opt", "--optimizer", "adam",
    ]) == 0


def test_zero_opt_rejected_for_pipeline_strategies():
    """Layer-wise placement would half-apply the flag (stage init
    shards, the pipeline update path would not re-pin): reject loudly."""
    from flexflow_tpu.runtime.pipeline import PlacementError, make_executor

    ff = _model(zero=True, batch=8)
    st = StrategyStore(8)
    st.set("fc1", ParallelConfig(n=4, device_ids=(0, 1, 2, 3)))
    st.set("fc2", ParallelConfig(n=4, device_ids=(4, 5, 6, 7)))
    with pytest.raises(PlacementError, match="zero-opt"):
        make_executor(ff, st, devices=jax.devices()[:8])
