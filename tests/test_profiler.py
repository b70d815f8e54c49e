"""Profiling subsystem: per-op timing, trace capture, --profiling flag."""

import os

import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime import Executor, Trainer, profile_ops, report, trace


def _model(batch=8):
    ff = FFModel(FFConfig(batch_size=batch))
    x = ff.create_tensor((batch, 16), name="x")
    lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
    t = ff.dense(x, 32, activation="relu", name="fc1")
    t = ff.dense(t, 4, name="fc2")
    ff.softmax(t, lbl, name="softmax")
    return ff


def _batch(ex, batch=8):
    rng = np.random.default_rng(0)
    return {
        "x": rng.standard_normal((batch, 16)).astype(np.float32),
        "label": rng.integers(0, 4, size=(batch,)).astype(np.int32),
    }


def test_profile_ops_covers_every_op():
    ff = _model()
    store = StrategyStore(8)
    store.set("fc1", ParallelConfig(n=2, c=4))
    ex = Executor(ff, strategy=store)
    params, _, state = ex.init()
    profiles = profile_ops(ex, params, state, _batch(ex), reps=2, warmup=1)
    assert [p.name for p in profiles] == [op.name for op in ff.layers]
    assert all(p.time_us > 0 for p in profiles)
    text = report(profiles)
    assert "fc1" in text and "TOTAL" in text


def test_measured_cost_table_keys():
    from flexflow_tpu.runtime.profiler import measured_cost_table

    ff = _model()
    ex = Executor(ff)
    params, _, state = ex.init()
    table = measured_cost_table(ex, params, state, _batch(ex), reps=1)
    assert set(table) == {op.name for op in ff.layers}


def test_trace_writes_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    logdir = str(tmp_path / "trace")
    with trace(logdir):
        jax.jit(lambda x: x * 2)(jnp.ones((8, 8))).block_until_ready()
    found = []
    for root, _, files in os.walk(logdir):
        found.extend(f for f in files if f.endswith((".pb", ".pb.gz", ".json.gz")))
    assert found, "no trace events written"


@pytest.mark.slow  # ~28s app e2e (targeted suite: test_profiler)
def test_trace_flag_wires_through_fit(tmp_path):
    """--trace DIR captures the timed loop (app surface of the trace()
    context); jax writes at least one .xplane.pb under the dir."""
    from flexflow_tpu.apps import alexnet

    logdir = tmp_path / "xprof"
    assert alexnet.main([
        "-b", "4", "-i", "1", "--image-size", "67",
        "--trace", str(logdir),
    ]) == 0
    assert list(logdir.rglob("*.xplane.pb"))


def test_profiling_flag_prints_breakdown(capsys):
    ff = _model()
    ff.config.profiling = True
    ex = Executor(ff, optimizer=SGDOptimizer(lr=0.01))
    Trainer(ex).fit(iterations=2, warmup=1)
    out = capsys.readouterr().out
    assert "fc1" in out and "TOTAL" in out
    assert "tp = " in out  # the reference throughput printout
