"""Gradient accumulation + prefetching loader."""

import itertools

import jax
import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.data.loader import ArrayDataLoader, PrefetchLoader
from flexflow_tpu.graph import FFModel
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime.executor import Executor


def _model(batch):
    ff = FFModel(FFConfig(batch_size=batch))
    x = ff.create_tensor((batch, 16), name="x")
    lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
    t = ff.dense(x, 32, activation="relu", name="fc1")
    t = ff.dense(t, 4, name="fc2")
    ff.softmax(t, lbl, name="softmax")
    return ff


def test_accum_matches_full_batch(rng):
    """2 accumulated microbatches of 8 == one batch of 16 (losses are
    batch means, so mean-of-grads is exact)."""
    full = {
        "x": rng.standard_normal((16, 16)).astype(np.float32),
        "label": rng.integers(0, 4, size=(16,)).astype(np.int32),
    }
    opt = SGDOptimizer(lr=0.1, momentum=0.9)

    ex_full = Executor(_model(16), optimizer=opt, devices=jax.devices()[:1])
    params, opt_state, state = ex_full.init(seed=0)
    p_ref, *_ = ex_full.train_step(
        jax.tree.map(np.asarray, params), jax.tree.map(np.asarray, opt_state),
        state, full,
    )

    ex_acc = Executor(_model(8), optimizer=opt, devices=jax.devices()[:1])
    stacked = ex_acc.stack_microbatches(full, 2)
    step = ex_acc.accum_train_step(2)
    p_acc, *_ = step(
        jax.tree.map(np.asarray, params), jax.tree.map(np.asarray, opt_state),
        state, stacked,
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        p_ref, p_acc,
    )


def test_accum_metrics_counts_sum(rng):
    ex = Executor(_model(8), optimizer=SGDOptimizer(lr=0.01),
                  devices=jax.devices()[:1])
    params, opt_state, state = ex.init(seed=0)
    batch = {
        "x": rng.standard_normal((32, 16)).astype(np.float32),
        "label": rng.integers(0, 4, size=(32,)).astype(np.int32),
    }
    step = ex.accum_train_step(4)
    _, _, _, m = step(params, opt_state, state, ex.stack_microbatches(batch, 4))
    assert int(m["train_all"]) == 32  # summed over 4 microbatches
    assert np.isfinite(float(m["train_loss"]))


def test_accum_under_sharding(rng):
    ex = Executor(_model(8),
                  strategy=StrategyStore(8, {"fc1": ParallelConfig(n=2, c=4)}),
                  optimizer=SGDOptimizer(lr=0.1))
    params, opt_state, state = ex.init(seed=0)
    batch = {
        "x": rng.standard_normal((16, 16)).astype(np.float32),
        "label": rng.integers(0, 4, size=(16,)).astype(np.int32),
    }
    step = ex.accum_train_step(2)
    params, opt_state, state, m = step(
        params, opt_state, state, ex.stack_microbatches(batch, 2)
    )
    assert np.isfinite(float(m["train_loss"]))


def test_prefetch_preserves_order_and_content(rng):
    arrays = {"x": rng.standard_normal((64, 4)).astype(np.float32)}
    loader = ArrayDataLoader(arrays, batch_size=8)
    direct = [loader.next_batch()["x"].copy() for _ in range(8)]
    loader.reset()
    pf = PrefetchLoader(itertools.islice(iter(loader), 8), place_fn=lambda b: b)
    fetched = [next(pf)["x"] for _ in range(8)]
    for a, b in zip(direct, fetched):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(StopIteration):
        next(pf)


def test_prefetch_propagates_worker_error():
    def bad_source():
        yield {"x": np.zeros(3)}
        raise RuntimeError("loader exploded")

    pf = PrefetchLoader(bad_source(), place_fn=lambda b: b)
    next(pf)
    with pytest.raises(RuntimeError, match="loader exploded"):
        next(pf)


def test_prefetch_trains(rng):
    ex = Executor(_model(8), optimizer=SGDOptimizer(lr=0.1),
                  devices=jax.devices()[:1])
    params, opt_state, state = ex.init(seed=0)
    arrays = {
        "x": rng.standard_normal((64, 16)).astype(np.float32),
        "label": rng.integers(0, 4, size=(64,)).astype(np.int32),
    }
    loader = ArrayDataLoader(arrays, batch_size=8)
    pf = PrefetchLoader(itertools.islice(iter(loader), 10), ex.shard_batch)
    n = 0
    for batch in pf:
        params, opt_state, state, m = ex.train_step(params, opt_state, state, batch)
        n += 1
    assert n == 10
    assert np.isfinite(float(m["train_loss"]))


def test_trainer_evaluate(rng):
    from flexflow_tpu.runtime.trainer import Trainer

    ex = Executor(_model(8), optimizer=SGDOptimizer(lr=0.1),
                  devices=jax.devices()[:1])
    tr = Trainer(ex)
    params, opt_state, state = ex.init(seed=0)
    arrays = {
        "x": rng.standard_normal((32, 16)).astype(np.float32),
        "label": rng.integers(0, 4, size=(32,)).astype(np.int32),
    }
    loader = ArrayDataLoader(arrays, batch_size=8)
    out = tr.evaluate(params, state, itertools.islice(iter(loader), 4))
    assert out["batches"] == 4
    assert 0.0 <= out["accuracy"] <= 1.0
    assert np.isfinite(out["loss"])


def test_prefetch_terminal_states_sticky(rng):
    pf = PrefetchLoader(iter([{"x": np.zeros(2)}]), place_fn=lambda b: b)
    next(pf)
    with pytest.raises(StopIteration):
        next(pf)
    with pytest.raises(StopIteration):
        next(pf)  # must not block
    pf2 = PrefetchLoader(iter([{"x": np.zeros(2)}]), place_fn=lambda b: b)
    pf2.close()
    with pytest.raises(StopIteration):
        next(pf2)


def test_accum_rejects_sum_reduction(rng):
    ff = FFModel(FFConfig(batch_size=4))
    x = ff.create_tensor((4, 8), name="x")
    y = ff.create_tensor((4, 1), name="label")
    t = ff.dense(x, 1, name="fc")
    ff.mse_loss(t, y, reduction="sum", name="mse")
    ex = Executor(ff, optimizer=SGDOptimizer(lr=0.1), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="mean-reduction"):
        ex.accum_train_step(2)


def _fit_fixture(rng):
    ex = Executor(_model(8), optimizer=SGDOptimizer(lr=0.1),
                  devices=jax.devices()[:1])
    arrays = {
        "x": rng.standard_normal((64, 16)).astype(np.float32),
        "label": rng.integers(0, 4, size=(64,)).astype(np.int32),
    }
    return ex, arrays


def test_fit_owns_prefetch_and_closes(rng):
    """Trainer.fit wraps plain host batches in a PrefetchLoader by
    default (VERDICT r4 item 4) and stops the worker on return."""
    import threading
    import time

    from flexflow_tpu.runtime.trainer import Trainer

    def prefetch_workers():
        return [t for t in threading.enumerate()
                if t.name == "ff-prefetch" and t.is_alive()]

    ex, arrays = _fit_fixture(rng)
    loader = ArrayDataLoader(arrays, 8, shuffle=False)
    # Workers other tests of this xdist worker left to the collector
    # are not this fit's to close.
    others = set(prefetch_workers())
    stats = Trainer(ex).fit(iterations=4, batches=iter(loader), warmup=1)
    assert stats["samples_per_s"] > 0
    # The owned worker must be closed (give the daemon a beat to exit).
    deadline = time.time() + 5.0
    while set(prefetch_workers()) - others and time.time() < deadline:
        time.sleep(0.05)
    assert not set(prefetch_workers()) - others


def test_fit_prefetch_zero_matches_sync(rng):
    """prefetch=0 restores the synchronous path with identical numerics
    (same source order, same seed => same final loss)."""
    from flexflow_tpu.runtime.trainer import Trainer

    ex, arrays = _fit_fixture(rng)

    def run(depth):
        loader = ArrayDataLoader(arrays, 8, shuffle=False)
        return Trainer(ex).fit(iterations=4, batches=iter(loader),
                               warmup=1, prefetch=depth)["loss"]

    assert run(0) == pytest.approx(run(2), rel=1e-5)


def test_fit_prefetch_consumes_exactly(rng):
    """The owned prefetcher must pull exactly warmup+iterations batches
    from a caller-supplied iterator — reuse after fit() sees the rest."""
    from flexflow_tpu.runtime.trainer import Trainer

    ex, arrays = _fit_fixture(rng)
    loader = ArrayDataLoader(arrays, 8, shuffle=False)
    src = itertools.islice(iter(loader), 8)  # one epoch, 8 batches
    Trainer(ex).fit(iterations=4, batches=src, warmup=1)  # consumes 5
    leftovers = sum(1 for _ in src)
    assert leftovers == 3, f"prefetch over-consumed: {leftovers} left of 3"


def test_device_resident_loader_matches_host_path(rng):
    """The ZC-pattern loader (whole dataset staged on device, rows
    gathered with jnp.take per step, reference dlrm.cc:226-330) must
    produce the same batches as the host ArrayDataLoader — and train
    identically through Trainer.fit."""
    from flexflow_tpu.data.loader import DeviceResidentLoader
    from flexflow_tpu.runtime.trainer import Trainer

    ex, arrays = _fit_fixture(rng)
    host = ArrayDataLoader(arrays, 8, shuffle=False)
    dev = DeviceResidentLoader(arrays, 8, ex, shuffle=False)
    for _ in range(3):
        hb = ex.shard_batch(host.next_batch())
        db = dev.next_batch()
        for k in hb:
            np.testing.assert_array_equal(np.asarray(hb[k]),
                                          np.asarray(db[k]))
    # Training parity: same source order, same seed => same loss.
    loss_host = Trainer(ex).fit(
        iterations=4, batches=iter(ArrayDataLoader(arrays, 8)), warmup=1
    )["loss"]
    loss_dev = Trainer(ex).fit(
        iterations=4,
        batches=iter(DeviceResidentLoader(arrays, 8, ex)),
        warmup=1,
    )["loss"]
    assert loss_host == pytest.approx(loss_dev, rel=1e-5)


def test_device_resident_loader_under_sharding(rng):
    """Replicated staging + on-device gather + shard_batch must land
    batches that train under a DP/TP strategy on the 8-dev mesh."""
    from flexflow_tpu.data.loader import DeviceResidentLoader

    ex = Executor(
        _model(8),
        strategy=StrategyStore(8, {"fc1": ParallelConfig(n=2, c=4)}),
        optimizer=SGDOptimizer(lr=0.1),
    )
    arrays = {
        "x": rng.standard_normal((64, 16)).astype(np.float32),
        "label": rng.integers(0, 4, size=(64,)).astype(np.int32),
    }
    loader = DeviceResidentLoader(arrays, 8, ex, shuffle=True, seed=5)
    params, opt_state, state = ex.init(seed=0)
    for batch in itertools.islice(iter(loader), 4):
        params, opt_state, state, m = ex.train_step(
            params, opt_state, state, batch)
    assert np.isfinite(float(m["train_loss"]))
