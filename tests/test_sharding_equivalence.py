"""Strategy-invariance tests (SURVEY.md §4 plan (2)).

The reference's core promise is that any per-op strategy computes the
same function as single-device execution (it only ever asserts this
implicitly via partition-disjointness checks); here we assert it
numerically: train a small model under different strategies on the
8-device CPU mesh and require identical losses/params.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime.executor import Executor


def small_cnn(batch=8):
    ff = FFModel(FFConfig(batch_size=batch, seed=7))
    x = ff.create_tensor((batch, 8, 8, 4), name="x")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="lbl")
    t = ff.conv2d(x, 8, 3, 3, 1, 1, 1, 1, activation="relu", name="conv1")
    t = ff.pool2d(t, 2, 2, 2, 2, 0, 0, name="pool1")
    t = ff.flat(t, name="flat")
    t = ff.dense(t, 16, activation="relu", name="fc1")
    t = ff.dense(t, 4, activation=None, name="fc2")
    ff.softmax(t, lbl, name="softmax")
    return ff


def make_batch(ff, rng):
    return {
        "x": jnp.array(rng.standard_normal((8, 8, 8, 4)), jnp.float32),
        "lbl": jnp.array(rng.integers(0, 4, size=(8,)), jnp.int32),
    }


def train_losses(strategy_table, n_devices, steps=3):
    rng = np.random.default_rng(42)
    ff = small_cnn()
    store = StrategyStore(n_devices, strategy_table)
    ex = Executor(
        ff,
        strategy=store,
        optimizer=SGDOptimizer(lr=0.05, momentum=0.9),
        devices=jax.devices()[:n_devices],
    )
    params, opt_state, state = ex.init()
    losses = []
    for _ in range(steps):
        batch = ex.shard_batch(make_batch(ff, rng))
        params, opt_state, state, m = ex.train_step(params, opt_state, state, batch)
        losses.append(float(m["train_loss"]))
    return losses, jax.device_get(params)


def assert_same(run_a, run_b, rtol=2e-4):
    losses_a, params_a = run_a
    losses_b, params_b = run_b
    np.testing.assert_allclose(losses_a, losses_b, rtol=rtol, atol=1e-5)
    flat_a = jax.tree.leaves(params_a)
    flat_b = jax.tree.leaves(params_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=1e-5)


def test_dp_matches_single_device():
    single = train_losses({}, 1)
    dp = train_losses({}, 8)  # fallback: full data parallelism
    assert_same(single, dp)


def test_tp_matches_single_device():
    tp = {
        "fc1": ParallelConfig(n=2, c=4),
        "fc2": ParallelConfig(n=2, c=2),
    }
    assert_same(train_losses({}, 1), train_losses(tp, 8))


def test_spatial_matches_single_device():
    sp = {
        "conv1": ParallelConfig(n=2, h=2, w=2),
        "pool1": ParallelConfig(n=2, h=2),
    }
    assert_same(train_losses({}, 1), train_losses(sp, 8))


def test_hybrid_matches_dp():
    hybrid = {
        "conv1": ParallelConfig(n=4, c=2),
        "fc1": ParallelConfig(c=8),
        "fc2": ParallelConfig(n=8),
    }
    assert_same(train_losses({}, 8), train_losses(hybrid, 8))


def test_losses_decrease():
    losses, _ = train_losses({}, 8, steps=10)
    assert losses[-1] < losses[0]


# -- sharded embedding tables (ISSUE 20) --------------------------------------
#
# ``--shard-embeddings`` splits the table's vocab axis over the mesh
# c-axis (ops/embedding.py ``_sharded_gather``: owning shard resolves
# each id locally, psum combines — never a full-table all-gather).
# The DP≡strategy invariant must hold through the sharded gather, the
# sharded scatter-add backward, AND the lazy row-sparse optimizers.

VOCAB = 64


def emb_model(batch=8):
    ff = FFModel(FFConfig(batch_size=batch, seed=7, shard_embeddings=True))
    ids = ff.create_tensor((batch, 4), dtype=jnp.int32, name="ids")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="lbl")
    t = ff.embedding(ids, VOCAB, 8, aggr="sum", name="emb")
    t = ff.dense(t, 16, activation="relu", name="fc1")
    t = ff.dense(t, 4, activation=None, name="fc2")
    ff.softmax(t, lbl, name="softmax")
    return ff


def emb_train(strategy_table, n_devices, optimizer=None, steps=3):
    rng = np.random.default_rng(42)
    ff = emb_model()
    ex = Executor(
        ff,
        strategy=StrategyStore(n_devices, strategy_table),
        optimizer=optimizer or SGDOptimizer(lr=0.05, momentum=0.9),
        devices=jax.devices()[:n_devices],
    )
    params, opt_state, state = ex.init()
    losses = []
    for _ in range(steps):
        batch = ex.shard_batch({
            "ids": jnp.array(
                rng.integers(0, VOCAB, size=(8, 4)), jnp.int32),
            "lbl": jnp.array(rng.integers(0, 4, size=(8,)), jnp.int32),
        })
        params, opt_state, state, m = ex.train_step(
            params, opt_state, state, batch)
        losses.append(float(m["train_loss"]))
    return losses, jax.device_get(params)


@pytest.mark.parametrize("c", [2, 4])
def test_sharded_embedding_matches_dp(c):
    """c ∈ {2, 4}: the row-sharded table trains identically to full
    data parallelism (the acceptance-criterion invariant: sharded
    loss trajectory tracks the replicated DP run)."""
    sharded = {"emb": ParallelConfig(n=8 // c, c=c)}
    assert_same(emb_train({}, 8), emb_train(sharded, 8), rtol=1e-5)


def test_sharded_embedding_hybrid():
    """Hybrid n×c on the table composes with tensor parallelism on the
    dense tail."""
    hybrid = {
        "emb": ParallelConfig(n=2, c=2),
        "fc1": ParallelConfig(n=2, c=4),
        "fc2": ParallelConfig(n=8),
    }
    assert_same(emb_train({}, 8), emb_train(hybrid, 8))


def test_sharded_embedding_tight_vs_unsharded():
    """Same n-degree, only the table layout differs (c=4 sharded vs
    c=1 replicated): every other program is identical, so the
    trajectories agree to duplicate-id rounding (rtol 1e-6 — the
    sparse-suite precedent)."""
    a = emb_train({"emb": ParallelConfig(n=2, c=1)}, 8)
    b = emb_train({"emb": ParallelConfig(n=2, c=4)}, 8)
    np.testing.assert_allclose(a[0], b[0], rtol=1e-6)
    for x, y in zip(jax.tree.leaves(a[1]), jax.tree.leaves(b[1])):
        np.testing.assert_allclose(x, y, rtol=1e-6, atol=1e-7)


def test_lazy_adam_sharded_rows():
    """Lazy-sparse Adam over the c-sharded table: the row-sparse
    update (touched rows only) lands on the owning shards; the table
    trajectory matches the unsharded lazy run (the per-row Adam math
    is identical — only the scatter's shard-local RMW differs).

    Tolerance: 2 ULP at the scale of one Adam step (lr = 0.05, so
    atol 2**-27), not a ULP count on the entries themselves.  On jax
    0.9.0 XLA:CPU rounds the row update once differently inside the
    shard_map'd local program than in the unsharded one: after three
    steps exactly one of the 512 entries differs, by 3.7e-9 = one ULP
    of the 0.05 step, while the losses, the dense params and the other
    511 entries stay bit-identical.  An entry is the small residue of
    steps that nearly cancel (0.0059 here), so the same last-bit
    rounding reads as 8 ULP of the entry; a wrong or doubled row
    update would be off by the step itself."""
    from flexflow_tpu.optim import AdamOptimizer

    lr = 0.05
    mk = lambda c: emb_train(
        {"emb": ParallelConfig(n=2, c=c)}, 8,
        optimizer=AdamOptimizer(lr=lr, lazy_sparse=True),
    )
    a = mk(1)
    b = mk(4)
    np.testing.assert_array_equal(a[0], b[0])
    ta = np.asarray(a[1]["emb"]["table"])
    tb = np.asarray(b[1]["emb"]["table"]).reshape(ta.shape)
    np.testing.assert_allclose(ta, tb, rtol=0, atol=2.0 ** -27)
