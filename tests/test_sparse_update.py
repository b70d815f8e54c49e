"""Row-sparse embedding updates (Executor sparse path + Pallas row
kernels).

The sparse path differentiates w.r.t. the gathered rows and scatters
the row cotangent into the (donated) table — numerics must be
IDENTICAL to the dense-gradient path (plain SGD; SURVEY.md §2.2
embedding scatter-grad, reference ``embedding.cu:128-158``).
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


def _build(sparse, batch=8):
    cfg = FFConfig(batch_size=batch, sparse_embedding_updates=sparse)
    ff = FFModel(cfg)
    ids = ff.create_tensor((batch, 4), dtype=jnp.int32, name="ids")
    bag = ff.create_tensor((batch, 3), dtype=jnp.int32, name="bag")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="label")
    e1 = ff.multi_embedding(ids, 4, 16, 8, name="tables")
    e1 = ff.reshape(e1, (batch, 32), name="r1")
    e2 = ff.embedding(bag, 32, 8, aggr="avg", name="bagged")
    t = ff.concat([e1, e2], axis=1, name="cat")
    t = ff.dense(t, 4, name="fc")
    ff.softmax(t, lbl, name="softmax")
    return ff


def _batch(rng, batch=8):
    return {
        # narrow id range => duplicate rows exercise scatter accumulation
        "ids": rng.integers(0, 4, size=(batch, 4)).astype(np.int32),
        "bag": rng.integers(0, 6, size=(batch, 3)).astype(np.int32),
        "label": rng.integers(0, 4, size=(batch,)).astype(np.int32),
    }


def _run(ff, batch, n_devices=1, strategy=None, steps=3, lr=0.3):
    ex = Executor(
        ff, strategy=strategy, optimizer=SGDOptimizer(lr=lr),
        devices=jax.devices()[:n_devices],
    )
    params, opt_state, state = ex.init()
    b = ex.shard_batch(dict(batch))
    for _ in range(steps):
        params, opt_state, state, m = ex.train_step(params, opt_state, state, b)
    return ex, jax.device_get(params), float(jax.device_get(m["train_loss"]))


def test_sparse_matches_dense_exactly(rng):
    batch = _batch(rng)
    ex_d, pd, ld = _run(_build(False), batch)
    ex_s, ps, ls = _run(_build(True), batch)
    assert not ex_d._sparse_ops
    assert {op.name for op in ex_s._sparse_ops} == {"tables", "bagged"}
    assert ld == pytest.approx(ls, rel=1e-6)
    for opn in pd:
        for k in pd[opn]:
            np.testing.assert_allclose(
                pd[opn][k], ps[opn][k], rtol=1e-6, atol=1e-7,
                err_msg=f"{opn}/{k}",
            )


def test_sparse_sharded_matches_dense(rng):
    batch = _batch(rng)
    _, _, ld = _run(_build(False), batch)
    store = StrategyStore(8)
    store.set("tables", ParallelConfig(n=2, c=4))
    _, _, ls = _run(_build(True), batch, n_devices=8, strategy=store)
    assert ld == pytest.approx(ls, rel=2e-5)


def test_sparse_disabled_for_momentum_and_wd(rng):
    ff = _build(True)
    ex = Executor(ff, optimizer=SGDOptimizer(lr=0.1, momentum=0.9),
                  devices=jax.devices()[:1])
    assert not ex._sparse_ops  # momentum needs a dense buffer
    ex = Executor(ff, optimizer=SGDOptimizer(lr=0.1, weight_decay=1e-4),
                  devices=jax.devices()[:1])
    assert not ex._sparse_ops  # decay touches every row every step


def test_hetero_sparse_matches_dense(rng):
    vocabs = [10, 50, 100]

    def build(sparse):
        cfg = FFConfig(batch_size=8, sparse_embedding_updates=sparse)
        ff = FFModel(cfg)
        ids = ff.create_tensor((8, 3), dtype=jnp.int32, name="ids")
        lbl = ff.create_tensor((8,), dtype=jnp.int32, name="label")
        t = ff.hetero_embedding(ids, vocabs, 8, pad_to=4, name="tables")
        t = ff.reshape(t, (8, 24), name="r")
        t = ff.dense(t, 4, name="fc")
        ff.softmax(t, lbl, name="softmax")
        return ff

    batch = {
        "ids": np.stack(
            [rng.integers(0, v, size=8) for v in vocabs], axis=1
        ).astype(np.int32),
        "label": rng.integers(0, 4, size=(8,)).astype(np.int32),
    }
    _, pd, ld = _run(build(False), batch)
    ex_s, ps, ls = _run(build(True), batch)
    assert [op.name for op in ex_s._sparse_ops] == ["tables"]
    assert ld == pytest.approx(ls, rel=1e-6)
    np.testing.assert_allclose(
        pd["tables"]["table"], ps["tables"]["table"], rtol=1e-6, atol=1e-7
    )

    # Row-range-sharded tables now ride the sparse path too: the
    # owning-shard gather/scatter dispatches (ops/embedding.py
    # _sharded_gather/_sharded_scatter_add) keep the per-row protocol
    # intact under c>1, so the sharded run must match the replicated
    # dense oracle.
    store = StrategyStore(8)
    store.set("tables", ParallelConfig(n=2, c=4))
    ex_c, pc, lc = _run(build(True), batch, n_devices=8, strategy=store)
    assert [op.name for op in ex_c._sparse_ops] == ["tables"]
    assert ld == pytest.approx(lc, rel=1e-6)
    np.testing.assert_allclose(
        pd["tables"]["table"], pc["tables"]["table"], rtol=1e-6, atol=1e-7
    )


def test_word_embedding_sparse(rng):
    def build(sparse):
        cfg = FFConfig(batch_size=4, sparse_embedding_updates=sparse)
        ff = FFModel(cfg)
        tok = ff.create_tensor((4, 6), dtype=jnp.int32, name="tokens")
        lbl = ff.create_tensor((4, 6), dtype=jnp.int32, name="label")
        t = ff.word_embedding(tok, 32, 8, name="wte")
        t = ff.dense(t, 32, name="proj")
        ff.softmax(t, lbl, name="softmax")
        return ff

    batch = {
        "tokens": rng.integers(0, 32, size=(4, 6)).astype(np.int32),
        "label": rng.integers(0, 32, size=(4, 6)).astype(np.int32),
    }
    _, pd, ld = _run(build(False), batch)
    ex_s, ps, ls = _run(build(True), batch)
    assert [op.name for op in ex_s._sparse_ops] == ["wte"]
    assert ld == pytest.approx(ls, rel=1e-6)
    np.testing.assert_allclose(
        pd["wte"]["table"], ps["wte"]["table"], rtol=1e-6, atol=1e-7
    )


def test_row_kernels_interpret(rng):
    """gather_rows / scatter_add_rows vs numpy oracle (interpret mode
    on CPU — same code path the chip compiles)."""
    from flexflow_tpu.ops import pallas_kernels as pk

    table = jnp.asarray(rng.standard_normal((40, 128)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 40, size=(17,)), jnp.int32)
    upd = jnp.asarray(rng.standard_normal((17, 128)), jnp.float32)

    got = pk.gather_rows(table, idx, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(table)[np.asarray(idx)], rtol=1e-6
    )

    got = pk.scatter_add_rows(table, idx, upd, interpret=True)
    ref = np.asarray(table).copy()
    np.add.at(ref, np.asarray(idx), np.asarray(upd))  # dups accumulate
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("d", [64, 256, 32])
def test_scatter_rows_repacked_dims(rng, d):
    """Non-128 row dims run through the (P, 128) physical repack
    (Mosaic rejects any other HBM row-slice width on hardware; the
    same reduction executes under interpret so this pins its math):
    d=256 -> column-block split, d=64/32 -> lane packing; duplicate
    ids and packed-row sharing must still accumulate exactly."""
    from flexflow_tpu.ops import pallas_kernels as pk

    table = jnp.asarray(rng.standard_normal((40, d)), jnp.float32)
    # Adjacent ids (0,1) share a physical row in the packed layout;
    # duplicates (7,7) exercise the sequential-RMW guarantee.
    idx = jnp.asarray([0, 1, 7, 7, 39, 2], jnp.int32)
    upd = jnp.asarray(rng.standard_normal((6, d)), jnp.float32)

    got = pk.scatter_add_rows(table, idx, upd, interpret=True)
    ref = np.asarray(table).copy()
    np.add.at(ref, np.asarray(idx), np.asarray(upd))
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-6)
    assert pk.rows_supported(6, d, num_rows=40)


def _full_coverage_model(sparse, clip=0.0, batch=8):
    """Every table row is touched every step (ids = b % vocab), so the
    lazy row updates must agree with the dense optimizer exactly."""
    cfg = FFConfig(batch_size=batch, sparse_embedding_updates=sparse,
                   clip_norm=clip)
    ff = FFModel(cfg)
    ids = ff.create_tensor((batch, 4), dtype=jnp.int32, name="ids")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="label")
    e = ff.multi_embedding(ids, 4, 4, 8, name="tables")
    e = ff.reshape(e, (batch, 32), name="r1")
    t = ff.dense(e, 4, name="fc")
    ff.softmax(t, lbl, name="softmax")
    return ff


def _full_coverage_batch(rng, batch=8):
    return {
        "ids": np.tile(np.arange(4, dtype=np.int32)[:, None], (2, 4)),
        "label": rng.integers(0, 4, size=(batch,)).astype(np.int32),
    }


def _run_opt(ff, batch, optimizer, steps=3):
    ex = Executor(ff, optimizer=optimizer, devices=jax.devices()[:1])
    params, opt_state, state = ex.init()
    b = ex.shard_batch(dict(batch))
    for _ in range(steps):
        params, opt_state, state, m = ex.train_step(params, opt_state, state, b)
    return ex, jax.device_get(params), float(jax.device_get(m["train_loss"]))


def test_sparse_clip_norm_matches_dense(rng):
    """--clip-norm now runs WITH the row-sparse path: the exact global
    norm comes from per-unique-id segment sums of row cotangents
    (VERDICT r2 item 5) and must reproduce the dense clipped update."""
    batch = _batch(rng)
    clip = 0.05  # small enough to bind every step

    def build(sparse):
        ff = _build(sparse)
        ff.config.clip_norm = clip
        return ff

    ex_d, pd, ld = _run(build(False), batch)
    ex_s, ps, ls = _run(build(True), batch)
    assert {op.name for op in ex_s._sparse_ops} == {"tables", "bagged"}
    assert ld == pytest.approx(ls, rel=1e-5)
    for opn in pd:
        for k in pd[opn]:
            np.testing.assert_allclose(
                pd[opn][k], ps[opn][k], rtol=1e-5, atol=1e-7,
                err_msg=f"{opn}/{k}",
            )


def test_lazy_momentum_matches_dense_when_rows_hot(rng):
    """--lazy-sparse-opt keeps tables row-sparse under momentum SGD;
    rows touched every step update exactly like the dense path."""
    batch = _full_coverage_batch(rng)
    opt = lambda lazy: SGDOptimizer(lr=0.2, momentum=0.9, weight_decay=1e-3,
                                    lazy_sparse=lazy)
    _, pd, ld = _run_opt(_full_coverage_model(False), batch, opt(False))
    ex_s, ps, ls = _run_opt(_full_coverage_model(True), batch, opt(True))
    assert [op.name for op in ex_s._sparse_ops] == ["tables"]
    assert ld == pytest.approx(ls, rel=1e-5)
    np.testing.assert_allclose(
        pd["tables"]["tables"], ps["tables"]["tables"], rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        pd["fc"]["kernel"], ps["fc"]["kernel"], rtol=1e-5, atol=1e-6
    )


def test_lazy_adam_matches_dense_when_rows_hot(rng):
    from flexflow_tpu.optim import AdamOptimizer

    batch = _full_coverage_batch(rng)
    opt = lambda lazy: AdamOptimizer(lr=0.05, weight_decay=1e-3,
                                     lazy_sparse=lazy)
    _, pd, ld = _run_opt(_full_coverage_model(False), batch, opt(False))
    ex_s, ps, ls = _run_opt(_full_coverage_model(True), batch, opt(True))
    assert [op.name for op in ex_s._sparse_ops] == ["tables"]
    assert ld == pytest.approx(ls, rel=1e-5)
    np.testing.assert_allclose(
        pd["tables"]["tables"], ps["tables"]["tables"], rtol=1e-4, atol=1e-6
    )


def test_lazy_untouched_rows_frozen(rng):
    """The documented lazy deviation: rows the step never touches keep
    their parameters and moments (no decay) — torch SparseAdam
    semantics."""
    from flexflow_tpu.optim import AdamOptimizer

    cfg = FFConfig(batch_size=8, sparse_embedding_updates=True)
    ff = FFModel(cfg)
    ids = ff.create_tensor((8, 2), dtype=jnp.int32, name="ids")
    lbl = ff.create_tensor((8,), dtype=jnp.int32, name="label")
    e = ff.multi_embedding(ids, 2, 16, 8, name="tables")
    e = ff.reshape(e, (8, 16), name="r1")
    t = ff.dense(e, 4, name="fc")
    ff.softmax(t, lbl, name="softmax")
    ex = Executor(
        ff,
        optimizer=AdamOptimizer(lr=0.1, weight_decay=0.1, lazy_sparse=True),
        devices=jax.devices()[:1],
    )
    assert [op.name for op in ex._sparse_ops] == ["tables"]
    params, opt_state, state = ex.init()
    p0 = jax.device_get(params["tables"]["tables"])
    batch = ex.shard_batch({
        "ids": np.zeros((8, 2), np.int32),  # only row 0 of each table
        "label": rng.integers(0, 4, size=(8,)).astype(np.int32),
    })
    params, opt_state, state, _ = ex.train_step(params, opt_state, state, batch)
    p1 = jax.device_get(params["tables"]["tables"])
    assert not np.allclose(p0[:, 0], p1[:, 0])      # touched rows moved
    np.testing.assert_array_equal(p0[:, 1:], p1[:, 1:])  # cold rows frozen
    m1 = jax.device_get(opt_state["m"]["tables"]["tables"])
    assert np.all(m1[:, 1:] == 0)


# -- the dispatchers on the kernels' side -------------------------------------
#
# On the CPU ``_row_addressing`` answers "xla", so everything above
# runs jnp.take / .at[].add.  Here the dispatchers are told they sit on
# one TPU and the kernels run under the interpreter: the executor's
# sparse step through gather_rows / scatter_add_rows in the addressing
# each table's shape picks.


@pytest.fixture
def row_kernels_on_the_cpu(monkeypatch):
    from flexflow_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_interpret_default", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


#: name -> (tables, rows a table, width, the addressing its shape picks)
_KERNEL_TABLES = {
    "stacked-d16-edge-block": (4, 160, 16, "lane_major"),
    "stacked-d64": (2, 256, 64, "lane_major"),
    "stacked-d128": (2, 24, 128, "row_major"),
    "stacked-d8-short-table": (4, 16, 8, "row_major"),
}


def _kernel_model(sparse, tables, rows, dim, batch=8):
    cfg = FFConfig(batch_size=batch, sparse_embedding_updates=sparse)
    ff = FFModel(cfg)
    ids = ff.create_tensor((batch, tables), dtype=jnp.int32, name="ids")
    bag = ff.create_tensor((batch, 3), dtype=jnp.int32, name="bag")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="label")
    e1 = ff.multi_embedding(ids, tables, rows, dim, name="tables")
    e1 = ff.reshape(e1, (batch, tables * dim), name="r1")
    e2 = ff.embedding(bag, 200, 32, aggr="avg", name="bagged")  # 2-D, lane-major
    t = ff.concat([e1, e2], axis=1, name="cat")
    t = ff.dense(t, 4, name="fc")
    ff.softmax(t, lbl, name="softmax")
    return ff


def _kernel_batch(rng, tables, rows, batch=8):
    ids = rng.integers(0, rows, size=(batch, tables)).astype(np.int32)
    ids[1] = ids[0]          # the same rows again, far apart in flat order
    ids[2] = (ids[0] + 1) % rows  # and their neighbours in the block
    ids[-1] = rows - 1       # the last (edge) block
    return {
        "ids": ids,
        "bag": rng.integers(190, 200, size=(batch, 3)).astype(np.int32),
        "label": rng.integers(0, 4, size=(batch,)).astype(np.int32),
    }


def _events(path, name):
    import json

    with open(path) as f:
        return [ev for ev in map(json.loads, f) if ev.get("ev") == name]


@pytest.mark.parametrize("case", sorted(_KERNEL_TABLES))
def test_sparse_step_through_the_row_kernels_matches_dense(
        rng, case, row_kernels_on_the_cpu, tmp_path):
    """Plain SGD: the per-occurrence scatter-add of -lr * row_grad
    through the kernels against the dense jnp step, and the build-time
    ``embedding_rows`` event naming each op's addressing."""
    from flexflow_tpu.runtime.telemetry import Telemetry

    tables, rows, dim, addressing = _KERNEL_TABLES[case]
    batch = _kernel_batch(rng, tables, rows)
    _, pd, ld = _run(_kernel_model(False, tables, rows, dim), batch)
    with Telemetry(str(tmp_path)) as tel:
        ex_s, ps, ls = _run(_kernel_model(True, tables, rows, dim), batch)
        path = tel.path
    assert {op.name for op in ex_s._sparse_ops} == {"tables", "bagged"}
    assert ld == pytest.approx(ls, rel=1e-6)
    for opn in pd:
        for k in pd[opn]:
            np.testing.assert_allclose(
                pd[opn][k], ps[opn][k], rtol=1e-6, atol=1e-7,
                err_msg=f"{opn}/{k}",
            )
    noted = {ev["op"]: ev for ev in _events(path, "embedding_rows")}
    assert len(_events(path, "embedding_rows")) == 2  # one an op
    assert noted["tables"]["addressing"] == addressing
    assert (noted["tables"]["dim"], noted["tables"]["ids"]) == (dim, 8 * tables)
    assert noted["bagged"]["addressing"] == "lane_major"
    assert (noted["bagged"]["dim"], noted["bagged"]["ids"]) == (32, 24)


@pytest.mark.parametrize("optimizer", ["momentum", "adam"])
def test_lazy_row_step_through_the_row_kernels(rng, optimizer,
                                               row_kernels_on_the_cpu):
    """The stateful path gathers and scatters the table AND its
    optimizer-state buffers, all stacked (T, V, D): through the
    lane-major kernels it must land where the XLA path lands."""
    from flexflow_tpu.optim import AdamOptimizer

    tables, rows, dim = 4, 160, 16
    batch = _kernel_batch(rng, tables, rows)
    batch.pop("bag")

    def build():
        cfg = FFConfig(batch_size=8, sparse_embedding_updates=True)
        ff = FFModel(cfg)
        ids = ff.create_tensor((8, tables), dtype=jnp.int32, name="ids")
        lbl = ff.create_tensor((8,), dtype=jnp.int32, name="label")
        e = ff.multi_embedding(ids, tables, rows, dim, name="tables")
        e = ff.reshape(e, (8, tables * dim), name="r1")
        ff.softmax(ff.dense(e, 4, name="fc"), lbl, name="softmax")
        return ff

    def opt():
        if optimizer == "adam":
            return AdamOptimizer(lr=0.05, lazy_sparse=True)
        return SGDOptimizer(lr=0.2, momentum=0.9, lazy_sparse=True)

    ex_k, pk_, lk = _run_opt(build(), batch, opt())
    assert [op.name for op in ex_k._sparse_ops] == ["tables"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "cpu")
        _, px, lx = _run_opt(build(), batch, opt())
    assert lk == pytest.approx(lx, rel=1e-6)
    np.testing.assert_allclose(
        pk_["tables"]["tables"], px["tables"]["tables"], rtol=1e-6, atol=1e-7
    )


# -- the row-sharded dispatchers on the kernels' side --------------------------
#
# Inside the row-sharded ``shard_map`` a device sees a plain local
# array, so the shard's own shape picks the kernels (ISSUE 30): here on
# the CPU's virtual devices, the kernels under the interpreter.


def _sharded_op(ff, name, n, c):
    """(executor, bound op, its placed params) with ``name`` at (n, c)
    over ``n * c`` virtual devices."""
    store = StrategyStore(n * c)
    store.set(name, ParallelConfig(n=n, c=c))
    ex = Executor(ff, strategy=store, optimizer=SGDOptimizer(lr=0.5),
                  devices=jax.devices()[:n * c])
    (op,) = [o for o in ex.model.layers if o.name == name]
    op.bind_mesh(ex.plan, ex._pc(op))
    return ex, op, ex.init()[0][name]


def _sparse_protocol(op, params, ids, grads, lr=0.5):
    """One ``sparse_rows`` and one ``sparse_apply`` as the executor's
    step makes them (jitted, the table donated)."""
    rows = jax.jit(lambda p, i: op.sparse_rows(p, [i]))(params, ids)
    new = jax.jit(lambda p, i, g: op.sparse_apply(p, [i], g, lr),
                  donate_argnums=0)(params, ids, grads)
    return jax.device_get(rows), jax.device_get(new)


_STACKED = (4, 160, 16)  # tables, rows (a partial last 128-row block), width


def _stacked_model(batch=8):
    tables, rows, dim = _STACKED
    ff = FFModel(FFConfig(batch_size=batch, sparse_embedding_updates=True))
    ids = ff.create_tensor((batch, tables), dtype=jnp.int32, name="ids")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="label")
    e = ff.multi_embedding(ids, tables, rows, dim, name="tables")
    e = ff.reshape(e, (batch, tables * dim), name="r1")
    ff.softmax(ff.dense(e, 4, name="fc"), lbl, name="softmax")
    return ff


def _stacked_ids(duplicates, batch=8):
    """Every table's first and last row (so every shard's), rows of the
    partial last block, and distinct rows otherwise; ``duplicates``
    repeats rows within the batch, next to each other and far apart."""
    tables, rows, _ = _STACKED
    ids = np.stack([(37 * np.arange(batch) + 11 * t) % 120 + 1
                    for t in range(tables)], axis=1).astype(np.int32)
    ids[0], ids[1], ids[2], ids[3] = 0, rows - 1, 128, 141
    if duplicates:
        ids[4], ids[5], ids[7] = ids[1], ids[1], ids[0]
    return ids


@pytest.mark.parametrize("duplicates", [False, True],
                         ids=["unique", "duplicates"])
@pytest.mark.parametrize("n, c", [(1, 2), (1, 4), (2, 4)])
def test_sharded_multi_embedding_through_the_row_kernels(
        rng, n, c, duplicates, row_kernels_on_the_cpu, tmp_path):
    """A shard resolves its own tables' columns of the ids, and only
    those, with the kernels on the ``(T/c, V, D)`` shard as it lies:
    ``sparse_rows`` is the replicated take exactly, ``sparse_apply``
    the replicated scatter-add (exactly where no row repeats)."""
    from flexflow_tpu.runtime.telemetry import Telemetry

    tables, rows, dim = _STACKED
    ids = _stacked_ids(duplicates)
    grads = rng.standard_normal((8, tables, dim)).astype(np.float32)
    with Telemetry(str(tmp_path)) as tel:
        _, op, params = _sharded_op(_stacked_model(), "tables", n, c)
        table = jax.device_get(params["tables"])
        got_rows, new = _sparse_protocol(op, params, ids, grads)
        path = tel.path
    t_range = np.arange(tables)[None, :]
    np.testing.assert_array_equal(got_rows, table[t_range, ids])
    want = table.copy()
    np.add.at(want, (t_range.repeat(8, 0), ids), np.float32(-0.5) * grads)
    if duplicates:
        np.testing.assert_allclose(new["tables"], want, rtol=1e-6, atol=1e-7)
    else:
        np.testing.assert_array_equal(new["tables"], want)
    (gather,) = _events(path, "embedding_gather")
    (combine,) = _events(path, "embedding_combine")
    assert (gather["addressing"], gather["combine"]) == (
        "lane_major", "table_axis")
    assert (combine["addressing"], combine["shards"]) == ("lane_major", c)
    # The ids a device resolves: its batch rows times its own tables.
    (noted,) = _events(path, "embedding_rows")
    assert (noted["addressing"], noted["ids"]) == (
        "lane_major", 8 // n * tables // c)


@pytest.mark.parametrize("owners", ["every-shard", "one-shard"])
def test_shard_rows_embedding_masked_form_through_the_row_kernels(
        rng, owners, row_kernels_on_the_cpu, tmp_path):
    """A vocab-sharded table's owner depends on the id's value: every
    shard runs the kernels over all ids, clipped and masked, with ids
    in every shard's range (its first and last row among them) and with
    ids three of the four shards own none of."""
    from flexflow_tpu.runtime.telemetry import Telemetry

    vocab, dim, c = 512, 16, 4  # a (128, 16) shard: one lane-major block
    ff = FFModel(FFConfig(batch_size=8, sparse_embedding_updates=True,
                          shard_embeddings=True))
    bag = ff.create_tensor((8, 3), dtype=jnp.int32, name="bag")
    lbl = ff.create_tensor((8,), dtype=jnp.int32, name="label")
    e = ff.embedding(bag, vocab, dim, aggr="sum", name="emb")
    ff.softmax(ff.dense(e, 4, name="fc"), lbl, name="softmax")
    if owners == "every-shard":
        ids = rng.integers(0, vocab, size=(8, 3)).astype(np.int32)
        ids[0] = [0, 127, 128]
        ids[1] = [255, 256, 511]
        ids[2] = ids[0]  # the same rows again
    else:
        ids = rng.integers(128, 256, size=(8, 3)).astype(np.int32)
    grads = rng.standard_normal((8, 3, dim)).astype(np.float32)
    with Telemetry(str(tmp_path)) as tel:
        _, op, params = _sharded_op(ff, "emb", 2, c)
        table = jax.device_get(params["table"])
        got_rows, new = _sparse_protocol(op, params, ids, grads)
        path = tel.path
    np.testing.assert_array_equal(got_rows, table[ids])
    want = table.copy()
    np.add.at(want, ids, np.float32(-0.5) * grads)
    np.testing.assert_allclose(new["table"], want, rtol=1e-6, atol=1e-7)
    (gather,) = _events(path, "embedding_gather")
    assert (gather["addressing"], gather["combine"]) == ("lane_major", "psum")


def test_sharded_forward_stays_on_the_differentiable_take(
        rng, row_kernels_on_the_cpu, monkeypatch):
    """``forward`` is what autodiff traces: on a TPU too it reaches the
    shard's rows with ``jnp.take``, never a row kernel (jax has no AD
    rule for a scalar-prefetch ``pallas_call``)."""
    from flexflow_tpu.ops import pallas_kernels as pk

    def refuse(*a, **k):
        raise AssertionError("forward() dispatched to a row kernel")

    monkeypatch.setattr(pk, "gather_rows", refuse)
    monkeypatch.setattr(pk, "scatter_add_rows", refuse)
    tables, rows, dim = _STACKED
    _, op, params = _sharded_op(_stacked_model(), "tables", 1, 4)
    ids = _stacked_ids(duplicates=True)
    weight = rng.standard_normal((8, tables, dim)).astype(np.float32)

    def loss(p):
        (y,), _ = op.forward(p, [ids], {}, True)
        return jnp.sum(y * weight)

    grad = jax.device_get(jax.jit(jax.grad(loss))(params))["tables"]
    want = np.zeros((tables, rows, dim), np.float32)
    np.add.at(want, (np.arange(tables)[None, :].repeat(8, 0), ids), weight)
    np.testing.assert_allclose(grad, want, rtol=1e-6, atol=1e-7)
