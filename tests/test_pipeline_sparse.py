"""Per-stage sparse carry through the pipeline runtime (ISSUE 20).

``PipelineExecutor`` used to refuse ``--lazy-sparse-opt``; now each
stage Executor's ``_sparse_ops`` gate runs against the STAGE model
(ids entering an embedding stage are stage graph-inputs), the stage
backward emits ``(flat_ids, row_grads)`` per sparse op, the host loop
concatenates them in microbatch order, and the row update applies on
the stage's own submesh.  Invariants pinned here:

- **Gate** — an embedding stage under a sparse-capable optimizer takes
  the sparse path; dense config or momentum-SGD stays dense.
- **Sparse == dense oracle** — with globally-unique ids per step the
  stateless row update is BIT-IDENTICAL to the dense pipeline (each
  row touched once: ``p + (-lr*g) == p - lr*g``); with duplicate ids
  the trajectories agree to rtol 1e-6 (duplicates sum in a different
  association order — same tolerance as the full-mesh suite).
- **Chunk / schedule / compiled invariance** — the sparse carry is
  bit-identical across ``chunk``, across 1f1b/gpipe, and on the
  compiled whole-step path (which shares ``_stage_update_sparse``
  in-trace with the host loop).
- **Clip-norm** — per-stage unique-row gsum**2 folds into the ONE
  batched clip fence; chunk-invariant bitwise.
- **Lazy momentum / Adam** — the stateful row path (touched rows only)
  threads through stage boundaries; cold rows stay frozen.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.optim import AdamOptimizer, SGDOptimizer
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime.pipeline import PipelineExecutor

VOCAB = 96
BAG = 4
BATCH = 16


def _model(sparse=True):
    cfg = FFConfig(batch_size=BATCH, sparse_embedding_updates=sparse)
    ff = FFModel(cfg)
    ids = ff.create_tensor((BATCH, BAG), dtype=jnp.int32, name="ids")
    lbl = ff.create_tensor((BATCH,), dtype=jnp.int32, name="label")
    t = ff.embedding(ids, VOCAB, 8, aggr="sum", name="emb")
    t = ff.dense(t, 16, activation="relu", name="fc1")
    t = ff.dense(t, 4, activation=None, name="fc2")
    ff.softmax(t, lbl, name="softmax")
    return ff


def _store(nd=8):
    enc = tuple(range(nd // 2))
    dec = tuple(range(nd // 2, nd))
    store = StrategyStore(nd)
    store.set("emb", ParallelConfig(n=len(enc), device_ids=enc))
    for n in ("fc1", "fc2", "softmax"):
        store.set(n, ParallelConfig(n=len(dec), device_ids=dec))
    return store


def _optimizer(kind):
    if kind == "sgd":
        return SGDOptimizer(lr=0.1)
    if kind == "lazy_mom":
        return SGDOptimizer(lr=0.1, momentum=0.9, lazy_sparse=True)
    if kind == "lazy_adam":
        return AdamOptimizer(lr=0.05, lazy_sparse=True)
    raise ValueError(kind)


def _batches(n, seed=0, unique_ids=False):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        if unique_ids:
            # Every id distinct across the step: each table row is
            # touched exactly once, so sparse scatter == dense update
            # bitwise (no duplicate-sum association to reorder).
            ids = rng.permutation(VOCAB)[: BATCH * BAG].reshape(BATCH, BAG)
        else:
            ids = rng.integers(0, VOCAB, size=(BATCH, BAG))
        out.append({
            "ids": ids.astype(np.int32),
            "label": rng.integers(0, 4, size=(BATCH,)).astype(np.int32),
        })
    return out


@functools.lru_cache(maxsize=None)
def _pipe(sparse=True, opt="sgd", microbatches=4, chunk=1,
          schedule="1f1b", clip=0.0, compiled=False):
    cfg = FFConfig(batch_size=BATCH, clip_norm=clip,
                   sparse_embedding_updates=sparse)
    return PipelineExecutor(
        _model(sparse=sparse), _store(), config=cfg,
        optimizer=_optimizer(opt), microbatches=microbatches,
        schedule=schedule, chunk=chunk, compiled=compiled,
    )


def _run(pipe, batches):
    params, opt_state, state = pipe.init(seed=0)
    losses = []
    for b in batches:
        params, opt_state, state, m = pipe.train_step(
            params, opt_state, state, pipe.shard_batch(b)
        )
        losses.append(np.asarray(jax.device_get(m["train_loss"])))
    return np.array(losses), jax.device_get(params)


def _assert_bit_identical(run_a, run_b, msg=""):
    losses_a, params_a = run_a
    losses_b, params_b = run_b
    np.testing.assert_array_equal(losses_a, losses_b, err_msg=msg)
    for a, b in zip(jax.tree.leaves(params_a), jax.tree.leaves(params_b)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=msg
        )


def _assert_close(run_a, run_b, msg=""):
    losses_a, params_a = run_a
    losses_b, params_b = run_b
    np.testing.assert_allclose(losses_a, losses_b, rtol=1e-6, err_msg=msg)
    for a, b in zip(jax.tree.leaves(params_a), jax.tree.leaves(params_b)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7, err_msg=msg
        )


# -- the gate -----------------------------------------------------------------


def test_stage_sparse_gate():
    """Embedding stage takes the sparse path; the dense stage and the
    dense-config / dense-optimizer pipelines do not."""
    pipe = _pipe(sparse=True, opt="sgd")
    assert [op.name for op in pipe._stage_sparse[0]] == ["emb"]
    assert pipe._stage_sparse[1] == []

    assert all(not ops for ops in _pipe(sparse=False)._stage_sparse)
    # Plain momentum-SGD (not lazy) cannot take the row path.
    cfg = FFConfig(batch_size=BATCH, sparse_embedding_updates=True)
    dense_opt = PipelineExecutor(
        _model(sparse=True), _store(), config=cfg,
        optimizer=SGDOptimizer(lr=0.1, momentum=0.9), microbatches=4,
    )
    assert all(not ops for ops in dense_opt._stage_sparse)


# -- sparse vs the dense pipeline oracle --------------------------------------


def test_sparse_matches_dense_unique_ids():
    """Globally-unique ids: every row is touched once, so the sparse
    scatter equals the dense update row-for-row up to jit-program
    fusion noise (different programs reassociate fc matmul reductions;
    ulp-level per step, compounding over the 3-step trajectory) —
    rtol 1e-6, the full-mesh suite's precedent."""
    batches = _batches(3, unique_ids=True)
    sparse = _run(_pipe(sparse=True), batches)
    dense = _run(_pipe(sparse=False), batches)
    _assert_close(sparse, dense, "unique-id sparse vs dense")


def test_sparse_matches_dense_duplicate_ids():
    """Duplicate ids inside a step: sparse sums duplicate rows before
    the update (different association order) — rtol 1e-6, the same
    tolerance the full-mesh sparse suite pins."""
    batches = _batches(3, seed=1)
    sparse = _run(_pipe(sparse=True), batches)
    dense = _run(_pipe(sparse=False), batches)
    _assert_close(sparse, dense, "duplicate-id sparse vs dense")


# -- chunk / schedule / compiled invariance -----------------------------------


@pytest.mark.parametrize("chunk", [2, 4])
def test_chunked_sparse_bit_identical(chunk):
    """The scan's stacked (L, n, ...) carry flattens to concatenation
    in microbatch order — bit-identical to the per-microbatch loop."""
    batches = _batches(2, seed=2)
    ref = _run(_pipe(chunk=1), batches)
    got = _run(_pipe(chunk=chunk), batches)
    _assert_bit_identical(ref, got, f"sparse chunk={chunk}")


def test_sparse_schedule_invariant():
    """B events fire in microbatch order under BOTH schedules, so the
    concatenated carry (and the row update) is schedule-invariant."""
    batches = _batches(2, seed=4)
    _assert_bit_identical(
        _run(_pipe(schedule="1f1b"), batches),
        _run(_pipe(schedule="gpipe"), batches),
        "sparse 1f1b vs gpipe",
    )


def test_compiled_sparse_bit_identical():
    """The compiled whole-step path applies the SAME
    ``_stage_update_sparse`` in-trace — bit-identical to host-driven."""
    batches = _batches(2, seed=5)
    ref = _run(_pipe(chunk=1), batches)
    got = _run(_pipe(chunk=4, compiled=True), batches)
    _assert_bit_identical(ref, got, "sparse compiled vs host")


# -- clip-norm ----------------------------------------------------------------


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_clip_norm_sparse_chunk_invariant():
    """Unique-row gsum**2 folds into the batched clip fence; the global
    norm (and the scaled row update) is chunk-invariant bitwise and
    tracks the dense pipeline to the duplicate-id tolerance."""
    batches = _batches(2, seed=3)
    ref = _run(_pipe(chunk=1, clip=0.5), batches)
    got = _run(_pipe(chunk=4, clip=0.5), batches)
    _assert_bit_identical(ref, got, "sparse clip chunked")
    _assert_close(
        ref, _run(_pipe(sparse=False, clip=0.5), batches),
        "sparse clip vs dense clip",
    )
    # The clip actually engaged.
    noclip = _run(_pipe(chunk=1), batches)
    assert not np.array_equal(
        jax.tree.leaves(ref[1])[0], jax.tree.leaves(noclip[1])[0]
    )


def test_compiled_clip_norm_sparse():
    """Device-side hierarchical clip on the compiled path folds the
    sparse term identically to the host fence."""
    batches = _batches(2, seed=3)
    ref = _run(_pipe(chunk=1, clip=0.5), batches)
    got = _run(_pipe(chunk=4, clip=0.5, compiled=True), batches)
    _assert_bit_identical(ref, got, "sparse clip compiled")


# -- stateful (lazy) optimizers ----------------------------------------------


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
@pytest.mark.parametrize("opt", ["lazy_mom", "lazy_adam"])
def test_lazy_sparse_chunk_and_compiled_invariant(opt):
    """The stateful row path (``_sparse_stateful_apply`` on touched
    rows only) is chunk- and compiled-invariant through stage
    boundaries."""
    batches = _batches(2, seed=6)
    ref = _run(_pipe(opt=opt, chunk=1), batches)
    _assert_bit_identical(
        ref, _run(_pipe(opt=opt, chunk=4), batches), f"{opt} chunked"
    )
    _assert_bit_identical(
        ref, _run(_pipe(opt=opt, chunk=4, compiled=True), batches),
        f"{opt} compiled",
    )


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_lazy_cold_rows_frozen():
    """Lazy semantics survive the pipeline: rows no microbatch touched
    keep their initial value (dense momentum would still decay them
    once velocity is nonzero)."""
    rng = np.random.default_rng(7)
    # Only ids < 8 ever appear — rows 8.. are cold.
    batches = [{
        "ids": rng.integers(0, 8, size=(BATCH, BAG)).astype(np.int32),
        "label": rng.integers(0, 4, size=(BATCH,)).astype(np.int32),
    } for _ in range(2)]
    pipe = _pipe(opt="lazy_mom")
    params0, _, _ = pipe.init(seed=0)
    init_table = np.asarray(
        jax.device_get(params0[0]["emb"]["table"])
    ).reshape(VOCAB, -1)
    _, params = _run(pipe, batches)
    table = np.asarray(params[0]["emb"]["table"]).reshape(VOCAB, -1)
    np.testing.assert_array_equal(table[8:], init_table[8:])
    assert not np.array_equal(table[:8], init_table[:8])
