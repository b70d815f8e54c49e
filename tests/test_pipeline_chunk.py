"""Chunked-scan pipeline dispatch + pipeline supersteps (ISSUE 3).

The invariants pinned here extend the superstep family
(``tests/test_superstep.py``) to the layer-wise runtime:

- **Chunk invariance** — ``chunk=c`` folds each stage's per-microbatch
  fwd/bwd programs into ONE jitted ``lax.scan`` over ``c`` stacked
  microbatches; loss AND param trajectories must be BIT-IDENTICAL
  across ``c`` (the scan carries the running gradient/metric sums, so
  accumulation order is microbatch order regardless of chunking).
- **Dispatch accounting** — ``last_schedule`` records one event per
  host program: ``2*S*ceil(m/c)`` per step, dependency-valid at chunk
  granularity.
- **Pipeline supersteps** — ``Trainer.fit(steps_per_call=k)`` on a
  PipelineExecutor dispatches k steps back-to-back under ONE
  ``jax.device_get`` fence; trajectories bit-identical to k=1.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime.pipeline import PipelineExecutor
from flexflow_tpu.runtime.trainer import Trainer


def _model(batch=16, dropout=0.0):
    ff = FFModel(FFConfig(batch_size=batch))
    x = ff.create_tensor((batch, 12), name="x")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="label")
    t = ff.dense(x, 16, activation="relu", name="enc0")
    t = ff.dense(t, 16, activation="relu", name="enc1")
    if dropout > 0.0:
        t = ff.dropout(t, rate=dropout, name="drop")
    t = ff.dense(t, 16, activation="relu", name="dec0")
    t = ff.dense(t, 4, activation=None, name="dec1")
    ff.softmax(t, lbl, name="softmax")
    return ff


def _store(nd=8, with_dropout=False):
    enc = tuple(range(nd // 2))
    dec = tuple(range(nd // 2, nd))
    store = StrategyStore(nd)
    for n in ("enc0", "enc1"):
        store.set(n, ParallelConfig(n=len(enc), device_ids=enc))
    names = ("drop",) if with_dropout else ()
    for n in names + ("dec0", "dec1", "softmax"):
        store.set(n, ParallelConfig(n=len(dec), device_ids=dec))
    return store


def _batches(n, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {
            "x": rng.standard_normal((batch, 12)).astype(np.float32),
            "label": rng.integers(0, 4, size=(batch,)).astype(np.int32),
        }
        for _ in range(n)
    ]


def _pipe_fresh(microbatches=4, chunk=1, schedule="1f1b", clip=0.0,
                dropout=0.0):
    cfg = FFConfig(batch_size=16, clip_norm=clip)
    return PipelineExecutor(
        _model(dropout=dropout), _store(with_dropout=dropout > 0.0),
        config=cfg, optimizer=SGDOptimizer(lr=0.1, momentum=0.9),
        microbatches=microbatches, schedule=schedule, chunk=chunk,
    )


@functools.lru_cache(maxsize=None)
def _pipe(microbatches=4, chunk=1, schedule="1f1b", clip=0.0, dropout=0.0):
    """Executors are stateless between train_step calls (params are
    explicit), so tests sharing a config share its compiled stage
    programs — the suite runs on one core and compiles dominate."""
    return _pipe_fresh(microbatches, chunk, schedule, clip, dropout)


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


def _assert_one_ulp(run_a, run_b, msg=""):
    """Losses equal; params within one f32 ULP at their O(1) scale
    (atol 2**-23 — a relative bound would blow up on the entries that
    sit near zero)."""
    losses_a, params_a = run_a
    losses_b, params_b = run_b
    np.testing.assert_array_equal(losses_a, losses_b, err_msg=msg)
    for a, b in zip(jax.tree.leaves(params_a), jax.tree.leaves(params_b)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=2.0 ** -23,
            err_msg=msg,
        )


# -- chunk invariance ---------------------------------------------------------


@pytest.mark.parametrize(
    "chunk",
    # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
    [pytest.param(2, marks=pytest.mark.slow), 4],
)
def test_chunked_bit_identical_to_event_loop(chunk):
    """c in {2, m}: trajectories bit-identical to the c=1 per-microbatch
    event loop (the acceptance-criterion invariant)."""
    batches = _batches(3)
    ref = _run(_pipe(chunk=1), batches)
    got = _run(_pipe(chunk=chunk), batches)
    _assert_bit_identical(ref, got, f"chunk={chunk}")


def test_chunked_nondivisible_tail():
    """m=4, c=3: chunks of 3+1 microbatches — the short tail chunk is
    its own compiled scan length and numerics stay bit-identical."""
    batches = _batches(2)
    ref = _run(_pipe(chunk=1), batches)
    got = _run(_pipe(chunk=3), batches)
    _assert_bit_identical(ref, got, "chunk=3 (non-divisible)")


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_chunked_schedule_invariant():
    """Chunked numerics are also schedule-invariant (1f1b vs gpipe at
    chunk granularity)."""
    batches = _batches(2)
    _assert_bit_identical(
        _run(_pipe(chunk=2, schedule="1f1b"), batches),
        _run(_pipe(chunk=2, schedule="gpipe"), batches),
    )


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_chunked_clip_norm_bit_identical():
    """The batched clip-norm fence (ONE device_get of all S squared
    norms) preserves global-norm clipping numerics across chunking."""
    batches = _batches(2, seed=3)
    ref = _run(_pipe(chunk=1, clip=0.5), batches)
    got = _run(_pipe(chunk=4, clip=0.5), batches)
    _assert_bit_identical(ref, got, "clip_norm chunked")
    # And the clip actually engaged (scale < 1 at lr-sized grads).
    noclip = _run(_pipe(chunk=4), batches)
    assert not np.array_equal(
        jax.tree.leaves(ref[1])[0], jax.tree.leaves(noclip[1])[0]
    )


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_chunked_dropout_rng_chain():
    """The stacked-prestate remat threads the dropout RNG chain through
    the scan exactly as the per-microbatch loop does."""
    batches = _batches(2)
    ref = _run(_pipe(chunk=1, dropout=0.5), batches)
    got = _run(_pipe(chunk=2, dropout=0.5), batches)
    _assert_bit_identical(ref, got, "dropout chunked")


def test_chunked_skip_connection(rng):
    """A stage-0 output consumed by TWO later stages: stacked cotangent
    contributions sum on the producer's mesh per chunk.

    Tolerance one ULP on the updated params (losses stay equal), not
    bit-identity: on jax 0.9.0 XLA:CPU emits different code for this
    model's concat+dense backward inside the chunk scan's ``while``
    body than for the same jaxpr as a straight-line program, and every
    gradient leaf lands 1 ULP apart (max |diff| 6e-8 measured).  It is codegen, not accumulation
    order: with the scan fully unrolled (``unroll=True``, same jaxpr,
    no loop) the chunked run is bit-identical to the event loop again,
    and the chunked and compiled paths — both scans — stay bit-identical
    to each other (``test_compiled_skip_connection``)."""
    batch = 8
    ff = FFModel(FFConfig(batch_size=batch))
    x = ff.create_tensor((batch, 12), name="x")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="label")
    t0 = ff.dense(x, 8, activation="relu", name="s0")
    t1 = ff.dense(t0, 8, activation="relu", name="s1")
    t2 = ff.concat([t0, t1], axis=1, name="s2cat")
    t3 = ff.dense(t2, 4, activation=None, name="s2fc")
    ff.softmax(t3, lbl, name="softmax")
    store = StrategyStore(6)
    store.set("s0", ParallelConfig(n=2, device_ids=(0, 1)))
    store.set("s1", ParallelConfig(n=2, device_ids=(2, 3)))
    for name in ("s2cat", "s2fc", "softmax"):
        store.set(name, ParallelConfig(n=2, device_ids=(4, 5)))
    batch_data = {
        "x": rng.standard_normal((batch, 12)).astype(np.float32),
        "label": rng.integers(0, 4, size=(batch,)).astype(np.int32),
    }

    def run(chunk):
        pipe = PipelineExecutor(
            ff, store, optimizer=SGDOptimizer(lr=0.1),
            microbatches=2, chunk=chunk,
        )
        p, o, s = pipe.init(seed=0)
        p2, _, _, m = pipe.train_step(p, o, s, pipe.shard_batch(batch_data))
        return np.array(jax.device_get(m["train_loss"])), jax.device_get(p2)

    _assert_one_ulp(run(1), run(2), "skip connection chunked")


# -- dispatch accounting ------------------------------------------------------


@pytest.mark.parametrize("chunk,n_units", [(1, 4), (2, 2), (3, 2), (4, 1)])
def test_chunk_cuts_programs_per_step(chunk, n_units):
    """last_schedule records one event per host program: 2*S*ceil(m/c),
    dependency-valid at chunk granularity."""
    pipe = _pipe(microbatches=4, chunk=chunk)
    params, opt_state, state = pipe.init(seed=0)
    pipe.train_step(params, opt_state, state,
                    pipe.shard_batch(_batches(1)[0]))
    S = len(pipe.stages)
    ev = pipe.last_schedule
    assert len(ev) == 2 * S * n_units, (chunk, ev)
    assert ev == pipe.build_schedule(S, n_units)
    pos = {e: i for i, e in enumerate(ev)}
    for kind, si, ci in ev:
        if kind == "F" and si > 0:
            assert pos[("F", si - 1, ci)] < pos[("F", si, ci)]
        if kind == "B":
            assert pos[("F", si, ci)] < pos[("B", si, ci)]
            if si < S - 1:
                assert pos[("B", si + 1, ci)] < pos[("B", si, ci)]


def test_chunk_clamped_to_microbatches(caplog):
    import logging

    with caplog.at_level(logging.WARNING, logger="ff.pipeline"):
        pipe = _pipe_fresh(microbatches=2, chunk=8)
    assert pipe.chunk == 2
    assert any("clamping" in r.message for r in caplog.records)
    with pytest.raises(ValueError, match="chunk"):
        _pipe_fresh(chunk=0)


# -- pipeline supersteps ------------------------------------------------------


def test_pipeline_superstep_bit_identical():
    """k pipeline steps under ONE fence: loss/param trajectories
    bit-identical to steps_per_call=1, for c=1 and c=m."""
    n_steps, k = 6, 3
    batches = _batches(n_steps + 1)  # +1 warmup

    def fit(steps_per_call, chunk):
        pipe = _pipe(chunk=chunk)
        tr = Trainer(pipe)
        stats = tr.fit(
            iterations=n_steps, warmup=1, steps_per_call=steps_per_call,
            batches=iter(batches), prefetch=0,
        )
        return stats, jax.device_get(tr.final[0])

    s1, p1 = fit(1, 1)
    sk, pk = fit(k, 1)
    skc, pkc = fit(k, 4)
    assert sk["steps_per_call"] == k and sk["supersteps"] == 2
    for got in (pk, pkc):
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipeline_superstep_remainder_and_stats():
    """iterations not divisible by k: the tail superstep is shorter;
    stats account every step exactly once (no warmup rounding on the
    pipeline path — there is no k-sized compiled program)."""
    pipe = _pipe(chunk=4)
    stats = Trainer(pipe).fit(iterations=5, warmup=2, steps_per_call=2)
    assert stats["iterations"] == 5
    assert stats["steps_per_call"] == 2
    assert stats["supersteps"] == 3  # 2 + 2 + 1
    assert stats["samples_per_s"] > 0


def test_pipeline_superstep_clamps(caplog):
    import logging

    from flexflow_tpu.runtime.trainer import MAX_STEPS_PER_CALL

    pipe = _pipe(chunk=4)
    with caplog.at_level(logging.WARNING, logger="ff.trainer"):
        stats = Trainer(pipe).fit(
            iterations=2, warmup=0, steps_per_call=MAX_STEPS_PER_CALL + 5,
        )
    assert stats["steps_per_call"] == MAX_STEPS_PER_CALL
    assert any("clamping" in r.message for r in caplog.records)


def test_pipeline_superstep_clip_norm_warns_fence_floor(caplog):
    """clip_norm > 0 keeps a per-step fence (the global norm couples
    stages host-side): documented honestly with a loud warning, never
    silently serialized."""
    import logging

    pipe = _pipe(chunk=4, clip=1.0)
    with caplog.at_level(logging.WARNING, logger="ff.trainer"):
        Trainer(pipe).fit(iterations=2, warmup=1, steps_per_call=2)
    assert any("one-fence-per-step" in r.message for r in caplog.records)


def test_pipeline_superstep_accum_refused():
    pipe = _pipe(chunk=2)
    with pytest.raises(ValueError, match="accum"):
        Trainer(pipe).fit(iterations=2, steps_per_call=2, accum_steps=2)


# -- CLI / app plumbing -------------------------------------------------------


def test_pipeline_chunk_cli():
    assert FFConfig.parse_args(["--pipeline-chunk", "4"]).pipeline_chunk == 4
    assert FFConfig.parse_args([]).pipeline_chunk == 1
    with pytest.raises(SystemExit):
        FFConfig.parse_args(["--pipeline-chunk", "0"])


@pytest.mark.slow  # ~8s app e2e; tier1_smoke runs it unfiltered
def test_pipeline_chunk_app_end_to_end():
    """--pipeline --pipeline-chunk --steps-per-call through the shared
    app harness (the test_apps nmt --pipeline pattern)."""
    from flexflow_tpu.apps import nmt

    assert nmt.main([
        "-b", "16", "-i", "2", "--hidden", "8", "--vocab", "32",
        "--src-len", "4", "--tgt-len", "4", "--pipeline",
        "-ll:tpu", "8", "--microbatches", "2", "--pipeline-chunk", "2",
        "--steps-per-call", "2",
    ]) == 0


# -- compiled whole-step path (ISSUE 5) ---------------------------------------
#
# PipelineExecutor(compiled=True): the whole multi-stage step is ONE
# jitted program on the shared stage mesh.  The HOST-DRIVEN path above is
# the numerics oracle: loss AND param trajectories must be BIT-IDENTICAL
# for the same schedule, across stage counts, non-divisible m, dropout,
# nested n/c inside stages, skip connections, and clip-norm (which runs
# device-side here — no fence floor).


@functools.lru_cache(maxsize=None)
def _pipe_c(microbatches=4, clip=0.0, dropout=0.0, compiled=True,
            accum_steps=1):
    cfg = FFConfig(batch_size=16, clip_norm=clip)
    return PipelineExecutor(
        _model(dropout=dropout), _store(with_dropout=dropout > 0.0),
        config=cfg, optimizer=SGDOptimizer(lr=0.1, momentum=0.9),
        microbatches=microbatches, compiled=compiled,
        accum_steps=accum_steps,
    )


@pytest.mark.parametrize(
    "dropout,clip",
    [
        (0.0, 0.0),
        # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
        pytest.param(0.5, 0.0, marks=pytest.mark.slow),
        (0.0, 0.5),
    ],
    ids=["plain", "dropout", "clip_norm"],
)
def test_compiled_bit_identical_to_host(dropout, clip):
    """The headline gate: one compiled program per step, trajectories
    bit-identical to the host-driven event loop — incl. the dropout RNG
    chain and the device-side hierarchical clip-norm (vs the host
    path's fenced combine)."""
    batches = _batches(3, seed=3 if clip else 0)
    ref = _run(_pipe(chunk=1, clip=clip, dropout=dropout), batches)
    got = _run(_pipe_c(clip=clip, dropout=dropout), batches)
    _assert_bit_identical(ref, got, f"compiled dropout={dropout} clip={clip}")


def _deep_model(batch=16):
    ff = FFModel(FFConfig(batch_size=batch))
    x = ff.create_tensor((batch, 12), name="x")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="label")
    t = x
    for i in range(4):
        t = ff.dense(t, 16, activation="relu", name=f"fc{i}")
    t = ff.dense(t, 4, activation=None, name="head")
    ff.softmax(t, lbl, name="softmax")
    return ff


def _s4_store():
    st = StrategyStore(8)
    groups = [(0, 1), (2, 3), (4, 5), (6, 7)]
    assign = [["fc0"], ["fc1"], ["fc2"], ["fc3", "head", "softmax"]]
    for g, ns in zip(groups, assign):
        for n in ns:
            st.set(n, ParallelConfig(n=2, device_ids=g))
    return st


def _nc_store():
    st = StrategyStore(8)
    for n in ("fc0", "fc1"):
        st.set(n, ParallelConfig(n=2, c=2, device_ids=(0, 1, 2, 3)))
    for n in ("fc2", "fc3", "head"):
        st.set(n, ParallelConfig(n=2, c=2, device_ids=(4, 5, 6, 7)))
    st.set("softmax", ParallelConfig(n=4, device_ids=(4, 5, 6, 7)))
    return st


@pytest.mark.parametrize(
    "store_fn,mb,batch",
    [(_s4_store, 4, 16), (_s4_store, 3, 24), (_nc_store, 4, 16)],
    ids=["S4_n2", "S4_odd_m", "S2_nested_n2c2"],
)
@pytest.mark.slow  # ~14s matrix; tier1_smoke runs it unfiltered
def test_compiled_parity_corners(store_fn, mb, batch):
    """S=4 stage chains, m=3 (non-divisible 1f1b fill), and nested
    n/c sharding inside stages (the Linear contraction pin,
    ops/linear.py) — all bit-identical to the host path."""
    ff = _deep_model(batch)
    batches = _batches(2, batch=batch)

    def go(compiled):
        pipe = PipelineExecutor(
            ff, store_fn(), config=FFConfig(batch_size=batch),
            optimizer=SGDOptimizer(lr=0.1, momentum=0.9),
            microbatches=mb, compiled=compiled,
        )
        return _run(pipe, batches)

    _assert_bit_identical(go(False), go(True),
                          f"{store_fn.__name__} m={mb}")


def test_compiled_skip_connection(rng):
    """A stage-0 output consumed by TWO later stages: in-trace cotangent
    summation order matches _collect_douts'.  The host side runs the
    chunked scan (c = m), the form the compiled step mirrors program
    for program; the per-microbatch event loop sits 1 ULP away on this
    model under jax 0.9.0's XLA:CPU (``test_chunked_skip_connection``
    says why)."""
    batch = 8
    ff = FFModel(FFConfig(batch_size=batch))
    x = ff.create_tensor((batch, 12), name="x")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="label")
    t0 = ff.dense(x, 8, activation="relu", name="s0")
    t1 = ff.dense(t0, 8, activation="relu", name="s1")
    t2 = ff.concat([t0, t1], axis=1, name="s2cat")
    t3 = ff.dense(t2, 4, activation=None, name="s2fc")
    ff.softmax(t3, lbl, name="softmax")
    store = StrategyStore(6)
    store.set("s0", ParallelConfig(n=2, device_ids=(0, 1)))
    store.set("s1", ParallelConfig(n=2, device_ids=(2, 3)))
    for name in ("s2cat", "s2fc", "softmax"):
        store.set(name, ParallelConfig(n=2, device_ids=(4, 5)))
    batch_data = {
        "x": rng.standard_normal((batch, 12)).astype(np.float32),
        "label": rng.integers(0, 4, size=(batch,)).astype(np.int32),
    }

    def run(compiled):
        pipe = PipelineExecutor(
            ff, store, optimizer=SGDOptimizer(lr=0.1),
            microbatches=2, chunk=2, compiled=compiled,
        )
        p, o, s = pipe.init(seed=0)
        p2, _, _, m = pipe.train_step(p, o, s, pipe.shard_batch(batch_data))
        return np.array(jax.device_get(m["train_loss"])), jax.device_get(p2)

    _assert_bit_identical(run(False), run(True), "skip connection compiled")


def test_compiled_eval_parity():
    """Compiled eval (one program, in-trace stage-order combine) matches
    the host path's fenced host-side sum bit-for-bit."""
    b = _batches(1)[0]
    host, comp = _pipe(chunk=4), _pipe_c()
    p, o, s = host.init(seed=0)
    pc, oc, sc = comp.init(seed=0)
    loss_h, mets_h = host.eval_step(p, s, host.shard_batch(b))
    loss_c, mets_c = comp.eval_step(pc, sc, comp.shard_batch(b))
    assert loss_h == loss_c
    assert set(mets_h) == set(mets_c)
    for k in mets_h:
        np.testing.assert_array_equal(np.asarray(mets_h[k]),
                                      np.asarray(mets_c[k]))


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_compiled_accum_lowering():
    """--accum-steps on a layer-wise strategy lowers onto the microbatch
    loop: accumulating a groups of m microbatches IS the pipeline over
    a*m microbatches, on both runtimes."""
    batches = _batches(2)
    ref = _run(_pipe(microbatches=4, chunk=1), batches)
    for compiled in (False, True):
        got = _run(_pipe_c(microbatches=2, accum_steps=2,
                           compiled=compiled), batches)
        _assert_bit_identical(ref, got, f"accum lowered compiled={compiled}")


def test_compiled_zero_opt_refused():
    """--zero-opt stays refused on layer-wise strategies, naming the
    per-submesh moment-sharding blocker."""
    from flexflow_tpu.runtime.pipeline import PlacementError

    cfg = FFConfig(batch_size=16, zero_sharded_optimizer=True)
    with pytest.raises(PlacementError, match="PER-SUBMESH"):
        PipelineExecutor(_model(), _store(), config=cfg, microbatches=4)


def test_trainer_accum_requires_construction_lowering():
    """Trainer.fit(accum_steps=a) on a pipeline must match the
    executor's construction-time lowering — mismatches raise instead of
    silently double-stacking."""
    from flexflow_tpu.runtime.trainer import Trainer as Tr

    pipe = _pipe_c(microbatches=2, accum_steps=2)
    with pytest.raises(ValueError, match="lowered at construction"):
        Tr(pipe).fit(iterations=1, warmup=0, accum_steps=4)
    stats = Tr(pipe).fit(iterations=2, warmup=1, accum_steps=2)
    assert stats["iterations"] == 2


# -- fused pipeline supersteps ------------------------------------------------


def test_compiled_superstep_mode_promoted():
    """StrategyStore.superstep_mode: layer-wise stays "amortized" on the
    host path and promotes to "fused" on the compiled path; the
    executors expose the same split via superstep_fused."""
    store = _store()
    assert store.superstep_mode() == "amortized"
    assert store.superstep_mode(compiled=True) == "fused"
    assert not store.superstep_capable()
    assert store.superstep_capable(compiled=True)
    assert not _pipe(chunk=4).superstep_fused
    assert _pipe_c().superstep_fused


def test_compiled_superstep_bit_identical_and_counters(tmp_path):
    """--steps-per-call k on the compiled path: ONE dispatch + ONE
    fence per k steps (telemetry fence/programs counters audit it) and
    trajectories bit-identical to the k=1 host-driven run.  Warmup is
    sized to whole supersteps so both runs apply the same updates."""
    import json

    from flexflow_tpu.runtime.telemetry import Telemetry

    k, iters, warmup = 3, 6, 3
    batches = _batches(warmup + iters)

    def fit(pipe, steps_per_call):
        tr = Trainer(pipe)
        with Telemetry(str(tmp_path / f"k{steps_per_call}")) as tel:
            stats = tr.fit(
                iterations=iters, warmup=warmup,
                steps_per_call=steps_per_call, batches=iter(batches),
                prefetch=0,
            )
        with open(tel.path) as f:
            events = [json.loads(line) for line in f]
        return stats, jax.device_get(tr.final[0]), events

    s1, p1, _ = fit(_pipe(chunk=1), 1)
    sk, pk, events = fit(_pipe_c(), k)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pk)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Fused-path accounting: programs/step == 1/k, one superstep fence
    # per k steps, and the compiled_step event names the fusion.
    assert sk["telemetry"]["programs_per_step"] == round(1 / k, 4)
    ss = [e for e in events if e["ev"] == "superstep"]
    assert len(ss) == 2 and all(e["k"] == k and e["mode"] == "fused"
                                for e in ss)
    fences = [e for e in events if e["ev"] == "fence"
              and e["label"] == "superstep"]
    assert len(fences) == 2
    compiled_evs = [e for e in events if e["ev"] == "compiled_step"]
    assert any(e["k"] == k and e["S"] == 2 and e["m"] == 4
               for e in compiled_evs)
    # No clip fence, no per-step fence: the step is fence-free IR.
    assert not [e for e in events if e["ev"] == "fence"
                and e["label"] == "clip_norm"]


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_compiled_superstep_clip_norm_fence_free(tmp_path):
    """clip_norm > 0 on the compiled path keeps the fused superstep:
    NO per-step fence (the host path's loudly-warned floor is gone) and
    numerics bit-identical to the host-driven clipped run."""
    import json

    from flexflow_tpu.runtime.telemetry import Telemetry

    k, iters = 2, 4
    batches = _batches(k + iters, seed=3)

    def fit(pipe, steps_per_call):
        tr = Trainer(pipe)
        with Telemetry(str(tmp_path / f"clip{steps_per_call}")) as tel:
            tr.fit(iterations=iters, warmup=k,
                   steps_per_call=steps_per_call, batches=iter(batches),
                   prefetch=0)
        with open(tel.path) as f:
            events = [json.loads(line) for line in f]
        return jax.device_get(tr.final[0]), events

    p1, ev1 = fit(_pipe(chunk=1, clip=0.5), 1)
    pk, evk = fit(_pipe_c(clip=0.5), k)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pk)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [e for e in ev1 if e["ev"] == "fence"
            and e["label"] == "clip_norm"]  # host floor, still there
    assert not [e for e in evk if e["ev"] == "fence"
                and e["label"] == "clip_norm"]  # compiled: gone


def test_compiled_train_step_program_accounting():
    """One host program covers the whole compiled step: last_schedule
    records the single compiled event (vs 2*S*ceil(m/c) host events)."""
    pipe = _pipe_c()
    p, o, s = pipe.init(seed=0)
    pipe.train_step(p, o, s, pipe.shard_batch(_batches(1)[0]))
    assert pipe.last_schedule == [("C", 0, 0)]


# -- loud fallback ------------------------------------------------------------


def _fallback_warns(store, caplog, **kwargs):
    import logging

    from flexflow_tpu.runtime.pipeline import make_executor

    with caplog.at_level(logging.WARNING, logger="ff.pipeline"):
        ex = make_executor(_model(), store, config=FFConfig(batch_size=16),
                           optimizer=SGDOptimizer(lr=0.1),
                           microbatches=4, compiled=True, **kwargs)
    assert isinstance(ex, PipelineExecutor) and not ex.compiled
    assert any("--pipeline-compiled unavailable" in r.message
               for r in caplog.records)


def test_compiled_fallback_unequal_stages(caplog):
    """Unequal stage sizes have no shared stage mesh: loud fallback
    to the host-driven pipeline (the numerics oracle supports it)."""
    store = StrategyStore(8)
    for n in ("enc0", "enc1"):
        store.set(n, ParallelConfig(n=2, device_ids=(0, 1)))
    for n in ("dec0", "dec1", "softmax"):
        store.set(n, ParallelConfig(n=6, device_ids=(2, 3, 4, 5, 6, 7)))
    _fallback_warns(store, caplog)


def test_compiled_fallback_unverified_degrees(caplog):
    """Spatial (h/w) degrees and c on non-Linear ops are unverified
    against the submesh numerics: loud fallback, not silent 1-ulp
    drift."""
    store = _store()
    store.set("enc0", ParallelConfig(n=2, h=2, device_ids=(0, 1, 2, 3)))
    _fallback_warns(store, caplog)

    store = _store(with_dropout=True)
    store.set("drop", ParallelConfig(
        n=2, c=2, device_ids=tuple(range(4, 8))))
    ff = _model(dropout=0.5)
    import logging

    from flexflow_tpu.runtime.pipeline import make_executor

    with caplog.at_level(logging.WARNING, logger="ff.pipeline"):
        ex = make_executor(ff, store, config=FFConfig(batch_size=16),
                           optimizer=SGDOptimizer(lr=0.1),
                           microbatches=4, compiled=True)
    assert isinstance(ex, PipelineExecutor) and not ex.compiled


@pytest.mark.slow  # ~6s app e2e; tier1_smoke runs it unfiltered
def test_compiled_cli_and_app_end_to_end():
    """--pipeline-compiled parses and drives the fused superstep path
    through the shared app harness."""
    assert FFConfig.parse_args(["--pipeline-compiled"]).pipeline_compiled
    assert not FFConfig.parse_args([]).pipeline_compiled

    from flexflow_tpu.apps import nmt

    assert nmt.main([
        "-b", "16", "-i", "4", "--hidden", "8", "--vocab", "32",
        "--src-len", "4", "--tgt-len", "4", "--pipeline",
        "-ll:tpu", "8", "--microbatches", "2", "--pipeline-compiled",
        "--steps-per-call", "2",
    ]) == 0
