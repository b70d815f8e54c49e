"""Decomposed resharding at strategy boundaries.

GSPMD falls back to involuntary full rematerialization (replicate +
repartition) when a sharding transition moves mesh axes between tensor
dims while also adding/dropping axes — exactly what a spatial-conv ->
DP-dense or table-parallel -> DP boundary produces.  ``MeshPlan.
reshard_hops`` decomposes such transitions into slice / all-to-all /
all-gather hops and ``Executor._reshard_input`` applies them at
consumer inputs (reference analogue: Legion materializing explicit
copies for arbitrary repartitions, ``src/ops/flat.cu:81-124``).
"""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.parallel.mesh import build_mesh_plan
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime.executor import Executor


@pytest.fixture(scope="module")
def plan():
    return build_mesh_plan(8)


def test_no_hops_when_equal(plan):
    assert plan.reshard_hops(P("x0", None), P("x0", None), 2) == []


def test_no_hops_for_pure_add_or_drop(plan):
    # DP widen (add axes to the same dim) and narrow (drop axes): GSPMD
    # reshards these with one collective already.
    assert plan.reshard_hops(P("x0", None), P(("x0", "x1", "x2"), None), 2) == []
    assert plan.reshard_hops(P(("x0", "x1", "x2"), None), P("x0", None), 2) == []


def test_spatial_collapse_hops(plan):
    # conv/pool spatial (n,h,w) -> flat DP: h/w axes move onto the
    # sample dim, one all-to-all chunk per source dim; the chain ends
    # with the target spec itself (the caller applies exactly this).
    hops = plan.reshard_hops(
        P("x0", "x1", "x2", None), P(("x0", "x1", "x2"), None, None, None), 4
    )
    assert hops == [
        P(("x0", "x1"), None, "x2", None),
        P(("x0", "x1", "x2"), None, None, None),
    ]


def test_table_parallel_to_dp_hops(plan):
    # table-parallel embedding (c on dim1, x0 unused) -> DP reshape:
    # slice x0 onto the sample dim first, then all-to-all the c axes.
    hops = plan.reshard_hops(
        P(None, ("x1", "x2"), None), P(("x0", "x1", "x2"), None, None), 3
    )
    assert hops == [
        P("x0", ("x1", "x2"), None),
        P(("x0", "x1", "x2"), None, None),
    ]


def test_reverse_direction_hops(plan):
    # The backward-pass direction of the table-parallel boundary; the
    # final `to` spec performs the x0 drop (subgroup all-gather).
    hops = plan.reshard_hops(
        P(("x0", "x1", "x2"), None, None), P(None, ("x1", "x2"), None), 3
    )
    assert hops == [
        P("x0", ("x1", "x2"), None),
        P(None, ("x1", "x2"), None),
    ]


def test_single_move_returns_terminating_spec(plan):
    # A transition that is exactly one axis move must return [to]
    # (ADVICE r3: the old contract popped it and callers then applied
    # no constraint at all).
    hops = plan.reshard_hops(P("x0", "x1", None), P(("x0", "x1"), None, None), 3)
    assert hops == [P(("x0", "x1"), None, None)]


def test_non_minor_insert_declines_and_warns(plan, caplog):
    # x2 moves dims (so decomposition is attempted), but adding x0
    # under the existing x1 chain would not be a local slice; the
    # decomposition must decline rather than emit a bogus hop — and
    # must say so (VERDICT r3 item 5: the fallback used to be silent).
    import logging

    plan.__dict__.pop("_undecomposable_seen", None)  # per-plan seen set
    with caplog.at_level(logging.WARNING, logger="ff.mesh"):
        assert (
            plan.reshard_hops(
                P("x1", "x2", None), P(("x0", "x1"), None, "x2"), 3
            )
            == []
        )
    assert any("cannot decompose" in r.message for r in caplog.records)
    # Once per transition: a repeat does not re-log.
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="ff.mesh"):
        plan.reshard_hops(P("x1", "x2", None), P(("x0", "x1"), None, "x2"), 3)
    assert not caplog.records


def _boundary_model(batch=8):
    ff = FFModel(FFConfig(batch_size=batch))
    img = ff.create_tensor((batch, 8, 8, 4), name="image")
    ids = ff.create_tensor((batch, 4), dtype=jnp.int32, name="ids")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="label")
    t = ff.conv2d(img, 8, 3, 3, 1, 1, 1, 1, activation="relu", name="conv1")
    t = ff.pool2d(t, 2, 2, 2, 2, 0, 0, name="pool1")
    t = ff.flat(t, name="flat")
    e = ff.multi_embedding(ids, num_tables=4, num_entries=16, out_dim=8,
                           name="tables")
    e = ff.reshape(e, (batch, 32), name="er")
    t = ff.concat([t, e], axis=1, name="cat")
    t = ff.dense(t, 4, activation=None, name="fc")
    ff.softmax(t, lbl, name="softmax")
    store = StrategyStore(8)
    store.set("conv1", ParallelConfig(n=2, h=2, w=2))
    store.set("pool1", ParallelConfig(n=2, h=2, w=2))
    store.set("tables", ParallelConfig(c=4))
    return ff, store


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_boundary_numerics_match_dp(rng):
    """Spatial+table strategies with decomposed reshard hops produce
    the same step numerics as plain DP (the strategy-invariance
    contract, with the hop constraints in the graph)."""
    batch = 8
    batch_data = {
        "image": rng.standard_normal((batch, 8, 8, 4)).astype(np.float32),
        "ids": rng.integers(0, 16, size=(batch, 4)).astype(np.int32),
        "label": rng.integers(0, 4, size=(batch,)).astype(np.int32),
    }

    def run(store):
        ff, default_store = _boundary_model(batch)
        ex = Executor(
            ff,
            strategy=store or default_store,
            optimizer=SGDOptimizer(lr=0.1, momentum=0.9),
            devices=jax.devices()[:8],
        )
        params, opt_state, state = ex.init(seed=7)
        b = ex.shard_batch(batch_data)
        for _ in range(2):
            params, opt_state, state, metrics = ex.train_step(
                params, opt_state, state, b
            )
        return jax.device_get((metrics["train_loss"], params))

    loss_strat, params_strat = run(None)
    loss_dp, params_dp = run(StrategyStore(8))
    assert np.allclose(loss_strat, loss_dp, rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5),
        params_strat, params_dp,
    )


# Shared preamble for subprocess compile probes: the GSPMD remat
# warning comes from XLA's C++ logging, so probes compile in a fresh
# CPU-forced process and the tests grep its stderr.
_PROBE_PREAMBLE = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import jax
jax.config.update("jax_platforms", "cpu")
"""


def _run_probe(body: str, *argv: str):
    """Compile ``body`` (appended to the CPU-forcing preamble) in a
    subprocess; returns True iff GSPMD logged an involuntary full
    rematerialization.  ``body`` must print COMPILED on success."""
    out = subprocess.run(
        [sys.executable, "-c", _PROBE_PREAMBLE + body, *argv],
        capture_output=True,
        text=True,
        cwd=str(__import__("pathlib").Path(__file__).resolve().parent.parent),
        timeout=300,
    )
    assert "COMPILED" in out.stdout, out.stderr[-2000:]
    return "Involuntary full rematerialization" in out.stderr


_TRANSITION_PROBE = r"""
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from flexflow_tpu.parallel.mesh import build_mesh_plan

plan = build_mesh_plan(8)
frm, to = eval(sys.argv[1]), eval(sys.argv[2])
use_hops = sys.argv[3] == "hops"
chain = plan.reshard_hops(frm, to, max(len(frm), len(to))) if use_hops else [to]
assert chain, "expected a decomposition"

def f(x):
    x = jax.lax.with_sharding_constraint(x, NamedSharding(plan.mesh, frm))
    x = x * 2.0
    for spec in chain:
        x = jax.lax.with_sharding_constraint(x, NamedSharding(plan.mesh, spec))
    return x

nd = max(len(frm), len(to))
jax.jit(f).lower(jnp.zeros((8,) * nd, jnp.float32)).compile()
print("COMPILED")
"""


def _compile_transition(frm: str, to: str, mode: str):
    return _run_probe(_TRANSITION_PROBE, frm, to, mode)


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_hops_avoid_remat_gspmd_would_do():
    """The mechanism's value, pinned end to end: a TP-output ->
    hybrid-DP boundary (axes move dims AND an axis drops — the
    vocab-parallel dense -> DP transition) is full-rematerialized by
    GSPMD when constrained directly, and is NOT when walked through
    ``reshard_hops``' chain on the identical mesh."""
    frm, to = 'P(None, ("x0", "x1", "x2"))', 'P(("x0", "x1"), None)'
    assert _compile_transition(frm, to, "direct"), (
        "GSPMD now reshards this directly without remat; "
        "reshard_hops may no longer be needed for this shape"
    )
    assert not _compile_transition(frm, to, "hops")


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_declined_transitions_do_not_remat_today():
    """Documents what GSPMD does on transitions ``reshard_hops``
    DECLINES (and now warns about): on current XLA these compile
    without the involuntary-full-remat fallback, so the decline is
    conservative but not a performance hole.  If this ever starts
    failing, GSPMD regressed on these shapes and the decomposition
    should be extended to cover them."""
    declined = [
        # non-minor-most insert (x0 under x1's chain)
        ('P("x1", "x2", None)', 'P(("x0", "x1"), None, "x2")'),
        # non-suffix drop (x0 dropped from under x1) with a mover
        ('P(("x0", "x1"), "x2", None)', 'P("x1", None, "x2")'),
    ]
    for frm, to in declined:
        assert not _compile_transition(frm, to, "direct"), (frm, to)


_REMAT_PROBE = r"""
from tests.test_reshard import _boundary_model
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.runtime.executor import Executor

ff, store = _boundary_model()
ex = Executor(ff, strategy=store, optimizer=SGDOptimizer(lr=0.1),
              devices=jax.devices()[:8])
ex.lower_train_step().compile()
print("COMPILED")
"""


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_no_involuntary_full_remat():
    """The spatial->DP and table-parallel->DP boundaries compile
    without any GSPMD involuntary-full-rematerialization fallback."""
    assert not _run_probe(_REMAT_PROBE)
