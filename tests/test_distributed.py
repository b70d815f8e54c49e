"""Multi-host/DCN mesh planning, emulated on the 8-device CPU mesh
(2 granules x 4 devices — the reference's 2-node x 4-GPU simulator
topology, ``simulator.cc:32-33``)."""

import jax
import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.parallel.distributed import build_hybrid_mesh_plan
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime.executor import Executor


def test_dcn_axes_outermost():
    plan = build_hybrid_mesh_plan(num_granules=2)
    assert plan.axis_names == ("d0", "x0", "x1")
    assert plan.axis_sizes == (2, 2, 2)


def test_dp_lands_on_dcn_tp_on_ici():
    """n consumes the slow (DCN) axis first; c/s stay on ICI — the
    'collectives ride ICI' layout rule."""
    plan = build_hybrid_mesh_plan(num_granules=2)
    asg = plan.assign(ParallelConfig(n=2, c=2, s=2))
    assert asg["n"] == ("d0",)
    assert set(asg["c"]) | set(asg["s"]) <= {"x0", "x1"}
    # Larger DP spills from DCN into ICI, never the reverse.
    asg4 = plan.assign(ParallelConfig(n=4, c=2))
    assert "d0" in asg4["n"]
    assert asg4["c"][0].startswith("x")


def test_granule_grouping_is_process_major():
    devs = jax.devices()
    plan = build_hybrid_mesh_plan(num_granules=2, devices=devs)
    arr = np.asarray(plan.mesh.devices).reshape(2, 4)
    # Each granule is a contiguous block of jax.devices() order.
    assert [d.id for d in arr[0]] == [d.id for d in devs[:4]]
    assert [d.id for d in arr[1]] == [d.id for d in devs[4:]]


def test_hybrid_plan_trains_and_matches_single_device(rng):
    ff = FFModel(FFConfig(batch_size=8))
    x = ff.create_tensor((8, 16), name="x")
    lbl = ff.create_tensor((8,), dtype=np.int32, name="label")
    t = ff.dense(x, 32, activation="relu", name="fc1")
    t = ff.dense(t, 4, name="fc2")
    ff.softmax(t, lbl, name="softmax")
    batch = {
        "x": rng.standard_normal((8, 16)).astype(np.float32),
        "label": rng.integers(0, 4, size=(8,)).astype(np.int32),
    }
    opt = SGDOptimizer(lr=0.1, momentum=0.9)

    ex1 = Executor(ff, optimizer=opt, devices=jax.devices()[:1])
    params, opt_state, state = ex1.init(seed=0)
    p1, *_ = ex1.train_step(jax.tree.map(np.asarray, params),
                            jax.tree.map(np.asarray, opt_state), state, batch)

    plan = build_hybrid_mesh_plan(num_granules=2)
    store = StrategyStore(8, {"fc1": ParallelConfig(n=2, c=4)})
    exh = Executor(ff, strategy=store, mesh_plan=plan, optimizer=opt)
    ph, *_ = exh.train_step(jax.tree.map(np.asarray, params),
                            jax.tree.map(np.asarray, opt_state), state, batch)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        p1, ph,
    )


def test_initialize_single_process_noop_in_k8s(monkeypatch):
    """An ordinary k8s pod (KUBERNETES_SERVICE_HOST set, no JAX cluster)
    must degrade to the single-process no-op, not crash."""
    from flexflow_tpu.parallel.distributed import initialize

    monkeypatch.setenv("KUBERNETES_SERVICE_HOST", "10.0.0.1")
    initialize()  # must not raise


def test_initialize_rejects_partial_config(monkeypatch):
    from flexflow_tpu.parallel.distributed import initialize

    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.setenv("JAX_PROCESS_ID", "1")
    import pytest as _pytest
    with _pytest.raises(ValueError, match="process_id"):
        initialize()


def test_initialize_env_arg_precedence(monkeypatch):
    """The fallback ladder: explicit args win over JAX_* env, env wins
    over nothing — captured at the jax.distributed boundary."""
    from flexflow_tpu.parallel.distributed import initialize

    calls = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "env-host:1111")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "4")
    monkeypatch.setenv("JAX_PROCESS_ID", "3")
    initialize()
    assert calls[-1] == {"coordinator_address": "env-host:1111",
                         "num_processes": 4, "process_id": 3}
    initialize(coordinator_address="arg-host:2222",
               num_processes=2, process_id=1)
    assert calls[-1] == {"coordinator_address": "arg-host:2222",
                         "num_processes": 2, "process_id": 1}


def test_initialize_autodetect_failure_degrades(monkeypatch):
    """Cluster markers present but jax auto-detection unavailable
    (ordinary Slurm/k8s job with no JAX cluster behind it) must
    degrade to the single-process no-op, not crash the run."""
    from flexflow_tpu.parallel.distributed import initialize

    def boom(**kw):
        raise RuntimeError("Could not find coordinator address")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
              "JAX_PROCESS_ID"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("SLURM_JOB_ID", "12345")
    initialize()  # must not raise


def test_granule_count_validated():
    """User-facing ValueError (not a bare assert, which vanishes under
    ``python -O``) for granule counts that don't divide the devices."""
    with pytest.raises(ValueError, match="granule"):
        build_hybrid_mesh_plan(num_granules=3)
    with pytest.raises(ValueError, match="granule"):
        build_hybrid_mesh_plan(num_granules=0)


def test_world_single_process():
    from flexflow_tpu.parallel.distributed import world

    assert world() == (0, 1)


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_moe_expert_parallel_on_hybrid_mesh(rng):
    """Expert parallelism composes with the DCN-outer pod layout: dp
    rides the d0 (DCN) axis, the experts' c-shard stays on ICI axes,
    and numerics match the flat single-granule mesh."""
    from flexflow_tpu.models.transformer import (
        build_transformer_lm,
        transformer_strategy,
    )

    def run(plan):
        ff = build_transformer_lm(
            batch_size=4, seq_len=8, vocab_size=64, d_model=16,
            num_heads=2, num_layers=1, moe_experts=4,
            config=FFConfig(batch_size=4, seed=2),
        )
        store = transformer_strategy(8, num_layers=1, dp=2, tp=4, moe=True)
        ex = Executor(ff, strategy=store, optimizer=SGDOptimizer(lr=0.05),
                      mesh_plan=plan)
        params, opt_state, state = ex.init()
        r = np.random.default_rng(0)
        batch = ex.shard_batch({
            "tokens": r.integers(0, 64, size=(4, 8)).astype(np.int32),
            "label": r.integers(0, 64, size=(4, 8)).astype(np.int32),
        })
        params, opt_state, state, m = ex.train_step(
            params, opt_state, state, batch
        )
        jax.block_until_ready(m)
        return float(m["train_loss"])

    hybrid = run(build_hybrid_mesh_plan(num_granules=2))
    flat = run(build_hybrid_mesh_plan(num_granules=1))
    np.testing.assert_allclose(hybrid, flat, rtol=2e-4)
