"""Strategy-search subsystem: native simulator semantics + MCMC search.

The reference's equivalent is the offline simulator binary
(``scripts/simulator.cc``): event-driven list scheduling of shard +
comm tasks and Metropolis search.  The hand-computed schedule cases
here pin the scheduler's exact semantics (device timelines, channel
contention, rect-intersection comm volumes).
"""

import logging
import os

import numpy as np
import pytest

from flexflow_tpu.models.alexnet import build_alexnet
from flexflow_tpu.native import ffsim_search, ffsim_simulate
from flexflow_tpu.parallel.strategy import AXES, ParallelConfig, StrategyStore
from flexflow_tpu.search import search_strategy, simulate_strategy
from flexflow_tpu.search.problem import build_virtual_plan, shard_devices


def _problem(lines):
    return "\n".join(lines) + "\n"


class TestSimulatorSemantics:
    def test_single_op_compute_only(self):
        # One op, 2 shards of 5us each on distinct devices -> 5us.
        p = _problem([
            "ffsim 1", "ndevices 2", "devices_per_node 2",
            "bw_intra 10", "bw_inter 1",
            "nops 1",
            "op 0 1 solo",
            "cfg 2 1 1 1 1 5.0 0.0 0 1",
            "nedges 0",
        ])
        assert ffsim_simulate(p, [0]) == pytest.approx(5.0)

    def test_sync_cost_added_after_op(self):
        p = _problem([
            "ffsim 1", "ndevices 2", "devices_per_node 2",
            "bw_intra 10", "bw_inter 1",
            "nops 1",
            "op 0 1 solo",
            "cfg 2 1 1 1 1 5.0 3.0 0 1",
            "nedges 0",
        ])
        assert ffsim_simulate(p, [0]) == pytest.approx(8.0)

    def test_resharding_comm_hand_schedule(self):
        # op0 n-split rows of an (8,4) f32 tensor; op1 c-splits columns
        # and broadcasts rows.  Each cross-device transfer moves half a
        # source shard: 8 elems * 4B / bw 10 + 1us latency = 4.2us.
        # Comm starts when the producer shard finishes (5us); consumer
        # shards start at 9.2 and run 7us -> makespan 16.2.
        p = _problem([
            "ffsim 1", "ndevices 2", "devices_per_node 2",
            "bw_intra 10", "bw_inter 1",
            "nops 2",
            "op 0 1 producer",
            "cfg 2 1 1 1 1 5.0 0.0 0 1",
            "op 1 1 consumer",
            "cfg 1 2 1 1 1 7.0 0.0 0 1",
            "nedges 1",
            "edge 0 1 4 2 8 4 0 -1 -1 1",
        ])
        assert ffsim_simulate(p, [0, 0]) == pytest.approx(16.2)

    def test_same_device_transfer_is_free(self):
        # Same split on both ops, same placement: no comm, pure chain.
        p = _problem([
            "ffsim 1", "ndevices 2", "devices_per_node 2",
            "bw_intra 10", "bw_inter 1",
            "nops 2",
            "op 0 1 a",
            "cfg 2 1 1 1 1 5.0 0.0 0 1",
            "op 1 1 b",
            "cfg 2 1 1 1 1 7.0 0.0 0 1",
            "nedges 1",
            "edge 0 1 4 2 8 4 0 -1 0 -1",
        ])
        assert ffsim_simulate(p, [0, 0]) == pytest.approx(12.0)

    def test_search_picks_obvious_winner(self):
        # Config 1 halves the time with no comm downside; MCMC must
        # find it and report the config-0 start as the baseline.
        p = _problem([
            "ffsim 1", "ndevices 2", "devices_per_node 2",
            "bw_intra 10", "bw_inter 1",
            "nops 1",
            "op 0 2 solo",
            "cfg 1 1 1 1 1 10.0 0.0 0",
            "cfg 2 1 1 1 1 5.0 0.0 0 1",
            "nedges 0",
        ])
        res = ffsim_search(p, iters=50, seed=0, alpha=5.0)
        assert res["init_us"] == pytest.approx(10.0)
        assert res["best_us"] == pytest.approx(5.0)
        assert res["assign"] == [1]

    def test_bad_problem_raises(self):
        with pytest.raises(ValueError):
            ffsim_simulate("not a problem", [0])

    def test_zero_config_op_raises_not_crashes(self):
        p = _problem([
            "ffsim 1", "ndevices 2", "devices_per_node 2",
            "bw_intra 10", "bw_inter 1",
            "nops 1", "op 0 0 empty", "nedges 0",
        ])
        with pytest.raises(ValueError):
            ffsim_search(p, iters=10, seed=0, alpha=5.0)

    def test_bad_edge_axis_raises_not_crashes(self):
        p = _problem([
            "ffsim 1", "ndevices 2", "devices_per_node 2",
            "bw_intra 10", "bw_inter 1",
            "nops 2",
            "op 0 1 a", "cfg 2 1 1 1 1 5.0 0.0 0 1",
            "op 1 1 b", "cfg 2 1 1 1 1 5.0 0.0 0 1",
            "nedges 1",
            "edge 0 1 4 1 8 7 0",  # src axis 7 out of range
        ])
        with pytest.raises(ValueError):
            ffsim_simulate(p, [0, 0])


class TestShardDevices:
    def test_data_parallel_covers_all_devices(self):
        plan = build_virtual_plan(8)
        assert shard_devices(plan, ParallelConfig(n=8)) == list(range(8))

    def test_hybrid_covers_all_devices_once(self):
        plan = build_virtual_plan(8)
        devs = shard_devices(plan, ParallelConfig(n=2, c=4))
        assert sorted(devs) == list(range(8))

    def test_partial_split_replicates_on_first_coords(self):
        plan = build_virtual_plan(8)
        devs = shard_devices(plan, ParallelConfig(n=2))
        assert len(devs) == 2
        assert len(set(devs)) == 2

    def test_explicit_device_ids_win(self):
        plan = build_virtual_plan(8)
        pc = ParallelConfig(c=4, device_ids=(3, 1, 2, 0))
        assert shard_devices(plan, pc) == [3, 1, 2, 0]



def _run_one_train_step(ff, store, n_classes, image, n_devices=8):
    """One executor train step under a strategy; asserts finite loss."""
    import jax

    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.runtime.pipeline import make_executor

    ex = make_executor(ff, store, optimizer=SGDOptimizer(lr=0.01),
                       devices=jax.devices()[:n_devices])
    params, opt_state, state = ex.init()
    rng = np.random.default_rng(0)
    batch = ex.shard_batch({
        "image": rng.standard_normal(image).astype(np.float32),
        "label": rng.integers(0, n_classes, size=(image[0],)).astype(np.int32),
    })
    params, opt_state, state, metrics = ex.train_step(
        params, opt_state, state, batch
    )
    jax.block_until_ready(metrics)
    assert np.isfinite(float(metrics["train_loss"]))


class TestEndToEndSearch:
    @pytest.fixture(scope="class")
    def alexnet(self):
        return build_alexnet(batch_size=64, image_size=229, num_classes=1000)

    def test_search_beats_or_matches_dp(self, alexnet):
        res = search_strategy(alexnet, num_devices=8, iters=3000, seed=0)
        assert res.best_time_us <= res.dp_time_us
        # AlexNet's FC gradient sync makes DP clearly sub-optimal — the
        # ICML'18 result the search must reproduce in simulation.
        assert res.speedup > 1.5
        assert set(res.assignment) == {op.name for op in alexnet.layers}
        for pc in res.assignment.values():
            assert pc.num_parts <= 8

    def test_store_roundtrip_and_simulate(self, alexnet, tmp_path):
        res = search_strategy(alexnet, num_devices=8, iters=2000, seed=1)
        path = tmp_path / "strategy.json"
        res.store.save(str(path))
        loaded = StrategyStore.load(str(path))
        t = simulate_strategy(alexnet, loaded, 8)
        assert t == pytest.approx(res.best_time_us, rel=1e-6)

    def test_dp_store_matches_reported_baseline(self, alexnet):
        res = search_strategy(alexnet, num_devices=8, iters=100, seed=0)
        # A store with no entries = the runtime's DP fallback; candidate
        # 0 of every op is the same config, so times must agree.
        dp_t = simulate_strategy(alexnet, StrategyStore.data_parallel(8), 8)
        assert dp_t == pytest.approx(res.dp_time_us, rel=1e-6)

    def test_simulate_strategy_measured_costs(self, alexnet):
        """simulate_strategy prices ops from a measured table when
        given one (the ffsim-calibration path, tools/calibrate_ffsim)."""
        flat = {op.name: 1000.0 for op in alexnet.layers}
        store = StrategyStore.data_parallel(8)
        t_meas = simulate_strategy(alexnet, store, 8, measured_costs=flat)
        t_roof = simulate_strategy(alexnet, store, 8)
        assert t_meas != pytest.approx(t_roof)
        # 1000 us/op fwd (x the fwd+bwd factor) across a sequential
        # graph: the makespan must scale with op count.
        assert t_meas > 1000.0 * len(alexnet.layers) / 8

    def test_measured_costs_override_roofline(self, alexnet):
        """Per-op measured times (runtime.profiler.measured_cost_table
        format) replace the roofline estimate and change the simulated
        baseline accordingly."""
        flat = {op.name: 1000.0 for op in alexnet.layers}
        res = search_strategy(
            alexnet, num_devices=8, iters=100, seed=0, measured_costs=flat
        )
        res2 = search_strategy(alexnet, num_devices=8, iters=100, seed=0)
        assert res.dp_time_us != pytest.approx(res2.dp_time_us)
        assert res.best_time_us <= res.dp_time_us

    @pytest.mark.slow  # ~3 min of live per-op microbenchmarks
    def test_cli_measured_mode(self, tmp_path, capsys):
        """``python -m flexflow_tpu.search --measured`` microbenches
        every op live (the reference's measured simulator inputs,
        ``scripts/cnn.h:204+``) and still emits a loadable strategy."""
        from flexflow_tpu.search.__main__ import main

        out = tmp_path / "strategy.json"
        assert main([
            "--model", "alexnet", "-b", "2", "--devices", "4",
            "--iters", "200", "--measured", "-o", str(out),
        ]) in (0, None)
        assert "measured 13 op costs" in capsys.readouterr().out
        loaded = StrategyStore.load(str(out))
        assert loaded.num_devices == 4

    @pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
    def test_searched_strategy_runs_on_executor(self, alexnet):
        """The emitted table must be consumable by the runtime: compile
        and run one train step under the searched strategy on the
        8-device CPU mesh."""
        from flexflow_tpu.models.alexnet import build_alexnet as _b

        ff = _b(batch_size=8, image_size=67, num_classes=10)
        res = search_strategy(ff, num_devices=8, iters=500, seed=0)
        _run_one_train_step(ff, res.store, 10, (8, 67, 67, 3))

    @pytest.mark.slow  # ~78s Inception compile (targeted: test_search)
    def test_inception_op_parallel_strategy_runs(self):
        """BASELINE config #2: Inception-V3 blocks under a searched
        n/c/h/w operator-parallel strategy on 4 chips (virtual mesh).
        The searched table must beat or match simulated DP and run."""
        from flexflow_tpu.models import build_inception_v3

        ff = build_inception_v3(batch_size=4, image_size=75, num_classes=8)
        res = search_strategy(ff, num_devices=4, iters=300, seed=0)
        assert res.best_time_us <= res.dp_time_us * (1 + 1e-6)
        # At least one op got a non-pure-data-parallel config.
        assert any(
            pc.degree("c") > 1 or pc.degree("h") > 1 or pc.degree("w") > 1
            for pc in res.assignment.values()
        )
        _run_one_train_step(ff, res.store, 8, (4, 75, 75, 3), n_devices=4)

    def test_bad_edge_rank_raises_not_crashes(self):
        # nd = -1 previously hit vector::resize -> std::terminate.
        p = _problem([
            "ffsim 1", "ndevices 2", "devices_per_node 2",
            "bw_intra 10", "bw_inter 1",
            "nops 2",
            "op 0 1 a", "cfg 2 1 1 1 1 5.0 0.0 0 1",
            "op 1 1 b", "cfg 2 1 1 1 1 5.0 0.0 0 1",
            "nedges 1",
            "edge 0 1 4 -1",
        ])
        with pytest.raises(ValueError):
            ffsim_simulate(p, [0, 0])

    def test_oversized_counts_raise_not_allocate(self):
        p = _problem([
            "ffsim 1", "ndevices 2", "devices_per_node 2",
            "bw_intra 10", "bw_inter 1",
            "nops 2000000000",
        ])
        with pytest.raises(ValueError):
            ffsim_simulate(p, [0])

    def test_degree_exceeding_ndevices_raises(self):
        p = _problem([
            "ffsim 1", "ndevices 2", "devices_per_node 2",
            "bw_intra 10", "bw_inter 1",
            "nops 1", "op 0 1 a", "cfg 4 1 1 1 1 5.0 0.0 0 1 2 3",
            "nedges 0",
        ])
        with pytest.raises(ValueError):
            ffsim_simulate(p, [0])


class TestDeviceShiftedCandidates:
    def test_candidates_include_shifted_blocks(self):
        """Pure-n sub-mesh candidates exist on every aligned block, not
        just the mesh origin (the reference's per-table DLRM pinning
        freedom, dlrm_strategy.cc:11-19)."""
        from flexflow_tpu.config import FFConfig
        from flexflow_tpu.graph import FFModel
        from flexflow_tpu.search.problem import enumerate_candidates

        ff = FFModel(FFConfig(batch_size=8))
        x = ff.create_tensor((8, 16), name="x")
        ff.dense(x, 16, name="fc")
        plan = build_virtual_plan(4)
        cands = enumerate_candidates(ff.layers[0], plan)
        ids = {pc.device_ids for pc in cands if pc.device_ids is not None}
        assert (1,) in ids and (2,) in ids and (3,) in ids
        assert (2, 3) in ids

    def test_searched_placement_table_executes(self):
        """A searched table that mixes full-mesh and pinned ops (every
        op carrying explicit device_ids) must run via make_executor."""
        import jax

        from flexflow_tpu.config import FFConfig
        from flexflow_tpu.graph import FFModel
        from flexflow_tpu.optim import SGDOptimizer
        from flexflow_tpu.runtime.pipeline import make_executor

        ff = FFModel(FFConfig(batch_size=8))
        import jax.numpy as jnp

        ids_t = ff.create_tensor((8, 2), dtype=jnp.int32, name="ids")
        lbl = ff.create_tensor((8,), dtype=jnp.int32, name="label")
        e = ff.multi_embedding(ids_t, 2, 16, 4, name="tables")
        e = ff.reshape(e, (8, 8), name="r")
        t = ff.dense(e, 8, activation="relu", name="fc1")
        t = ff.dense(t, 4, name="fc2")
        ff.softmax(t, lbl, name="softmax")

        store = StrategyStore(4)
        # tables pinned off-origin, trunk on the full mesh.
        store.set("tables", ParallelConfig(device_ids=(2,)))
        for name in ("r", "fc1", "fc2", "softmax"):
            store.set(name, ParallelConfig(n=4, device_ids=(0, 1, 2, 3)))
        t_sim = simulate_strategy(ff, store, 4)
        assert np.isfinite(t_sim) and t_sim > 0
        ex = make_executor(ff, store, optimizer=SGDOptimizer(lr=0.1),
                           devices=jax.devices()[:4])
        params, opt_state, state = ex.init()
        rng = np.random.default_rng(0)
        batch = ex.shard_batch({
            "ids": rng.integers(0, 16, size=(8, 2)).astype(np.int32),
            "label": rng.integers(0, 4, size=(8,)).astype(np.int32),
        })
        params, opt_state, state, m = ex.train_step(
            params, opt_state, state, batch
        )
        assert np.isfinite(float(jax.device_get(m["train_loss"])))


class TestMeasuredDegrees:
    """Per-(op, degree) measured cost tables (the reference's
    ``computeTime[config]`` cache filled by live microbenchmarks per
    parallel degree, ``scripts/cnn.h:204-260``, ``simulator.cc:
    142-151``) replacing the whole-op / num_parts linear assumption."""

    def _model(self):
        import jax.numpy as jnp

        from flexflow_tpu.config import FFConfig
        from flexflow_tpu.graph import FFModel

        batch = 8
        ff = FFModel(FFConfig(batch_size=batch))
        x = ff.create_tensor((batch, 1024), name="x")
        lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="label")
        t = ff.dense(x, 1024, activation="relu", name="fc")
        t = ff.dense(t, 16, name="head")
        ff.softmax(t, lbl, name="softmax")
        return ff

    def test_shard_local_shapes(self):
        from flexflow_tpu.runtime.profiler import _shard_shapes

        ff = self._model()
        fc = ff.layers[0]
        xs, ps, _ = _shard_shapes(fc, ParallelConfig(n=2, c=4))
        # Input: batch split by n, contracted feature dim kept FULL.
        assert xs == [(4, 1024)]
        # Kernel rows (out features, 'c') split 4-ways; bias likewise.
        assert ps["kernel"] == (256, 1024)
        assert ps["bias"] == (256,)

    @pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
    def test_structural_cache_dedupes(self):
        """Identical shard geometries (same type/attrs/local shapes)
        are measured once — the reference's computeTime[] keyed by op
        hash + config (``simulator.cc:142-151``)."""
        from flexflow_tpu.runtime.profiler import measured_degree_table

        import jax.numpy as jnp

        from flexflow_tpu.config import FFConfig
        from flexflow_tpu.graph import FFModel

        calls = []

        def measure(op, pc, p, xs, s):
            calls.append((op.name, tuple(x.shape for x in xs)))
            return 10.0

        # Two structurally identical dense layers (the repeated-block
        # Inception case): the second one's candidates must all hit
        # the first one's cache entries.
        ff = FFModel(FFConfig(batch_size=8))
        x = ff.create_tensor((8, 64), name="x")
        lbl = ff.create_tensor((8,), dtype=jnp.int32, name="label")
        t = ff.dense(x, 64, activation="relu", name="fc1")
        t = ff.dense(t, 64, activation="relu", name="fc2")
        ff.softmax(t, lbl, name="softmax")
        table = measured_degree_table(ff, 8, measure=measure)
        assert set(table) == {"fc1", "fc2", "softmax"}
        assert table["fc1"] == table["fc2"]
        assert not any(name == "fc2" for name, _ in calls)
        assert all(us > 0 for v in table.values() for us in v.values())

    @pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
    def test_measured_search_diverges_from_roofline(self):
        """The VERDICT-item acceptance: measured per-degree costs make
        the search pick a different (simulated-better-under-measure)
        strategy than the roofline on the same graph.  The injected
        measure models an MXU utilization floor: per-shard time scales
        with local rows but TP shards pay a fixed small-tile penalty —
        exactly the nonlinearity the old measured/parts linear scaling
        could not express."""
        from flexflow_tpu.runtime.profiler import measured_degree_table

        ff = self._model()
        roofline = search_strategy(ff, num_devices=8, iters=5000, seed=0)
        # Roofline: the big fc weight makes DP grad-sync dominant, so
        # the search tensor-parallelizes fc.
        assert roofline.assignment["fc"].c > 1

        def measure(op, pc, p, xs, s):
            return 10.0 * xs[0].shape[0] + 200.0 * (pc.degree("c") - 1)

        table = measured_degree_table(ff, 8, measure=measure)
        measured = search_strategy(
            ff, num_devices=8, iters=5000, seed=0, measured_costs=table
        )
        assert measured.assignment["fc"].c == 1
        assert measured.assignment["fc"] != roofline.assignment["fc"]

    @pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
    def test_measured_bwd_asymmetry_changes_strategy(self):
        """VERDICT r4 acceptance: an op whose BACKWARD cost scales
        differently from its forward must steer the search away from
        the strategy the legacy fwd-only x3.0 assumption picks — the
        reason the reference measures ``t1+t2+t3`` per config instead
        of scaling forward (``scripts/cnn.h:252-277``)."""
        from flexflow_tpu.runtime.profiler import measured_degree_table

        ff = self._model()

        def fwd_only(op, pc, p, xs, s):
            # Legacy scalar entries: downstream applies x3.0.
            return 10.0 * xs[0].shape[0]

        def fwd_bwd(op, pc, p, xs, s):
            # Identical forward; backward pays a per-degree penalty
            # under c-splits (the conv-halo / embedding-scatter shape
            # of asymmetry) that no fwd-derived factor can express.
            fwd = 10.0 * xs[0].shape[0]
            return (fwd, 2.0 * fwd + 500.0 * (pc.degree("c") - 1))

        legacy = search_strategy(
            ff, num_devices=8, iters=5000, seed=0,
            measured_costs=measured_degree_table(ff, 8, measure=fwd_only),
        )
        measured = search_strategy(
            ff, num_devices=8, iters=5000, seed=0,
            measured_costs=measured_degree_table(ff, 8, measure=fwd_bwd),
        )
        # Same forward numbers; only the measured bwd leg differs —
        # the big fc flips from TP (grad-sync relief) to replicated.
        assert legacy.assignment["fc"].c > 1
        assert measured.assignment["fc"].c == 1
        assert measured.assignment["fc"] != legacy.assignment["fc"]

    @pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
    def test_real_timing_smoke(self):
        """The real two-point fori_loop timer produces positive,
        finite per-degree times on the CPU backend for a tiny model
        and the search consumes them end to end."""
        import jax.numpy as jnp

        from flexflow_tpu.config import FFConfig
        from flexflow_tpu.graph import FFModel
        from flexflow_tpu.runtime.profiler import measured_degree_table

        ff = FFModel(FFConfig(batch_size=8))
        x = ff.create_tensor((8, 32), name="x")
        lbl = ff.create_tensor((8,), dtype=jnp.int32, name="label")
        t = ff.dense(x, 16, activation="relu", name="fc")
        ff.softmax(t, lbl, name="softmax")
        table = measured_degree_table(ff, 4, loops=(2, 6))
        assert table
        for v in table.values():
            for fwd_us, bwd_us in v.values():
                assert np.isfinite(fwd_us) and fwd_us > 0
                assert np.isfinite(bwd_us) and bwd_us >= 0
        res = search_strategy(
            ff, num_devices=4, iters=1000, seed=0, measured_costs=table
        )
        assert res.best_time_us > 0


class TestSearchTemperature:
    def test_large_graph_finds_single_improving_move(self):
        """Round-3 regression: on a 120-op chain where exactly one op
        has a better config, the search must find it.  The old
        delta/current acceptance (p(+1%) = 0.95) random-walked off the
        DP optimum on graphs this size and returned best == init."""
        lines = [
            "ffsim 1", "ndevices 4", "devices_per_node 4",
            "bw_intra 100", "bw_inter 10", "nops 120",
        ]
        for i in range(120):
            lines.append(f"op {i} 2 op{i}")
            # DP config: 4 shards of 10us; alternative: 2 shards of
            # 25us (worse) — except op 60, whose alternative is 2
            # shards of 1us with no sync (strictly better).
            lines.append("cfg 4 1 1 1 1 10.0 5.0 0 1 2 3")
            if i == 60:
                lines.append("cfg 2 1 1 1 1 1.0 0.0 0 1")
            else:
                lines.append("cfg 2 1 1 1 1 25.0 5.0 0 1")
        lines.append("nedges 0")
        p = "\n".join(lines) + "\n"
        res = ffsim_search(p, 20000, 0, 5.0)
        assert res["best_us"] < res["init_us"]
        assert res["assign"][60] == 1
        assert sum(res["assign"]) == 1  # and ONLY op 60 moved

    def test_inception_speedup_above_one(self):
        """VERDICT r2 item 4: the ICML'18 model family must show a
        simulated operator-parallel gain (coordinated per-branch h/w
        splits; see OP_PARALLEL.md for the v5e-roofline analysis)."""
        from flexflow_tpu.models.cnn_catalog import build_inception_v3

        res = search_strategy(
            build_inception_v3(batch_size=64), num_devices=4,
            iters=20_000, seed=0,
        )
        assert res.speedup > 1.03


def _mlp(batch=8, width=32, ndev_classes=4, seed=3):
    """Tiny MLP for execution-config search tests (fast compiles)."""
    import jax.numpy as jnp

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.graph import FFModel

    ff = FFModel(FFConfig(batch_size=batch, seed=seed))
    x = ff.create_tensor((batch, width), name="x")
    lbl = ff.create_tensor((batch,), dtype=jnp.int32, name="label")
    t = ff.dense(x, width, activation="relu", name="fc1")
    t = ff.dense(t, width, activation="relu", name="fc2")
    t = ff.dense(t, ndev_classes, name="head")
    ff.softmax(t, lbl, name="softmax")
    return ff


class TestCalibration:
    """The dispatch/fence constant loader (search/cost_model.py):
    fitted from a run's own JSONL telemetry, with the measured-host
    defaults as the LOUD uncalibrated fallback (SEARCH.md protocol)."""

    def test_defaults_are_uncalibrated(self):
        from flexflow_tpu.search.cost_model import (
            DEFAULT_DISPATCH_MS,
            DEFAULT_FENCE_MS,
            Calibration,
        )

        cal = Calibration()
        assert not cal.calibrated
        assert cal.dispatch_ms == DEFAULT_DISPATCH_MS
        assert cal.fence_ms == DEFAULT_FENCE_MS
        assert "uncalibrated" in cal.describe()

    def test_from_run_end_calibration_block(self, tmp_path):
        """A complete log's run_end ``calibration`` block wins — the
        single-run protocol (OBSERVABILITY.md)."""
        import json

        from flexflow_tpu.search import Calibration

        path = tmp_path / "run-1.jsonl"
        events = [
            {"ev": "run_start", "seq": 0},
            {"ev": "step", "seq": 1, "wall_s": 0.004},
            {"ev": "run_end", "seq": 2, "calibration": {
                "steps": 30, "fences_per_step": 0.066,
                "programs_per_step": 16.0, "step_ms_p50": 17.6,
                "dispatch_ms_per_program": 1.1, "fence_ms": 0.9,
                "fence_samples": 2,
            }},
        ]
        path.write_text("\n".join(json.dumps(e) for e in events) + "\n")
        cal = Calibration.from_jsonl(str(path))
        assert cal.calibrated
        assert cal.dispatch_ms == pytest.approx(1.1)
        assert cal.fence_ms == pytest.approx(0.9)
        assert cal.step_ms_p50 == pytest.approx(17.6)
        assert cal.programs_per_step == pytest.approx(16.0)
        assert cal.steps == 30
        # Complete accounting + no `search` event: this run can anchor
        # the compute-scale fit.
        assert cal.complete and not cal.auto_executed

    def test_from_truncated_log_rederives(self, tmp_path):
        """A crashed run's log has no run_end: the constants re-derive
        from the raw step/fence/superstep events (min non-warmup fence
        = round-trip floor; step p50)."""
        import json

        from flexflow_tpu.search import Calibration

        path = tmp_path / "run-crashed.jsonl"
        events = (
            [{"ev": "run_start", "seq": 0}]
            + [{"ev": "fence", "label": "warmup", "wall_s": 0.5}]
            + [{"ev": "step", "step": i, "wall_s": 0.010 + 0.001 * (i % 3)}
               for i in range(9)]
            + [{"ev": "fence", "label": "log", "wall_s": 0.002},
               {"ev": "fence", "label": "log", "wall_s": 0.003}]
        )
        path.write_text("\n".join(json.dumps(e) for e in events)
                        + '\n{"torn tail')  # crashed mid-write
        cal = Calibration.from_jsonl(str(path))
        assert cal.calibrated
        assert cal.step_ms_p50 == pytest.approx(11.0)
        # min non-warmup fence, NOT the 500ms compile-inclusive warmup.
        assert cal.fence_ms == pytest.approx(2.0)
        assert cal.steps == 9
        # Truncated: programs-per-step may be unrecoverable, so this
        # source must NOT anchor the compute-scale fit.
        assert not cal.complete

    def test_missing_file_falls_back_loudly(self, tmp_path, caplog):
        from flexflow_tpu.search import Calibration

        with caplog.at_level(logging.WARNING, logger="ff.search"):
            cal = Calibration.from_jsonl(str(tmp_path / "nope.jsonl"))
        assert not cal.calibrated
        assert any("uncalibrated" in r.message for r in caplog.records)

    def test_from_dir_picks_latest_excluding_active(self, tmp_path):
        import json

        from flexflow_tpu.search import Calibration

        old = tmp_path / "run-a.jsonl"
        new = tmp_path / "run-b.jsonl"
        for p, fence in ((old, 3.0), (new, 7.0)):
            p.write_text(json.dumps({
                "ev": "run_end",
                "calibration": {"steps": 4, "fences_per_step": 1.0,
                                "fence_ms": fence, "fence_samples": 4},
            }) + "\n")
        os.utime(old, (1, 1))
        assert Calibration.from_dir(str(tmp_path)).fence_ms == 7.0
        # The ACTIVE run's own (still-empty) file must not self-feed.
        cal = Calibration.from_dir(str(tmp_path), exclude=str(new))
        assert cal.fence_ms == 3.0


class TestExecutionConfigAccounting:
    """programs/fences-per-step must be the EXACT formulas the run
    telemetry pins (OBSERVABILITY.md dispatch audit): ``2*S*ceil(m/c)``
    host-driven, ``1/k`` fused/compiled — the searcher optimizing any
    OTHER accounting would tune a phantom runtime."""

    def _ecfg(self, **kw):
        from flexflow_tpu.parallel.strategy import StrategyStore
        from flexflow_tpu.search.execution import ExecutionConfig

        return ExecutionConfig(store=StrategyStore.data_parallel(8), **kw)

    def test_host_pipeline_programs(self):
        assert self._ecfg(stages=4, microbatches=8).programs_per_step() == 64
        assert self._ecfg(
            stages=4, microbatches=8, chunk=8
        ).programs_per_step() == 8
        # Non-divisible chunk tail: ceil(8/3) = 3 chunk programs/stage.
        assert self._ecfg(
            stages=2, microbatches=8, chunk=3
        ).programs_per_step() == 2 * 2 * 3
        # Accum lowers onto the microbatch loop (a*m microbatches).
        assert self._ecfg(
            stages=2, microbatches=4, accum_steps=2
        ).programs_per_step() == 2 * 2 * 8

    def test_fused_paths_are_one_program_per_k(self):
        assert self._ecfg().programs_per_step() == 1.0
        assert self._ecfg(steps_per_call=8).programs_per_step() == 1 / 8
        assert self._ecfg(
            stages=4, microbatches=8, compiled=True, steps_per_call=8
        ).programs_per_step() == 1 / 8

    def test_fence_accounting(self):
        assert self._ecfg().fences_per_step() == 0.0  # unfenced k=1 loop
        assert self._ecfg(steps_per_call=8).fences_per_step() == 1 / 8
        # The loudly-warned clip-norm floor on the host-driven pipeline.
        assert self._ecfg(
            stages=4, microbatches=8
        ).fences_per_step(clip_norm=1.0) == 1.0
        assert self._ecfg(
            stages=4, microbatches=8, compiled=True
        ).fences_per_step(clip_norm=1.0) == 0.0  # device-side clip


# PIPELINE_OVERHEAD.md round 7 (2026-08-04, 8-dev virtual CPU mesh,
# 30 timed steps, same-day A/B) — the recorded dispatch-amortization
# sweeps the simulator must reproduce the ranking of.  ms/step.
_R7_DISPATCH_BOUND = {  # S=4 mb=8, b64 x w256: dispatch dominates
    "host_c1": 113.7,      # 64 programs/step
    "host_cm": 50.6,       # c=m=8 -> 8 programs/step
    "compiled": 43.4,      # 1 program/step
    "compiled_k8": 45.9,   # 1/8 programs/step (fence-neutral on CPU)
}
_R7_COMPUTE_BOUND = {  # S=2 mb=8, b512 x w1024: compute dominates
    "host_c1": 2308.0,     # 32 programs/step
    "host_cm": 1882.0,     # c=m=8 -> 4 programs/step
    "compiled": 1917.0,    # 1 program/step
}
# Same-day re-measurement drift on this box is ~7% (round 6/7 notes);
# measured pairs closer than that are ties the predictor need not
# (and cannot honestly) order.
_R7_NOISE = 1.07


class TestRankingConsistency:
    """ISSUE 6 acceptance: simulator-predicted ranking matches the
    MEASURED ranking across the dispatch-amortization variants at one
    dispatch-bound and one compute-bound shape — golden recorded
    constants, no live timing in tier-1."""

    def _predict(self, recorded, S, m, dispatch_ms):
        from flexflow_tpu.parallel.strategy import StrategyStore
        from flexflow_tpu.search.cost_model import Calibration
        from flexflow_tpu.search.execution import (
            REMAT_FACTOR,
            ExecutionConfig,
            predict_step_ms,
        )

        # The calibration protocol applied to the recorded sweep: the
        # compiled row is compute + ONE dispatch, so the recorded
        # compute term is its ms minus one program's dispatch.
        compute_us = (recorded["compiled"] - dispatch_ms) / REMAT_FACTOR * 1e3
        cal = Calibration(dispatch_ms=dispatch_ms, fence_ms=dispatch_ms,
                          calibrated=True)
        store = StrategyStore.data_parallel(8)
        variants = {
            "host_c1": ExecutionConfig(store=store, stages=S,
                                       microbatches=m, chunk=1),
            "host_cm": ExecutionConfig(store=store, stages=S,
                                       microbatches=m, chunk=m),
            "compiled": ExecutionConfig(store=store, stages=S,
                                        microbatches=m, compiled=True),
            "compiled_k8": ExecutionConfig(store=store, stages=S,
                                           microbatches=m, compiled=True,
                                           steps_per_call=8),
        }
        return {
            name: predict_step_ms(None, e, 8, calibration=cal,
                                  compute_us=compute_us)
            for name, e in variants.items()
        }

    def _assert_ranking_matches(self, recorded, predicted):
        """Every measured-distinguishable pair (outside the recorded
        noise floor) must be predicted in the measured order."""
        for a in recorded:
            for b in recorded:
                if recorded[a] > recorded[b] * _R7_NOISE:
                    assert predicted[a] > predicted[b], (
                        f"measured {a}={recorded[a]} > {b}={recorded[b]} "
                        f"but predicted {predicted[a]:.2f} <= "
                        f"{predicted[b]:.2f}"
                    )

    def test_dispatch_bound_shape(self):
        rec = _R7_DISPATCH_BOUND
        # Per-program host dispatch fitted from the sweep itself:
        # (c1 - compiled) / (64 - 1 programs) ~= 1.1 ms/program.
        dispatch_ms = (rec["host_c1"] - rec["compiled"]) / 63.0
        pred = self._predict(rec, S=4, m=8, dispatch_ms=dispatch_ms)
        self._assert_ranking_matches(rec, pred)
        # c1 is exact by construction; the INDEPENDENT c=m point must
        # land near its measured value (the linear-dispatch model).
        assert pred["host_c1"] == pytest.approx(rec["host_c1"], rel=1e-6)
        assert pred["host_cm"] == pytest.approx(rec["host_cm"], rel=0.15)
        # Dispatch amortization must never be predicted as a slowdown.
        assert pred["compiled_k8"] <= pred["compiled"]

    def test_compute_bound_shape(self):
        rec = _R7_COMPUTE_BOUND
        # Same host: the DISPATCH-bound sweep's constant carries over.
        dispatch_ms = (
            _R7_DISPATCH_BOUND["host_c1"] - _R7_DISPATCH_BOUND["compiled"]
        ) / 63.0
        pred = self._predict(rec, S=2, m=8, dispatch_ms=dispatch_ms)
        pred.pop("compiled_k8")  # not recorded at this shape
        self._assert_ranking_matches(rec, pred)
        # Where compute dominates, the predictor must NOT promise the
        # dispatch-bound win: predicted compiled-vs-c1 gain small here,
        # large at the dispatch-bound shape (matching 1.08x vs 2.6x
        # measured).
        gain_compute = pred["host_c1"] / pred["compiled"]
        assert gain_compute < 1.10
        pred_db = self._predict(_R7_DISPATCH_BOUND, S=4, m=8,
                                dispatch_ms=dispatch_ms)
        assert pred_db["host_c1"] / pred_db["compiled"] > 1.5


class TestExecutionSearch:
    """search_execution_config: the full execution-config space, with
    legality REUSED from the runtime so every emitted candidate is
    executor-legal (ISSUE 6 acceptance)."""

    @pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
    def test_every_emitted_candidate_is_runnable(self, caplog):
        """Each config the searcher emits executes without a loud
        fallback — built via make_executor and trained one superstep's
        worth of iterations at ITS steps_per_call."""
        import jax

        from flexflow_tpu.optim import SGDOptimizer
        from flexflow_tpu.runtime.pipeline import (
            PipelineExecutor,
            make_executor,
        )
        from flexflow_tpu.runtime.trainer import Trainer
        from flexflow_tpu.search import search_execution_config

        ff = _mlp()
        res = search_execution_config(
            ff, 4, iters=200, seed=0, ks=(1, 4),
            stage_options=(2,), microbatch_options=(2,),
        )
        assert len(res.candidates) >= 4
        families = set()
        for ecfg in res.candidates:
            families.add((ecfg.stages, ecfg.compiled))
            with caplog.at_level(logging.WARNING):
                caplog.clear()
                ex = make_executor(
                    ff, ecfg.store if ecfg.store.table else None,
                    optimizer=SGDOptimizer(lr=0.01),
                    devices=jax.devices()[:4],
                    microbatches=ecfg.microbatches, chunk=ecfg.chunk,
                    compiled=ecfg.compiled,
                )
                stats = Trainer(ex).fit(
                    iterations=max(ecfg.steps_per_call, 1), warmup=0,
                    steps_per_call=ecfg.steps_per_call,
                )
            fallback = [
                r.message for r in caplog.records
                if "falling back" in r.message or "refus" in r.message
                or "unavailable" in r.message
            ]
            assert not fallback, (ecfg.describe(), fallback)
            # The requested dispatch form was REALIZED, not degraded.
            if ecfg.compiled:
                assert isinstance(ex, PipelineExecutor) and ex.compiled
            elif ecfg.layer_wise:
                assert isinstance(ex, PipelineExecutor) and not ex.compiled
            else:
                assert not isinstance(ex, PipelineExecutor)
            assert np.isfinite(stats["loss"])
        # The reduced space still exercised every family: full-mesh,
        # host-driven pipeline, compiled pipeline.
        assert (1, False) in families and (2, False) in families
        assert (2, True) in families

    def test_search_space_legality_reuse(self):
        """Candidate k-values route through the runtime's OWN
        superstep_mode: amortized strategies under --resilient stay at
        k=1 (the loop refuses k>1 there), compiled candidates appear
        only when compiled_unsupported_reason is None."""
        from flexflow_tpu.runtime.pipeline import (
            compiled_unsupported_reason,
        )
        from flexflow_tpu.search import search_execution_config

        ff = _mlp()
        res = search_execution_config(
            ff, 4, iters=0, seed=0, ks=(1, 4),
            stage_options=(2,), microbatch_options=(2,), resilient=True,
        )
        for c in res.candidates:
            if c.layer_wise and not c.compiled:
                assert c.steps_per_call == 1
            if c.compiled:
                assert compiled_unsupported_reason(ff, c.store) is None

    def test_calibration_steers_the_winner(self):
        """The dispatch term must actually steer: an expensive
        per-program host pushes the winner to the fused
        minimum-dispatch form; a free-dispatch host ranks by compute
        alone and keeps programs-per-step irrelevant."""
        from flexflow_tpu.search import Calibration, search_execution_config

        ff = _mlp()
        costly = search_execution_config(
            ff, 4, iters=0, seed=0, ks=(1, 8),
            stage_options=(2,), microbatch_options=(2,),
            calibration=Calibration(dispatch_ms=16.0, fence_ms=16.0,
                                    calibrated=True),
        )
        assert costly.best.programs_per_step() <= 1 / 8
        free = search_execution_config(
            ff, 4, iters=0, seed=0, ks=(1, 8),
            stage_options=(2,), microbatch_options=(2,),
            calibration=Calibration(dispatch_ms=0.0, fence_ms=0.0,
                                    calibrated=True),
        )
        by_compute = min(free.candidates, key=lambda c: c.compute_ms)
        assert free.best.predicted_ms == pytest.approx(
            by_compute.compute_ms
        )

    def test_compute_scale_fit_from_measured_p50(self):
        """A calibrated step_ms_p50 anchors the compute term: measured
        p50 minus the run's OWN dispatch/fence overhead is what the
        baseline's simulated compute must scale to."""
        from flexflow_tpu.search import Calibration, search_execution_config

        ff = _mlp()
        cal = Calibration(dispatch_ms=1.0, fence_ms=1.0, calibrated=True,
                          step_ms_p50=21.0, programs_per_step=1.0,
                          fences_per_step=0.0, steps=30, complete=True)
        res = search_execution_config(
            ff, 4, iters=0, seed=0, ks=(1,),
            stage_options=(2,), microbatch_options=(2,), calibration=cal,
        )
        # baseline = DP k=1: predicted = scale*compute + 1 dispatch
        # must equal the measured p50 the scale was solved from.
        assert res.baseline.predicted_ms == pytest.approx(21.0, rel=1e-6)
        assert res.compute_scale > 0

    def test_auto_run_calibration_does_not_anchor_scale(self, tmp_path):
        """A calibration log that carries a ``search`` event trained
        under an auto-CHOSEN config: its step p50 measures the winner,
        not the baseline, so the compute-scale fit must be skipped
        (the dispatch/fence constants still apply)."""
        import json

        from flexflow_tpu.search import Calibration, search_execution_config

        path = tmp_path / "run-auto.jsonl"
        events = [
            {"ev": "run_start"},
            {"ev": "search", "chosen": {"label": "won"}},
            {"ev": "run_end", "calibration": {
                "steps": 20, "fences_per_step": 0.0, "step_ms_p50": 5.0,
                "fence_ms": 1.25, "fence_samples": 1,
            }},
        ]
        path.write_text("\n".join(json.dumps(e) for e in events) + "\n")
        cal = Calibration.from_jsonl(str(path))
        assert cal.calibrated and cal.auto_executed
        res = search_execution_config(
            _mlp(), 4, iters=0, seed=0, ks=(1,),
            stage_options=(2,), microbatch_options=(2,), calibration=cal,
        )
        assert res.compute_scale == 1.0
        assert res.calibration.fence_ms == pytest.approx(1.25)

    def test_search_result_is_deterministic(self):
        from flexflow_tpu.search import search_execution_config

        ff = _mlp()
        a = search_execution_config(ff, 4, iters=300, seed=0,
                                    stage_options=(2,),
                                    microbatch_options=(2,))
        b = search_execution_config(ff, 4, iters=300, seed=0,
                                    stage_options=(2,),
                                    microbatch_options=(2,))
        assert a.best.describe() == b.best.describe()
        assert a.best.predicted_ms == pytest.approx(b.best.predicted_ms)

    def test_cli_auto_mode(self, tmp_path, capsys):
        """``python -m flexflow_tpu.search --auto`` prints the ranked
        execution configs + the app flags that run the winner, and
        still writes a loadable strategy file."""
        from flexflow_tpu.search.__main__ import main

        out = tmp_path / "strategy.json"
        assert main([
            "--model", "alexnet", "-b", "8", "--devices", "4",
            "--iters", "200", "--auto", "-o", str(out),
        ]) in (0, None)
        printed = capsys.readouterr().out
        assert "best    =" in printed
        assert "run it: -s" in printed
        assert "uncalibrated" in printed  # no calibration file given
        StrategyStore.load(str(out))

    def test_build_stage_partition_legality(self):
        """The synthetic stage-partition builder returns None (skip)
        rather than an illegal store: stage count vs ops, divisibility
        of the batch across microbatches x intra-stage DP."""
        from flexflow_tpu.search.problem import build_stage_partition

        ff = _mlp(batch=8)
        store = build_stage_partition(ff, 8, 2, microbatches=2)
        assert store is not None and store.layer_wise
        # 4 ops cannot split into 8 stages; 8 devices % 3 stages != 0.
        assert build_stage_partition(ff, 8, 8) is None
        assert build_stage_partition(ff, 8, 3) is None
        # batch 8 / m=4 = 2 rows, intra-stage DP n=4 cannot shard them.
        assert build_stage_partition(ff, 8, 2, microbatches=4) is None


class TestScheduleValidation:
    """ffsim self-check — the reference's VERBOSE schedule-consistency
    mode (``simulator.cc:1012-1031``): every compute/comm occupancy
    recorded and checked for per-resource overlap."""

    def test_valid_schedule_passes(self):
        from flexflow_tpu.native import ffsim_validate

        p = _problem([
            "ffsim 1", "ndevices 2", "devices_per_node 2",
            "bw_intra 10", "bw_inter 1",
            "nops 2",
            "op 0 1 producer",
            "cfg 2 1 1 1 1 5.0 0.0 0 1",
            "op 1 1 consumer",
            "cfg 1 2 1 1 1 7.0 0.0 0 1",
            "nedges 1",
            "edge 0 1 4 2 8 4 0 -1 -1 1",
        ])
        out = ffsim_validate(p, [0, 0])
        assert out["valid"] == 1
        # 2 producer shards + 2 consumer shards + 2 cross-device
        # transfers (each consumer pulls the remote half).
        assert out["ntasks"] == 6
        assert out["time_us"] == pytest.approx(16.2)

    def test_search_result_validates(self):
        res = search_strategy(
            build_alexnet(batch_size=64, image_size=229, num_classes=1000),
            num_devices=4, iters=2000, seed=0,
        )  # search_strategy itself runs ffsim_validate on the winner
        assert res.best_time_us <= res.dp_time_us

    def test_overlap_detected(self):
        from flexflow_tpu.native import ffsim_check_intervals

        ffsim_check_intervals([(0, 0.0, 5.0), (0, 5.0, 9.0), (1, 1.0, 2.0)])
        with pytest.raises(ValueError, match="schedule inconsistent"):
            ffsim_check_intervals([(0, 0.0, 5.0), (0, 4.0, 9.0)])

    def test_bad_bounds_detected(self):
        from flexflow_tpu.native import ffsim_check_intervals

        with pytest.raises(ValueError, match="schedule inconsistent"):
            ffsim_check_intervals([(0, -1.0, 5.0)])
        with pytest.raises(ValueError, match="schedule inconsistent"):
            ffsim_check_intervals([(0, 3.0, 2.0)])
        with pytest.raises(ValueError, match="schedule inconsistent"):
            ffsim_check_intervals([(0, 0.0, float("inf"))])
