"""SLO-aware serving scheduler (SERVING.md "Scheduler policy").

Pinned invariants:

- **Workload determinism**: ``make_workload`` / ``uniform_workload``
  are pure functions of their spec (per-request seeded rngs) — the
  bit-identical-replay precondition; ``uniform_workload`` draws the
  SAME token content as the deprecated ``synthetic_requests`` path.
- **Replay determinism**: two runs of the same workload produce the
  same decision log, virtual-clock stats and tokens (the chaos
  ``serving_overload_shed`` scenario's foundation).
- **Priority-inversion freedom**: under the slo policy no request is
  admitted while a STRICTLY higher tier waits.
- **Preemption is loss-free**: an evicted request resumes via
  re-prefill over (prompt ‖ carried tokens) and its final sequence is
  byte-identical to an unpreempted run; scheduling policy never
  changes WHAT a request generates, only WHEN (cross-policy parity).
- **Sim == real**: simulate mode (the serve-auto cost oracle) matches
  the real engine decision for decision and dispatch for dispatch.
- **serve-auto legality**: every searched config is executor-legal —
  ``ServingConfig`` validation mirrors ``ServingExecutor``'s, and the
  chosen config constructs a real executor (the runnable pattern).

Fast cases run the compute-free simulate mode; the real-engine cases
share one module-scoped tiny LM.
"""

import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.runtime.serving import (
    Request,
    ServingExecutor,
    ServingFaultInjector,
    synthetic_requests,
)
from flexflow_tpu.serving import (
    ScheduledServer,
    SchedulerPolicy,
    ServingConfig,
    ServingLatencyModel,
    ServingResilience,
    SlotShape,
    WorkloadSpec,
    make_workload,
    search_serving_config,
    uniform_workload,
)

V, D, H, L, S = 64, 32, 2, 2, 64

SHAPE = SlotShape(max_batch=2, max_seq=32, buckets=(8, 32))

BURSTY = WorkloadSpec(n_requests=16, vocab=V, prompt_len=(3, 6),
                      max_new=(2, 10), mean_gap_ms=1.0, burst=8,
                      priorities=3, slo_ms=60.0, seed=5)

#: Virtual-clock / accounting stats — everything except wall time.
VIRT = ("requests", "completed", "failed", "tokens", "decode_supersteps",
        "prefills", "request_sheds", "request_preempts",
        "queue_wait_ms_p50", "queue_wait_ms_p95", "queue_wait_ms_p99",
        "e2e_ms_p50", "e2e_ms_p99", "slo_attainment")


def _virt(stats):
    return {k: stats[k] for k in VIRT if k in stats}


def _sim(policy=None, shape=SHAPE, decode_steps=8):
    return ScheduledServer.simulated(
        shape, decode_steps=decode_steps,
        policy=policy or SchedulerPolicy(name="slo"),
    )


@pytest.fixture(scope="module")
def lm():
    return build_transformer_lm(
        batch_size=2, seq_len=S, vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, config=FFConfig(batch_size=2),
    )


@pytest.fixture(scope="module")
def sex(lm):
    return ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8, S),
                           decode_kernel=False)


@pytest.fixture(scope="module")
def weights(sex):
    return sex.init(seed=0)


def _req(rid, plen, max_new, arrival_ms=0.0, priority=0,
         slo_ms=float("inf")):
    return Request(id=rid,
                   prompt=(np.arange(1, plen + 1, dtype=np.int32)
                           * 3 % V),
                   max_new_tokens=max_new, arrival_ms=arrival_ms,
                   priority=priority, slo_ms=slo_ms)


# -- workload -----------------------------------------------------------------


def test_workload_deterministic():
    a, b = make_workload(BURSTY), make_workload(BURSTY)
    assert [r.arrival_ms for r in a] == [r.arrival_ms for r in b]
    assert [r.priority for r in a] == [r.priority for r in b]
    assert [r.slo_ms for r in a] == [r.slo_ms for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]


def test_workload_shape():
    reqs = make_workload(BURSTY)
    assert len(reqs) == BURSTY.n_requests
    lo, hi = BURSTY.prompt_len
    assert all(lo <= len(r.prompt) <= hi for r in reqs)
    assert all(1 <= r.max_new_tokens <= BURSTY.max_new[1] for r in reqs)
    assert all(0 <= r.priority < BURSTY.priorities for r in reqs)
    # Tiered deadlines: tier t gets slo_ms * (t + 1).
    assert all(r.slo_ms == BURSTY.slo_ms * (r.priority + 1)
               for r in reqs)
    arrivals = [r.arrival_ms for r in reqs]
    assert arrivals == sorted(arrivals)
    # Bursts arrive back to back: within each burst group, one gap.
    assert arrivals[0] == arrivals[BURSTY.burst - 1]
    assert arrivals[BURSTY.burst] > arrivals[BURSTY.burst - 1]


def test_workload_validation():
    with pytest.raises(ValueError):
        make_workload(WorkloadSpec(prompt_alpha=1.0))
    with pytest.raises(ValueError):
        make_workload(WorkloadSpec(prompt_len=(6, 3)))
    with pytest.raises(ValueError):
        make_workload(WorkloadSpec(priorities=0))


def test_uniform_workload_matches_retired_synthetic():
    """The migration contract after PR 13's retirement:
    ``synthetic_requests(arrival_every=...)`` now REFUSES (its
    one-release deprecation grace is up), and uniform_workload draws
    the SAME token content with arrivals on the virtual clock."""
    with pytest.raises(ValueError, match="retired"):
        synthetic_requests(4, V, prompt_len=(3, 6), max_new_tokens=6,
                           arrival_every=2, seed=5)
    legacy = synthetic_requests(4, V, prompt_len=(3, 6),
                                max_new_tokens=6, seed=5)
    new = uniform_workload(4, V, prompt_len=(3, 6), max_new_tokens=6,
                           every_ms=7.5, seed=5)
    assert all((a.prompt == b.prompt).all() for a, b in zip(legacy, new))
    assert [r.max_new_tokens for r in legacy] == \
        [r.max_new_tokens for r in new]
    assert [r.arrival_ms for r in new] == [0.0, 7.5, 15.0, 22.5]


# -- replay determinism (sim) -------------------------------------------------


def test_replay_determinism_sim():
    s1, s2 = _sim(), _sim()
    _, st1 = s1.run(make_workload(BURSTY))
    _, st2 = s2.run(make_workload(BURSTY))
    assert s1.decisions == s2.decisions
    assert _virt(st1) == _virt(st2)


def test_shed_determinism_sim():
    pol = SchedulerPolicy(name="slo", shed_depth=4)
    outs = []
    for _ in range(2):
        srv = _sim(pol)
        res, st = srv.run(make_workload(BURSTY))
        outs.append((sorted(r for r in res if res[r].error
                            and res[r].error.startswith("shed")),
                     st["request_sheds"], srv.decisions))
    assert outs[0] == outs[1]
    assert outs[0][1] > 0, "burst never tripped shed_depth"
    assert len(outs[0][0]) == outs[0][1]


def test_priority_inversion_freedom_sim():
    """slo-policy admission order: the admit log never records a
    strictly higher-priority (lower tier number) request left waiting
    at the moment a lower-priority one was admitted."""
    srv = _sim()
    srv.run(make_workload(BURSTY))
    admits = [d for d in srv.decisions if d["d"] == "admit"]
    assert admits
    for a in admits:
        if a["waiting_min_tier"] is not None:
            assert a["tier"] <= a["waiting_min_tier"], (
                f"priority inversion: admitted tier {a['tier']} while "
                f"tier {a['waiting_min_tier']} waited: {a}"
            )


def test_fifo_admits_in_arrival_order_sim():
    srv = _sim(SchedulerPolicy.fifo())
    reqs = make_workload(BURSTY)
    srv.run(reqs)
    admits = [d["id"] for d in srv.decisions if d["d"] == "admit"]
    arrival = {r.id: (r.arrival_ms, r.id) for r in reqs}
    assert admits == sorted(admits, key=lambda i: arrival[i])


def test_adaptive_k_bounds_sim():
    """Chosen k never exceeds decode_steps and the decode accounting
    matches: supersteps equals the number of decode decisions."""
    srv = _sim(decode_steps=8)
    _, st = srv.run(make_workload(BURSTY))
    ks = [d["k"] for d in srv.decisions if d["d"] == "decode"]
    assert ks and all(1 <= k <= 8 for k in ks)
    assert len(ks) == st["decode_supersteps"]
    # Deep queue pushes k down at least once under bursty overload.
    assert min(ks) < 8


# -- preemption (real engine) -------------------------------------------------


def _preempt_pair():
    """A tier-1 hog admitted first + a tight-deadline tier-0 arrival
    that is infeasible by waiting — the eviction trigger."""
    return [_req(0, 4, 40, 0.0, priority=1),
            _req(1, 4, 4, 5.0, priority=0, slo_ms=20.0)]


def test_preempt_byte_parity(lm, weights):
    """Loss-free preemption: the evicted request's final sequence is
    byte-identical to an unpreempted solo run (re-prefill over
    prompt ‖ carried tokens resumes exactly)."""
    params, state = weights
    sex1 = ServingExecutor(lm, max_batch=1, max_seq=S, buckets=(8, S),
                           decode_kernel=False)
    pol = SchedulerPolicy(name="slo")
    srv = ScheduledServer(sex1, params, state, decode_steps=8,
                          policy=pol)
    res, st = srv.run(_preempt_pair())
    assert st["request_preempts"] == 1
    assert res[0].error is None and res[1].error is None
    solo, _ = ScheduledServer(sex1, params, state, decode_steps=8,
                              policy=pol).run([_req(0, 4, 40, 0.0,
                                                    priority=1)])
    assert res[0].tokens == solo[0].tokens
    # The preempt telemetry/log trail exists and names the evictor.
    evicts = [d for d in srv.decisions if d["d"] == "evict"]
    assert len(evicts) == 1 and evicts[0]["id"] == 0
    assert evicts[0]["by"] == 1


def test_preempt_byte_parity_sampled(lm, weights):
    """Sampled preemption is loss-free too: the resume re-prefill
    replays the decode head's (seed, request, pos) draw at the
    regenerated position (the sampled ``build_prefill`` variant), so
    the evicted request's sequence matches an unpreempted solo run."""
    params, state = weights
    sex1 = ServingExecutor(lm, max_batch=1, max_seq=S, buckets=(8, S),
                           decode_kernel=False)
    pol = SchedulerPolicy(name="slo")
    kw = dict(temperature=0.8, top_k=8, sample_seed=3)
    srv = ScheduledServer(sex1, params, state, decode_steps=8,
                          policy=pol, **kw)
    res, st = srv.run(_preempt_pair())
    assert st["request_preempts"] == 1
    assert res[0].error is None and res[1].error is None
    solo, _ = ScheduledServer(sex1, params, state, decode_steps=8,
                              policy=pol, **kw).run(
        [_req(0, 4, 40, 0.0, priority=1)])
    assert res[0].tokens == solo[0].tokens


def test_preempt_infeasible_deadline_not_honored(lm, weights):
    """An already-lost deadline never evicts (the slack < need gate):
    same pair but an SLO the candidate cannot meet even on a free
    slot."""
    params, state = weights
    sex1 = ServingExecutor(lm, max_batch=1, max_seq=S, buckets=(8, S),
                           decode_kernel=False)
    srv = ScheduledServer(sex1, params, state, decode_steps=8,
                          policy=SchedulerPolicy(name="slo"))
    reqs = [_req(0, 4, 40, 0.0, priority=1),
            _req(1, 4, 4, 5.0, priority=0, slo_ms=10.0)]
    _, st = srv.run(reqs)
    assert st["request_preempts"] == 0


def test_cross_policy_output_parity(sex, weights):
    """Scheduling policy changes WHEN, never WHAT: per-request token
    sequences are identical under fifo and slo over the same
    workload."""
    params, state = weights
    reqs = list(make_workload(WorkloadSpec(
        n_requests=6, vocab=V, prompt_len=(3, 6), max_new=(2, 8),
        mean_gap_ms=1.0, burst=3, priorities=2, slo_ms=60.0, seed=9,
    )))
    out = {}
    for pol in (SchedulerPolicy.fifo(), SchedulerPolicy(name="slo")):
        res, _ = ScheduledServer(sex, params, state, decode_steps=4,
                                 policy=pol).run(reqs)
        assert all(r.error is None for r in res.values())
        out[pol.name] = {i: res[i].tokens for i in res}
    assert out["fifo"] == out["slo"]


# -- sim == real --------------------------------------------------------------


def test_sim_matches_real_dispatch_exactly(sex, weights):
    """Simulate mode (the serve-auto pricing oracle) runs the EXACT
    decision code: decision log, prefill count and superstep count all
    equal the real engine's, and the telemetry program counters agree
    with the superstep count."""
    from flexflow_tpu.runtime.telemetry import Telemetry

    params, state = weights
    spec = WorkloadSpec(n_requests=8, vocab=V, prompt_len=(3, 6),
                        max_new=(2, 8), mean_gap_ms=1.0, burst=4,
                        priorities=2, slo_ms=60.0, seed=7)
    pol = SchedulerPolicy(name="slo")
    real = ScheduledServer(sex, params, state, decode_steps=8,
                           policy=pol)
    tel = Telemetry(None)
    with tel:
        _, real_st = real.run(make_workload(spec))
    sim = _sim(pol, SlotShape(max_batch=2, max_seq=S, buckets=(8, S)))
    _, sim_st = sim.run(make_workload(spec))
    assert sim.decisions == real.decisions
    assert sim_st["prefills"] == real_st["prefills"]
    assert sim_st["decode_supersteps"] == real_st["decode_supersteps"]
    assert _virt(sim_st) == _virt(real_st)
    # One host program per superstep in the training-style counters.
    assert tel.counts["host_programs"] == real_st["decode_supersteps"]
    assert tel.counts["program_steps"] == sum(
        d["k"] for d in real.decisions if d["d"] == "decode")


# -- serve-auto ---------------------------------------------------------------


def test_serving_config_legality():
    pol = SchedulerPolicy(name="slo")
    with pytest.raises(ValueError):
        ServingConfig(buckets=(8, 64), decode_steps=8, max_batch=2,
                      max_seq=32, policy=pol)  # bucket > max_seq
    with pytest.raises(ValueError):
        ServingConfig(buckets=(8, 32), decode_steps=0, max_batch=2,
                      max_seq=32, policy=pol)
    with pytest.raises(ValueError):
        ServingConfig(buckets=(8, 32), decode_steps=99, max_batch=2,
                      max_seq=32, policy=pol)  # the fused-step bound


def test_serve_auto_emits_only_legal_configs_and_chosen_runs(lm, weights):
    """Every candidate the search scored is executor-legal (the
    ServingConfig gate) and the chosen one actually constructs a real
    ServingExecutor — the runnable pattern."""
    from flexflow_tpu.runtime.serving import MAX_DECODE_STEPS_PER_CALL

    params, state = weights
    reqs = make_workload(WorkloadSpec(
        n_requests=8, vocab=V, prompt_len=(3, 6), max_new=(2, 8),
        mean_gap_ms=1.0, burst=4, priorities=2, slo_ms=60.0, seed=7,
    ))
    base = ServingConfig(buckets=(8, S), decode_steps=8, max_batch=2,
                         max_seq=S, policy=SchedulerPolicy(name="slo"))
    res = search_serving_config(reqs, base, max_batch_cap=4)
    assert len(res.candidates) > 1
    for c in res.candidates:
        cfg = c.config
        assert cfg.buckets[-1] <= cfg.max_seq
        assert 1 <= cfg.decode_steps <= MAX_DECODE_STEPS_PER_CALL
        assert cfg.max_batch <= 4
        assert c.predicted_dispatches > 0
    assert res.chosen.predicted_p99_ms <= res.baseline.predicted_p99_ms
    # The runnable pattern: the winner builds a real executor + runs.
    win = res.chosen.config
    sexw = ServingExecutor(lm, max_batch=win.max_batch,
                           max_seq=win.max_seq, buckets=win.buckets,
                           decode_kernel=False)
    pw, sw = sexw.init(seed=0)
    out, stats = ScheduledServer(
        sexw, pw, sw, decode_steps=win.decode_steps, policy=win.policy,
    ).run(reqs)
    assert stats["completed"] + stats["failed"] == len(reqs)
    # Predicted dispatches are EXACT for the chosen config.
    assert (stats["prefills"] + stats["decode_supersteps"]
            == res.chosen.predicted_dispatches)


def test_search_deterministic():
    reqs = make_workload(BURSTY)
    base = ServingConfig(buckets=(8, 32), decode_steps=8, max_batch=2,
                         max_seq=32, policy=SchedulerPolicy(name="slo"))
    a = search_serving_config(reqs, base)
    b = search_serving_config(reqs, base)
    assert a.chosen.config.to_json() == b.chosen.config.to_json()
    assert [c.config.to_json() for c in a.candidates] == \
        [c.config.to_json() for c in b.candidates]


# -- latency model ------------------------------------------------------------


def test_latency_model_defaults_and_fit():
    m = ServingLatencyModel.from_calibration()
    assert not m.calibrated
    assert m.prefill_ms(8) == pytest.approx(3.0 + 8 * 0.05)
    assert m.decode_ms(8) == pytest.approx(3.0 + 8 * 0.2)
    fitted = m.fit_events([
        {"ev": "prefill", "bucket": 8, "wall_s": 0.0038},
        {"ev": "prefill", "bucket": 8, "wall_s": 0.0042},
        {"ev": "prefill", "bucket": 8, "wall_s": 0.0046},
        {"ev": "decode_superstep", "k": 8, "wall_s": 0.0110},
    ], source="test")
    assert fitted.prefill_token_ms == pytest.approx(
        ((0.0042 * 1e3) - 3.0) / 8)
    assert fitted.decode_token_ms == pytest.approx((11.0 - 3.0) / 8)
    assert fitted.source == "test"
    # Sub-constant walls floor at 0, never negative.
    floored = m.fit_events(
        [{"ev": "decode_superstep", "k": 8, "wall_s": 0.0001}],
        source="t")
    assert floored.decode_token_ms == 0.0


# -- telemetry / obs round trip ----------------------------------------------


def test_scheduler_events_reconstruct(tmp_path, sex, weights):
    """request_shed/request_preempt/sched_decision land in the JSONL;
    the obs reader's reconstruction reproduces the folded summary's
    scheduler rows bit-identically."""
    from flexflow_tpu.obs.reader import RunLog
    from flexflow_tpu.runtime.telemetry import Telemetry

    params, state = weights
    pol = SchedulerPolicy(name="slo", shed_depth=3)
    tel = Telemetry(str(tmp_path))
    path = tel.path
    with tel:
        _, stats = ScheduledServer(
            sex, params, state, decode_steps=8, policy=pol,
        ).run(make_workload(BURSTY))
    run = RunLog.load(path)
    assert not run.unknown_events
    assert len(run.select("sched_decision")) == stats["decode_supersteps"]
    assert len(run.select("request_shed")) == stats["request_sheds"] > 0
    rec = run.reconstruct_summary()
    summ = run.summary()
    for k in ("queue_wait_ms_p50", "queue_wait_ms_p95",
              "queue_wait_ms_p99", "request_sheds", "request_preempts",
              "slo_attainment"):
        assert rec.get(k) == summ.get(k) == stats[k], k


# -- CLI ----------------------------------------------------------------------


@pytest.mark.slow  # end-to-end CLI cases (~40s): full app wiring
def test_serve_cli_scheduled(capsys):
    from flexflow_tpu.apps import serve

    rc = serve.main([
        "--max-seq", "32", "--max-batch", "2", "--decode-steps", "4",
        "--requests", "6", "--max-new", "6", "--vocab", "64",
        "--d-model", "16", "--heads", "2", "--layers", "1",
        "--prompt-len", "3:6", "--workload-trace", "--slo-ms", "50",
        "--priorities", "2",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "policy = slo" in out
    assert "queue wait p50" in out and "(virtual)" in out
    assert "SLO attainment" in out


@pytest.mark.slow  # end-to-end CLI: search-then-run + exact epilogue
def test_serve_cli_serve_auto(capsys):
    from flexflow_tpu.apps import serve

    rc = serve.main([
        "--max-seq", "32", "--max-batch", "2", "--decode-steps", "4",
        "--requests", "6", "--max-new", "6", "--vocab", "64",
        "--d-model", "16", "--heads", "2", "--layers", "1",
        "--prompt-len", "3:6", "--serve-auto", "--slo-ms", "50",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "serve-auto: chose" in out
    assert "predicted e2e p99" in out
    # The predicted-vs-measured epilogue: dispatch counts are EXACT.
    epi = [l for l in out.splitlines()
           if l.startswith("serve-auto: predicted e2e")]
    assert len(epi) == 1
    pred = int(epi[0].split("predicted dispatches ")[1].split(",")[0])
    execd = int(epi[0].split("executed ")[1])
    assert pred == execd


def test_serve_cli_arrival_every_retired():
    """The retired alias refuses LOUDLY (SystemExit with the
    migration pointer), before any model or device work."""
    from flexflow_tpu.apps import serve

    with pytest.raises(SystemExit, match="retired"):
        serve.main([
            "--max-seq", "32", "--max-batch", "2", "--decode-steps",
            "4", "--requests", "4", "--max-new", "6", "--vocab", "64",
            "--d-model", "16", "--heads", "2", "--layers", "1",
            "--prompt-len", "3:6", "--arrival-every", "2",
        ])


@pytest.mark.slow  # end-to-end CLI: scheduler dry run audits all ks
def test_serve_cli_sched_dry_run(capsys):
    from flexflow_tpu.apps import serve

    rc = serve.main([
        "--max-seq", "32", "--max-batch", "2", "--decode-steps", "8",
        "--requests", "4", "--vocab", "64", "--d-model", "16",
        "--heads", "2", "--layers", "1", "--prompt-len", "3:6",
        "--sched", "slo", "--dry-run",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "DRY RUN OK" in out
    assert "audit: clean" in out
    # Every adaptive-k candidate width is shape-checked + audited.
    for k in (1, 2, 4, 8):
        assert f"decode k={k}" in out


# -- paged capacity on the scheduled path (SERVING.md "Cache layout") ---------


def test_slot_shape_paged_validation():
    """SlotShape mirrors the executor's paged validation, so a config
    that simulates is a config the executor accepts."""
    with pytest.raises(ValueError, match="divide"):
        SlotShape(max_batch=2, max_seq=32, buckets=(8, 32), kv_block=5)
    with pytest.raises(ValueError, match="kv_block"):
        SlotShape(max_batch=2, max_seq=32, buckets=(8, 32), kv_blocks=4)
    shp = SlotShape(max_batch=2, max_seq=32, buckets=(8, 32), kv_block=8)
    assert shp.paged and shp.kv_blocks == 2 * 4 + 1  # worst case
    led = shp.make_ledger()
    assert led.capacity_blocks == shp.kv_blocks - 1


def test_sim_matches_real_dispatch_paged(lm, weights):
    """The sim==real contract EXTENDS to the paged layout: ledger
    gating is pure host arithmetic shared by both engines, so a
    block-starved pool produces the same kv_wait decisions, prefill
    count and superstep count in simulation as on the device."""
    from flexflow_tpu.runtime.telemetry import Telemetry

    params, state = weights
    # kv_block=16 over max_seq=64, pool of 4 allocatable blocks:
    # two long requests (3 blocks each) cannot share the pool.
    sex_paged = ServingExecutor(lm, max_batch=2, max_seq=S,
                                buckets=(8, S), decode_kernel=False,
                                kv_block=16, kv_blocks=5)
    reqs = lambda: [_req(0, 4, 40, 0.0), _req(1, 5, 40, 0.0),
                    _req(2, 3, 6, 1.0), _req(3, 6, 30, 2.0)]
    pol = SchedulerPolicy(name="slo")
    real = ScheduledServer(sex_paged, params, state, decode_steps=8,
                           policy=pol)
    with Telemetry(None):
        _, real_st = real.run(reqs())
    sim = _sim(pol, SlotShape(max_batch=2, max_seq=S, buckets=(8, S),
                              kv_block=16, kv_blocks=5))
    _, sim_st = sim.run(reqs())
    assert sim.decisions == real.decisions
    assert any(d["d"] == "kv_wait" for d in real.decisions)
    assert sim_st["prefills"] == real_st["prefills"]
    assert sim_st["decode_supersteps"] == real_st["decode_supersteps"]
    assert real_st["kv_layout"] == "paged"
    assert sim_st["kv_layout"] == "paged"
    assert _virt(sim_st) == _virt(real_st)


def test_sched_paged_output_parity(sex, weights):
    """Cache layout changes CAPACITY, never content: per-request
    greedy sequences on a block-starved paged scheduler equal the
    padded scheduler's."""
    params, state = weights
    sex_paged = ServingExecutor(sex.model, max_batch=2, max_seq=S,
                                buckets=(8, S), decode_kernel=False,
                                kv_block=16, kv_blocks=5)
    reqs = lambda: [_req(0, 4, 20, 0.0), _req(1, 5, 20, 0.0),
                    _req(2, 3, 20, 1.0)]
    pol = SchedulerPolicy(name="slo")
    base, _ = ScheduledServer(sex, params, state, decode_steps=4,
                              policy=pol).run(reqs())
    paged, _ = ScheduledServer(sex_paged, params, state, decode_steps=4,
                               policy=pol).run(reqs())
    for rid in (0, 1, 2):
        assert paged[rid].error is None
        assert paged[rid].tokens == base[rid].tokens


def test_sim_matches_real_dispatch_prefix(lm, weights):
    """sim==real EXTENDS to the prefix cache: the ledger (refcounts +
    content-hash index) is shared verbatim by both engines, a full hit
    skips the prefill dispatch in BOTH loops, and the kv_wait gate
    admits against need - shared blocks."""
    from flexflow_tpu.runtime.telemetry import Telemetry

    params, state = weights
    # kv_block=8 over max_seq=64, pool of 8 allocatable blocks.  _req
    # prompts share content positionally, so every plen>=8 request
    # shares its first block.  The index only lives while a holder is
    # resident (refcount > 0), so the chain is arranged to overlap:
    # r0+r1 co-admit (r1 a FULL hit — memoised next token, zero
    # dispatch), r2 partial-hits r1's still-resident block (offset
    # prefill), and r3 (7 blocks) must kv_wait behind r2's pool share.
    sex_pfx = ServingExecutor(lm, max_batch=2, max_seq=S,
                              buckets=(8, S), decode_kernel=False,
                              kv_block=8, kv_blocks=9,
                              prefix_cache=True)
    reqs = lambda: [_req(0, 8, 4, 0.0), _req(1, 8, 8, 0.0),
                    _req(2, 12, 20, 1.0), _req(3, 8, 40, 2.0)]
    pol = SchedulerPolicy(name="slo")
    real = ScheduledServer(sex_pfx, params, state, decode_steps=4,
                           policy=pol)
    with Telemetry(None):
        _, real_st = real.run(reqs())
    sim = _sim(pol, SlotShape(max_batch=2, max_seq=S, buckets=(8, S),
                              kv_block=8, kv_blocks=9,
                              prefix_cache=True), decode_steps=4)
    _, sim_st = sim.run(reqs())
    assert sim.decisions == real.decisions
    assert any(d["d"] == "kv_wait" for d in real.decisions)
    assert real_st["prefix_cache"] and sim_st["prefix_cache"]
    assert real_st["prefix_hits"] == sim_st["prefix_hits"] >= 2
    assert real_st["prefill_tokens_saved"] == \
        sim_st["prefill_tokens_saved"] > 0
    assert sim_st["prefills"] == real_st["prefills"]
    assert sim_st["decode_supersteps"] == real_st["decode_supersteps"]
    assert _virt(sim_st) == _virt(real_st)


def test_sched_prefix_output_parity(sex, weights):
    """Prefix sharing changes DISPATCH COUNT, never content: greedy
    sequences through hits (full and partial) equal the padded
    scheduler's, byte for byte."""
    params, state = weights
    sex_pfx = ServingExecutor(sex.model, max_batch=2, max_seq=S,
                              buckets=(8, S), decode_kernel=False,
                              kv_block=8, kv_blocks=17,
                              prefix_cache=True)
    reqs = lambda: [_req(0, 8, 10, 0.0), _req(1, 8, 10, 1.0),
                    _req(2, 12, 10, 2.0)]
    pol = SchedulerPolicy(name="slo")
    base, _ = ScheduledServer(sex, params, state, decode_steps=4,
                              policy=pol).run(reqs())
    pfx, st = ScheduledServer(sex_pfx, params, state, decode_steps=4,
                              policy=pol).run(reqs())
    assert st["prefix_hits"] >= 1
    for rid in (0, 1, 2):
        assert pfx[rid].error is None
        assert pfx[rid].tokens == base[rid].tokens


def test_serve_auto_kv_layout_candidates():
    """A paged baseline searches block-size variants at fixed pool
    HBM; every candidate is executor-legal; a padded baseline stays
    padded."""
    from flexflow_tpu.serving.search import candidate_kv_layouts

    pol = SchedulerPolicy(name="slo")
    padded = ServingConfig(buckets=(8, 32), decode_steps=8, max_batch=2,
                           max_seq=32, policy=pol)
    assert candidate_kv_layouts(padded) == [(0, None, False)]
    paged = ServingConfig(buckets=(8, 32), decode_steps=8, max_batch=2,
                          max_seq=32, policy=pol, kv_block=8,
                          kv_blocks=9)
    variants = candidate_kv_layouts(paged)
    assert (8, 9, False) in variants and len(variants) >= 4
    # Every paged layout is offered with the prefix cache off AND on.
    assert (8, 9, True) in variants
    assert {p for _, _, p in variants} == {False, True}
    # Pool-token capacity is preserved across block-size variants.
    for blk, n, _pfx in variants:
        assert (n - 1) * blk == 64
    reqs = make_workload(WorkloadSpec(
        n_requests=6, vocab=V, prompt_len=(3, 6), max_new=(2, 8),
        mean_gap_ms=1.0, seed=3,
    ))
    res = search_serving_config(
        reqs, paged, model=ServingLatencyModel.from_calibration())
    assert any(s.config.kv_block not in (0, 8) for s in res.candidates)
    assert res.chosen.config.kv_block > 0  # paged stays paged


# -- production-trace workload (shared data-plane source) ---------------------


def test_production_workload_live_source():
    """The prod: workload reads prompt TOKENS from the LIVE
    data/trace.py ProductionTraceSource (shared source), keeps
    make_workload's length/budget/arrival draws, and is deterministic."""
    from flexflow_tpu.data.trace import ProductionTraceSource
    from flexflow_tpu.serving import production_workload

    spec = WorkloadSpec(n_requests=8, vocab=V, prompt_len=(3, 8),
                        max_new=(2, 8), mean_gap_ms=2.0, burst=2,
                        priorities=2, slo_ms=50.0, seed=11)
    a = production_workload(spec, id_alpha=1.3)
    b = production_workload(spec, id_alpha=1.3)
    zipfy = make_workload(spec)
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    # Same non-content draws as the zipf generator...
    assert [r.arrival_ms for r in a] == [r.arrival_ms for r in zipfy]
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in zipfy]
    assert [r.max_new_tokens for r in a] == \
        [r.max_new_tokens for r in zipfy]
    assert [r.priority for r in a] == [r.priority for r in zipfy]
    # ...but token CONTENT comes from the trace source itself.
    hi = spec.prompt_len[1]
    src = ProductionTraceSource(num_samples=spec.n_requests * hi,
                                dense_dim=1, vocab_sizes=[V],
                                alpha=1.3, seed=spec.seed,
                                block=max(hi, 64))
    for r in a:
        expect = src.read(r.id * hi,
                          r.id * hi + len(r.prompt))["sparse_input"][:, 0]
        assert (r.prompt == expect.astype(np.int32)).all()
        assert r.prompt.max() < V


# -- speculative decoding on the scheduled path (SERVING.md) ------------------


def test_sim_matches_real_dispatch_spec(tmp_path, sex, weights):
    """The sim==real contract EXTENDS to spec mode: the simulated
    engine fabricates FULL acceptance, and a full self-draft (the
    degenerate case) accepts everything, so with draft == serving
    params the decision log, prefill/draft-prefill and superstep
    counts all agree — and exactly one ``spec_verify`` event lands
    per superstep, reconstructing the folded spec stats
    bit-identically."""
    from flexflow_tpu.obs.reader import RunLog
    from flexflow_tpu.runtime.telemetry import Telemetry

    params, state = weights
    spec = WorkloadSpec(n_requests=8, vocab=V, prompt_len=(3, 6),
                        max_new=(2, 8), mean_gap_ms=1.0, burst=4,
                        priorities=2, slo_ms=60.0, seed=7)
    pol = SchedulerPolicy(name="slo")
    real = ScheduledServer(sex, params, state, decode_steps=8,
                           policy=pol, speculate=3)
    tel = Telemetry(str(tmp_path))
    path = tel.path
    with tel:
        _, real_st = real.run(make_workload(spec))
    assert real_st["speculate"] == 3
    assert real_st["spec_acceptance_rate"] == 1.0
    assert real_st["draft_prefills"] == real_st["prefills"]
    sim = ScheduledServer.simulated(
        SlotShape(max_batch=2, max_seq=S, buckets=(8, S)),
        decode_steps=8, policy=pol, speculate=3)
    _, sim_st = sim.run(make_workload(spec))
    assert sim.decisions == real.decisions
    assert sim_st["prefills"] == real_st["prefills"]
    assert sim_st["draft_prefills"] == real_st["draft_prefills"]
    assert sim_st["decode_supersteps"] == real_st["decode_supersteps"]
    assert sim_st["spec_acceptance_rate"] == \
        real_st["spec_acceptance_rate"]
    assert sim_st["spec_tokens_per_dispatch"] == \
        real_st["spec_tokens_per_dispatch"]
    assert _virt(sim_st) == _virt(real_st)
    run = RunLog.load(path)
    assert not run.unknown_events
    assert len(run.select("spec_verify")) == real_st["decode_supersteps"]
    rec = run.reconstruct_summary()
    summ = run.summary()
    for k in ("spec_acceptance_rate", "spec_tokens_per_dispatch"):
        assert rec.get(k) == summ.get(k) == real_st[k], k


@pytest.mark.slow  # extra draft-model program set under the scheduler
def test_sched_spec_output_parity_rejecting_draft(sex, weights):
    """Speculation changes dispatch count, never content — even when
    the draft REJECTS: an unrelated-weights draft under the scheduler
    produces byte-identical per-request sequences to plain decode.
    (Sim==real is NOT asserted here: the simulated draft accepts
    fully, so exactness requires a fully-accepting draft — the
    documented contract.)"""
    params, state = weights
    bad_draft, _ = sex.init(seed=99)

    def reqs():
        return [_req(0, 4, 10, 0.0), _req(1, 5, 8, 1.0),
                _req(2, 3, 6, 2.0)]

    pol = SchedulerPolicy(name="slo")
    base, _ = ScheduledServer(sex, params, state, decode_steps=4,
                              policy=pol).run(reqs())
    spec_res, spec_st = ScheduledServer(
        sex, params, state, decode_steps=4, policy=pol,
        speculate=4, draft_params=bad_draft,
    ).run(reqs())
    assert spec_st["spec_acceptance_rate"] < 1.0
    for rid in (0, 1, 2):
        assert spec_res[rid].error is None
        assert spec_res[rid].tokens == base[rid].tokens


def test_serve_auto_speculate_knob():
    """Draft depth d joins the serve-auto knobs ONLY when the baseline
    speculates (the draft source is a deployment fact); candidates are
    {0, d/2, d, 2d} clamped, spec candidates pin k (adaptive-k is
    bypassed in spec mode), and the search stays deterministic."""
    from flexflow_tpu.runtime.serving import MAX_DECODE_STEPS_PER_CALL

    pol = SchedulerPolicy(name="slo")
    with pytest.raises(ValueError, match="speculate"):
        ServingConfig(buckets=(8, 32), decode_steps=8, max_batch=2,
                      max_seq=32, policy=pol,
                      speculate=MAX_DECODE_STEPS_PER_CALL + 1)
    reqs = make_workload(BURSTY)
    plain = ServingConfig(buckets=(8, 32), decode_steps=8, max_batch=2,
                          max_seq=32, policy=pol)
    assert all(c.config.speculate == 0
               for c in search_serving_config(reqs, plain).candidates)
    base = ServingConfig(buckets=(8, 32), decode_steps=8, max_batch=2,
                         max_seq=32, policy=pol, speculate=4)
    res = search_serving_config(reqs, base)
    depths = {c.config.speculate for c in res.candidates}
    assert {0, 2, 4, 8} <= depths
    for c in res.candidates:
        assert c.config.to_json()["speculate"] == c.config.speculate
        if c.config.speculate:
            assert c.config.decode_steps == base.decode_steps
            assert c.config.policy.adaptive_k == pol.adaptive_k
    assert res.chosen.predicted_p99_ms <= res.baseline.predicted_p99_ms
    res2 = search_serving_config(reqs, base)
    assert [c.config.to_json() for c in res.candidates] == \
        [c.config.to_json() for c in res2.candidates]


# -- failure model (SERVING.md "Failure model") -------------------------------


def test_resilience_validation():
    with pytest.raises(ValueError):
        ServingResilience(max_retries=-1)
    with pytest.raises(ValueError):
        ServingResilience(max_restarts=-1)
    with pytest.raises(ValueError):
        ServingResilience(retry_backoff_ms=0.0)
    with pytest.raises(ValueError):
        ServingResilience(kernel_fault_rung=-1)


def test_retry_backoff_deterministic_sim():
    """Slot-isolated faults spend the per-request retry budget with
    DETERMINISTIC virtual-clock exponential backoff (8, 16, ... ms):
    the retry decisions are part of the replayable decision log, and
    the request still completes once the fault clears."""
    def run():
        srv = ScheduledServer.simulated(
            SHAPE, decode_steps=4, policy=SchedulerPolicy(name="slo"),
            resilience=ServingResilience(max_retries=2),
            fault_injector=ServingFaultInjector(
                nan_cache_at={0: 0, 1: 0}),
        )
        results, stats = srv.run([_req(0, 4, 6)])
        return srv, results, stats

    a, res_a, st_a = run()
    b, res_b, st_b = run()
    assert st_a["request_retries"] == 2
    assert res_a[0].error is None and len(res_a[0].tokens) == 6
    backoffs = [d["backoff"] for d in a.decisions if d["d"] == "retry"]
    assert backoffs == [8.0, 16.0]
    assert a.decisions == b.decisions
    assert _virt(st_a) == _virt(st_b)


def test_retry_budget_exhaustion_fails_request_sim():
    """A fault past the retry budget errors the request out — the
    legacy fail-fast behavior is the budget-0 fixed point."""
    srv = ScheduledServer.simulated(
        SHAPE, decode_steps=4, policy=SchedulerPolicy(name="slo"),
        resilience=ServingResilience(max_retries=1),
        fault_injector=ServingFaultInjector(
            nan_cache_at={0: 0, 1: 0}),
    )
    results, stats = srv.run([_req(0, 4, 6)])
    assert stats["request_retries"] == 1
    assert results[0].error is not None
    assert stats["failed"] == 1


def test_expiry_counts_as_miss_sim():
    """``expire_waiting``: a finite-SLO request still queued past its
    deadline is refused — and counted as an SLO miss (attainment stays
    goodput; expiry can't game the bar)."""
    reqs = [_req(0, 4, 12, priority=0),
            _req(1, 4, 12, priority=0),
            _req(2, 4, 4, priority=1, slo_ms=1.0)]
    srv = ScheduledServer.simulated(
        SHAPE, decode_steps=4, policy=SchedulerPolicy(name="slo"),
        resilience=ServingResilience(expire_waiting=True),
    )
    results, stats = srv.run(reqs)
    assert results[2].error is not None
    assert results[2].error.startswith("expired")
    assert stats["request_expiries"] == 1
    assert stats["completed"] == 2 and stats["failed"] == 1
    # r2 is the only finite-SLO request and it missed.
    assert stats["slo_attainment"] == 0.0


def test_sim_matches_real_through_retry_and_restart(sex, weights):
    """The serve-auto exactness contract survives the failure model:
    with the SAME fault plan (one slot-NaN retry + one engine-class
    crash/restart), simulate mode matches the real engine decision for
    decision and dispatch for dispatch."""
    params, state = weights
    spec = WorkloadSpec(n_requests=8, vocab=V, prompt_len=(3, 6),
                        max_new=(2, 8), mean_gap_ms=1.0, burst=4,
                        priorities=2, slo_ms=60.0, seed=7)
    pol = SchedulerPolicy(name="slo")
    res = ServingResilience(max_retries=1, max_restarts=1)

    def injector():
        return ServingFaultInjector(nan_cache_at={1: 0},
                                    engine_raise_at={3: "boom"})

    real = ScheduledServer(sex, params, state, decode_steps=8,
                           policy=pol, resilience=res,
                           fault_injector=injector())
    _, real_st = real.run(make_workload(spec))
    sim = ScheduledServer.simulated(
        SlotShape(max_batch=2, max_seq=S, buckets=(8, S)),
        decode_steps=8, policy=pol, resilience=res,
        fault_injector=injector())
    _, sim_st = sim.run(make_workload(spec))
    assert real_st["request_retries"] == 1
    assert real_st["engine_restarts"] == 1
    assert sim.decisions == real.decisions
    assert sim_st["prefills"] == real_st["prefills"]
    assert sim_st["decode_supersteps"] == real_st["decode_supersteps"]
    assert sim_st["request_retries"] == real_st["request_retries"]
    assert sim_st["engine_restarts"] == real_st["engine_restarts"]
    assert _virt(sim_st) == _virt(real_st)


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_degraded_decode_oracle_rung():
    """Degraded-mode ladder rung 1: after ``kernel_fault_rung``
    decode-phase engine faults the flash_decode kernel is disabled and
    serving falls back to the ``_einsum_decode`` oracle — loudly,
    recorded in ``degraded_rungs`` — with tokens byte-identical to an
    unfaulted run (the kernel-vs-oracle numerics pin).  128 positions:
    the smallest cache the kernel's gate takes."""
    S = 128
    lm = build_transformer_lm(
        batch_size=2, seq_len=S, vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, config=FFConfig(batch_size=2),
    )

    def reqs():
        return [_req(0, 4, 6), _req(1, 5, 6)]

    base_ex = ServingExecutor(lm, max_batch=2, max_seq=S,
                              buckets=(8, S), decode_kernel=True)
    params, state = base_ex.init(seed=0)
    base = ScheduledServer(base_ex, params, state, decode_steps=4,
                           policy=SchedulerPolicy(name="slo"))
    base_res, _ = base.run(reqs())

    ex = ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8, S),
                         decode_kernel=True)
    srv = ScheduledServer(
        ex, params, state, decode_steps=4,
        policy=SchedulerPolicy(name="slo"),
        resilience=ServingResilience(max_restarts=3,
                                     kernel_fault_rung=2),
        fault_injector=ServingFaultInjector(
            engine_raise_at={0: "kernel fault 1", 1: "kernel fault 2"}),
    )
    results, stats = srv.run(reqs())
    assert stats["engine_restarts"] == 2
    assert stats["degraded_rungs"] == ["decode_oracle"]
    assert ex.decode_kernel is False
    for rid in (0, 1):
        assert results[rid].error is None
        assert results[rid].tokens == base_res[rid].tokens


def test_degraded_shrink_batch_rung(lm, weights, monkeypatch):
    """Degraded-mode capacity rung (padded layout): a KV cache over
    ``FF_DEVICE_MEM_BYTES`` shrinks ``max_batch`` stepwise — loudly,
    recorded — and refuses only at the one-slot floor."""
    from flexflow_tpu.data.loader import DeviceMemoryError

    params, state = weights
    # 512 B/token at (D=32, H=2, L=2); a max_seq=64 slot = 32768 B.
    # 4 slots = 131072 B > 70000 > 2 slots = 65536 B: exactly one rung.
    monkeypatch.setenv("FF_DEVICE_MEM_BYTES", "70000")
    ex = ServingExecutor(lm, max_batch=4, max_seq=S, buckets=(8,),
                         decode_kernel=False)
    srv = ScheduledServer(ex, params, state, decode_steps=4,
                          policy=SchedulerPolicy(name="slo"))
    assert ex.max_batch == 2
    assert srv.degraded_rungs == [
        {"rung": "shrink_batch", "max_batch": 2, "prev": 4}]
    results, stats = srv.run([_req(i, 4, 4) for i in range(3)])
    assert stats["degraded_rungs"] == ["shrink_batch"]
    assert all(results[i].error is None for i in range(3))

    # Below the one-slot floor the refusal stays loud.
    monkeypatch.setenv("FF_DEVICE_MEM_BYTES", "20000")
    ex1 = ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8,),
                          decode_kernel=False)
    with pytest.raises(DeviceMemoryError):
        ScheduledServer(ex1, params, state, decode_steps=4,
                        policy=SchedulerPolicy(name="slo"))


# -- fleet redistribution parity (SERVING.md "Fleet") -------------------------


@pytest.mark.parametrize("variant", [
    "greedy",
    pytest.param("sampled", marks=pytest.mark.slow),
    pytest.param("paged", marks=pytest.mark.slow),
])
def test_fleet_redistribution_parity(lm, weights, variant):
    """A request STARTED on replica A and FINISHED on replica B (after
    A's engine fault exhausts its restart budget and the router
    transplants A's journaled prefix into B's journal) generates a
    byte-identical sequence to a single-replica run — greedy because
    decode logits match the full-seq forward, sampled because draws
    are keyed (seed, id, position), paged because cache layout changes
    capacity, never content."""
    from flexflow_tpu.serving import FleetRouter, MemoryJournal

    params, state = weights
    kw = {}
    if variant == "sampled":
        kw = dict(temperature=0.8, top_k=8, sample_seed=3)

    def make_ex():
        paged = dict(kv_block=8) if variant == "paged" else {}
        return ServingExecutor(lm, max_batch=2, max_seq=S,
                               buckets=(8, S), decode_kernel=False,
                               **paged)

    def reqs():
        return [_req(i, 4 + i % 3, 10) for i in range(4)]

    sex_a, sex_b = make_ex(), make_ex()
    # The survivor shares its executor with the baseline run — shared
    # compiled programs, and parity must hold through that reuse too.
    base, _ = ScheduledServer(sex_b, params, state, decode_steps=4,
                              **kw).run(reqs())
    assert all(r.error is None for r in base.values())
    inj = ServingFaultInjector(engine_raise_at={1: "replica A down"})
    rep_a = ScheduledServer(
        sex_a, params, state, decode_steps=4,
        resilience=ServingResilience(max_restarts=0),
        journal=MemoryJournal(), fault_injector=inj, **kw)
    rep_b = ScheduledServer(
        sex_b, params, state, decode_steps=4,
        resilience=ServingResilience(max_restarts=0),
        journal=MemoryJournal(), **kw)
    fleet = FleetRouter([rep_a, rep_b])
    results, stats = fleet.run(reqs())
    assert stats["dead_replicas"] == 1 and fleet.dead == [0]
    moved = [d for d in fleet.decisions if d["d"] == "redistribute"]
    assert moved and any(d["carried"] for d in moved)
    assert stats["redistributed"] == len(moved)
    assert all(r.error is None for r in results.values())
    # Byte parity regardless of which replica finished each request.
    assert ({i: results[i].tokens for i in results}
            == {i: base[i].tokens for i in base})
    if variant == "paged":
        assert stats["kv_layout"] == "paged"
    if variant == "sampled":
        assert stats["sampled"]
