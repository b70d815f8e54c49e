"""Per-request span timelines + tail autopsy (OBSERVABILITY.md
"Reading a request", ``flexflow_tpu/obs/spans.py``).

Pinned invariants:

- **Exact reconciliation**: every request's phase totals telescope to
  EXACTLY ``us(e2e_ms)`` — integer-microsecond equality, no tolerance.
  The scheduler's stamps and its ``e2e_ms`` come from the same rounded
  virtual-clock values, so any gap is an instrumentation bug.  Holds
  through kv_wait, preemption, retry backoff and replica-loss
  transplant.
- **Stats == log**: the scheduler's in-memory ``span_events`` fold and
  the telemetry-JSONL fold produce bit-identical timelines and the
  same ``slo_autopsy`` block (the ``sev`` dual-write) — and
  ``RunLog.reconstruct_summary`` rebuilds that block from the log
  alone.
- **Fleet merge**: a replica-loss run yields a complete timeline for
  EVERY request — transplanted ones archive the donor segment and
  still reconcile; a 1-replica fleet's merged stream equals the
  single-server fold; a torn tail in one stream of a multi-stream
  load never poisons the merged timeline.
- **The measured loop** (``runtime/serving.py::Server.run``, the loop a
  benchmark cell times): its events carry the run's own real clock
  (``t_ms``; both edges of a prefill and of a decode round) and fold
  through the SAME ``build_timelines``, reconciling exactly like the
  scheduler's — padded, paged and speculative, with a rejected request
  and a non-finite finish, over a stream that holds two runs of the
  loop with colliding ids; a stream without stamps is skipped.
- **Latency-model prefix pricing** (satellite): ``expected_prefill_ms``
  defaults to ``prefill_ms`` exactly; fitting from ``prefix_hit``
  events discounts it; serve-auto still ranks prefix-cache-on first
  on the shared-prefix workload.

The scheduler's cases run the compute-free simulated loop (no jax
programs); its real-engine reconciliation lives in
``test_serving_sched.py``'s telemetered run.  The measured loop's
cases run a tiny model on the CPU.
"""

import numpy as np
import pytest

from flexflow_tpu.obs import spans
from flexflow_tpu.obs.reader import RunLog
from flexflow_tpu.runtime.serving import (
    Request,
    Server,
    ServingExecutor,
    ServingFaultInjector,
)
from flexflow_tpu.runtime.telemetry import Telemetry
from flexflow_tpu.serving import (
    FleetRouter,
    ScheduledServer,
    SchedulerPolicy,
    ServingLatencyModel,
    ServingResilience,
    SlotShape,
    WorkloadSpec,
    make_workload,
    search_serving_config,
)
from flexflow_tpu.serving.search import ServingConfig

V, S = 64, 32

SHAPE = SlotShape(max_batch=2, max_seq=S, buckets=(8, S))

#: Bursty overload with tight tier-0 deadlines — guarantees misses, so
#: the autopsy block is non-empty.
BURSTY = WorkloadSpec(n_requests=16, vocab=V, prompt_len=(3, 6),
                      max_new=(2, 10), mean_gap_ms=1.0, burst=8,
                      priorities=3, slo_ms=20.0, seed=5)

FLEET_BURSTY = WorkloadSpec(n_requests=12, vocab=V, prompt_len=(3, 6),
                            max_new=(2, 10), mean_gap_ms=1.0, burst=6,
                            priorities=3, slo_ms=60.0, seed=5)


def _req(rid, plen, max_new, arrival_ms=0.0, priority=0,
         slo_ms=float("inf")):
    return Request(id=rid,
                   prompt=(np.arange(1, plen + 1, dtype=np.int32)
                           * 3 % V),
                   max_new_tokens=max_new, arrival_ms=arrival_ms,
                   priority=priority, slo_ms=slo_ms)


def _sim(shape=SHAPE, decode_steps=4, **kw):
    return ScheduledServer.simulated(
        shape, decode_steps=decode_steps,
        policy=SchedulerPolicy(name="slo"), **kw)


def _assert_all_reconciled(tls):
    bad = [i for i in sorted(tls) if not tls[i].reconciled]
    assert not bad, {
        i: (tls[i].phase_ms, tls[i].total_us, spans.us(tls[i].e2e_ms))
        for i in bad
    }


# -- the microsecond currency -------------------------------------------------


def test_us_lossless_on_rounded_stamps():
    for x in (0.0, 0.001, 8.25, 41.667, 12345.999):
        assert spans.us(round(x, 3)) == int(round(x * 1000.0))
    assert spans.us(round(0.1 + 0.2, 3)) == 300


def test_kv_wait_event_registered():
    # The catalog<->FF008 equality pin lives in test_obs; this pins
    # that the span layer's phase events are actually registered.
    from flexflow_tpu.obs.events import EVENT_CATALOG
    for name in ("kv_wait", "sched_decision", "request_retry",
                 "request_preempt", "spec_verify"):
        assert name in EVENT_CATALOG, name


# -- reconciliation on the simulated loop -------------------------------------


def test_bursty_sim_reconciles_and_autopsy_three_ways(tmp_path):
    """Every request reconciles exactly; stats-side, run_end-side and
    log-reconstructed autopsies are bit-identical; every missed tier-0
    request carries a dominant phase."""
    tel = Telemetry(str(tmp_path))
    path = tel.path
    with tel:
        srv = _sim()
        results, stats = srv.run(make_workload(BURSTY))
    assert stats["completed"] + stats["failed"] == BURSTY.n_requests

    tls = spans.build_timelines(srv.span_events)
    assert len(tls) == BURSTY.n_requests
    _assert_all_reconciled(tls)

    run = RunLog.load(path)
    assert not run.unknown_events
    log_tls = spans.timelines_from_run(run)
    assert sorted(log_tls) == sorted(tls)
    for i in tls:
        assert log_tls[i].phase_us == tls[i].phase_us, i
        assert log_tls[i].e2e_ms == tls[i].e2e_ms, i

    # The run missed SLOs (overloaded by construction) and the autopsy
    # agrees between the stats block, run_end and the reconstruction.
    autopsy = stats["slo_autopsy"]
    assert autopsy
    assert run.summary()["slo_autopsy"] == autopsy
    assert run.reconstruct_summary()["slo_autopsy"] == autopsy

    # 100% dominant-phase coverage over the missed tier-0 class.
    missed_t0 = [tl for tl in tls.values()
                 if tl.slo_ok is False and tl.tier == 0]
    assert missed_t0
    assert autopsy["0"]["missed"] == len(missed_t0)
    for tl in missed_t0:
        assert tl.dominant_phase in spans.PHASES
    assert autopsy["0"]["dominant_phase"] in spans.PHASES


def test_replay_determinism_of_span_events():
    def virt(evs):
        # Everything but wall time is virtual-clock deterministic.
        return [{k: v for k, v in e.items()
                 if k not in ("latency_s", "wall_s")} for e in evs]

    a, b = _sim(), _sim()
    a.run(make_workload(BURSTY))
    b.run(make_workload(BURSTY))
    assert virt(a.span_events) == virt(b.span_events)


def test_kv_wait_phase_reconciles():
    """A block-starved paged pool produces kv_wait spans that still
    telescope exactly."""
    shp = SlotShape(max_batch=2, max_seq=64, buckets=(8, 64),
                    kv_block=16, kv_blocks=5)
    srv = _sim(shape=shp)
    _, stats = srv.run([_req(0, 4, 30), _req(1, 4, 30, 1.0),
                        _req(2, 4, 8, 2.0)])
    assert any(d["d"] == "kv_wait" for d in srv.decisions)
    tls = spans.build_timelines(srv.span_events)
    _assert_all_reconciled(tls)
    assert any(tl.phase_us.get("kv_wait", 0) > 0 for tl in tls.values())


def test_preempted_phase_reconciles():
    """An evicted request's out-of-slot gap is attributed to the
    ``preempted`` phase and the timeline still reconciles."""
    shp = SlotShape(max_batch=1, max_seq=S, buckets=(8, S))
    srv = _sim(shape=shp, decode_steps=8)
    _, stats = srv.run([_req(0, 4, 40, 0.0, priority=1),
                        _req(1, 4, 4, 5.0, priority=0, slo_ms=20.0)])
    assert stats["request_preempts"] == 1
    tls = spans.build_timelines(srv.span_events)
    _assert_all_reconciled(tls)
    assert tls[0].phase_us.get("preempted", 0) > 0


def test_retry_backoff_span_splits_at_until():
    """The retry window is its own phase, clamped at ``until_ms``:
    8 ms + 16 ms of deterministic backoff show up as exactly 24000 µs
    of ``retry_backoff``."""
    srv = _sim(
        resilience=ServingResilience(max_retries=2),
        fault_injector=ServingFaultInjector(nan_cache_at={0: 0, 1: 0}),
    )
    results, stats = srv.run([_req(0, 4, 6)])
    assert stats["request_retries"] == 2
    assert results[0].error is None
    tls = spans.build_timelines(srv.span_events)
    _assert_all_reconciled(tls)
    assert tls[0].phase_us["retry_backoff"] == spans.us(8.0) + spans.us(16.0)


def test_dominant_phase_tie_breaks_to_earlier():
    tl = spans.RequestTimeline(
        id=0, arrival_ms=0.0, end_ms=2.0, e2e_ms=2.0,
        queue_wait_ms=1.0, tier=0, slo_ok=False, error=None, tokens=1,
        spans=[], donor_spans=[], transplanted=False,
        phase_us={"queued": 1000, "decode": 1000},
    )
    assert tl.dominant_phase == "queued"
    assert tl.total_us == 2000
    assert tl.reconciled


def test_render_waterfall_smoke():
    srv = _sim()
    srv.run(make_workload(BURSTY))
    tls = spans.build_timelines(srv.span_events)
    txt = spans.render_waterfall(tls[0])
    assert "request 0" in txt and "reconciled=yes" in txt
    assert "phase totals" in txt


# -- fleet: transplant + merged streams ---------------------------------------


def test_fleet_replica_loss_complete_timelines():
    """The ISSUE acceptance bar: after a replica loss, EVERY request —
    transplanted included — yields a complete, exactly-reconciled
    timeline from the merged span stream; transplants archive the
    donor segment."""
    inj = {0: ServingFaultInjector(engine_raise_at={1: "sim death"})}
    fleet = FleetRouter.simulated(
        SHAPE, 2, decode_steps=4, policy=SchedulerPolicy(name="slo"),
        resilience=ServingResilience(max_restarts=0),
        fault_injectors=inj,
    )
    results, stats = fleet.run(make_workload(FLEET_BURSTY))
    assert fleet.dead == [0] and stats["redistributed"] > 0

    tls = spans.build_timelines(fleet.span_events)
    assert sorted(tls) == list(range(FLEET_BURSTY.n_requests))
    _assert_all_reconciled(tls)
    moved = [i for i in tls if tls[i].transplanted]
    assert len(moved) == stats["redistributed"]
    # A request transplanted mid-flight archives the donor replica's
    # segment; one transplanted while still queued on the donor has no
    # donor stamps to archive.  Either way the pin is completeness +
    # exact reconciliation (asserted above for all ids).
    assert any(tls[i].donor_spans for i in moved)
    assert any(tls[i].phase_us.get("transplanted", 0) > 0 for i in moved)


def test_fleet_single_replica_merges_equal_to_single_server():
    fleet = FleetRouter.simulated(
        SHAPE, 1, decode_steps=4, policy=SchedulerPolicy(name="slo"))
    fleet.run(make_workload(FLEET_BURSTY))
    single = _sim()
    single.run(make_workload(FLEET_BURSTY))
    ft = spans.build_timelines(fleet.span_events)
    st = spans.build_timelines(single.span_events)
    assert sorted(ft) == sorted(st)
    for i in st:
        assert ft[i].phase_us == st[i].phase_us, i
        assert ft[i].e2e_ms == st[i].e2e_ms, i


def test_load_streams_torn_tail_does_not_poison_merge(tmp_path):
    """Satellite: a fleet-style multi-stream load — the events split
    across two files, one with a torn tail — folds to the SAME
    timelines as the intact single stream."""
    tel = Telemetry(str(tmp_path / "whole"))
    path = tel.path
    with tel:
        srv = _sim()
        srv.run(make_workload(BURSTY))
    lines = open(path).read().splitlines(keepends=True)
    cut = len(lines) // 2
    a, b = str(tmp_path / "s0.jsonl"), str(tmp_path / "s1.jsonl")
    open(a, "w").writelines(lines[:cut])
    with open(b, "w") as fh:
        fh.writelines(lines[cut:])
        fh.write('{"ev": "request_end", "id": 99, "torn')  # torn tail
    merged = RunLog.load_streams([a, b])
    assert merged.torn_tail
    assert merged.read_error is None
    whole_tls = spans.timelines_from_run(RunLog.load(path))
    merged_tls = spans.timelines_from_run(merged)
    assert sorted(merged_tls) == sorted(whole_tls)
    for i in whole_tls:
        assert merged_tls[i].phase_us == whole_tls[i].phase_us, i
    _assert_all_reconciled(merged_tls)


def test_load_streams_all_unreadable_sets_read_error(tmp_path):
    merged = RunLog.load_streams([str(tmp_path / "gone.jsonl")])
    assert merged.read_error is not None
    assert merged.events == []


def test_fleet_journal_paths_and_outcomes(tmp_path):
    from flexflow_tpu.serving.journal import RequestJournal

    base = str(tmp_path / "journal.jsonl")
    journals = [RequestJournal(f"{base}.r{i}") for i in range(2)]
    inj = {0: ServingFaultInjector(engine_raise_at={1: "sim death"})}
    fleet = FleetRouter.simulated(
        SHAPE, 2, decode_steps=4, policy=SchedulerPolicy(name="slo"),
        resilience=ServingResilience(max_restarts=0),
        fault_injectors=inj, journals=journals,
    )
    results, stats = fleet.run(make_workload(FLEET_BURSTY))
    paths = spans.fleet_journal_paths(base)
    assert paths == [f"{base}.r0", f"{base}.r1"]
    rows = spans.journal_outcomes(paths)
    done = {i for i, r in results.items() if r.error is None}
    assert done <= set(rows)
    for i in done:
        assert rows[i]["tokens"] == len(results[i].tokens)


# -- autopsy in the drift sentry ----------------------------------------------


def test_compare_flattens_autopsy_and_gates_drift():
    from flexflow_tpu.obs.compare import compare_runs

    def log(missed):
        return RunLog.from_events([
            {"ev": "run_start", "app": "serve"},
            {"ev": "run_end", "exit": "clean", "summary": {
                "slo_attainment": 0.8,
                "slo_autopsy": {"0": {
                    "missed": missed, "dominant_phase": "queued",
                    "phase_ms": {"queued": 30.0, "decode": 5.0},
                }},
            }},
        ])

    same = compare_runs(log(3), log(3))
    assert same.verdict == "ok"
    metrics = {r.metric for r in same.rows}
    assert "slo_missed_t0" in metrics
    assert "autopsy_t0_queued_ms" in metrics
    drift = compare_runs(log(3), log(5))
    assert drift.verdict.startswith("drift:slo_missed_t0")


def test_registry_carries_serving_keys():
    from flexflow_tpu.obs.registry import _INDEX_SUMMARY_KEYS

    for k in ("queue_wait_ms_p99", "slo_attainment", "request_sheds",
              "engine_restarts", "fleet_replicas"):
        assert k in _INDEX_SUMMARY_KEYS, k


# -- obs request CLI ----------------------------------------------------------


def test_obs_request_cli(tmp_path, capsys):
    from flexflow_tpu.obs.__main__ import main

    tel = Telemetry(str(tmp_path))
    path = tel.path
    with tel:
        _sim().run(make_workload(BURSTY))
    assert main(["request", path]) == 0
    table = capsys.readouterr().out
    assert "dominant" in table
    assert main(["request", path, "0"]) == 0
    assert "reconciled=yes" in capsys.readouterr().out
    assert main(["request", path, "--slo-miss", "--worst", "2"]) == 0
    out = capsys.readouterr().out
    assert "slo=miss" in out
    assert main(["request", str(tmp_path / "gone")]) == 2
    capsys.readouterr()


def test_obs_report_serving_block(tmp_path, capsys):
    from flexflow_tpu.obs.__main__ import main

    tel = Telemetry(str(tmp_path))
    path = tel.path
    with tel:
        _sim().run(make_workload(BURSTY))
    assert main(["report", path]) == 0
    out = capsys.readouterr().out
    assert "serving:" in out
    assert "slo autopsy" in out


# -- latency-model prefix pricing (satellite) ---------------------------------


def test_expected_prefill_defaults_to_exact_prefill():
    m = ServingLatencyModel.from_calibration()
    for bucket in (8, 32, 64):
        assert m.expected_prefill_ms(bucket) == m.prefill_ms(bucket)


def test_fit_events_prices_prefix_hits():
    events = [
        {"ev": "prefix_hit", "id": 1, "tokens_saved": 8, "full": False},
        {"ev": "prefix_hit", "id": 2, "tokens_saved": 16, "full": True},
        {"ev": "prefill", "id": 0, "bucket": 32, "wall_s": 0.004},
        {"ev": "prefill", "id": 1, "bucket": 32, "wall_s": 0.004},
        {"ev": "prefill", "id": 3, "bucket": 32, "wall_s": 0.004},
    ]
    m = ServingLatencyModel.from_calibration().fit_events(events)
    # 2 hits over 4 admissions (3 prefills + 1 full hit), mean 12
    # tokens saved per hit.
    assert m.prefix_hit_rate == pytest.approx(0.5)
    assert m.prefix_mean_offset == pytest.approx(12.0)
    assert m.expected_prefill_ms(32) < m.prefill_ms(32)
    assert m.expected_prefill_ms(32) == pytest.approx(
        m.prefill_ms(32) - 6.0 * m.prefill_token_ms)
    # No prefix events at all -> the defaults (and the exact price).
    m2 = ServingLatencyModel.from_calibration().fit_events(
        [{"ev": "prefill", "id": 0, "bucket": 32, "wall_s": 0.004}])
    assert m2.prefix_hit_rate == 0.0
    assert m2.expected_prefill_ms(32) == m2.prefill_ms(32)


def test_serve_auto_ranks_prefix_cache_on_shared_prefix_workload():
    reqs = make_workload(WorkloadSpec(
        n_requests=10, vocab=V, prompt_len=(9, 12), max_new=(2, 6),
        mean_gap_ms=1.0, burst=5, priorities=2, slo_ms=40.0, seed=7,
        shared_prefix=8, shared_frac=0.9,
    ))
    base = ServingConfig(
        buckets=(16, S), decode_steps=4, max_batch=2, max_seq=S,
        policy=SchedulerPolicy(name="slo"), kv_block=8, kv_blocks=9,
        prefix_cache=True,
    )
    res = search_serving_config(
        reqs, base, model=ServingLatencyModel.from_calibration())
    flags = {s.config.prefix_cache for s in res.candidates}
    assert flags == {True, False}
    assert res.chosen.config.prefix_cache is True


# -- the measured loop: Server.run on its own real clock ----------------------

#: ``ServingExecutor`` keywords, ``Server`` keywords.
MEASURED = {
    "padded": ({}, {}),
    "paged": (dict(kv_block=4), {}),
    "paged_prefix": (dict(kv_block=4, prefix_cache=True), {}),
    "speculate": (dict(draft_layers=1), dict(speculate=3)),
}


@pytest.fixture(scope="module")
def tiny_lm():
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_transformer_lm

    return build_transformer_lm(batch_size=2, seq_len=S, vocab_size=V,
                                d_model=32, num_heads=2, num_layers=2,
                                config=FFConfig(batch_size=2))


@pytest.fixture(scope="module")
def measured_streams(tiny_lm, tmp_path_factory):
    """``case -> (path, results of the second run)``: one stream a case
    holding TWO runs of ``Server.run``, as a benchmark cell's does (the
    warm-up, then the window), both numbering their requests from 0.
    The second run serves five requests over two slots, rejects a sixth
    (no bucket holds it) and loses one to a NaN'd cache row (not under
    the prefix cache, where the row's blocks are other requests' too)."""
    out = {}

    def get(case):
        if case in out:
            return out[case]
        ex_kw, srv_kw = MEASURED[case]
        sex = ServingExecutor(tiny_lm, max_batch=2, max_seq=S,
                              buckets=(8, 16), decode_kernel=False, **ex_kw)
        params, state = sex.init(seed=0)
        rng = np.random.default_rng(1)
        # A shared 8-token span in front under the prefix cache, so that
        # the later admissions are hits (a full one among them).
        span = rng.integers(0, V, size=8 if "prefix" in case else 0)

        def reqs(n):
            return [Request(id=i, max_new_tokens=5 + i % 3,
                            prompt=np.concatenate(
                                [span, rng.integers(0, V, size=(
                                    0 if "prefix" in case and i % 2 else 3 + i))]
                            ).astype(np.int32)) for i in range(n)]

        tel = Telemetry(str(tmp_path_factory.mktemp(case)))
        with tel:
            Server(sex, params, state, decode_steps=4, **srv_kw).run(reqs(2))
            window = reqs(5) + [Request(
                id=5, max_new_tokens=4,
                prompt=rng.integers(0, V, size=20).astype(np.int32))]
            results, stats = Server(
                sex, params, state, decode_steps=4,
                fault_injector=None if "prefix" in case else
                ServingFaultInjector(nan_cache_at={1: 1}),
                **srv_kw).run(window)
        out[case] = (tel.path, results, stats)
        return out[case]

    return get


@pytest.mark.parametrize("case", list(MEASURED))
def test_measured_loop_reconciles(measured_streams, case):
    """Every request of both runs of the stream folds to a timeline
    whose phases sum to its ``e2e_ms`` in integer microseconds; the two
    runs' colliding ids stay apart; the rejected request is all
    ``queued`` and the faulted one ends where its error was seen."""
    path, results, stats = measured_streams(case)
    run = RunLog.load(path)
    assert not run.unknown_events
    assert spans.count_runs(run.iter_raw()) == 2
    warm = spans.timelines_from_run(run, 0)
    tls = spans.timelines_from_run(run)          # the last run: the window
    assert sorted(warm) == [0, 1] and sorted(tls) == list(range(6))
    assert {t.run for t in warm.values()} == {0}
    assert {t.run for t in tls.values()} == {1}
    _assert_all_reconciled(warm)
    _assert_all_reconciled(tls)
    assert spans.timelines_from_run(run, 1)[0].phase_us == tls[0].phase_us
    assert spans.timelines_from_run(run, 2) == {}
    # ids 0 and 1 of the warm-up are not ids 0 and 1 of the window
    assert warm[0].e2e_ms != tls[0].e2e_ms
    for i, tl in tls.items():
        assert tl.error == results[i].error and tl.tokens == len(results[i].tokens)
        assert tl.arrival_ms == 0.0 and tl.e2e_ms == tl.end_ms
        # a span never reaches past the request's end, and they abut
        assert [s.start_ms for s in tl.spans[1:]] == [s.end_ms for s in tl.spans[:-1]]
    assert stats["failed"] == (1 if case == "paged_prefix" else 2)
    rejected = tls[5]
    assert "exceeds the largest pad bucket" in rejected.error
    assert rejected.phase_us["queued"] == rejected.total_us > 0
    faulted = [t for t in tls.values() if t.error and "non-finite" in t.error]
    assert len(faulted) == stats["failed"] - 1
    assert all(t.phase_us["decode"] > 0 for t in faulted)
    served = [t for t in tls.values() if t.error is None]
    assert all(t.phase_us["prefill"] > 0 or case == "paged_prefix" for t in served)
    assert all(t.phase_us["decode"] > 0 for t in served)
    # two slots, five requests: the later ones queued for a slot
    assert tls[4].phase_us["queued"] > tls[0].phase_us["queued"]
    if case == "paged_prefix":
        # a FULL hit closes its prefill at length 0 on this clock too
        hits = [e for e in run.iter_raw() if e["ev"] == "prefix_hit"]
        assert all(e.get("t_ms") is not None for e in hits)
        assert any(e["full"] for e in hits)


@pytest.mark.parametrize("case", list(MEASURED))
def test_measured_loop_round_is_one_line(measured_streams, case):
    """What replaced the ``k`` ``step`` lines a superstep: the round's
    one event carries ``superstep``, ``wall_s`` and ``k`` (``d``), the
    summary's step counters are kept in memory, and the reader rebuilds
    them from the event to the bit.  A run adds one ``serve_run`` line
    and nothing a superstep, a request or an admission."""
    path, _, stats = measured_streams(case)
    run = RunLog.load(path)
    kinds = [e["ev"] for e in run.iter_raw()]
    assert "step" not in kinds and kinds.count("serve_run") == 2
    rounds = [e for e in run.iter_raw()
              if e["ev"] in ("decode_superstep", "spec_verify")]
    k_eff = 4                       # decode_steps, and d + 1 of speculate=3
    assert sum(len(r["slots"]) > 0 for r in rounds) == len(rounds)
    window = rounds[-stats["decode_supersteps"]:]
    # numbered as the loop counts them (its spans carry the same key);
    # a raised fault skips an index, a NaN'd row does not
    assert [r["superstep"] for r in window] == list(range(len(window)))
    assert all(r["t0_ms"] < r["t_ms"] for r in rounds)
    assert all(abs((r["t_ms"] - r["t0_ms"]) - r["wall_s"] * 1e3) < 2e-3 for r in rounds)
    # one fence line and one round line a superstep
    assert kinds.count("fence") == len(rounds) + kinds.count("prefill")
    rec, summ = run.reconstruct_summary(), run.summary()
    assert rec["steps"] == summ["steps"] == len(rounds) * k_eff
    for key in ("fences", "fences_per_step", "step_ms_p50", "step_ms_p95",
                "step_ms_max"):
        assert rec[key] == summ[key], key
    assert summ["programs_per_step"] == pytest.approx(1 / k_eff)


def test_slot_wait_of_the_first_admitted_is_the_other_admissions():
    """To the microsecond, on a hand-written stream: request 0 is
    admitted first and holds slot 0 through request 1's admission
    (1.250 ms) and request 2's (0.875 ms, between two rounds); each
    round is ``decode`` for its occupants, and nothing else is left."""
    ev = [
        {"ev": "serve_run", "requests": 3, "capacity": 2, "k": 4},
        {"ev": "request_start", "id": 0, "bucket": 8, "t_ms": 0.100},
        {"ev": "prefill", "id": 0, "t0_ms": 0.150, "t_ms": 1.100},
        {"ev": "request_start", "id": 1, "bucket": 8, "t_ms": 1.100},
        {"ev": "prefill", "id": 1, "t0_ms": 1.200, "t_ms": 2.350},
        {"ev": "decode_superstep", "k": 4, "slots": [0, 1], "superstep": 0,
         "t0_ms": 2.350, "t_ms": 4.350},
        {"ev": "request_end", "id": 1, "tokens": 5, "error": None,
         "arrival_ms": 0.0, "e2e_ms": 4.350, "t_ms": 4.350},
        {"ev": "request_start", "id": 2, "bucket": 8, "t_ms": 4.350},
        {"ev": "prefill", "id": 2, "t0_ms": 4.400, "t_ms": 5.225},
        {"ev": "decode_superstep", "k": 4, "slots": [0, 2], "superstep": 1,
         "t0_ms": 5.225, "t_ms": 7.000},
        {"ev": "request_end", "id": 0, "tokens": 9, "error": None,
         "arrival_ms": 0.0, "e2e_ms": 7.000, "t_ms": 7.000},
        {"ev": "request_end", "id": 2, "tokens": 5, "error": None,
         "arrival_ms": 0.0, "e2e_ms": 7.125, "t_ms": 7.125},
    ]
    tls = spans.build_timelines(ev)
    _assert_all_reconciled(tls)
    assert tls[0].phase_us == {**{p: 0 for p in spans.PHASES}, "queued": 100,
                               "prefill": 1000, "decode": 2000 + 1775,
                               "slot_wait": 1250 + 875}
    assert tls[1].phase_us["queued"] == 1100 and tls[1].phase_us["slot_wait"] == 0
    assert tls[2].phase_us["slot_wait"] == 125   # bookkeeping after its last round
    assert [s.phase for s in tls[0].spans] == [
        "queued", "prefill", "slot_wait", "decode", "slot_wait", "decode"]


def test_slot_wait_holds_the_other_admissions_on_a_real_run(measured_streams):
    """On the real loop the first admitted waits at least through every
    admission made while it held its slot (they run one after another on
    the one host thread), plus installs, pack and bookkeeping."""
    path, _, _ = measured_streams("padded")
    evs = [e for e in RunLog.load(path).iter_raw()]
    evs = evs[max(i for i, e in enumerate(evs) if e["ev"] == "serve_run"):]
    tl = spans.build_timelines(evs)[0]
    start = {e["id"]: e["t_ms"] for e in evs if e["ev"] == "request_start"}
    others = sum(spans.us(e["t_ms"]) - spans.us(start[e["id"]])
                 for e in evs if e["ev"] == "prefill" and e["id"] != 0
                 and start[e["id"]] < tl.end_ms)
    assert others > 0
    assert tl.phase_us["slot_wait"] >= others


def test_unstamped_stream_is_skipped_not_raised_on(measured_streams):
    """A stream from before the stamps (or a parent's): no timeline, no
    error; one whose rounds alone lack them still reconciles, the
    rounds' time read as ``slot_wait``."""
    path, _, _ = measured_streams("padded")
    evs = [dict(e) for e in RunLog.load(path).iter_raw()]
    bare = [{k: v for k, v in e.items()
             if k not in ("t_ms", "t0_ms", "arrival_ms", "e2e_ms")}
            for e in evs if e["ev"] != "serve_run"]
    assert spans.build_timelines(bare) == {}
    assert spans.count_runs(bare) == 1
    no_rounds = [{k: v for k, v in e.items() if k not in ("t_ms", "t0_ms")}
                 if e["ev"] == "decode_superstep" else e for e in evs]
    tls = spans.build_timelines(no_rounds)
    _assert_all_reconciled(tls)
    assert all(t.phase_us["decode"] == 0 for t in tls.values())


def test_obs_request_cli_on_a_measured_stream(measured_streams, capsys):
    """``obs request`` on the stream a benchmark cell leaves: says that
    it holds two runs, shows the window by default, the warm-up on
    ``--loop-run 0``."""
    from flexflow_tpu.obs.__main__ import main

    path, _, _ = measured_streams("padded")
    assert main(["request", path]) == 0
    table = capsys.readouterr().out
    assert "holds 2 runs" in table and "showing run 1" in table
    assert "dominant" in table and "WARNING" not in table
    assert main(["request", path, "4"]) == 0
    out = capsys.readouterr().out
    assert "request 4" in out and "reconciled=yes" in out and "slot_wait" in out
    assert main(["request", path, "4", "--loop-run", "0"]) == 2   # two warm-up requests
    capsys.readouterr()
    assert main(["request", path, "1", "--loop-run", "0"]) == 0
    assert "showing run 0" in capsys.readouterr().out
