"""bench.py stdout contract: exactly ONE JSON line on stdout.

The driver parses bench.py's stdout as a single JSON record; every
human-readable printout (the reference's ``tp = ...`` lines, Trainer
timing, sub-benchmark chatter) must land on stderr.  Until now this
CLAUDE.md invariant was enforced only by convention — this test pins
the plumbing with the heavy benchmark legs stubbed out (each stub
prints to ITS caller's stdout exactly like Trainer.fit does, so the
redirect_stdout routing itself is what is under test).
"""

import io
import json
import os
import sys

import pytest

# bench.py lives at the repo root (a driver script, not a package
# module); resolvable regardless of how pytest was invoked.
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


@pytest.fixture
def stubbed_bench(monkeypatch):
    import bench

    def chatty(value):
        # Mimic Trainer.fit's reference-protocol prints: they go to
        # whatever stdout is current, and main() must reroute them.
        print("time = 0.0001s")
        print("tp = 1.00 samples/s")
        return value

    # The suite runs under JAX_PLATFORMS=cpu (tests/conftest.py): the
    # one case in which bench.py accepts the CPU backend.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(
        bench, "bench_alexnet", lambda n, t: chatty((100.0, None, 32))
    )
    monkeypatch.setattr(
        bench, "bench_dlrm", lambda n, t: chatty((50.0, None))
    )
    monkeypatch.setattr(
        bench, "bench_transformer", lambda t: chatty((1000.0, 0.2))
    )
    monkeypatch.setattr(
        bench, "bench_transformer_longctx", lambda t: chatty((500.0, 0.15))
    )
    monkeypatch.setattr(
        bench, "bench_transformer_32k", lambda t: chatty((100.0, 0.1))
    )
    monkeypatch.setattr(bench, "bench_candle", lambda t: chatty(10.0))
    monkeypatch.setattr(
        bench, "bench_nmt", lambda n, t: chatty((1.0, 20.0, 2))
    )
    monkeypatch.setattr(
        bench, "bench_superstep",
        lambda n, t: chatty({"k1_ms_per_step": 2.0, "k8_ms_per_step": 1.0}),
    )
    monkeypatch.setattr(
        bench, "bench_pipeline",
        lambda n, t: chatty({
            "s2_mb4_c1_ms_per_step": 4.0, "s2_mb4_c1_programs": 16,
            "s2_mb4_c4_ms_per_step": 2.0, "s2_mb4_c4_programs": 4,
            "s2_mb4_compiled_ms_per_step": 1.0,
            "s2_mb4_compiled_programs": 1,
            "chunk_amortization": 2.0,
            "compiled_speedup": 2.0,
            "superstep_k8_ms_per_step": 1.5,
            "superstep_k8_compiled_ms_per_step": 0.75,
        }),
    )
    monkeypatch.setattr(
        bench, "bench_telemetry",
        lambda n, t: chatty({
            "fences_per_step": 1.06, "programs_per_step": 8.0,
            "step_ms_p50": 2.0, "step_ms_p95": 3.0, "step_ms_max": 4.0,
            "overhead_pct": 0.5,
        }),
    )
    monkeypatch.setattr(
        bench, "bench_serving",
        lambda n, t: chatty({
            "k1_tokens_per_s": 100.0, "k8_tokens_per_s": 400.0,
            "k1_decode_ms_per_token": 4.0, "k8_decode_ms_per_token": 1.0,
            "fused_speedup_k8_vs_k1": 4.0,
            "request_latency_ms_p50": 50.0,
            "request_latency_ms_p95": 80.0,
            "programs_per_decode_superstep": 1,
            "queue_wait_ms_p50": 5.0, "queue_wait_ms_p95": 20.0,
            "queue_wait_ms_p99": 30.0, "e2e_ms_p99": 55.0,
            "slo_attainment": 0.95, "request_sheds": 0,
            "request_preempts": 1,
            "fifo_queue_wait_ms_p99": 45.0,
            "fifo_slo_attainment": 0.8,
            "fifo_vs_slo_queue_wait_p99": 1.5,
            "request_retries": 1,
            "request_expiries": 0,
            "engine_restarts": 1,
            "hbm_per_slot_bytes": 32768,
            "paged_hbm_per_slot_bytes": 8192,
            "padded_max_admitted_batch": 4,
            "paged_max_admitted_batch": 14,
            "paged_tokens_per_s": 390.0,
            "sharded_mesh": [2, 1],
            "sharded_tokens_per_s": 600.0,
            "sharded_vs_single_mesh_tokens_per_s": 1.5,
            "speculate": 12,
            "spec_tokens_per_s": 700.0,
            "spec_acceptance_rate": 1.0,
            "spec_tokens_per_dispatch": 9.0,
            "plain_tokens_per_dispatch": 6.0,
            "spec_vs_plain_tokens_per_dispatch": 1.5,
            "spec_match": True,
            "fleet_replicas": 2,
            "fleet_router": "least-loaded",
            "fleet_queue_wait_ms_p99": 18.0,
            "fleet_slo_attainment": 0.99,
            "fleet_vs_single_attainment": 1.042,
            "fleet_dead_replicas": 1,
            "fleet_redistributed": 3,
            "fleet_loss_slo_attainment": 0.9,
            "prefix_hits": 9,
            "prefix_hit_rate": 0.75,
            "prefill_tokens_saved": 72,
            "prefix_kv_cows": 2,
            "prefix_prefills": 3,
            "prefix_off_prefills": 12,
            "prefix_match": True,
        }),
    )
    monkeypatch.setattr(
        bench, "bench_search",
        lambda n, t: chatty({
            "default_ms_per_step": 2.0, "auto_ms_per_step": 1.0,
            "auto_speedup": 2.0, "auto_config": "full-mesh dp k=8",
            "predicted_ms_per_step": 1.1, "search_wall_s": 0.5,
            "calibrated": True,
        }),
    )
    monkeypatch.setattr(
        bench, "bench_data_plane",
        lambda n, t: chatty({
            "array_samples_per_s": 1000.0, "zc_samples_per_s": 1200.0,
            "stream_samples_per_s": 1100.0, "stream_vs_zc": 0.917,
            "input_wait_ms_p50": 0.05, "input_wait_ms_p95": 0.4,
            "throttled_stream_samples_per_s": 900.0,
            "throttled_unprefetched_samples_per_s": 450.0,
            "throttled_overlap_speedup": 2.0,
            "emb_budget_bytes": 73728,
            "max_vocab_replicated": 1024,
            "max_vocab_sharded_c4": 4096,
            "vocab_capacity_ratio": 4.0,
            "replicated_emb_samples_per_s": 800.0,
            "sharded_emb_samples_per_s": 700.0,
            "sharded_vs_replicated": 0.875,
        }),
    )
    monkeypatch.setattr(
        bench, "bench_op_parallel_speedup",
        lambda n: {"op_parallel_speedup_sim": 1.5},
    )
    return bench


def test_bench_stdout_is_exactly_one_json_line(stubbed_bench, monkeypatch):
    out, err = io.StringIO(), io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    monkeypatch.setattr(sys, "stderr", err)
    rc = stubbed_bench.main()
    assert rc == 0
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, f"stdout must be ONE JSON line, got: {lines}"
    record = json.loads(lines[0])
    assert record["metric"] == "alexnet_imgs_per_sec_per_chip"
    assert record["value"] == 100.0
    assert record["extra"]["superstep"]["k8_ms_per_step"] == 1.0
    # The pipeline leg's schema: per-config ms/step + last_schedule
    # program counts (the 2*S*m -> 2*S*ceil(m/c) dispatch audit) and
    # the chunk/superstep amortization headlines.
    pipe = record["extra"]["pipeline"]
    assert pipe["s2_mb4_c1_programs"] == 16
    assert pipe["s2_mb4_c4_programs"] == 4
    assert pipe["chunk_amortization"] == 2.0
    assert pipe["superstep_k8_ms_per_step"] == 1.5
    # The compiled whole-step column (ONE program per step) and its
    # A/B headlines vs the chunked host path.
    assert pipe["s2_mb4_compiled_programs"] == 1
    assert pipe["s2_mb4_compiled_ms_per_step"] == 1.0
    assert pipe["compiled_speedup"] == 2.0
    assert pipe["superstep_k8_compiled_ms_per_step"] == 0.75
    # The telemetry summary block: dispatch/fence counters + host-side
    # step-time percentiles (the observability layer's headline
    # numbers, OBSERVABILITY.md).
    tele = record["extra"]["telemetry"]
    assert tele["fences_per_step"] == 1.06
    assert tele["programs_per_step"] == 8.0
    assert tele["step_ms_p50"] == 2.0
    assert tele["step_ms_p95"] == 3.0
    assert tele["step_ms_max"] == 4.0
    assert tele["overhead_pct"] == 0.5
    # The serving leg (ISSUE 7): continuous-batching KV-cache decode —
    # request latency p50/p95, tokens/s, one program per K-token
    # decode superstep, and the fused-vs-per-token dispatch A/B.
    serving = record["extra"]["serving"]
    assert serving["k8_tokens_per_s"] == 400.0
    assert serving["k1_decode_ms_per_token"] == 4.0
    assert serving["k8_decode_ms_per_token"] == 1.0
    assert serving["fused_speedup_k8_vs_k1"] == 4.0
    assert serving["request_latency_ms_p50"] == 50.0
    assert serving["request_latency_ms_p95"] == 80.0
    assert serving["programs_per_decode_superstep"] == 1
    # The scheduler A/B columns (SERVING.md "Scheduler policy"):
    # virtual-clock queue-wait percentiles + SLO attainment under the
    # slo policy, and the FIFO baseline's p99 for the headline ratio.
    assert serving["queue_wait_ms_p50"] == 5.0
    assert serving["queue_wait_ms_p95"] == 20.0
    assert serving["queue_wait_ms_p99"] == 30.0
    assert serving["e2e_ms_p99"] == 55.0
    assert serving["slo_attainment"] == 0.95
    assert serving["request_sheds"] == 0
    assert serving["request_preempts"] == 1
    assert serving["fifo_queue_wait_ms_p99"] == 45.0
    assert serving["fifo_vs_slo_queue_wait_p99"] == 1.5
    # Failure-model columns (ISSUE 15): injected slot + engine faults
    # exercise retry / restart; zeros on a healthy run.
    assert serving["request_retries"] == 1
    assert serving["request_expiries"] == 0
    assert serving["engine_restarts"] == 1
    # The capacity columns (ISSUE 13, SERVING.md "Cache layout"):
    # per-slot HBM under both layouts, the paged-vs-padded max batch a
    # fixed cache budget admits, and paged / sharded tokens/s against
    # the single-mesh padded run (sharded_mesh None = loud fallback).
    assert serving["hbm_per_slot_bytes"] == 32768
    assert serving["paged_hbm_per_slot_bytes"] == 8192
    assert serving["padded_max_admitted_batch"] == 4
    assert serving["paged_max_admitted_batch"] == 14
    assert serving["paged_tokens_per_s"] == 390.0
    assert serving["sharded_mesh"] == [2, 1]
    assert serving["sharded_tokens_per_s"] == 600.0
    assert serving["sharded_vs_single_mesh_tokens_per_s"] == 1.5
    # The speculation columns (ISSUE 16, SERVING.md "Speculative
    # decoding"): tokens per decode dispatch under a d=12 self-draft
    # vs the plain fused k=8 run, with the byte-parity match bit.
    assert serving["speculate"] == 12
    assert serving["spec_acceptance_rate"] == 1.0
    assert serving["spec_tokens_per_dispatch"] == 9.0
    assert serving["plain_tokens_per_dispatch"] == 6.0
    assert serving["spec_vs_plain_tokens_per_dispatch"] == 1.5
    assert serving["spec_match"] is True
    # The fleet columns (SERVING.md "Fleet"): 2-replica attainment vs
    # the single-replica slo run, plus the replica-loss sub-leg's
    # dead/redistributed counters (the loss path provably ran).
    assert serving["fleet_replicas"] == 2
    assert serving["fleet_router"] == "least-loaded"
    assert serving["fleet_queue_wait_ms_p99"] == 18.0
    assert serving["fleet_slo_attainment"] == 0.99
    assert serving["fleet_vs_single_attainment"] == 1.042
    assert serving["fleet_dead_replicas"] == 1
    assert serving["fleet_redistributed"] == 3
    assert serving["fleet_loss_slo_attainment"] == 0.9
    # Prefix-cache columns (ISSUE 18): ref-counted block sharing —
    # hit rate, prefill dispatches saved vs the cache-off paged run,
    # and the byte-parity bit (shared decode == unshared decode).
    assert serving["prefix_hits"] == 9
    assert serving["prefix_hit_rate"] == 0.75
    assert serving["prefill_tokens_saved"] == 72
    assert serving["prefix_kv_cows"] == 2
    assert serving["prefix_prefills"] == 3
    assert serving["prefix_off_prefills"] == 12
    assert serving["prefix_match"] is True
    # The execution-autotuner leg (ISSUE 6): auto-chosen config with
    # its predicted-vs-measured ms/step + the search wall time.
    search = record["extra"]["search"]
    assert search["default_ms_per_step"] == 2.0
    assert search["auto_ms_per_step"] == 1.0
    assert search["auto_speedup"] == 2.0
    assert search["auto_config"] == "full-mesh dp k=8"
    assert search["predicted_ms_per_step"] == 1.1
    assert search["search_wall_s"] == 0.5
    assert search["calibrated"] is True
    # The streaming data-plane leg (DATA.md): per-tier samples/s,
    # input-starvation percentiles, and the throttled-source overlap
    # A/B (reader thread + prefetch hiding disk latency).
    dp = record["extra"]["data_plane"]
    assert dp["array_samples_per_s"] == 1000.0
    assert dp["zc_samples_per_s"] == 1200.0
    assert dp["stream_samples_per_s"] == 1100.0
    assert dp["stream_vs_zc"] == 0.917
    assert dp["input_wait_ms_p50"] == 0.05
    assert dp["input_wait_ms_p95"] == 0.4
    assert dp["throttled_stream_samples_per_s"] == 900.0
    assert dp["throttled_unprefetched_samples_per_s"] == 450.0
    assert dp["throttled_overlap_speedup"] == 2.0
    # Sharded-embedding capacity columns (ISSUE 20): max vocab the
    # zero-copy tier admits under FF_DEVICE_MEM_BYTES, replicated vs
    # c=4 row-sharded, and the throughput ratio at a common vocab.
    assert dp["emb_budget_bytes"] == 73728
    assert dp["max_vocab_replicated"] == 1024
    assert dp["max_vocab_sharded_c4"] == 4096
    assert dp["vocab_capacity_ratio"] == 4.0
    assert dp["replicated_emb_samples_per_s"] == 800.0
    assert dp["sharded_emb_samples_per_s"] == 700.0
    assert dp["sharded_vs_replicated"] == 0.875
    # The box-state fingerprint (obs/registry.py): pairs this artifact
    # with telemetry runs for cross-run drift detection.  Every field
    # present; values may be None on a degraded box but the schema is
    # pinned here.
    fp = record["extra"]["fingerprint"]
    assert set(fp) == {"git_sha", "jax", "jaxlib", "platform",
                       "devices", "host", "process_id", "process_count"}
    assert fp["jax"] is not None
    assert fp["platform"] == "cpu"
    # Every result names its device; a CPU run carries no MFU.
    assert record["extra"]["platform"] == "cpu"
    assert record["extra"]["device_kind"] == "cpu"
    assert record["extra"]["alexnet_mfu"] is None
    # The chatter landed on stderr, not stdout.
    assert "tp = " in err.getvalue()


def test_bench_stdout_json_even_when_legs_fail(stubbed_bench, monkeypatch):
    def boom(*a, **k):
        print("partial output before the crash")
        raise RuntimeError("leg exploded")

    monkeypatch.setattr(stubbed_bench, "bench_dlrm", boom)
    monkeypatch.setattr(stubbed_bench, "bench_superstep", boom)
    monkeypatch.setattr(stubbed_bench, "bench_pipeline", boom)
    monkeypatch.setattr(stubbed_bench, "bench_telemetry", boom)
    monkeypatch.setattr(stubbed_bench, "bench_serving", boom)
    monkeypatch.setattr(stubbed_bench, "bench_search", boom)
    monkeypatch.setattr(stubbed_bench, "bench_data_plane", boom)
    out, err = io.StringIO(), io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    monkeypatch.setattr(sys, "stderr", err)
    # The report still lands on stdout; the failures make the exit
    # code non-zero after it.
    assert stubbed_bench.main() == 1
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1
    record = json.loads(lines[0])
    assert "leg exploded" in record["extra"]["dlrm_error"]
    assert "leg exploded" in record["extra"]["superstep_error"]
    assert "leg exploded" in record["extra"]["pipeline_error"]
    assert "leg exploded" in record["extra"]["telemetry_error"]
    assert "leg exploded" in record["extra"]["serving_error"]
    assert "leg exploded" in record["extra"]["search_error"]
    assert "leg exploded" in record["extra"]["data_plane_error"]


def test_bench_one_failed_leg_is_a_nonzero_exit(stubbed_bench, monkeypatch,
                                                capsys):
    """A leg that raises: the JSON line is still printed, with the
    other legs' numbers and the failure under ``<leg>_error``, and the
    run exits non-zero — never a quiet 0 over a missing measurement."""
    def boom(*a, **k):
        raise RuntimeError("leg exploded")

    monkeypatch.setattr(stubbed_bench, "bench_transformer", boom)
    assert stubbed_bench.main() == 1
    (line,) = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    extra = json.loads(line)["extra"]
    assert "leg exploded" in extra["transformer_error"]
    assert "transformer_tokens_per_s" not in extra
    assert extra["transformer_8k_tokens_per_s"] == 500.0
    assert extra["dlrm_samples_per_s"] == 50.0


def test_bench_without_a_chip_is_a_nonzero_exit(stubbed_bench, monkeypatch,
                                                capsys):
    """jax found only the CPU and JAX_PLATFORMS=cpu was not asked for:
    no leg runs, nothing is printed on stdout, the exit is non-zero.
    No probe child, no CPU fallback, no older record stapled in."""
    ran = []
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(stubbed_bench, "bench_alexnet",
                        lambda n, t: ran.append(1))
    rc = stubbed_bench.main()
    assert rc not in (0, None)
    assert ran == []
    assert capsys.readouterr().out.strip() == ""


def test_bench_unknown_device_kind_is_an_error(stubbed_bench, monkeypatch):
    """MFU divides by a published peak looked up by device kind; a kind
    that is not in the table raises instead of assuming a v5e."""
    assert stubbed_bench.DEVICE_PEAKS["TPU v5 lite"]["bf16_flops"] == 1.97e14
    with pytest.raises(KeyError, match="no published peak"):
        stubbed_bench.peak_bf16_flops()  # the suite's device kind is "cpu"
