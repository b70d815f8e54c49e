"""Transformer LM serving driver — the inference half of the north
star (SERVING.md; FlexFlow Serve lineage).

Builds the transformer LM at serving shapes, restores params from a
TRAINING checkpoint when ``--ckpt-dir`` names one (the
strategy-portable train->serve handoff; fresh init otherwise), and
drives the continuous-batching loop (``runtime/serving.py``) over a
synthetic request stream: pad-to-bucket prefill per admission, K-token
fused decode supersteps (one dispatch + one ``jax.device_get`` fence
per K tokens across the whole slot batch), admit/evict between
supersteps.

Any scheduler flag below routes the run through the SLO-aware
scheduler (``flexflow_tpu/serving/``, SERVING.md "Scheduler policy"):
open-loop arrivals on a deterministic virtual clock, priority/EDF
admission, adaptive decode-K, preemption and load shedding.

Flags beyond the common set:
  --max-seq N        serving context length (cache rows per slot; 64)
  --max-batch N      decode slots (4)
  --decode-steps K   fused decode tokens per dispatch (8, clamped 20)
  --buckets A,B,..   prefill pad buckets (default max_seq/4, /2, full)
  --requests N       synthetic request count (8)
  --prompt-len LO:HI prompt length range (4:12)
  --max-new N        generation budget per request (16)
  --eos ID           greedy EOS token id (unset = budget-bounded)
  --no-decode-kernel force the pure-jnp decode oracle (A/B, tests)
  --vocab --d-model --heads --layers   model shape (transformer app)
  --model-config PATH|PRESET   build the graph from a model's own
                               configuration keys instead (a JSON file,
                               or a preset of models/transformer.py:
                               deepseek-v3-tiny, deepseek-v3-smoke,
                               xing4-tiny, xing4-smoke,
                               solar-open2-tiny, solar-open2-smoke,
                               keye-vl2-tiny, keye-vl2-smoke,
                               laguna-tiny, laguna-smoke)

Capacity flags (SERVING.md "Cache layout"):
  --kv-block N       paged KV caches: N-token blocks + per-slot block
                     tables instead of pad-to-max_seq rows (0 = padded;
                     N must divide max_seq)
  --kv-blocks N      paged pool size incl. the scratch block (default:
                     worst case, max_batch * max_seq/kv_block + 1 —
                     shrink it to serve under an HBM budget)
  --shard N,C        shard the decode batch over mesh axis n and the
                     KV heads over c (build_mesh_plan over N*C
                     devices); falls back loudly below N*C devices
  --prefix-cache     prefix sharing on the paged pool (needs
                     --kv-block; SERVING.md "Prefix sharing"):
                     ref-counted blocks + a content-hash index share
                     resident full-block prompt prefixes at admission
                     — the shared span's prefill compute is SKIPPED
                     (offset prefill; zero dispatches on a memoized
                     full hit), decode stays byte-identical to the
                     unshared run

Speculation flags (SERVING.md "Speculative decoding"):
  --speculate d      speculative decoding: draft d tokens + verify
                     d+1 in ONE fused dispatch; each round emits
                     accepted+1 tokens (clamped at 20 with the other
                     fused chains).  Greedy output is bit-identical
                     to plain decode; only the dispatch count changes.
  --draft-ckpt PATH  restore the DRAFT model's params from their own
                     training checkpoint (same architecture; default:
                     the serving params — self-draft)
  --draft-layers L   self-draft via the first L transformer blocks
                     only (0 = the full model, acceptance 1.0)

Sampling flags (greedy stays the default and the parity oracle):
  --temperature T    in-program temperature sampling (0 = greedy)
  --top-k N          restrict sampling to the N best logits (0 = all)
  --sample-seed S    base sampling seed; draws are keyed by
                     (S, request id, position) — replayable across
                     batch compositions and superstep boundaries

Scheduler flags (each enables the scheduled path):
  --sched POLICY     fifo | slo (default slo when another scheduler
                     flag is present)
  --workload-trace [SRC]  open-loop workload instead of the uniform
                     stream: bare = zipf/bursty lengths (data/trace.py
                     shape); ``prod[:alpha=A,prefix=P]`` = prompt
                     tokens read LIVE from data/trace.py
                     ProductionTraceSource (the shared power-law id
                     source); ``prefix=P`` arms the WorkloadSpec
                     shared_prefix knob — a P-token system-prompt span
                     most requests share (the prefix-cache workload)
  --trace-alpha A    zipf skew for prompt/output lengths (1.5)
  --mean-gap-ms X    mean inter-arrival gap, virtual ms (8.0)
  --burst N          requests arriving back-to-back per burst (4)
  --slo-ms X         tier-0 SLO deadline, virtual ms (tier t gets
                     X*(t+1); unset = best-effort)
  --priorities N     priority tiers, 0 = highest (1)
  --shed-depth N     shed waiting requests past this queue depth (0 =
                     off)
  --serve-auto       search (buckets x K x max_batch x kv layout x
                     policy knobs, + draft depth d when --speculate,
                     + replica count x router when --replicas > 1)
                     against the calibrated serving latency model and
                     run the winner (--calibration feeds constants)

Fleet flags (SERVING.md "Fleet"; each enables the scheduled path):
  --replicas N       run N ScheduledServer replicas behind the
                     failure-aware FleetRouter: deterministic routing
                     on the shared virtual clock, each replica with
                     its own executor and journal (--journal PATH
                     becomes PATH.rI).  A replica that exhausts its
                     --serve-max-restarts budget is marked dead and
                     its journaled in-flight work is redistributed to
                     peers (byte-identical resume); ALL replicas dead
                     exits 78 (EXIT_FLEET_FAILURE — 76/77 keep their
                     meanings)
  --router POLICY    least-loaded | tier-aware | affinity (default
                     least-loaded)

Failure-model flags (SERVING.md "Failure model"):
  --journal PATH     append-only request journal (JSONL), written at
                     the existing decode-superstep fence (no added
                     fences); re-running with the same PATH replays
                     it — completed requests are not re-run, in-flight
                     requests resume with carried tokens, byte-
                     identical to an uninterrupted run.  Also arms
                     drain-on-SIGTERM.  Works on the legacy AND the
                     scheduled path.
  --serve-retries N  per-request retry budget for slot-isolated faults
                     (deterministic exponential backoff on the virtual
                     clock; scheduled path)
  --retry-backoff-ms X  base backoff, virtual ms (8.0)
  --serve-max-restarts N  engine-restart (crash-loop) budget; budget
                     exhausted exits 77 (EXIT_SERVING_FAILURE) for an
                     external supervisor (default: cfg --max-restarts
                     when the failure model is armed)
  --expire-waiting   expire waiting requests past their deadline
                     (counted as SLO misses — attainment is goodput)

``--arrival-every`` is RETIRED (PR 12's one-release deprecation grace
is up): the run refuses it loudly — use ``--workload-trace`` or
``serving.workload.uniform_workload(every_ms=...)``.

Example::

    python -m flexflow_tpu.apps.serve --max-seq 64 --max-batch 4 \
        --decode-steps 8 --requests 8 --ckpt-dir ./ckpts
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time

from flexflow_tpu.apps.common import (
    check_help,
    enable_compile_cache,
    pop_float,
    pop_int,
)
from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import (
    build_lm,
    build_transformer_lm,
    load_model_config,
)


def _pop_str(argv, flag, default):
    if flag not in argv:
        return default
    i = argv.index(flag)
    try:
        val = argv[i + 1]
    except IndexError:
        raise SystemExit(f"{flag} expects a value")
    del argv[i:i + 2]
    return val


def _pop_flag(argv, flag):
    if flag in argv:
        argv.remove(flag)
        return True
    return False


def _pop_opt_str(argv, flag):
    """A flag with an OPTIONAL value: absent -> None, bare -> "",
    ``--flag val`` -> "val" (a following ``-...`` token is not
    consumed)."""
    if flag not in argv:
        return None
    i = argv.index(flag)
    if i + 1 < len(argv) and not argv[i + 1].startswith("-"):
        val = argv[i + 1]
        del argv[i:i + 2]
        return val
    del argv[i]
    return ""


def _dry_run(sex, decode_ks, speculate=0, replicas=1,
             router="least-loaded") -> int:
    """Compute-free serving validation: eval_shape every prefill
    bucket and every decode-superstep width the scheduler may
    dispatch (plus the draft-prefill and fused spec programs when
    speculating), print the program/cache table (the --dry-run
    contract of the training apps)."""
    decode_ks = sorted(set(decode_ks))
    table = sex.abstract_programs(decode_steps=decode_ks[-1],
                                  speculate=speculate)
    print(f"{'program':<18} {'shape':<28} notes")
    for name, aval in sorted(table["cache"].items()):
        print(f"{'cache ' + name:<18} {str(tuple(aval.shape)):<28} "
              f"{aval.dtype}")
    for bucket, aval in sorted(table["prefill"].items()):
        print(f"{'prefill L=' + str(bucket):<18} "
              f"{'(1, ' + str(bucket) + ') -> token':<28} "
              f"1 dispatch + 1 fence per admission")
    for bucket in sorted(table.get("prefill_from", {})):
        o = sex.kv_block
        print(f"{'prefill L=' + str(bucket) + ' o=' + str(o):<18} "
              f"{'(1, ' + str(bucket) + ') from row ' + str(o):<28} "
              f"offset prefill (shared prefix skipped)")
    for k in decode_ks:
        shape = (k,) + tuple(table["decode"].shape[1:])
        print(f"{'decode k=' + str(k):<18} "
              f"{str(shape) + ' tokens':<28} "
              f"1 dispatch + 1 fence per {k} tokens")
    if speculate:
        shape = tuple(table["spec"].shape)
        print(f"{'spec d=' + str(speculate):<18} "
              f"{str(shape) + ' tokens':<28} "
              f"1 dispatch + 1 fence per round "
              f"(<= {speculate + 1} accepted)")
    # The program audit over the exact serving programs this run would
    # build (purity + K-tokens-per-dispatch accounting, ANALYSIS.md) —
    # every decode width the scheduler may choose is audited.
    from flexflow_tpu import analysis
    from flexflow_tpu.runtime import telemetry as _telemetry

    if replicas > 1:
        # Routing is host-side: every replica builds this SAME program
        # family, so auditing one executor covers the fleet.
        print(f"fleet: {replicas} replicas (router={router}) x the "
              f"program family above; no extra programs")
    violations = []
    for k in decode_ks:
        violations += analysis.audit_serving(sex, decode_steps=k,
                                             speculate=speculate)
    print(analysis.summary_line(violations))
    for v in violations:
        print(f"  {v}")
    _telemetry.current().emit(
        "analysis", clean=not violations,
        violations=[str(v) for v in violations],
    )
    print("DRY RUN OK (no device compute)")
    return 0


def _latency_model(cfg: FFConfig):
    """Calibrated serving latency model: dispatch/fence constants via
    the ``-s auto`` calibration resolution (``--calibration`` wins,
    else the latest run under the telemetry dir), per-token slopes
    fitted from that run's own serving events when it has any."""
    from flexflow_tpu.apps.common import _resolve_calibration
    from flexflow_tpu.obs.reader import RunLog
    from flexflow_tpu.serving import ServingLatencyModel

    cal = _resolve_calibration(cfg)
    model = ServingLatencyModel.from_calibration(cal)
    if cal.source and os.path.isfile(cal.source):
        model = model.fit_events(
            RunLog.load(cal.source).iter_raw(), source=cal.source
        )
    return model


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    check_help(argv, __doc__)
    max_seq = pop_int(argv, "--max-seq", 64)
    max_batch = pop_int(argv, "--max-batch", 4)
    decode_steps = pop_int(argv, "--decode-steps", 8)
    n_requests = pop_int(argv, "--requests", 8)
    max_new = pop_int(argv, "--max-new", 16)
    if "--arrival-every" in argv:
        raise SystemExit(
            "--arrival-every is retired (its PR 12 deprecation grace "
            "is up): pass an open-loop workload instead — "
            "--workload-trace on this CLI, or "
            "serving.workload.uniform_workload(every_ms=...) in code."
        )
    eos = pop_int(argv, "--eos", -1)
    vocab = pop_int(argv, "--vocab", 32 * 1024)
    d_model = pop_int(argv, "--d-model", 512)
    heads = pop_int(argv, "--heads", 8)
    layers = pop_int(argv, "--layers", 4)
    model = _pop_str(argv, "--model-config", "")
    if model:
        model = load_model_config(model)
        vocab = model["vocab_size"]
    plen_s = _pop_str(argv, "--prompt-len", "4:12")
    buckets_s = _pop_str(argv, "--buckets", "")
    no_kernel = _pop_flag(argv, "--no-decode-kernel")
    kv_block = pop_int(argv, "--kv-block", 0)
    kv_blocks = pop_int(argv, "--kv-blocks", 0)
    prefix_cache = _pop_flag(argv, "--prefix-cache")
    shard_s = _pop_str(argv, "--shard", "")
    temperature = pop_float(argv, "--temperature", 0.0)
    top_k = pop_int(argv, "--top-k", 0)
    sample_seed = pop_int(argv, "--sample-seed", 0)
    speculate = pop_int(argv, "--speculate", 0)
    draft_ckpt = _pop_str(argv, "--draft-ckpt", "")
    draft_layers = pop_int(argv, "--draft-layers", 0)
    # Scheduler flags (SERVING.md "Scheduler policy"): any of them
    # routes through the SLO-aware scheduled path.
    sched_s = _pop_str(argv, "--sched", "")
    workload_trace = _pop_opt_str(argv, "--workload-trace")
    trace_alpha = pop_float(argv, "--trace-alpha", 1.5)
    mean_gap_ms = pop_float(argv, "--mean-gap-ms", 8.0)
    burst = pop_int(argv, "--burst", 4)
    slo_ms = pop_float(argv, "--slo-ms", 0.0)
    priorities = pop_int(argv, "--priorities", 0)
    shed_depth = pop_int(argv, "--shed-depth", 0)
    serve_auto = _pop_flag(argv, "--serve-auto")
    # Fleet flags (SERVING.md "Fleet").
    router_given = "--router" in argv
    replicas = pop_int(argv, "--replicas", 1)
    router = _pop_str(argv, "--router", "least-loaded")
    # Failure-model flags (SERVING.md "Failure model").
    journal_path = _pop_str(argv, "--journal", "")
    serve_retries = pop_int(argv, "--serve-retries", 0)
    retry_backoff_ms = pop_float(argv, "--retry-backoff-ms", 8.0)
    serve_max_restarts = pop_int(argv, "--serve-max-restarts", -1)
    expire_waiting = _pop_flag(argv, "--expire-waiting")
    cfg = FFConfig.parse_args(argv)
    enable_compile_cache()
    try:
        lo, hi = (int(v) for v in plen_s.split(":"))
    except ValueError:
        raise SystemExit("--prompt-len expects LO:HI")
    if sched_s and sched_s not in ("fifo", "slo"):
        raise SystemExit(f"--sched expects fifo|slo, got {sched_s!r}")
    if workload_trace not in (None, "", "zipf") \
            and not workload_trace.startswith("prod"):
        raise SystemExit(
            f"--workload-trace expects nothing, 'zipf' or "
            f"'prod[:alpha=A,prefix=P]', got {workload_trace!r}"
        )
    if prefix_cache and kv_block <= 0:
        raise SystemExit(
            "--prefix-cache shares blocks of the PAGED pool and needs "
            "--kv-block N (SERVING.md \"Prefix sharing\")"
        )
    if speculate < 0:
        raise SystemExit(f"--speculate expects d >= 0, got {speculate}")
    if replicas < 1:
        raise SystemExit(f"--replicas expects N >= 1, got {replicas}")
    if router not in ("least-loaded", "tier-aware", "affinity"):
        raise SystemExit(
            f"--router expects least-loaded|tier-aware|affinity, "
            f"got {router!r}"
        )
    if (draft_ckpt or draft_layers) and not speculate:
        raise SystemExit(
            "--draft-ckpt/--draft-layers configure the DRAFT source "
            "and need --speculate d to arm speculation"
        )
    shard = None
    if shard_s:
        try:
            sn, sc = (int(v) for v in shard_s.split(","))
        except ValueError:
            raise SystemExit("--shard expects N,C (e.g. --shard 2,2)")
        shard = (sn, sc)
    if buckets_s:
        buckets = tuple(int(b) for b in buckets_s.split(","))
    else:
        buckets = tuple(sorted({max(max_seq // 4, hi), max_seq // 2,
                                max_seq}))
    buckets = tuple(b for b in buckets if b <= max_seq)

    # Retry/expiry/restart knobs are scheduler semantics (virtual-clock
    # backoff); the journal alone stays on whichever path was chosen.
    use_sched = bool(
        sched_s or workload_trace is not None or slo_ms > 0
        or priorities > 0 or shed_depth > 0 or serve_auto
        or serve_retries > 0 or serve_max_restarts >= 0
        or expire_waiting or replicas > 1 or router_given
    )
    if not use_sched:
        return _run_legacy(
            cfg, max_seq=max_seq, max_batch=max_batch,
            decode_steps=decode_steps, n_requests=n_requests,
            max_new=max_new, eos=eos, vocab=vocab, model_cfg=model, d_model=d_model,
            heads=heads, layers=layers, lo=lo, hi=hi, buckets=buckets,
            no_kernel=no_kernel, kv_block=kv_block, kv_blocks=kv_blocks,
            prefix_cache=prefix_cache,
            shard=shard, temperature=temperature, top_k=top_k,
            sample_seed=sample_seed, journal_path=journal_path,
            speculate=speculate, draft_ckpt=draft_ckpt,
            draft_layers=draft_layers,
        )
    return _run_scheduled(
        cfg, max_seq=max_seq, max_batch=max_batch,
        decode_steps=decode_steps, n_requests=n_requests,
        max_new=max_new, eos=eos, vocab=vocab, model_cfg=model, d_model=d_model,
        heads=heads, layers=layers, lo=lo, hi=hi, buckets=buckets,
        no_kernel=no_kernel, kv_block=kv_block, kv_blocks=kv_blocks,
        prefix_cache=prefix_cache,
        shard=shard, temperature=temperature, top_k=top_k,
        sample_seed=sample_seed, policy_name=sched_s or "slo",
        workload_trace=workload_trace, trace_alpha=trace_alpha,
        mean_gap_ms=mean_gap_ms, burst=burst, slo_ms=slo_ms,
        priorities=max(priorities, 1), shed_depth=shed_depth,
        serve_auto=serve_auto, journal_path=journal_path,
        serve_retries=serve_retries, retry_backoff_ms=retry_backoff_ms,
        serve_max_restarts=serve_max_restarts,
        expire_waiting=expire_waiting, speculate=speculate,
        draft_ckpt=draft_ckpt, draft_layers=draft_layers,
        replicas=replicas, router=router,
    )


def _build_model(model, cfg, max_batch, max_seq, vocab, d_model, heads,
                 layers):
    """The served graph: from ``--model-config``'s keys, else the GPT-2
    block family at the positional widths."""
    if model:
        return build_lm(model, max_batch, max_seq, cfg)
    return build_transformer_lm(
        batch_size=max_batch, seq_len=max_seq, vocab_size=vocab,
        d_model=d_model, num_heads=heads, num_layers=layers, config=cfg,
    )


def _run_legacy(cfg, *, max_seq, max_batch, decode_steps, n_requests,
                max_new, eos, vocab, d_model, heads, layers, lo, hi, model_cfg=None,
                buckets, no_kernel, kv_block, kv_blocks, shard,
                temperature, top_k, sample_seed, prefix_cache=False,
                journal_path="", speculate=0, draft_ckpt="",
                draft_layers=0) -> int:
    """The closed-loop FIFO path — still the chaos decode-fault
    harness and the scheduler's numerics oracle."""
    from flexflow_tpu.runtime import telemetry as _telemetry
    from flexflow_tpu.runtime.serving import (
        Server,
        ServingExecutor,
        synthetic_requests,
    )
    from flexflow_tpu.serving import RequestJournal

    ff = _build_model(model_cfg, cfg, max_batch, max_seq, vocab, d_model,
                      heads, layers)
    sex = ServingExecutor(
        ff, cfg, max_batch=max_batch, max_seq=max_seq, buckets=buckets,
        decode_kernel=False if no_kernel else None,
        kv_block=kv_block, kv_blocks=kv_blocks or None, shard=shard,
        prefix_cache=prefix_cache, draft_layers=draft_layers,
    )
    if cfg.dry_run:
        # Inside maybe_run so the dry run's `analysis` audit event
        # lands in the JSONL stream when telemetry is armed.
        with _telemetry.maybe_run(cfg, meta={"app": "serve"}):
            return _dry_run(sex, [decode_steps], speculate=speculate)

    with _telemetry.maybe_run(cfg, meta={"app": "serve"}):
        if cfg.ckpt_dir:
            step, params, state = sex.restore(cfg.ckpt_dir)
            print(f"restored training checkpoint step {step} "
                  f"from {cfg.ckpt_dir}")
        else:
            params, state = sex.init(cfg.seed)
        draft_params = None
        if draft_ckpt:
            dstep, draft_params, _ds = sex.restore(draft_ckpt)
            print(f"restored draft checkpoint step {dstep} "
                  f"from {draft_ckpt}")
        requests = synthetic_requests(
            n_requests, vocab, prompt_len=(lo, hi),
            max_new_tokens=max_new, seed=cfg.seed,
        )
        srv = Server(sex, params, state, decode_steps=decode_steps,
                     eos_id=None if eos < 0 else eos,
                     temperature=temperature, top_k=top_k,
                     sample_seed=sample_seed,
                     journal=(RequestJournal(journal_path)
                              if journal_path else None),
                     speculate=speculate, draft_params=draft_params)
        t0 = time.perf_counter()
        results, stats = srv.run(requests)
        elapsed = time.perf_counter() - t0
    print(f"requests = {stats['requests']} "
          f"completed = {stats['completed']} failed = {stats['failed']}")
    _print_layout(stats)
    if stats.get("drained"):
        print(f"drained: remainder journaled in {journal_path or '?'} "
              f"(re-run with the same --journal to resume)")
    print(f"time = {elapsed:.4f}s")
    print(f"tokens/s = {stats['tokens_per_s']:.1f}")
    print(f"request latency p50 = {stats['request_latency_ms_p50']:.1f} ms "
          f"p95 = {stats['request_latency_ms_p95']:.1f} ms")
    print(f"decode supersteps = {stats['decode_supersteps']} "
          f"(k={stats['decode_steps_per_call']}, 1 dispatch + 1 fence "
          f"per superstep)")
    return _report_failures(results, stats)


def _run_scheduled(cfg, *, max_seq, max_batch, decode_steps, n_requests,
                   max_new, eos, vocab, d_model, heads, layers, lo, hi, model_cfg=None,
                   buckets, no_kernel, kv_block, kv_blocks, shard,
                   temperature, top_k, sample_seed, policy_name,
                   prefix_cache=False,
                   workload_trace, trace_alpha, mean_gap_ms, burst,
                   slo_ms, priorities, shed_depth, serve_auto,
                   journal_path="", serve_retries=0,
                   retry_backoff_ms=8.0, serve_max_restarts=-1,
                   expire_waiting=False, speculate=0, draft_ckpt="",
                   draft_layers=0, replicas=1,
                   router="least-loaded") -> int:
    from flexflow_tpu.runtime import telemetry as _telemetry
    from flexflow_tpu.runtime.serving import (
        EXIT_SERVING_FAILURE,
        ServingCrashLoop,
        ServingExecutor,
    )
    from flexflow_tpu.runtime.trainer import clamp_fused_steps
    from flexflow_tpu.serving import (
        EXIT_FLEET_FAILURE,
        FleetCrashLoop,
        FleetRouter,
        RequestJournal,
        ScheduledServer,
        SchedulerPolicy,
        ServingConfig,
        ServingResilience,
        SlotShape,
        WorkloadSpec,
        make_workload,
        production_workload,
        search_serving_config,
        uniform_workload,
    )

    decode_steps = clamp_fused_steps(decode_steps, what="decode_steps")
    resilience = ServingResilience(
        max_retries=serve_retries,
        retry_backoff_ms=retry_backoff_ms,
        max_restarts=(serve_max_restarts if serve_max_restarts >= 0
                      else cfg.max_restarts),
        expire_waiting=expire_waiting,
    ) if (serve_retries > 0 or serve_max_restarts >= 0
          or expire_waiting or journal_path) else None
    base_slo = slo_ms if slo_ms > 0 else float("inf")
    if policy_name == "fifo":
        policy = SchedulerPolicy.fifo()
    else:
        policy = SchedulerPolicy(name="slo", shed_depth=shed_depth)

    with _telemetry.maybe_run(cfg, meta={"app": "serve"}):
        model = _latency_model(cfg)
        if workload_trace is not None:
            spec = WorkloadSpec(
                n_requests=n_requests, vocab=vocab,
                prompt_len=(lo, hi), prompt_alpha=trace_alpha,
                max_new=(1, max_new), output_alpha=trace_alpha,
                mean_gap_ms=mean_gap_ms, burst=burst,
                priorities=priorities, slo_ms=base_slo, seed=cfg.seed,
            )
            if workload_trace.startswith("prod"):
                # LIVE data-plane trace: prompt tokens read from
                # data/trace.py ProductionTraceSource (shared source).
                args = workload_trace[5:] \
                    if workload_trace.startswith("prod:") else ""
                kv = dict(p.split("=", 1) for p in args.split(",") if p)
                id_alpha = float(kv.pop("alpha", 1.2))
                shared_prefix = int(kv.pop("prefix", 0))
                if kv:
                    raise SystemExit(
                        f"--workload-trace prod: unknown args "
                        f"{sorted(kv)} (supported: alpha=A, prefix=P)"
                    )
                if shared_prefix:
                    spec = dataclasses.replace(
                        spec, shared_prefix=shared_prefix
                    )
                requests = production_workload(spec, id_alpha=id_alpha)
            else:
                requests = make_workload(spec)
        else:
            requests = uniform_workload(
                n_requests, vocab, prompt_len=(lo, hi),
                max_new_tokens=max_new, seed=cfg.seed, slo_ms=base_slo,
            )

        choice = None
        if serve_auto:
            baseline = ServingConfig(
                buckets=buckets, decode_steps=decode_steps,
                max_batch=max_batch, max_seq=max_seq, policy=policy,
                kv_block=kv_block, kv_blocks=kv_blocks or None,
                prefix_cache=prefix_cache,
                shard=shard, speculate=speculate,
                replicas=replicas, router=router,
            )
            res = search_serving_config(requests, baseline, model)
            choice = res.chosen
            if choice.config.to_json() == baseline.to_json():
                print("serve-auto: the app's default serving config "
                      "already wins the searched space; keeping it")
            print(res.describe())
            print(f"serve-auto: {model.describe()}")
            buckets = choice.config.buckets
            decode_steps = choice.config.decode_steps
            max_batch = choice.config.max_batch
            policy = choice.config.policy
            kv_block = choice.config.kv_block
            kv_blocks = choice.config.kv_blocks or 0
            prefix_cache = choice.config.prefix_cache
            speculate = choice.config.speculate
            replicas = choice.config.replicas
            router = choice.config.router
            _telemetry.current().emit(
                "search", kind="serving",
                chosen=choice.config.to_json(),
                baseline=res.baseline.config.to_json(),
                predicted_p99_ms=round(choice.predicted_p99_ms, 4),
                baseline_predicted_p99_ms=round(
                    res.baseline.predicted_p99_ms, 4),
                predicted_dispatches=choice.predicted_dispatches,
                latency_model=model.to_json(),
                candidates=len(res.candidates),
                wall_s=round(res.wall_s, 3),
            )

        ff = _build_model(model_cfg, cfg, max_batch, max_seq, vocab,
                          d_model, heads, layers)

        def make_executor():
            return ServingExecutor(
                ff, cfg, max_batch=max_batch, max_seq=max_seq,
                buckets=buckets,
                decode_kernel=False if no_kernel else None,
                kv_block=kv_block, kv_blocks=kv_blocks or None,
                prefix_cache=prefix_cache,
                shard=shard, draft_layers=draft_layers,
            )

        sex = make_executor()
        srv_proto = ScheduledServer.simulated(
            SlotShape(max_batch=max_batch, max_seq=max_seq,
                      buckets=buckets, kv_block=kv_block,
                      kv_blocks=kv_blocks or None,
                      prefix_cache=prefix_cache),
            decode_steps=decode_steps, policy=policy,
            latency_model=model,
        )
        if cfg.dry_run:
            return _dry_run(sex, srv_proto._k_candidates,
                            speculate=speculate, replicas=replicas,
                            router=router)

        if cfg.ckpt_dir:
            step, params, state = sex.restore(cfg.ckpt_dir)
            print(f"restored training checkpoint step {step} "
                  f"from {cfg.ckpt_dir}")
        else:
            params, state = sex.init(cfg.seed)
        draft_params = None
        if draft_ckpt:
            dstep, draft_params, _ds = sex.restore(draft_ckpt)
            print(f"restored draft checkpoint step {dstep} "
                  f"from {draft_ckpt}")

        def make_server(sex_i, journal_i):
            return ScheduledServer(
                sex_i, params, state, decode_steps=decode_steps,
                eos_id=None if eos < 0 else eos, policy=policy,
                latency_model=model, temperature=temperature,
                top_k=top_k, sample_seed=sample_seed,
                resilience=resilience, journal=journal_i,
                speculate=speculate, draft_params=draft_params,
            )

        t0 = time.perf_counter()
        if replicas > 1:
            # The fleet: replica 0 reuses the executor built above,
            # peers get their own (each owns programs + caches;
            # params/state are shared).  --journal PATH fans out to
            # per-replica PATH.rI files — the redistribution medium.
            servers = []
            for i in range(replicas):
                sex_i = sex if i == 0 else make_executor()
                jr = RequestJournal(f"{journal_path}.r{i}") \
                    if journal_path else None
                servers.append(make_server(sex_i, jr))
            fleet = FleetRouter(servers, router=router)
            try:
                results, stats = fleet.run(requests)
            except FleetCrashLoop as e:
                print(f"fleet crash: {e}", file=sys.stderr)
                print(f"exiting {EXIT_FLEET_FAILURE} for the external "
                      f"supervisor (every replica's restart budget "
                      f"exhausted; the per-replica journals carry "
                      f"completed + in-flight state)")
                return EXIT_FLEET_FAILURE
        else:
            srv = make_server(sex, RequestJournal(journal_path)
                              if journal_path else None)
            try:
                results, stats = srv.run(requests)
            except ServingCrashLoop as e:
                print(f"serving crash loop: {e}", file=sys.stderr)
                print(f"exiting {EXIT_SERVING_FAILURE} for the external "
                      f"supervisor (engine restart budget exhausted; "
                      f"the journal carries completed + in-flight "
                      f"state)")
                return EXIT_SERVING_FAILURE
        elapsed = time.perf_counter() - t0

    print(f"policy = {policy.describe()}")
    if replicas > 1:
        print(f"fleet = {stats['replicas']} replicas "
              f"router={stats['router']} "
              f"live={stats['live_replicas']} "
              f"dead={stats['dead_replicas']} "
              f"redistributed={stats['redistributed']}")
    print(f"requests = {stats['requests']} "
          f"completed = {stats['completed']} failed = {stats['failed']} "
          f"shed = {stats['request_sheds']} "
          f"preempted = {stats['request_preempts']}")
    _print_layout(stats)
    print(f"time = {elapsed:.4f}s")
    print(f"tokens/s = {stats['tokens_per_s']:.1f}")
    print(f"queue wait p50 = {stats['queue_wait_ms_p50']:.1f} ms "
          f"p95 = {stats['queue_wait_ms_p95']:.1f} ms "
          f"p99 = {stats['queue_wait_ms_p99']:.1f} ms (virtual)")
    print(f"e2e p50 = {stats['e2e_ms_p50']:.1f} ms "
          f"p99 = {stats['e2e_ms_p99']:.1f} ms (virtual)")
    if "slo_attainment" in stats:
        print(f"SLO attainment = {stats['slo_attainment'] * 100:.1f}%")
    if stats.get("slo_autopsy"):
        # Tail autopsy (OBSERVABILITY.md "Reading a request"):
        # per-tier dominant phase over the misses; waterfalls via
        # `python -m flexflow_tpu.obs request`.
        for tier, row in stats["slo_autopsy"].items():
            print(f"slo autopsy tier {tier}: {row['missed']} missed, "
                  f"dominant phase = {row['dominant_phase']}")
    print(f"decode supersteps = {stats['decode_supersteps']} "
          f"(k<={stats['decode_steps_per_call']}, 1 dispatch + 1 fence "
          f"per superstep)")
    if stats.get("request_retries") or stats.get("request_expiries") \
            or stats.get("engine_restarts"):
        print(f"failure model: retries = {stats['request_retries']} "
              f"expiries = {stats['request_expiries']} "
              f"engine restarts = {stats['engine_restarts']}")
    if stats.get("degraded_rungs"):
        print(f"DEGRADED: rungs taken = "
              f"{', '.join(stats['degraded_rungs'])}")
    if stats.get("drained"):
        print(f"drained: remainder journaled in {journal_path or '?'} "
              f"(re-run with the same --journal to resume)")
    if choice is not None:
        print(f"serve-auto: predicted e2e p99 "
              f"{choice.predicted_p99_ms:.3f} ms, measured "
              f"{stats['e2e_ms_p99']:.3f} ms (virtual clock); "
              f"predicted dispatches {choice.predicted_dispatches}, "
              f"executed "
              f"{stats['prefills'] + stats['decode_supersteps']}")
    return _report_failures(results, stats)


def _print_layout(stats) -> None:
    if stats.get("kv_layout") == "paged":
        print(f"kv layout = paged ({stats['kv_blocks']} x "
              f"{stats['kv_block']}-token blocks incl. scratch)")
    if stats.get("prefix_cache"):
        print(f"prefix cache = {stats['prefix_hits']} hits "
              f"(rate {stats['prefix_hit_rate'] * 100:.1f}%), "
              f"{stats['prefill_tokens_saved']} prefill tokens saved, "
              f"{stats['kv_cows']} CoW blocks")
    if stats.get("shard"):
        n, c = stats["shard"]
        print(f"mesh shard = batch n={n} x heads c={c}")
    if stats.get("sampled"):
        print("sampling = seeded temperature/top-k (replayable)")
    if stats.get("speculate"):
        print(f"speculation = d={stats['speculate']} "
              f"(draft_layers={stats['draft_layers']}, acceptance "
              f"{stats['spec_acceptance_rate'] * 100:.1f}%, "
              f"{stats['spec_tokens_per_dispatch']:.2f} tokens/"
              f"dispatch, {stats['draft_prefills']} draft prefills)")


def _report_failures(results, stats) -> int:
    if stats["failed"]:
        for rid in sorted(results):
            r = results[rid]
            if r.error:
                print(f"request {rid} FAILED: {r.error}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
