"""The registered telemetry event-name catalog (OBSERVABILITY.md).

ONE name set, living next to the schema table's code home: the write
side (``runtime/telemetry.py``) emits these, the read side
(``obs/reader.py``) validates against them, and fflint rule FF008
(``analysis/lint.py``) rejects ``emit`` call sites outside the
telemetry module that use a name not registered here — the
schema-drift guard.  Adding an event = add the OBSERVABILITY.md row
AND the name here (the lint module keeps a dependency-free copy,
sync-pinned by ``tests/test_obs.py``).

Beside the events, the three kinds of name a profiler trace carries
(OBSERVABILITY.md "Spans, kernels, scopes"), under the same rule and
the same pin: host spans (``telemetry.span``), Pallas kernels
(``pallas_call(name=...)``) and device phases (``jax.named_scope``).
What reads a trace (``obs/trace.py``, the benchmark's metric files)
finds them by these names and by nothing else.

This module imports nothing (no jax) so every reader — the obs CLI,
the lint sync pin, offline tools — can load it anywhere.
"""

from __future__ import annotations

#: Every event type the runtime may emit, one per OBSERVABILITY.md
#: schema row.  frozenset: membership is the only operation.
EVENT_CATALOG = frozenset({
    # lifecycle
    "run_start",
    "run_end",
    # training loop
    "step",
    "input_wait",
    "superstep",
    "fence",
    "compiled_step",
    "program_cost",
    "program_build",
    "embedding_gather",
    "embedding_combine",
    "embedding_rows",
    # checkpoint / resilience
    "ckpt_save",
    "ckpt_restore",
    "ckpt_torn",
    "fault",
    "rollback",
    "replay",
    "preempt",
    # watchdog
    "stall",
    "stall_recovered",
    # static analysis + execution search
    "analysis",
    "search",
    # serving (SERVING.md)
    "serve_run",
    "request_start",
    "kv_wait",
    "prefill",
    "prefix_hit",
    "kv_cow",
    "decode_superstep",
    "spec_verify",
    "request_end",
    "serving_program",
    # serving scheduler (SERVING.md "Scheduler policy")
    "sched_decision",
    "request_preempt",
    "request_shed",
    # serving failure model (SERVING.md "Failure model")
    "request_retry",
    "request_expire",
    "serving_drain",
    "engine_restart",
    "degraded_mode",
    # serving fleet (SERVING.md "Fleet")
    "replica_route",
    "replica_loss",
    "fleet_state",
    # multi-host / elastic (RESILIENCE.md "Host loss & elastic resize")
    "distributed_init",
    "elastic_resize",
})

#: Host spans (``telemetry.span``: a ``jax.profiler.TraceAnnotation``,
#: so on the profiler's clock beside the device operations).  The eight
#: of ``Server.run`` tile its loop; ``ScheduledServer``'s real engine
#: opens the dispatch and fence names at the same calls.
SPAN_CATALOG = frozenset({
    "ff/serve/admit",
    "ff/serve/prefill_dispatch",
    "ff/serve/prefill_fence",
    "ff/serve/install",
    "ff/serve/decode_pack",
    "ff/serve/decode_dispatch",
    "ff/serve/decode_fence",
    "ff/serve/bookkeep",
})

#: ``name=`` of every ``pl.pallas_call`` (``ops/pallas_kernels.py``).
KERNEL_CATALOG = frozenset({
    "ff_flash_fwd",
    "ff_flash_dq",
    "ff_flash_dkv",
    "ff_flash_decode",
    "ff_flash_fwd_uneven",
    "ff_flash_fwd_window",
    "ff_attend_kept",
    "ff_mla_decode",
    "ff_grouped_matmul",
    # No kernel carries it since PR 46 (``ff_kda_chunk`` makes the Gram
    # matrices itself).  benchmark/metrics/kernel_roofline.kda_chunk.json
    # still lists its pattern, and the guard on the metric files holds
    # every pattern to this catalog: it goes with that entry.
    "ff_kda_intra",
    "ff_kda_chunk",
    "ff_kda_decode",
    "ff_softmax_xent_fwd",
    "ff_softmax_xent_bwd",
    "ff_gather_rows",
    "ff_scatter_add_rows",
})

#: ``jax.named_scope`` phases beside the per-op scopes (``op.name``):
#: of a train step the loss ops and every optimizer update; of a serving
#: program the two parts of a token selector, a gated norm's gate, the
#: group step of a router and a short convolution's window write.
SCOPE_CATALOG = frozenset({
    "ff_loss",
    "ff_opt",
    # inside an attention op that selects (ops/token_select.py): the
    # selector's projections and scores; its top-k and the row gather
    "ff_index",
    "ff_select",
    # inside a gated RMSNorm (ops/norm.py): the low-rank gate's two maps,
    # its sigmoid and the product
    "ff_gnorm",
    # inside an expert layer's router under expert groups (ops/moe.py):
    # the groups' standing, the kept groups and the mask
    "ff_route_group",
    # inside a gated short convolution (ops/short_conv.py): the window a
    # slot keeps, shifted by a decode step or picked at a prefill's length
    "ff_conv_state",
})

#: ``run_end.exit`` classifications (the reader adds ``truncated`` for
#: logs that never reached ``run_end`` at all).
EXIT_CLEAN = "clean"
EXIT_PREEMPT = "preempt"
EXIT_TRUNCATED = "truncated"


def exit_exception(exc_type_name: str) -> str:
    """The ``exception:<type>`` exit form for ``run_end.exit``."""
    return f"exception:{exc_type_name}"
