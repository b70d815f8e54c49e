"""Operations and bytes the A.X-K2 family's serving kernels need, from
shapes and the telemetry stream's buckets and routed counts.

``flash_fwd_uneven`` (the leading ``index_topk`` rows of a prefill, where
every row keeps its whole past: expanded latent attention, causal, half
the square): ``2 (qk + v)`` flops a (query, key) pair a head; q, k, v and
o move once.  The rows past ``index_topk`` run masked products outside
any kernel.

``grouped_matmul`` (the expert layers' two calls a forward): ``6 d f``
flops an assignment that falls on a held expert; each held expert that
received a token has its three matrices read once a forward, and each
such assignment's rows go in and out of both calls.  The stream counts
the held experts touched; the assignments that fall on them are taken at
their expectation, ``tokens x top_k x held / routed``: group-limited
routing keeps that mean (a group is kept ``topk_group / n_group`` of the
time and then holds ``n_group / topk_group`` times its uniform share).

A decode step's selection (scores over the selector's keys, the top-k,
the gather of the chosen latent rows and the absorbed attention over
them) is plain XLA: no kernel, so no roofline share.  What a kernel for
it would have to move and do, for the PR that writes one: a selected latent
row once for all heads, ``kv_lora_rank + qk_rope_head_dim`` values, at
``2 h (row + kv_lora_rank)`` flops (``decode_superstep.kv_rows_fetched``
rows a layer, the 2048 gathered and not the whole cache); an indexer key
once, ``index_head_dim`` values, at ``2 index_n_heads index_head_dim``
flops (``idx_rows_fetched``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.costs.solar_open2 import _forwards  # (tokens, held experts touched) a forward

ITEM = 2   # bf16, the dtype the configuration computes in


def kernel_cost(kind: str, rctx: Dict[str, Any], calls: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of the ``calls`` calls of kernel ``kind`` the
    trace shows in this cell's window."""
    cfg = rctx["config"]
    h, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    if kind == "flash_fwd_uneven":
        wide = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
        flops = byts = 0.0
        for e in rctx["events"]:
            if e["ev"] == "prefill":
                t = min(e["bucket"], cfg["index_topk"])
                flops += h * t * t / 2.0 * 2 * wide
                byts += h * t * 2 * wide * ITEM
        return flops * layers, byts * layers
    if kind == "grouped_matmul":
        d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
        share = len(cfg["held_experts"]) / cfg["published"]["n_routed_experts"]
        flops = byts = 0.0
        for tokens, touched in _forwards(rctx):
            assigned = tokens * cfg["num_experts_per_tok"] * share
            flops += assigned * 6.0 * d * f
            byts += (touched * 3 * d * f + assigned * 2 * (d + f)) * ITEM
        sparse = layers - cfg["first_k_dense_replace"]
        return flops * sparse, byts * sparse
    raise KeyError(kind)
