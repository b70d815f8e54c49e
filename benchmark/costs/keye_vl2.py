"""Operations and bytes the Keye-VL-2.0 family's serving kernels need,
from shapes and the telemetry stream's buckets and routed counts.

``gqa_prefill`` (``ff_flash_fwd_uneven``: the leading ``topk`` rows of a
prefill, where every row keeps its whole past, causal: half the square):
``4 d_head`` flops a (query, key) pair a query head; q and o move once a
query head, k and v once a KV head.  The rows past ``topk`` run masked
products outside any kernel.

``grouped_matmul`` (the expert layers' two calls a forward): ``6 d f``
flops an assignment; each expert that received a token has its three
matrices read once a forward, and each assignment's rows go in and out
of both calls.  Every expert is held.

A decode step's selection (scores over the selector's keys, the top-k,
the gather of the chosen rows of K and V and the attention over them) is
plain XLA: no kernel, so no roofline share.  What a kernel for it would
have to move and do, for the PR that writes one: a selected row's K and
V once a KV head at ``4 group d_head`` flops a KV head
(``decode_superstep.kv_rows_fetched`` rows a layer); an indexer key once
at ``2 heads d_index`` flops (``idx_rows_fetched``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.costs.deepseek_v3 import _forwards  # (assignments, experts touched) a forward

ITEM = 2   # bf16, the dtype the configuration computes in


def kernel_cost(kind: str, rctx: Dict[str, Any], calls: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of the ``calls`` calls of kernel ``kind`` the
    trace shows in this cell's window."""
    cfg = rctx["config"]
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    layers, topk = cfg["num_hidden_layers"], cfg["sa_config"]["topk"]
    if kind == "gqa_prefill":
        flops = byts = 0.0
        for e in rctx["events"]:
            if e["ev"] == "prefill":
                t = min(e["bucket"], topk)
                flops += h * t * t / 2.0 * 4 * hd
                byts += 2 * (h + hkv) * t * hd * ITEM
        return flops * layers, byts * layers
    if kind == "grouped_matmul":
        d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
        flops = byts = 0.0
        for assigned, touched in _forwards(rctx):
            flops += assigned * 6.0 * d * f
            byts += (touched * 3 * d * f + assigned * 2 * (d + f)) * ITEM
        return flops * layers, byts * layers
    raise KeyError(kind)
