"""Operations and bytes the LFM2-MoE family's serving kernels need, from
shapes and the telemetry stream's live positions and routed counts,
whatever implements them.

``gqa_decode`` (``ff_flash_decode`` under grouped queries, one call an
attention layer a step over every slot): a live position's K and V are
read once a cached head (``2 h_kv d_head`` values) and cost every query
head a score and a value (``4 h d_head`` flops); queries and outputs
ride beside.

``flash_fwd_uneven`` (the attention layers' prefill over the bucket,
causal: half the square): ``4 d_head`` flops a (query, key) pair a query
head; q and o move once a query head, k and v once a cached head.

``grouped_matmul`` (the expert layers' two calls a forward: gate and up
fused, then down): ``6 d f`` flops an assignment; each expert that
received a token has its three matrices read once a forward, and each
assignment's rows go in and out of both calls.  Tile padding is not
work.  Every expert is held: the assignments of a decode step are
``slots x top_k`` (an empty slot still routes its stale token); of a
prefill, ``bucket x top_k``.

The convolution layers run no kernel of their own.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.costs import deepseek_v3
from benchmark.costs.deepseek_v3 import _forwards  # (assignments, experts touched) a forward

ITEM = 2  # bf16, the dtype the configuration computes in


def _layers(cfg) -> Tuple[int, int]:
    """``(attention layers, expert layers)`` among the layers held."""
    n = cfg["num_hidden_layers"]
    attn = sum(1 for kind in cfg["layer_types"][:n] if kind == "full_attention")
    return attn, n - cfg["num_dense_layers"]


def live_columns(rctx: Dict[str, Any]) -> float:
    """Cache positions the decode kernel's calls of this window had to
    read, over the attention layers: the latent family's count (a slot
    at position p reads p + 1 in each of the k steps of a superstep, an
    empty slot the positions below its stale one) over as many layers
    as cache by position here."""
    return deepseek_v3.live_columns(
        {**rctx, "config": {"num_hidden_layers": _layers(rctx["config"])[0]}})


def kernel_cost(kind: str, rctx: Dict[str, Any], calls: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of the ``calls`` calls of kernel ``kind`` the
    trace shows in this cell's window."""
    cfg, traffic = rctx["config"], rctx["traffic"]
    d, h, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    attn_layers, moe_layers = _layers(cfg)
    if kind == "gqa_decode":
        cols = live_columns(rctx)
        qo = 2 * calls * traffic["slots"] * h * hd * ITEM
        return 4.0 * h * hd * cols, 2.0 * hkv * hd * ITEM * cols + qo
    if kind == "flash_fwd_uneven":
        flops = byts = 0.0
        for e in rctx["events"]:
            if e["ev"] == "prefill":
                t = e["bucket"]
                flops += h * t * t / 2.0 * 4 * hd
                byts += 2 * (h + hkv) * t * hd * ITEM
        return flops * attn_layers, byts * attn_layers
    if kind == "grouped_matmul":
        f = cfg["moe_intermediate_size"]
        flops = byts = 0.0
        for assigned, touched in _forwards(rctx):
            flops += moe_layers * assigned * 6.0 * d * f
            byts += moe_layers * (touched * 3 * d * f + assigned * 2 * (d + f)) * ITEM
        return flops, byts
    raise KeyError(kind)
