"""Operations and bytes the DeepSeek-V3 family's serving kernels need,
from shapes and the telemetry stream's live rows and routed counts.

``mla_decode`` (absorbed latent decode): a live cache column is read
once, ``row = kv_lora_rank + qk_rope_head_dim`` values, and costs every
head a score over the whole column and a value over its latent part:
``2 h (row + kv_lora_rank)`` flops; the queries and outputs ride beside.

``grouped_matmul`` (the expert layers' two calls a forward: gate and up
fused, then down): ``6 d f`` flops an assignment; each expert that
received a token has its three matrices read once a forward, and each
assignment's rows go in and out of both calls.  Tile padding is not
work.  The assignments of a decode step are ``slots x top_k`` (an empty
slot still routes its stale token); of a prefill, ``bucket x top_k``.

``flash_fwd_uneven`` (expanded prefill attention, causal: half the
square): ``2 (qk + v)`` flops a (query, key) pair a head; q, k, v and o
move once.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

ITEM = 2  # bf16, the dtype the configuration computes in


def _moe_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def live_columns(rctx: Dict[str, Any]) -> float:
    """Cache columns the decode kernel's calls of this window had to
    read, over every layer: a slot at position p reads p + 1, in each of
    the k steps of a superstep (an empty slot reads the columns below
    its stale position 0, 1, ..)."""
    traffic = rctx["traffic"]
    plen = {r["id"]: len(r["prompt"]) for r in rctx["result"]["backlog"]}
    budget = {r["id"]: r["max_new_tokens"] for r in rctx["result"]["backlog"]}
    made: Dict[int, int] = {}
    cols = 0.0
    for e in rctx["events"]:
        if e["ev"] != "decode_superstep":
            continue
        k = e["k"]
        for rid in e["slots"]:
            done = made.get(rid, 1)  # the prefill made the first token
            cols += k * (plen[rid] + done) + k * (k - 1) / 2.0
            made[rid] = min(budget[rid], done + k)
        cols += (traffic["slots"] - len(e["slots"])) * k * (k + 1) / 2.0
    return cols * rctx["config"]["num_hidden_layers"]


def _forwards(rctx):
    """``(assignments, experts touched a layer)`` of every forward of the
    expert layers in the window: ``k`` a decode superstep, one a prefill.
    Events from before the counters existed give nothing."""
    cfg, traffic = rctx["config"], rctx["traffic"]
    top_k = cfg["num_experts_per_tok"]
    out = []
    for e in rctx["events"]:
        if "experts_touched" not in e:
            continue
        if e["ev"] == "decode_superstep":
            out += [(traffic["slots"] * top_k, e["experts_touched"])] * e["k"]
        elif e["ev"] == "prefill":
            out.append((e["bucket"] * top_k, e["experts_touched"]))
    return out


def kernel_cost(kind: str, rctx: Dict[str, Any], calls: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of the ``calls`` calls of kernel ``kind`` the
    trace shows in this cell's window."""
    cfg, traffic = rctx["config"], rctx["traffic"]
    h, d = cfg["num_attention_heads"], cfg["hidden_size"]
    if kind == "mla_decode":
        r = cfg["kv_lora_rank"]
        row = r + cfg["qk_rope_head_dim"]
        cols = live_columns(rctx)
        qo = calls * traffic["slots"] * h * (row + r) * ITEM
        return 2.0 * h * (row + r) * cols, row * ITEM * cols + qo
    if kind == "grouped_matmul":
        f, layers = cfg["moe_intermediate_size"], _moe_layers(cfg)
        flops = byts = 0.0
        for assigned, touched in _forwards(rctx):
            flops += layers * assigned * 6.0 * d * f
            byts += layers * (touched * 3 * d * f + assigned * 2 * (d + f)) * ITEM
        return flops, byts
    if kind == "flash_fwd_uneven":
        qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        v = cfg["v_head_dim"]
        flops = byts = 0.0
        for e in rctx["events"]:
            if e["ev"] == "prefill":
                t = e["bucket"]
                flops += h * t * t / 2.0 * 2 * (qk + v)
                byts += h * t * 2 * (qk + v) * ITEM
        layers = cfg["num_hidden_layers"]
        return flops * layers, byts * layers
    raise KeyError(kind)
