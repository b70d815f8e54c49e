"""Operations and bytes the Laguna family's serving kernels need, from
shapes and the telemetry stream's slots, positions and routed counts.
Each function counts the work the mathematics asks for, whatever
implements it.

``window_prefill`` (``ff_flash_fwd_window`` over the bucket on the window
layers): query ``t`` meets ``min(t + 1, W)`` keys, ``T W - W (W - 1) / 2``
pairs a query head over a bucket of ``T``, ``4 d_head`` flops a pair; q
and o move once a query head, k and v once a KV head (each block of the
band read once).

``gqa_prefill`` (``ff_flash_fwd_uneven`` over the bucket on the full
layers, causal: half the square): ``4 d_head`` flops a (query, key) pair
a query head; q and o once a query head, k and v once a KV head.

``gqa_decode`` (``ff_flash_decode``, one call a layer a step over every
slot): a live position's K and V are read once a KV head (``2 h_kv
d_head`` values) and cost every query head of the layer a score and a
value (``4 h d_head`` flops): every live position on a full layer, the
``min(live, W)`` of its ring on a window layer; queries and outputs ride
beside.

``grouped_matmul`` (the expert layers' two calls a forward): ``6 d f``
flops an assignment that falls on a held expert; each held expert that
received a token has its three matrices read once a forward, and each
such assignment's rows go in and out of both calls.  The stream counts
the held experts touched; the assignments that fall on them are taken at
their expectation, ``tokens x top_k x held / routed`` (the router's
scores are near uniform under seeded weights).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from benchmark.costs.solar_open2 import _forwards  # (tokens, held experts touched) a forward

ITEM = 2   # bf16, the dtype the configuration computes in


def _layers(cfg) -> List[Tuple[int, bool]]:
    """``(query heads, under a window)`` of every layer held."""
    n = cfg["num_hidden_layers"]
    return [(h, kind == "sliding_attention") for h, kind in
            zip(cfg["num_attention_heads_per_layer"][:n], cfg["layer_types"][:n])]


def live_columns(rctx: Dict[str, Any], window: int = 0) -> float:
    """Cache positions one layer's decode calls of this window had to
    read: a slot at position p reads p + 1 (at most ``window`` of them
    where there is one), in each of the k steps of a superstep (an empty
    slot reads the positions below its stale position 0, 1, ..)."""
    traffic = rctx["traffic"]
    plen = {r["id"]: len(r["prompt"]) for r in rctx["result"]["backlog"]}
    budget = {r["id"]: r["max_new_tokens"] for r in rctx["result"]["backlog"]}
    made: Dict[int, int] = {}
    cols = 0.0

    def read(first: int, k: int) -> float:
        return float(sum(min(first + j, window) if window else first + j
                         for j in range(k)))

    for e in rctx["events"]:
        if e["ev"] != "decode_superstep":
            continue
        k = e["k"]
        for rid in e["slots"]:
            done = made.get(rid, 1)  # the prefill made the first token
            cols += read(plen[rid] + done, k)
            made[rid] = min(budget[rid], done + k)
        cols += (traffic["slots"] - len(e["slots"])) * read(1, k)
    return cols


def kernel_cost(kind: str, rctx: Dict[str, Any], calls: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of the ``calls`` calls of kernel ``kind`` the
    trace shows in this cell's window."""
    cfg, traffic = rctx["config"], rctx["traffic"]
    hkv, hd, w = cfg["num_key_value_heads"], cfg["head_dim"], cfg["sliding_window"]
    layers = _layers(cfg)
    buckets = [e["bucket"] for e in rctx["events"] if e["ev"] == "prefill"]
    if kind in ("window_prefill", "gqa_prefill"):
        flops = byts = 0.0
        for h, ring in layers:
            if ring != (kind == "window_prefill"):
                continue
            for t in buckets:
                pairs = t * w - w * (w - 1) / 2.0 if ring and t > w else t * (t + 1) / 2.0
                flops += h * pairs * 4 * hd
                byts += 2 * (h + hkv) * t * hd * ITEM
        return flops, byts
    if kind == "gqa_decode":
        full, ring = live_columns(rctx), live_columns(rctx, w)
        steps = calls / len(layers)
        flops = byts = 0.0
        for h, under in layers:
            cols = ring if under else full
            flops += 4.0 * h * hd * cols
            byts += 2.0 * hkv * hd * ITEM * cols + 2 * steps * traffic["slots"] * h * hd * ITEM
        return flops, byts
    if kind == "grouped_matmul":
        d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
        share = len(cfg["held_experts"]) / cfg["published"]["num_experts"]
        sparse = sum(k != "dense" for k in cfg["mlp_layer_types"][:cfg["num_hidden_layers"]])
        flops = byts = 0.0
        for tokens, touched in _forwards(rctx):
            assigned = tokens * cfg["num_experts_per_tok"] * share
            flops += assigned * 6.0 * d * f
            byts += (touched * 3 * d * f + assigned * 2 * (d + f)) * ITEM
        return flops * sparse, byts * sparse
    raise KeyError(kind)
