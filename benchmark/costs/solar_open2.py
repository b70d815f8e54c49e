"""Operations and bytes the Solar-Open2 family's serving kernels need,
from shapes and the telemetry stream's live positions and routed counts.

``kda_intra`` (``ff_kda_intra``: the two decayed Gram matrices of every
chunk of 64 of a prefill): the causal half of two ``C x C`` matrices at
one multiply-add a key channel (``2 C^2 d_k`` flops a chunk of a head);
q, k and the running log decay are read once and the two matrices
written once, float32.

``kda_chunk`` (``ff_kda_chunk``: the walk over a prefill's chunks
with the state in VMEM; the triangular systems XLA solves in between,
batched over all chunks, are no kernel's): a chunk of a head is four float32 products, ``u = u0
- w S``, ``o = q~ S + B u``, ``S' = S g + u^T k^`` (``6 C d_k d_v + 2 C^2
d_v`` flops), and reads its five operands and writes its outputs once
(four ``C x 128`` and one ``C x C`` float32 in, one ``C x 128`` out, the
chunk's decay row).  Every token of a prefill's bucket passes, the pad
too (it is computed, and leaves the state alone).

``kda_decode`` (``ff_kda_decode``, one call a delta layer a step over
every slot): a head's state tile is read and written once (``2 d_k d_v``
float32) and costs a decay, a prediction, a correction and a read
(``7 d_k d_v`` flops); the five vectors ride beside.

``gqa_decode`` (``ff_flash_decode`` under grouped queries): a live
position's K and V are read once a KV head (``2 h_kv d_head`` values)
and cost every query head a score and a value (``4 h d_head`` flops);
queries and outputs ride beside.

``gqa_prefill`` (``ff_flash_fwd_uneven`` over the bucket, causal: half
the square): ``4 d_head`` flops a (query, key) pair a query head; q and
o move once a query head, k and v once a KV head.

``grouped_matmul`` (the expert layers' two calls a forward): ``6 d f``
flops an assignment that falls on a held expert; each held expert that
received a token has its three matrices read once a forward, and each
such assignment's rows go in and out of both calls.  The stream counts
the held experts touched; the assignments that fall on them are taken
at their expectation, ``tokens x top_k x held / routed`` (the router's
scores are near uniform under seeded weights).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

ITEM = 2   # bf16, the dtype the configuration computes in
CHUNK = 64  # flexflow_tpu/ops/pallas_kernels.py::KDA_CHUNK


def _layers(cfg) -> Tuple[int, int]:
    """``(grouped-query layers, delta layers)`` among the layers held."""
    gqa = sum(1 for i in range(cfg["num_hidden_layers"]) if i in cfg["gqa_layers"])
    return gqa, cfg["num_hidden_layers"] - gqa


def live_columns(rctx: Dict[str, Any]) -> float:
    """Cache positions the decode kernel's calls of this window had to
    read, over the grouped-query layers: a slot at position p reads p +
    1, in each of the k steps of a superstep (an empty slot reads the
    positions below its stale position 0, 1, ..)."""
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
    return cols * _layers(rctx["config"])[0]


def _forwards(rctx):
    """``(tokens, held experts touched a layer)`` of every forward of the
    expert layers in the window: ``k`` a decode superstep, one a prefill.
    Events from before the counters existed give nothing."""
    out = []
    for e in rctx["events"]:
        if "experts_touched" not in e:
            continue
        if e["ev"] == "decode_superstep":
            out += [(rctx["traffic"]["slots"], e["experts_touched"])] * e["k"]
        elif e["ev"] == "prefill":
            out.append((e["bucket"], e["experts_touched"]))
    return out


def _prefill_buckets(rctx):
    return [e["bucket"] for e in rctx["events"] if e["ev"] == "prefill"]


def kernel_cost(kind: str, rctx: Dict[str, Any], calls: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of the ``calls`` calls of kernel ``kind`` the
    trace shows in this cell's window."""
    cfg, traffic = rctx["config"], rctx["traffic"]
    lin = cfg["linear_attn_config"]
    nh, dk = lin["num_heads"], lin["head_dim"]
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    gqa_layers, kda_layers = _layers(cfg)
    if kind in ("kda_intra", "kda_chunk"):
        tokens = sum(_prefill_buckets(rctx)) * kda_layers * nh      # (token, head) pairs
        if kind == "kda_intra":
            return 2.0 * CHUNK * dk * tokens, 4 * (3 * dk + 2 * CHUNK) * tokens
        flops = 6.0 * dk * dk + 2.0 * CHUNK * dk
        byts = 4 * (5 * dk + CHUNK + dk / CHUNK)
        return flops * tokens, byts * tokens
    if kind == "kda_decode":
        heads = calls * traffic["slots"] * nh
        return 7.0 * dk * dk * heads, (2 * dk * dk + 6 * dk) * 4 * heads
    if kind == "gqa_decode":
        cols = live_columns(rctx)
        qo = 2 * calls * traffic["slots"] * h * hd * ITEM
        return 4.0 * h * hd * cols, 2.0 * hkv * hd * ITEM * cols + qo
    if kind == "gqa_prefill":
        flops = byts = 0.0
        for t in _prefill_buckets(rctx):
            flops += h * t * t / 2.0 * 4 * hd
            byts += 2 * (h + hkv) * t * hd * ITEM
        return flops * gqa_layers, byts * gqa_layers
    if kind == "grouped_matmul":
        d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
        share = len(cfg["held_experts"]) / cfg["published"]["n_routed_experts"]
        flops = byts = 0.0
        for tokens, touched in _forwards(rctx):
            assigned = tokens * cfg["num_experts_per_tok"] * share
            flops += assigned * 6.0 * d * f
            byts += (touched * 3 * d * f + assigned * 2 * (d + f)) * ITEM
        layers = cfg["num_hidden_layers"]
        return flops * layers, byts * layers
    raise KeyError(kind)
