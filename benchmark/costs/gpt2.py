"""Operations and bytes GPT-2 needs, from shapes alone.

Training: ``6 * N`` a token over the matmul parameters (the blocks'
``12 d^2`` a layer and the untied head's ``V d``; the token and position
tables are lookups) plus causal attention ``6 L s d`` a token (QK^T and
PV at ``2 s d`` each forward over the lower triangle, three times that
with the backward).  Nothing recomputed is counted.

The flash kernels, one call over ``(b*h, t, hd)`` in the compute dtype,
causal (half the square): forward ``4`` flops a (q, k, hd) triple; the
dq kernel recomputes the scores and forms dP and dQ, ``6``; the dkv
kernel recomputes the scores and forms dV, dP and dK, ``8``.  Bytes are
each operand read or written once.  The decode kernel reads the live
K/V rows once (``2 h hd`` values a row), with q and o beside them, and
spends ``4 h hd`` flops a row.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def matmul_params(cfg: Dict[str, Any]) -> int:
    d = cfg["n_embd"]
    return cfg["n_layer"] * 12 * d * d + cfg["vocab_size"] * d


def train_flops_per_item(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    """FLOPs of forward and backward for one token at ``seq_len``."""
    return 6.0 * matmul_params(cfg) + 6.0 * cfg["n_layer"] * traffic["seq_len"] * cfg["n_embd"]


def _flash_shapes(cfg, traffic, batch):
    h = cfg["n_head"]
    return batch * h, traffic["seq_len"], cfg["n_embd"] // h


def kernel_cost(kind: str, rctx: Dict[str, Any], calls: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of ``calls`` calls of kernel ``kind`` in this
    cell's window."""
    cfg, traffic = rctx["config"], rctx["traffic"]
    item = 2  # bf16, the dtype the configuration computes in
    if kind in ("flash_fwd", "flash_dq", "flash_dkv"):
        bh, t, hd = _flash_shapes(cfg, traffic, rctx["result"]["batch"])
        tri = bh * t * t * hd / 2.0
        tile, row = bh * t * hd * item, bh * t * 4
        flops = {"flash_fwd": 4, "flash_dq": 6, "flash_dkv": 8}[kind] * tri
        byts = {"flash_fwd": 4 * tile + row,            # q k v -> o, lse
                "flash_dq": 5 * tile + 2 * row,         # q k v do -> dq; lse, delta
                "flash_dkv": 6 * tile + 2 * row}[kind]  # q k v do -> dk dv; lse, delta
        return flops * calls, byts * calls
    if kind == "flash_decode":
        # Live rows of every call in the window, from the telemetry
        # stream: a slot at position p reads p + 1 rows, in each of the
        # k steps of a superstep, in every layer.
        h, hd = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
        rows = live_rows(rctx)
        steps = sum(e["k"] for e in rctx["events"] if e["ev"] == "decode_superstep")
        qo = 2 * steps * cfg["n_layer"] * traffic["slots"] * h * hd * item
        return 4.0 * h * hd * rows, 2.0 * h * hd * item * rows + qo
    raise KeyError(kind)


def live_rows(rctx: Dict[str, Any]) -> float:
    """K/V rows the decode kernel's calls of this window had to read."""
    traffic, layers = rctx["traffic"], rctx["config"]["n_layer"]
    plen = {r["id"]: len(r["prompt"]) for r in rctx["result"]["backlog"]}
    budget = {r["id"]: r["max_new_tokens"] for r in rctx["result"]["backlog"]}
    made: Dict[int, int] = {}
    rows = 0.0
    for e in rctx["events"]:
        if e["ev"] != "decode_superstep":
            continue
        k = e["k"]
        for rid in e["slots"]:
            done = made.get(rid, 1)  # the prefill made the first token
            pos = plen[rid] + done - 1
            rows += k * (pos + 1) + k * (k - 1) / 2.0
            made[rid] = min(budget[rid], done + k)
        rows += (traffic["slots"] - len(e["slots"])) * k * (k + 1) / 2.0
    return rows * layers
