"""Attention operators: multi-head attention with ring context parallelism.

The reference predates transformers; its long-context mechanism is the
NMT sequence decomposition — per-chunk ops with P2P state handoff
(``rnn.h:21-23``, ``rnn.cu:304-319``).  SURVEY.md §2.7 calls for that
mechanism generalized to attention: **ring attention** over the ICI
torus.  Under an ``s``-degree strategy each device owns one sequence
chunk of Q/K/V; K/V blocks rotate around the ring via ``lax.ppermute``
while each device's queries accumulate attention with a streaming
(flash-style) log-sum-exp, so the full T×T score matrix never
materializes and sequence length scales with the number of devices.

Tensor parallelism composes orthogonally: the projection weights carry
a 'c' tag on their head/output dim, so a ``c``-degree strategy gives
Megatron-style head-parallel attention via GSPMD (the analogue of the
reference Linear's column split, ``linear.cu:100-138``) — no explicit
collectives needed there.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec

from flexflow_tpu.initializers import GlorotUniform, OnesInitializer, ZeroInitializer
from flexflow_tpu.ops import pallas_kernels
from flexflow_tpu.ops.base import CacheEntry, Op, ParamSpec, TensorSpec
from flexflow_tpu.ops.norm import rms_norm
from flexflow_tpu.ops.token_select import TokenSelector, rope_half

_NEG_INF = -1e30


# Streaming-softmax merge of flash partials, shared with the chunked
# single-device decomposition (pallas_kernels.merge_lse).
_merge_lse = pallas_kernels.merge_lse


def _einsum_decode(q, cache_k, cache_v, pos):
    """Dense reference decode attention: one query per (batch, head)
    against a (B, max_seq, h, hd) KV cache, f32 scores, masked to key
    positions ``<= pos`` (the query's own position — its K/V are
    already written into the cache).  ``q``: (B, h, hd); ``pos``: (B,)
    int32.  A cache of fewer heads is read a group of query heads a
    cached head (grouped-query attention).  The numerics oracle the
    Pallas ``flash_decode`` kernel is
    pinned against (tests/test_serving.py), and the fallback when the
    kernel does not support the cache shape."""
    dtype = q.dtype
    qf = q.astype(jnp.float32)
    kf = cache_k.astype(jnp.float32)
    vf = cache_v.astype(jnp.float32)
    group = q.shape[1] // cache_k.shape[2]
    if group > 1:
        kf, vf = (jnp.repeat(c, group, axis=2) for c in (kf, vf))
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhd,bshd->bhs", qf, kf) * scale
    mask = jnp.arange(cache_k.shape[1])[None, :] <= pos[:, None]  # (B, S)
    scores = jnp.where(mask[:, None, :], scores, _NEG_INF)
    attn = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", attn, vf).astype(dtype)


def _einsum_attention(q, k, v, causal: bool, scale: Optional[float] = None):
    """Dense reference attention on (b, h, t, hd) heads, f32 scores
    times ``scale`` (default ``hd ** -0.5``); returns the input dtype.
    The fallback when no flash formulation applies — including inside a
    ``shard_map``ped local shard, where it is numerically identical to
    the flash kernel it replaces."""
    dtype = q.dtype
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        t = scores.shape[-1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    attn = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", attn, v).astype(dtype)


def _band_attention(q, k, v, window: int):
    """Dense attention on (b, h, t, hd) heads under the causal band
    ``t - window < s <= t``, f32 scores; returns the input dtype."""
    dtype = q.dtype
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    rows = jnp.arange(q.shape[2])[:, None]
    cols = jnp.arange(k.shape[2])[None, :]
    mask = (cols <= rows) & (cols > rows - window)
    attn = jax.nn.softmax(jnp.where(mask[None, None], scores, _NEG_INF), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", attn, v).astype(dtype)


def _ring_rows(length, t: int, window: int):
    """Which of a prefill's ``t`` positions each of the ring's
    ``window`` rows takes when ``length`` of them are the prompt's: row
    ``r`` the newest ``s < length`` with ``s mod window == r`` (rows no
    position reaches yet point at some row of the call; a decode step's
    live count keeps them out)."""
    r = jnp.arange(window)
    turns = jnp.maximum(length - 1 - r, 0) // window
    return jnp.clip(r + window * turns, 0, t - 1)


def _gate_heads(out, logits, head_dim: int):
    """``out`` (b, t, heads * head_dim) times ``sigmoid(logits)`` (b, t,
    heads), one value a head: the sigmoid in f32, the product in the
    compute dtype."""
    b, t, _ = out.shape
    gate = jax.nn.sigmoid(logits.astype(jnp.float32)).astype(out.dtype)
    return (out.reshape(b, t, -1, head_dim) * gate[..., None]).reshape(out.shape)


def selected_walk(sel, t: int):
    """How ``_attend_selected`` walks ``t > sel.topk`` query rows:
    ``(c, head, groups)``.  ``c`` rows a chunk (``sel.q_chunk``, or all
    ``t`` where that does not divide them); the leading ``head`` rows,
    whole chunks under ``topk``, keep their whole past; ``groups`` the
    ``(lo, hi)`` runs of chunks that share a key width ``hi``, each run
    ending at twice its start (one compiled loop a width, and a chunk's
    selector scores at most twice the keys it can see)."""
    c = t if t % sel.q_chunk else sel.q_chunk   # one chunk: odd sizes
    head = (sel.topk // c) * c
    groups, lo = [], head
    while lo < t:
        hi = t if lo == 0 else min(2 * lo, t)
        groups.append((lo, hi))
        lo = hi
    return c, head, groups


def kept_blocks(sel, t: int, q_heads: int, k_shape, dk: int, v_width: int,
                shared: Optional[int]) -> Dict[str, Any]:
    """What a serving prefill of ``t`` rows does with its masked chunks,
    by shape (the ``serving_program`` event's fields): ``kept_kernel``,
    whether they attend through ``pallas_kernels.attend_kept``;
    ``kept_key_blocks``, the 512-key blocks they visit when they do (a
    chunk stops at its own last row), and ``kept_key_blocks_square``,
    what the runs' widths hold (what the selector scores, and what the
    ``jnp`` path attends).  One op's; zeros under ``topk``."""
    if t <= sel.topk:
        return dict(kept_kernel=False, kept_key_blocks=0,
                    kept_key_blocks_square=0)
    c, _, groups = selected_walk(sel, t)
    starts = [(s, hi) for lo, hi in groups for s in range(lo, hi, c)]
    return dict(
        kept_kernel=pallas_kernels.attend_kept_supported(
            (1, q_heads, c, dk), k_shape, v_width, shared),
        kept_key_blocks=sum(-(-(s + c) // 512) for s, _ in starts),
        kept_key_blocks_square=sum(-(-hi // 512) for _, hi in starts))


def _on_one_device(op) -> bool:
    """Whether ``op`` runs unsharded (the serving kernels' condition)."""
    plan = getattr(op, "_plan", None)
    return plan is None or plan.num_devices == 1


def causal_blocks(sel, t: int, q_heads: int, h_kv: int, dk: int,
                  v_width: int, dtype) -> Dict[str, int]:
    """What a serving prefill of ``t`` rows costs in its call of
    ``pallas_kernels.flash_fwd_uneven``, by shape (the
    ``serving_program`` event's fields): ``causal_blocks``, the key
    blocks a head visits (the ``n (n + 1) / 2`` a query can see), and
    ``causal_steps``, the grid steps a head rides (equal where the walk
    is the live one).  Under a selector ``sel`` the call covers the
    leading rows that keep their whole past.  One op's; nothing where
    the gate refuses the shape or no row goes that way."""
    if sel is not None and t > sel.topk:
        t = selected_walk(sel, t)[1]
    shape = (1, q_heads, t, dk)
    if not t or not pallas_kernels.flash_uneven_supported(shape, v_width):
        return {}
    n = t // pallas_kernels.flash_uneven_walk(shape, h_kv, v_width, dtype)[0]
    return dict(causal_blocks=n * (n + 1) // 2,
                causal_steps=len(pallas_kernels.flash_uneven_pairs(n)[0]))


def _attend_kept_heads(qc, kh, vh, keep, scale: float, shared_k=None):
    """The plain form of ``pallas_kernels.attend_kept`` (its oracle, and
    the path of the shapes its gate refuses and of a differentiated
    forward): a chunk's queries ``qc`` (b, h, c, dk) over the leading
    ``width`` keys under ``keep`` (b, c, width), a query head at a time
    (a head's scores of 512 rows against 32k keys are 64 MB in f32; all
    heads' at once would be 2 GB).  Every pair of the width is computed
    and the masked ones thrown away.  (b, h, c, dv) float32."""
    width = keep.shape[-1]
    g = qc.shape[1] // kh.shape[1]

    def one_head(j):
        q = qc[:, j]
        k, v = kh[:, j // g, :width], vh[:, j // g, :width]
        if shared_k is not None:
            own = k.shape[-1]
            s = jnp.einsum("bqd,bsd->bqs", q[..., :own], k,
                           preferred_element_type=jnp.float32) \
                + jnp.einsum("bqd,bsd->bqs", q[..., own:],
                             shared_k[:, :width],
                             preferred_element_type=jnp.float32)
            s = s * scale
        else:
            s = jnp.einsum("bqd,bsd->bqs", q, k,
                           preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(keep, s, _NEG_INF), axis=-1)
        return jnp.einsum("bqs,bsd->bqd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    return lax.map(one_head, jnp.arange(qc.shape[1])).transpose(1, 0, 2, 3)


def _attend_selected(sel, t: int, placed_q, kh, vh, index, scale: float,
                     dense, dtype, shared_k=None, serving: bool = False):
    """Causal attention of ``t`` query rows over (b, h_kv, t, dk) keys
    and (b, h_kv, t, dv) values starting at position 0, each query row
    over the positions the selector ``sel`` keeps for it (``index``: its
    ``(q, k, w)`` of the call's tokens).  The keys come placed; the
    queries a chunk at a time, ``placed_q(start, n)`` (b, h, n, dk).
    Rows under ``topk`` keep their whole past: whole chunks of them go
    to ``dense(q, k, v, dtype)`` (the op's own causal path, (b, n, h *
    dv)).  The rest run a chunk of ``sel.q_chunk`` query rows at a time
    (``selected_walk``): the chunk's selector scores against the keys of
    its run's width, its rows' ``topk``-th largest as the threshold, and
    attention under that mask.  In a serving program (``serving``: one
    device, nothing differentiated) whose shapes
    ``pallas_kernels.attend_kept_supported`` takes, the chunk attends
    through ``attend_kept``: the scores stay in VMEM and the walk stops
    at the chunk's own last row, so only the selector pays for the
    width.  Elsewhere ``_attend_kept_heads`` computes every pair of the
    width.  Two forms for an op whose whole-sequence arrays would not
    fit: the selector's queries may be a function ``(start, n) -> (b, n,
    heads, hd)`` made a chunk at a time, and ``shared_k`` (b, t, r) is a
    part of every head's key held once: a query's trailing ``r`` values
    are scored against it and ``kh`` holds the heads' own part alone
    (``dense`` is handed ``kh`` as it is: the op puts the two together
    for those rows)."""
    iq, ik, iw = index
    index_q = iq if callable(iq) else \
        lambda start, n: lax.dynamic_slice_in_dim(iq, start, n, axis=1)
    b = kh.shape[0]
    if t <= sel.topk:
        return dense(placed_q(0, t), kh, vh, dtype)
    c, head, groups = selected_walk(sel, t)
    outs = []
    if head:
        outs.append(dense(placed_q(0, head), kh[:, :, :head],
                          vh[:, :, :head], dtype))

    def chunk_out(start, width):
        rows = start + jnp.arange(c)
        with jax.named_scope("ff_index"):
            scores = sel.scores(
                index_q(start, c),
                lax.dynamic_slice_in_dim(iw, start, c, axis=1),
                ik[:, :width])                               # (b, c, width)
        with jax.named_scope("ff_select"):
            keep = sel.keep(scores, rows)
        qc = placed_q(start, c)                               # (b, h, c, dk)
        if serving and pallas_kernels.attend_kept_supported(
                qc.shape, kh.shape, vh.shape[-1],
                None if shared_k is None else shared_k.shape[-1]):
            o = pallas_kernels.attend_kept(qc, kh, vh, keep, start, scale,
                                           shared_k=shared_k)
        else:
            o = _attend_kept_heads(qc, kh, vh, keep, scale, shared_k)
        return o.transpose(0, 2, 1, 3).reshape(b, c, -1).astype(dtype)

    for lo, hi in groups:
        o = lax.map(lambda s, hi=hi: chunk_out(s, hi),
                    jnp.arange(lo, hi, c))                    # (n, b, c, h*dv)
        outs.append(o.transpose(1, 0, 2, 3).reshape(b, hi - lo, -1))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


class LayerNorm(Op):
    """Layer normalization over the last (feature) dim."""

    def __init__(self, name: str, x: TensorSpec, eps: float = 1e-5):
        super().__init__(name, [x])
        self.attrs = dict(eps=eps)
        self._make_output(x.shape, x.dtype, x.dim_axes)

    def param_specs(self) -> Dict[str, ParamSpec]:
        d = self.inputs[0].shape[-1]
        dt = self.outputs[0].dtype
        return {
            "scale": ParamSpec((d,), dt, OnesInitializer()),
            "bias": ParamSpec((d,), dt, ZeroInitializer()),
        }

    def forward(self, params, xs, state, training):
        (x,) = xs
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.attrs["eps"])
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
        return [y.astype(x.dtype)], state


class PositionEmbedding(Op):
    """Adds a learned (seq, dim) position table to (batch, seq, dim)."""

    def __init__(self, name: str, x: TensorSpec, initializer=None):
        super().__init__(name, [x])
        assert x.ndim == 3
        self.initializer = initializer or GlorotUniform()
        self._make_output(x.shape, x.dtype, x.dim_axes)

    def param_specs(self) -> Dict[str, ParamSpec]:
        _, t, d = self.inputs[0].shape
        return {
            "table": ParamSpec((t, d), self.outputs[0].dtype, self.initializer,
                               ("s", None))
        }

    def forward(self, params, xs, state, training):
        (x,) = xs
        table = params["table"]
        if "pos" in state:
            # Serving inference mode (runtime/serving.py): ``pos`` is
            # the per-slot position of this call's FIRST token.  Decode
            # (t == 1) gathers one table row per slot; prefill starts
            # every slot at position 0 and may be shorter than the
            # declared sequence (pad-to-bucket), so slice.  The
            # offset-prefill chunk sub-mode (prefix sharing) starts the
            # call at absolute row ``chunk`` — a static int, so the
            # slice stays static.
            if x.shape[1] == 1:
                rows = jnp.take(table, state["pos"], axis=0)[:, None]
                return [x + rows], state
            start = int(state.get("chunk", 0))
            return [x + table[None, start:start + x.shape[1]]], state
        return [x + table[None]], state


def _streaming_attention_block(q, k, v, scores_mask, m, denom, acc):
    """One flash-attention accumulation step in f32.

    q: (b, h, tq, hd); k/v: (b, h, tk, hd); scores_mask: (tq, tk) bool
    (True = attend) or None; m/denom: (b, h, tq); acc: (b, h, tq, hd).
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if scores_mask is not None:
        scores = jnp.where(scores_mask[None, None], scores, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    p = jnp.exp(scores - m_new[..., None])
    corr = jnp.exp(m - m_new)
    acc = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    denom = denom * corr + jnp.sum(p, axis=-1)
    return m_new, denom, acc


class MultiHeadAttention(Op):
    """Self-attention over (batch, seq, dim).

    ``s``-degree strategies run the ring-attention path; otherwise a
    plain fused attention that GSPMD shards over batch (and heads,
    via the 'c'-tagged projection weights).

    ``num_kv_heads`` (a divisor of ``num_heads``; default all) makes it
    grouped-query attention: query head j reads key/value head ``j //
    (num_heads // num_kv_heads)``, and the cache holds the key/value
    heads alone.  ``head_dim`` (default ``dim // num_heads``) frees the
    heads' width from the model's.  ``gate`` multiplies the attended
    values, before the output projection, by ``sigmoid(x W_gate)``
    elementwise.

    Three more arguments, each absent by default (the op then lowers to
    the program it always did).  ``qk_norm`` (an epsilon): an RMSNorm
    with a learned weight over each head of q and k, before positions.
    ``rope`` (``{"theta", "sections"}``): rotary positions over the whole
    head, half-split pairs (``token_select.rope_half``); ``sections``
    are a model's ``mrope_section`` (pairs turned by each of the
    position's components: ``state["positions"]`` (b, t, 3) where a
    caller has them, else the token's index for all three, which is
    plain rotary).  ``select`` (a model's ``sa_config``): a learned
    token selector (``ops/token_select.py``) chooses the ``topk`` past
    positions each query attends; the op then keeps a third cache entry
    (the selector's keys), declares K and V positions-major with a
    position's heads in one row (a decode step gathers the chosen rows
    and no others), and serves on one device from the padded layout
    alone: the paged pool, the offset prefill and ``shard=`` raise
    ``NotImplementedError`` (ROADMAP B-M1), and so does the ring path
    for ``rope`` or ``select``.

    Three more again (PR 44), each absent by default.  ``gate="per_head"``:
    one gate value a query head (``wg`` is ``(d, heads)``) where
    ``gate=True`` has one a value.  ``rope`` may carry ``rotary_dim`` (the
    leading sub-width of the head the pairs are taken from; the rest
    passes) and ``scaling`` (a ``rope_scaling`` dict for
    ``rope_frequencies``: YaRN's blended frequencies, cos and sin times
    its ``attention_factor``).  ``window`` (W): query ``t`` attends keys
    ``s`` with ``t - W < s <= t``, and the op keeps for a slot a RING of W
    positions a head, not ``max_seq`` (``sequence=False`` entries: the
    executor tells a prefill its true ``length``): a prefill installs its
    last ``min(length, W)`` positions at their residues ``s mod W``, a
    decode step writes position ``p`` at ``p mod W`` and attends ``min(p +
    1, W)`` rows.  Keys are rotated before they are cached, so the order
    of the ring's rows does not matter to the softmax.  The padded layout
    on one device alone: the paged pool, the offset prefill, ``shard=``
    and the ring-attention path raise ``NotImplementedError`` (ROADMAP
    B-M4).
    """

    def __init__(
        self,
        name: str,
        x: TensorSpec,
        num_heads: int,
        causal: bool = True,
        use_bias: bool = True,
        kernel_initializer=None,
        num_kv_heads: Optional[int] = None,
        head_dim: Optional[int] = None,
        gate: bool = False,
        qk_norm: Optional[float] = None,
        rope: Optional[dict] = None,
        select: Optional[dict] = None,
        window: Optional[int] = None,
    ):
        super().__init__(name, [x])
        assert x.ndim == 3, f"attention input must be (batch, seq, dim), got {x.shape}"
        d = x.shape[-1]
        if head_dim is None:
            assert d % num_heads == 0, (d, num_heads)
            head_dim = d // num_heads
        kv = num_heads if num_kv_heads is None else int(num_kv_heads)
        assert kv >= 1 and num_heads % kv == 0, (num_heads, num_kv_heads)
        self.attrs = dict(num_heads=num_heads, causal=causal, use_bias=use_bias,
                          num_kv_heads=kv, head_dim=int(head_dim),
                          gate=gate if gate == "per_head" else bool(gate),
                          qk_norm=qk_norm, rope=rope, select=select,
                          window=None if window is None else int(window))
        if select is not None and not causal:
            raise ValueError(f"{name}: a token selector reads the causal past")
        if window is not None and (not causal or select is not None
                                   or int(window) < 1):
            raise ValueError(
                f"{name}: window={window!r} is a causal band of at least one "
                f"position, and is not built under a token selector")
        #: The token selector composed into this op, if any.
        self.select = None if select is None else TokenSelector(
            select, theta=(rope or {}).get("theta", 10000.0),
            eps=qk_norm if qk_norm is not None else 1e-6)
        #: A head that fills whole lane tiles is cached positions-last,
        #: the order ``flash_decode`` reads: the chip stores ``(max_seq,
        #: h, hd)`` row-major there, and the decode superstep would pay
        #: two cache-sized relayouts (SERVING.md "Cache layout").  The
        #: paged pool, the offset prefill and the sharded specs read the
        #: other order: an executor in one of those regimes clears this
        #: (static, bound like ``decode_kernel``).
        self.positions_last = self.lane_tile_heads
        self.kernel_initializer = kernel_initializer or GlorotUniform()
        self._make_output(x.shape, x.dtype, x.dim_axes)

    @property
    def cache_paged(self) -> bool:
        return self.select is None and self.attrs["window"] is None

    @property
    def decode_window(self) -> Optional[int]:
        return self.attrs["window"]

    @property
    def group(self) -> int:
        return self.attrs["num_heads"] // self.attrs["num_kv_heads"]

    @property
    def lane_tile_heads(self) -> bool:
        """Whether the cache may lie positions-last.  Never under a
        selector: a chosen position's K (or V) has to be one contiguous
        row to be fetched alone."""
        return self.attrs["head_dim"] % 128 == 0 and self.select is None

    def param_specs(self) -> Dict[str, ParamSpec]:
        a = self.attrs
        d = self.inputs[0].shape[-1]
        dq, dkv = a["num_heads"] * a["head_dim"], a["num_kv_heads"] * a["head_dim"]
        dt = self.outputs[0].dtype
        ki = self.kernel_initializer
        specs = {
            "wq": ParamSpec((d, dq), dt, ki, (None, "c")),
            "wk": ParamSpec((d, dkv), dt, ki, (None, "c")),
            "wv": ParamSpec((d, dkv), dt, ki, (None, "c")),
            "wo": ParamSpec((dq, d), dt, ki, ("c", None)),
        }
        if a["gate"]:
            wide = a["num_heads"] if a["gate"] == "per_head" else dq
            specs["wg"] = ParamSpec((d, wide), dt, ki, (None, "c"))
        if a["use_bias"]:
            specs["bq"] = ParamSpec((dq,), dt, ZeroInitializer(), ("c",))
            specs["bk"] = ParamSpec((dkv,), dt, ZeroInitializer(), ("c",))
            specs["bv"] = ParamSpec((dkv,), dt, ZeroInitializer(), ("c",))
            specs["bo"] = ParamSpec((d,), dt, ZeroInitializer())
        if a["qk_norm"] is not None:
            specs["q_norm"] = ParamSpec((a["head_dim"],), dt, OnesInitializer())
            specs["k_norm"] = ParamSpec((a["head_dim"],), dt, OnesInitializer())
        if self.select is not None:
            specs.update(self.select.param_specs(d, dt, ki))
        return specs

    def cache_entries(self, max_seq: int) -> Dict[str, CacheEntry]:
        h, hd = self.attrs["num_kv_heads"], self.attrs["head_dim"]
        if self.select is not None:
            # A position's heads side by side: the row a decode step
            # gathers.  The selector's keys beside them.
            row = CacheEntry((max_seq, h * hd), self.outputs[0].dtype)
            return {"k": row, "v": row, TokenSelector.ENTRY:
                    self.select.cache_entry(max_seq, self.outputs[0].dtype)}
        if self.attrs["window"] is not None:
            # A ring of ``window`` positions whatever ``max_seq``.
            w = self.attrs["window"]
            row = CacheEntry((h, hd, w) if self.positions_last else (w, h, hd),
                             self.outputs[0].dtype, sequence=False)
        elif self.positions_last:
            row = CacheEntry((h, hd, max_seq), self.outputs[0].dtype,
                             ("c", None, None))
        else:
            row = CacheEntry((max_seq, h, hd), self.outputs[0].dtype,
                             (None, "c", None))
        return {"k": row, "v": row}

    def serving_path(self, decode: bool) -> str:
        """Which attention formulation a serving program of this op
        compiles (the ``serving_program`` event's ``attention``)."""
        kind = "gqa" if self.group > 1 else "kv"
        if self.select is not None:
            kind += "_select"
        if self.attrs["window"] is not None:
            kind += "_window"
        return f"{kind}_decode" if decode else f"{kind}_dense"

    def _kernel_block(self, slots: int, max_seq: int, c: int = 1) -> int:
        """``flash_decode``'s chunk over a device's cache of ``slots``
        slots and a ``c``-th of the heads; 0 where its gate refuses."""
        h, hd = self.attrs["num_kv_heads"], self.attrs["head_dim"]
        local, dtype = (slots, max_seq, h // c, hd), self.outputs[0].dtype
        if h % c or not pallas_kernels.flash_decode_supported(
                local, dtype, self.group):
            return 0
        return pallas_kernels.flash_decode_chunk(*local[1:], dtype, self.group)

    def decode_fetch_block(self, slots, max_seq, kernel, c=1):
        if self.select is not None:
            # Rows, not blocks: the gather fetches the chosen positions.
            return 1
        if self.attrs["window"] is not None:
            return self._ring_block(slots, kernel) or self.attrs["window"]
        block = 0 if kernel is False else self._kernel_block(slots, max_seq, c)
        return block or max_seq

    def decode_heads_per_step(self, slots: int, max_seq: int,
                              kernel: Optional[bool]) -> int:
        """The cached heads one step of ``flash_decode``'s grouped body
        takes together on a device holding ``slots`` slots
        (``pallas_kernels.flash_decode_heads_per_step``, by shape: the
        ``serving_program`` event's ``decode_heads_per_step``); 0 where
        this op's decode step does not run that body (one query head a
        cached head, a selector's gather, a mesh, the einsum oracle)."""
        a = self.attrs
        if self.group == 1 or self.select is not None or kernel is False \
                or not _on_one_device(self):
            return 0
        block = self._ring_block(slots, kernel) if a["window"] is not None \
            else self._kernel_block(slots, max_seq)
        return block and pallas_kernels.flash_decode_heads_per_step(
            a["num_kv_heads"], a["head_dim"], self.group,
            self.outputs[0].dtype)

    # -- helpers -----------------------------------------------------------

    def _project(self, params, x):
        pc = getattr(self, "_pc", None)
        if (pc is None or pc.c == 1) and self.group == 1:
            # One fused (d, 3d) QKV matmul: XLA does not merge the
            # three separate gemms itself, and one (tokens, d) x
            # (d, 3d) dot tiles the MXU better than three (tokens, d)
            # x (d, d) dots.  Params stay separate (checkpoint layout
            # unchanged); the per-step concat is one cheap weight-
            # sized copy, and numerics are bit-identical (each output
            # column contracts only its own weight column either way).
            w = jnp.concatenate(
                [params["wq"], params["wk"], params["wv"]], axis=1
            )
            qkv = x @ w
            if self.attrs["use_bias"]:
                qkv = qkv + jnp.concatenate(
                    [params["bq"], params["bk"], params["bv"]]
                )
            return jnp.split(qkv, 3, axis=-1)
        # Head-parallel (c-split) strategies keep the three gemms
        # separate: the fused concat's column interleaving does not
        # align with the 'c' shard boundaries, so GSPMD would have to
        # regather the weights every step — exactly the comm the
        # Megatron-style split exists to avoid.  So do grouped queries:
        # their wide W_q is the traffic, and a decode step's concat of
        # it would cost as much again.
        q = x @ params["wq"]
        k = x @ params["wk"]
        v = x @ params["wv"]
        if self.attrs["use_bias"]:
            q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
        return q, k, v

    def _split_heads(self, x):
        """(b, t, d) -> (b, h, t, hd), keeping the compute dtype: the
        flash kernels dot in the input dtype (bf16 rides the MXU at
        bf16 rate) with f32 accumulation; the einsum fallbacks cast to
        f32 themselves."""
        b, t, d = x.shape
        hd = self.attrs["head_dim"]
        return x.reshape(b, t, d // hd, hd).transpose(0, 2, 1, 3)

    def _merge_heads(self, x, dtype):
        b, h, t, hd = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b, t, h * hd).astype(dtype)

    def forward(self, params, xs, state, training):
        (x,) = xs
        if "cache_k" in state:
            return self._forward_cached(params, x, state)
        pc = getattr(self, "_pc", None)
        S = pc.s if pc is not None else 1
        if S > 1 and self.attrs["window"] is not None:
            raise NotImplementedError(
                f"{self.name}: no ring-attention path under a window "
                f"(ROADMAP B-M4)")
        q, k, v = self._project(params, x)
        if self.positional:
            if S > 1:
                raise NotImplementedError(
                    f"{self.name}: no ring path under rope or select "
                    f"(ROADMAP B-M1)")
            index, pos = self._positions(state, *x.shape[:2])
            kh, vh = map(self._split_heads, (k, v))
            kh = self._place_heads(kh, params.get("k_norm"), pos)
            if self.select is None:
                qh = self._place_heads(self._split_heads(q),
                                       params.get("q_norm"), pos)
                out = self._attend_heads(qh, kh, vh, x.dtype)
            else:
                out = self._attend_selected(
                    params, q, kh, vh, pos, self._index(params, x, index),
                    x.dtype, self._attend_heads)
        elif S <= 1:
            out = self._attend_dense(q, k, v, x.dtype)
        else:
            assert self.group == 1, f"{self.name}: no grouped-query ring path"
            out = self._attend_ring(q, k, v, x.dtype)
        return [self._output(params, x, out)], state

    # -- positions and selection ---------------------------------------------

    @property
    def positional(self) -> bool:
        """Whether q and k pass ``_place`` (none of the three arguments
        present: the op's programs are what they were without them)."""
        a = self.attrs
        return a["qk_norm"] is not None or a["rope"] is not None \
            or self.select is not None

    def _positions(self, state, b: int, t: int):
        """``(index (b, t), rotary positions)`` of the call's tokens: a
        decode step's index from the slots' ``pos``, else ``chunk ..``
        (0 where there is none); the rotary positions are the index, or
        the caller's ``positions`` (b, t, components)."""
        if t == 1 and "pos" in state:
            index = state["pos"][:, None]
        else:
            start = int(state.get("chunk", 0))
            index = jnp.broadcast_to(start + jnp.arange(t)[None], (b, t))
        return index, state.get("positions", index)

    def _place(self, params, qh, kh, pos):
        """Heads (b, h, t, hd) of q and k through the head norm and
        the rotary positions ``pos`` (b, t) or (b, t, components), as far
        as the op has them."""
        return (self._place_heads(qh, params.get("q_norm"), pos),
                self._place_heads(kh, params.get("k_norm"), pos))

    def _place_heads(self, heads, scale, pos):
        a = self.attrs
        if a["qk_norm"] is not None:
            heads = rms_norm(heads, scale, a["qk_norm"])
        if a["rope"] is not None:
            rope = a["rope"]
            more = {}
            if rope.get("rotary_dim") or rope.get("scaling"):
                more = self._rope_turn(rope)
            heads = rope_half(heads, pos[:, None], rope["theta"],
                              rope.get("sections"), **more)
        return heads

    def _rope_turn(self, rope):
        """``rope_half``'s further arguments under a rotary sub-width or
        a ``scaling``: the sub-width, its pairs' frequencies and the
        scale of cos and sin (``rope_frequencies``; a ``scaling`` that
        states its own ``attention_factor`` has to agree with it)."""
        r = int(rope.get("rotary_dim") or self.attrs["head_dim"])
        scaling = rope.get("scaling")
        inv, wave, soft = rope_frequencies(r, rope["theta"], scaling)
        stated = (scaling or {}).get("attention_factor")
        if soft != 1.0 or (stated is not None
                           and abs(float(stated) - wave) > 1e-6 * wave):
            raise ValueError(
                f"{self.name}: rope scaling {scaling!r}: cos and sin scale by "
                f"{wave!r}, the softmax by {soft!r}; only a scale of cos and "
                f"sin that agrees with attention_factor is built")
        return dict(rotary_dim=r, inv=inv, wave=wave)

    def _index(self, params, x, index):
        """The selector's ``(q, k, w)`` of this call's tokens at their
        indices (b, t)."""
        with jax.named_scope("ff_index"):
            return self.select.project(params, x, index)

    def _attend_selected(self, params, q, kh, vh, pos, index, dtype, dense,
                         serving: bool = False):
        """``_attend_selected`` over queries ``q`` (b, t, h * hd) as
        projected: split into heads and placed (``_place_heads`` at
        ``pos``) a chunk at a time (all 32 heads of a 32k prefill are
        256 MB transposed and 512 MB in f32)."""
        def placed_q(start, n):
            return self._place_heads(
                self._split_heads(lax.dynamic_slice_in_dim(q, start, n, axis=1)),
                params.get("q_norm"),
                lax.dynamic_slice_in_dim(pos, start, n, axis=1))

        return _attend_selected(
            self.select, q.shape[1], placed_q, kh, vh, index,
            1.0 / math.sqrt(self.attrs["head_dim"]), dense, dtype,
            serving=serving)

    def kept_blocks(self, t: int) -> Dict[str, Any]:
        """``kept_blocks`` of a serving prefill of ``t`` rows."""
        a = self.attrs
        h, hd = a["num_kv_heads"], a["head_dim"]
        return kept_blocks(self.select, t, h * self.group, (1, h, t, hd),
                           hd, hd, None)

    def causal_blocks(self, t: int) -> Dict[str, int]:
        """``causal_blocks`` of a serving prefill of ``t`` rows: nothing
        under a window (the banded forward) or where the prefill takes
        another causal path (``_forward_cached``)."""
        a = self.attrs
        if a["window"] is not None or not (
                self.select is not None or self.positions_last
                or self.group > 1 or self.positional) \
                or not (a["causal"] and _on_one_device(self)):
            return {}
        h, hd = a["num_kv_heads"], a["head_dim"]
        return causal_blocks(self.select, t, h * self.group, h, hd, hd,
                             self.outputs[0].dtype)

    def _forward_selected(self, params, x, state):
        """The cached forward of an op with a selector: caches ``k`` and
        ``v`` (B, S, h_kv * hd), a position a row, and ``idx`` (B, S,
        selector head), the selector's keys.  Prefill (t > 1): the three
        written at rows ``0..t-1`` and ``_attend_selected``.  Decode
        (t == 1): the token at ``pos`` writes its three rows (a slice
        update a slot, in place), the selector scores every row of its
        small cache, keeps ``topk`` among the live ones, and K and V of
        those rows alone are gathered and attended."""
        sel, plan = self.select, getattr(self, "_plan", None)
        if "block_table" in state or "chunk" in state or \
                (plan is not None and plan.num_devices > 1):
            raise NotImplementedError(
                f"{self.name}: selection over a paged pool, an offset "
                f"prefill or a sharded cache is not built (ROADMAP B-M1)")
        ck, cv, ci = (state[f"cache_{e}"] for e in ("k", "v", sel.ENTRY))
        b, t, _ = x.shape
        index, pos = self._positions(state, b, t)
        q, k, v = self._project(params, x)
        kh, vh = map(self._split_heads, (k, v))                 # (B, h_kv, t, hd)
        iq, ik, iw = self._index(params, x, index)
        kh = self._place_heads(kh, params.get("k_norm"), pos)
        rows_k = kh.transpose(0, 2, 1, 3).reshape(b, t, -1).astype(ck.dtype)
        new_state = dict(state)
        if t > 1:
            new = (rows_k, v.astype(cv.dtype), ik.astype(ci.dtype))
            ck, cv, ci = (c.at[:, :t].set(r) for c, r in zip((ck, cv, ci), new))
            y = self._attend_selected(params, q, kh, vh, pos, (iq, ik, iw),
                                      x.dtype, self._attend_prefill,
                                      serving=True)
        else:
            qh = self._place_heads(self._split_heads(q), params.get("q_norm"),
                                   pos)
            at = state["pos"]
            for i in range(b):
                ck, cv, ci = (
                    lax.dynamic_update_slice(c, r[i][None].astype(c.dtype),
                                             (i, at[i], 0))
                    for c, r in ((ck, rows_k), (cv, v), (ci, ik)))
            with jax.named_scope("ff_index"):
                scores = sel.scores(iq, iw, ci)[:, 0]            # (B, S)
            with jax.named_scope("ff_select"):
                idx, valid = sel.pick(scores, at)
                kg, vg = (jnp.take_along_axis(c, idx[:, :, None], axis=1)
                          for c in (ck, cv))                     # (B, k, h_kv*hd)
            y = self._decode_selected(qh[:, :, 0], kg, vg, valid, x.dtype)
        new_state["cache_k"], new_state["cache_v"] = ck, cv
        new_state[f"cache_{sel.ENTRY}"] = ci
        return [self._output(params, x, y)], new_state

    def _decode_selected(self, q1, kg, vg, valid, dtype):
        """One query a head ``q1`` (B, h, hd) over a slot's gathered rows
        ``kg``/``vg`` (B, k, h_kv * hd), the ``valid`` (B, k) ones: f32
        scores and softmax, the products in the operands' dtype.
        Returns (B, 1, h * hd)."""
        b, h, hd = q1.shape
        hkv = h // self.group
        kg, vg = (c.reshape(b, -1, hkv, hd) for c in (kg, vg))
        qg = q1.reshape(b, hkv, self.group, hd)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, kg,
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(valid[:, None, None, :], s, _NEG_INF),
                           axis=-1)
        o = jnp.einsum("bkgs,bskd->bkgd", p.astype(vg.dtype), vg,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, 1, h * hd).astype(dtype)

    def _output(self, params, x, out):
        """The output gate (where the op has one) and projection."""
        if self.attrs["gate"] == "per_head":
            out = _gate_heads(out, x @ params["wg"], self.attrs["head_dim"])
        elif self.attrs["gate"]:
            # The sigmoid in f32; the product in the compute dtype (an
            # f32 copy of a 32k-token prefill's values is 1 GiB).
            gate = jax.nn.sigmoid((x @ params["wg"]).astype(jnp.float32))
            out = out * gate.astype(out.dtype)
        y = out @ params["wo"]
        if self.attrs["use_bias"]:
            y = y + params["bo"]
        return y

    # -- KV-cache inference protocol (runtime/serving.py) -------------------
    #
    # The serving executor threads an inference mode through the
    # existing ``state`` mechanism: when ``state`` carries
    # ``cache_k``/``cache_v`` — preallocated (B, max_seq, heads,
    # d_head) caches, (B, heads, d_head, max_seq) under
    # ``positions_last`` — plus the per-slot position vector ``pos`` (B,)
    # int32, ``forward`` takes this path instead.  Two sub-modes by
    # query length:
    #
    # - **prefill** (t > 1): the full-sequence causal forward — the
    #   EXACT training attention path, so prefill logits are
    #   bit-identical to a training forward on the same tokens — that
    #   additionally writes this call's K/V into cache rows 0..t-1
    #   (every prefilled slot starts at position 0; pad-to-bucket
    #   rows beyond a prompt's true length hold pad-token K/V that
    #   decode overwrites before its causal mask can reach them).
    # - **decode** (t == 1): the token at position ``pos`` writes its
    #   K/V at ``cache[b, pos[b]]`` and attends key positions
    #   ``<= pos``: both inside the Pallas ``flash_decode`` kernel
    #   (q_len=1 streaming softmax over the live blocks of a cache read
    #   in the order the chip stores it; shard_map-wrapped when a
    #   multi-device serving plan is bound), or a scatter and the
    #   pure-jnp ``_einsum_decode`` oracle.  When ``state`` additionally
    #   carries ``block_table``, the caches are PAGED global block
    #   pools and decode scatters/gathers through the table
    #   (runtime/serving.py KVBlockLedger).
    #
    # **Speculative rollback contract** (SERVING.md "Speculative
    # decoding"): the fused verify scan drives this same t == 1 path
    # once per draft position, so a rejected draft leaves K/V rows
    # written PAST the accepted position.  No explicit rollback is
    # needed — a row at position p participates in attention only
    # when the querying token's ``pos >= p`` (the ``<= pos`` mask),
    # and the position walk resumes from ``accepted + 1``, so every
    # stale row is either never attended or overwritten by the token
    # that legitimately owns that position before any query can see
    # it.  Paged layouts get the same guarantee one level up:
    # out-of-reservation scatters land in scratch block 0, which the
    # ledger never allocates and the mask never admits.
    #
    # **Paged × sharded**: the paged decode branch below is pure jnp
    # (scatter + table gather + einsum oracle — no pallas_call), so
    # under a serving mesh it partitions via plain GSPMD: the pool
    # shards its HEAD axis on 'c' exactly like the padded cache, the
    # host-side block table replicates, and 'n' replicates the pool
    # (block indices are batch-global, so there is no batch axis to
    # split).  ``_project``'s fused-QKV matmul keeps fused-vs-split
    # numerics bit-identical, which is what pins the sharded paged
    # path to the single-mesh paged oracle (tests/test_serving.py).
    #
    # **Positions and a third entry** (PR 42).  An op built with
    # ``qk_norm`` or ``rope`` passes q and k through ``_place`` first
    # (the head norm, then the rotary turn at the call's positions: a
    # decode step's from ``pos``, a prefill's ``0..t-1``, an offset
    # prefill's from ``chunk``; ``state["positions"]`` where a caller
    # brings multimodal components); what reaches the caches and the
    # decode kernel is rotated, and nothing below changes.  An op built
    # with ``select`` leaves this path at its first line for
    # ``_forward_selected``: three entries (``k`` and ``v`` positions-
    # major with a position's heads one row, ``idx`` the selector's
    # keys), a decode step that gathers the chosen rows, and no paged
    # pool, offset prefill or mesh (``NotImplementedError`` naming
    # ROADMAP B-M1).
    #
    # Training never sets cache keys, so the differentiable pure-jnp
    # contract on the training path is untouched (the decode kernel
    # has no VJP — it is reachable only from the forward-only serving
    # programs, the same reachability discipline as the sparse
    # protocol's scalar-prefetch kernels, ops/base.py).

    #: Decode-kernel routing: None = auto (kernel when the cache shape
    #: supports it), True/False force.  Static (bound by the serving
    #: executor, like ``bind_mesh``) so the traced program is stable.
    decode_kernel: Optional[bool] = None

    def _forward_cached(self, params, x, state):
        if self.select is not None:
            return self._forward_selected(params, x, state)
        if self.attrs["window"] is not None:
            return self._forward_window(params, x, state)
        ck, cv = state["cache_k"], state["cache_v"]
        q, k, v = self._project(params, x)
        qh, kh, vh = map(self._split_heads, (q, k, v))   # (B, h, t, hd)
        b, h, t, hd = qh.shape
        if self.positional:
            qh, kh = self._place(params, qh, kh, self._positions(state, b, t)[1])
        if self.positions_last:
            # Caches (B, h_kv, hd, S): the padded layout's prefill and
            # decode only (a paged or sharded executor binds the other
            # order).
            if "block_table" in state or "chunk" in state:
                raise NotImplementedError(
                    f"{self.name}: a positions-last cache has no paged "
                    f"pool or offset prefill yet (ROADMAP Queue B)")
            if t == 1:
                out, ck, cv = self._decode_attend(
                    qh[:, :, 0], kh[:, :, 0], vh[:, :, 0], ck, cv,
                    state["pos"])
                y = self._merge_heads(out[:, :, None], x.dtype)
            else:
                ck = ck.at[..., :t].set(kh.transpose(0, 1, 3, 2).astype(ck.dtype))
                cv = cv.at[..., :t].set(vh.transpose(0, 1, 3, 2).astype(cv.dtype))
                y = self._attend_prefill(qh, kh, vh, x.dtype)
            new_state = dict(state)
            new_state["cache_k"], new_state["cache_v"] = ck, cv
            return [self._output(params, x, y)], new_state
        if t == 1 and "block_table" in state:
            # Paged decode (SERVING.md "Cache layout"): ck/cv are the
            # GLOBAL block pools (kv_blocks, kv_block, h, hd); the
            # per-slot block table (B, nblk) int32 maps each slot's
            # logical kv_block-sized chunks onto pool blocks.  The
            # token at ``pos`` scatters into its slot's owning block
            # at (pos // bs, pos % bs); attention then gathers the
            # slot's blocks into a transient padded (B, nblk*bs, ...)
            # view and runs the einsum oracle — persistent HBM is the
            # pool alone, which is what the capacity win measures.
            # Positions past a slot's reservation map to scratch
            # block 0, whose garbage the <= pos mask excludes.
            pos = state["pos"]
            bt = state["block_table"]
            bs = ck.shape[1]
            rows = jnp.arange(b)
            dest = bt[rows, pos // bs]
            ck = ck.at[dest, pos % bs].set(kh[:, :, 0].astype(ck.dtype))
            cv = cv.at[dest, pos % bs].set(vh[:, :, 0].astype(cv.dtype))
            view_k = ck[bt].reshape(b, -1, ck.shape[-2], hd)
            view_v = cv[bt].reshape(b, -1, cv.shape[-2], hd)
            out = _einsum_decode(qh[:, :, 0], view_k, view_v, pos)
            y = self._merge_heads(out[:, :, None], x.dtype)
        elif t == 1:
            out, ck, cv = self._decode_attend(
                qh[:, :, 0], kh[:, :, 0], vh[:, :, 0], ck, cv, state["pos"])
            y = self._merge_heads(out[:, :, None], x.dtype)
        elif "chunk" in state:
            # Offset-prefill chunk sub-mode (SERVING.md "Prefix
            # sharing"): the t tokens sit at ABSOLUTE rows
            # [o, o + t) of a cache whose rows [0, o) already hold the
            # shared prefix's K/V (gathered from the paged pool).
            # Queries attend the full [0, o + t) key span under the
            # offset-causal mask — key j visible to query i iff
            # j <= o + i — so row o + i sees exactly the history the
            # unshared full prefill gives it, which is what keeps the
            # tail KV and logits bit-identical to the unshared run
            # (the masked-out _NEG_INF scores underflow to exact
            # zeros, same as the dense path's causal tril).
            o = int(state["chunk"])
            ck = ck.at[:, o:o + t].set(
                kh.transpose(0, 2, 1, 3).astype(ck.dtype)
            )
            cv = cv.at[:, o:o + t].set(
                vh.transpose(0, 2, 1, 3).astype(cv.dtype)
            )
            y = self._attend_chunk(qh, ck, cv, o, t, x.dtype)
        else:
            ck = ck.at[:, :t].set(kh.transpose(0, 2, 1, 3).astype(ck.dtype))
            cv = cv.at[:, :t].set(vh.transpose(0, 2, 1, 3).astype(cv.dtype))
            y = self._attend_prefill(qh, kh, vh, x.dtype) \
                if self.group > 1 or self.positional \
                else self._attend_dense(q, k, v, x.dtype)
        new_state = dict(state)
        new_state["cache_k"] = ck
        new_state["cache_v"] = cv
        return [self._output(params, x, y)], new_state

    def _attend_prefill(self, qh, kh, vh, dtype):
        """A serving prefill's causal attention on heads (B, h, t, hd)
        against (B, h_kv, t, hd) keys and values: the streamed forward
        kernel, a group's query heads over one fetched K/V block (no
        repeated copy), where its gate takes the shape; else the dense
        path over repeated heads."""
        if self.attrs["causal"] and _on_one_device(self) and \
                pallas_kernels.flash_uneven_supported(qh.shape, qh.shape[-1]):
            out = pallas_kernels.flash_fwd_uneven(
                qh, kh, vh, 1.0 / math.sqrt(qh.shape[-1]))
            return self._merge_heads(out, dtype)
        return self._attend_heads(qh, kh, vh, dtype)

    def _attend_chunk(self, qh, ck, cv, offset, t, dtype):
        """Offset-prefill attention: ``t`` queries at absolute
        positions ``offset .. offset+t-1`` against cache rows
        ``[0, offset + t)`` — the shared prefix rows plus this call's
        own writes.  Pure-jnp einsum formulation (the offset-causal
        mask has no flash kernel shape; the span is one prefill
        bucket, so the dense score matrix is small)."""
        span = offset + t
        kh = ck[:, :span].transpose(0, 2, 1, 3)      # (B, h, span, hd)
        vh = cv[:, :span].transpose(0, 2, 1, 3)
        if self.group > 1:
            kh, vh = (jnp.repeat(c, self.group, axis=1) for c in (kh, vh))
        q, k, v = (x.astype(jnp.float32) for x in (qh, kh, vh))
        scale = 1.0 / math.sqrt(q.shape[-1])
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if self.attrs["causal"]:
            mask = (
                jnp.arange(span)[None, :]
                <= (offset + jnp.arange(t))[:, None]
            )
            scores = jnp.where(mask[None, None], scores, _NEG_INF)
        attn = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", attn, v)
        return self._merge_heads(out, dtype)

    def _decode_attend(self, q1, k1, v1, ck, cv, pos):
        """Padded-layout decode step: this token's K/V (``k1``/``v1``,
        (B, h, hd) like ``q1``) go into the caches at ``pos`` and the
        query attends positions ``<= pos``.  Returns ``(out, ck, cv)``.

        The Pallas ``flash_decode`` kernel does both, on the caches in
        the order the chip stores them (an XLA scatter in front of it
        would be laid out row-major and bring a cache-sized copy a
        cache into every step: SERVING.md "Cache layout") --
        shard_map-wrapped per local shard when a multi-device plan is
        bound (batch on 'n', heads on 'c', the ``_flash_dense``
        discipline: a pallas_call has no GSPMD partitioning rule).
        Shapes its gate refuses, and ``decode_kernel=False``, scatter
        the column and run the pure-jnp ``_einsum_decode`` oracle,
        which under a mesh partitions via plain GSPMD (decode softmax
        is local per (batch, head): zero collectives either way)."""
        plan = getattr(self, "_plan", None)
        sharded = plan is not None and plan.num_devices > 1
        last = self.positions_last
        b, h, hd, s = ck.shape if last else \
            tuple(ck.shape[i] for i in (0, 2, 3, 1))
        n_entry = c_entry = None
        n_deg = c_deg = 1
        if sharded:
            (n_entry, n_deg), (c_entry, c_deg) = plan.local_degrees(
                self._pc, "n", "c"
            )
            n_deg, c_deg = max(n_deg, 1), max(c_deg, 1)
        local = (b // n_deg, s, h // c_deg, hd)
        # Under a mesh only the plain layout has a shard_map'd kernel.
        supported = (b % n_deg == 0 and not (sharded and (last or self.group > 1))
                     and self._kernel_block(local[0], s, c_deg) > 0)
        use = self.decode_kernel
        if use is None:
            use = supported
        elif use and not supported:
            import logging

            logging.getLogger("ff.attention").warning(
                "%s: flash_decode unsupported for local cache shape %s "
                "— falling back to the einsum decode oracle",
                self.name, local,
            )
            use = False
        if not use and last:
            rows = jnp.arange(b)
            ck = ck.at[rows, :, :, pos].set(k1.astype(ck.dtype))
            cv = cv.at[rows, :, :, pos].set(v1.astype(cv.dtype))
            out = _einsum_decode(q1, ck.transpose(0, 3, 1, 2),
                                 cv.transpose(0, 3, 1, 2), pos)
            return out, ck, cv
        if not use:
            rows = jnp.arange(b)
            ck = ck.at[rows, pos].set(k1.astype(ck.dtype))
            cv = cv.at[rows, pos].set(v1.astype(cv.dtype))
            return _einsum_decode(q1, ck, cv, pos), ck, cv
        if not sharded:
            return pallas_kernels.flash_decode(q1, k1, v1, ck, cv, pos + 1,
                                               positions_last=last)
        q_spec = PartitionSpec(n_entry, c_entry, None)
        kv_spec = PartitionSpec(n_entry, None, c_entry, None)
        return jax.shard_map(
            lambda ql, k1l, v1l, kl, vl, pl: pallas_kernels.flash_decode(
                ql, k1l, v1l, kl, vl, pl + 1
            ),
            mesh=plan.mesh,
            in_specs=(q_spec, q_spec, q_spec, kv_spec, kv_spec,
                      PartitionSpec(n_entry)),
            out_specs=(q_spec, kv_spec, kv_spec),
            check_vma=False,
        )(q1, k1, v1, ck, cv, pos)

    def _attend_dense(self, q, k, v, dtype):
        return self._attend_heads(*map(self._split_heads, (q, k, v)), dtype)

    def _attend_heads(self, q, k, v, dtype):
        """Dense attention on split heads; grouped keys and values are
        repeated a query head (the differentiable path)."""
        if self.group > 1:
            k, v = (jnp.repeat(x, self.group, axis=1) for x in (k, v))
        if self.attrs["window"] is not None:
            # No window in the training flash kernels (ROADMAP B-M4).
            return self._merge_heads(
                _band_attention(q, k, v, self.attrs["window"]), dtype)
        out = self._flash_dense(q, k, v)
        if out is None:
            out = _einsum_attention(q, k, v, self.attrs["causal"])
        return self._merge_heads(out, dtype)

    # -- a window: the band and the ring (PR 44) ------------------------------

    def _ring_block(self, slots: int, kernel: Optional[bool]) -> int:
        """``flash_decode``'s chunk over a ring of ``window`` positions;
        0 where the ring is decoded by the einsum oracle (``kernel``
        false, a cache not positions-last, one query head a cached head:
        the kernel's ring is its grouped body's)."""
        h, hd = self.attrs["num_kv_heads"], self.attrs["head_dim"]
        w, dtype = self.attrs["window"], self.outputs[0].dtype
        if kernel is False or not self.positions_last or self.group == 1 or \
                not pallas_kernels.flash_decode_supported(
                    (slots, w, h, hd), dtype, self.group):
            return 0
        return pallas_kernels.flash_decode_chunk(w, h, hd, dtype, self.group)

    def _forward_window(self, params, x, state):
        """The cached forward of an op with a ``window`` W: caches ``k``
        and ``v`` are rings (B, h_kv, hd, W) (or (B, W, h_kv, hd) where
        the head does not fill lane tiles), row ``r`` holding the newest
        position ``s`` with ``s mod W == r``.  Prefill (t > 1): banded
        attention over the call's own keys, and the ring filled from the
        last ``min(length, W)`` of the ``length`` true positions
        (``state["length"]``; the bucket's padding never enters the
        ring).  Decode (t == 1): position ``p`` written at ``p mod W``,
        ``min(p + 1, W)`` rows attended, in ``flash_decode`` where its
        gate takes the ring."""
        w, plan = self.attrs["window"], getattr(self, "_plan", None)
        if "block_table" in state or "chunk" in state or \
                (plan is not None and plan.num_devices > 1):
            raise NotImplementedError(
                f"{self.name}: a window's ring over a paged pool, an offset "
                f"prefill or a sharded cache is not built (ROADMAP B-M4)")
        ck, cv = state["cache_k"], state["cache_v"]
        q, k, v = self._project(params, x)
        qh, kh, vh = map(self._split_heads, (q, k, v))   # (B, h, t, hd)
        b, _, t, _ = qh.shape
        if self.positional:
            qh, kh = self._place(params, qh, kh,
                                 self._positions(state, b, t)[1])
        if t == 1:
            out, ck, cv = self._decode_ring(qh[:, :, 0], kh[:, :, 0],
                                            vh[:, :, 0], ck, cv, state["pos"])
            y = self._merge_heads(out[:, :, None], x.dtype)
        else:
            rows = _ring_rows(state.get("length", t), t, w)
            order = (0, 1, 3, 2) if self.positions_last else (0, 2, 1, 3)
            ck, cv = (jnp.take(c, rows, axis=2).transpose(order).astype(ring.dtype)
                      for c, ring in ((kh, ck), (vh, cv)))
            y = self._attend_band(qh, kh, vh, x.dtype)
        new_state = dict(state)
        new_state["cache_k"], new_state["cache_v"] = ck, cv
        return [self._output(params, x, y)], new_state

    def _attend_band(self, qh, kh, vh, dtype):
        """A serving prefill's banded attention on heads (B, h, t, hd)
        against (B, h_kv, t, hd): the banded forward kernel, which
        visits a query block's band alone, where its gate takes the
        shape; else the dense band over repeated heads."""
        w, plan = self.attrs["window"], getattr(self, "_plan", None)
        if (plan is None or plan.num_devices == 1) and \
                pallas_kernels.flash_window_supported(qh.shape, w):
            out = pallas_kernels.flash_fwd_window(
                qh, kh, vh, 1.0 / math.sqrt(qh.shape[-1]), w)
            return self._merge_heads(out, dtype)
        return self._attend_heads(qh, kh, vh, dtype)

    def _ring_index(self, pos):
        """``(the row position ``pos`` is written at, the rows live once
        it is)`` of the ring."""
        w = self.attrs["window"]
        return lax.rem(pos, w), jnp.minimum(pos + 1, w)

    def _decode_ring(self, q1, k1, v1, ck, cv, pos):
        """One decode step over the ring: ``k1``/``v1`` (B, h_kv, hd)
        written at ``pos mod W``, the query ``q1`` (B, h, hd) attending
        the ``min(pos + 1, W)`` live rows.  ``(out, ck, cv)``."""
        last = self.positions_last
        at, live = self._ring_index(pos)
        if self._ring_block(ck.shape[0], self.decode_kernel):
            return pallas_kernels.flash_decode(
                q1, k1, v1, ck, cv, live, positions_last=True, write_at=at)
        if self.decode_kernel:
            import logging

            logging.getLogger("ff.attention").warning(
                "%s: flash_decode does not take the ring %s -- falling back "
                "to the einsum decode oracle", self.name, ck.shape)
        rows = jnp.arange(ck.shape[0])
        if last:
            ck = ck.at[rows, :, :, at].set(k1.astype(ck.dtype))
            cv = cv.at[rows, :, :, at].set(v1.astype(cv.dtype))
            return _einsum_decode(q1, ck.transpose(0, 3, 1, 2),
                                  cv.transpose(0, 3, 1, 2), live - 1), ck, cv
        ck = ck.at[rows, at].set(k1.astype(ck.dtype))
        cv = cv.at[rows, at].set(v1.astype(cv.dtype))
        return _einsum_decode(q1, ck, cv, live - 1), ck, cv

    def _flash_dense(self, q, k, v):
        """Run the Pallas flash kernel on the dense path, or None to
        fall back to the einsum formulation.

        A ``pallas_call`` is a Mosaic custom call with no GSPMD
        partitioning rule, so under a multi-device mesh it must be
        wrapped in ``shard_map`` over the axes the strategy shards
        (batch 'n', heads via the projections' 'c' tag) — otherwise
        XLA would all-gather q/k/v onto every device.
        """
        causal = self.attrs["causal"]

        def kernel_for(shape, dtype):
            # Single launch when the shape fits the VMEM cap; the
            # chunked decomposition (per-chunk launches + lse merges)
            # for longer sequences; None -> einsum fallback.
            if not pallas_kernels.flash_any_supported(shape, dtype):
                return None

            def fn(ql, kl, vl):
                res = pallas_kernels.flash_attention_lse_auto(ql, kl, vl, causal)
                if res is None:
                    # Support gates said yes but the dispatcher
                    # declined — only reachable if the two ever drift;
                    # the local einsum keeps the jitted forward alive
                    # (and is exact) even under the shard_map wrapper.
                    return _einsum_attention(ql, kl, vl, causal)
                return res[0]

            return fn

        plan = getattr(self, "_plan", None)
        if plan is None or plan.num_devices == 1:
            fn = kernel_for(q.shape, q.dtype)
            return fn(q, k, v) if fn is not None else None
        (n_entry, n_deg), (c_entry, c_deg) = plan.local_degrees(
            self._pc, "n", "c"
        )
        b, h, t, hd = q.shape
        if b % n_deg or h % c_deg:
            return None
        local_shape = (b // n_deg, h // c_deg, t, hd)
        fn = kernel_for(local_shape, q.dtype)
        if fn is None:
            return None
        spec = PartitionSpec(n_entry, c_entry, None, None)
        return jax.shard_map(
            fn,
            mesh=plan.mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)

    # -- ring attention (context parallelism) ------------------------------

    def _attend_ring(self, q, k, v, dtype):
        plan, pc = self._plan, self._pc
        (s_entry, S), (n_entry, _) = plan.local_degrees(pc, "s", "n")
        batch, seq, d = q.shape
        assert seq % S == 0, f"{self.name}: seq {seq} not divisible by s={S}"
        spec = PartitionSpec(n_entry, s_entry, None)
        causal = self.attrs["causal"]

        def local_fn(q, k, v):
            # q/k/v: (b_loc, t_loc, d) — this device's sequence chunk.
            s_idx = lax.axis_index(tuple(s_entry))
            qh = self._split_heads(q)
            kh = self._split_heads(k)
            vh = self._split_heads(v)
            use_flash = pallas_kernels.flash_any_supported(qh.shape, qh.dtype)
            if use_flash:
                return self._ring_flash(qh, kh, vh, s_idx, S, s_entry, dtype)
            qh, kh, vh = (x.astype(jnp.float32) for x in (qh, kh, vh))
            b, h, t, hd = qh.shape
            m = jnp.full((b, h, t), _NEG_INF, jnp.float32)
            denom = jnp.zeros((b, h, t), jnp.float32)
            acc = jnp.zeros((b, h, t, hd), jnp.float32)
            q_pos = s_idx * t + jnp.arange(t)
            ring = [(i, (i + 1) % S) for i in range(S)]
            k_cur, v_cur = kh, vh
            # Unrolled ring: step j holds the K/V chunk of device
            # (s_idx - j) mod S; XLA overlaps the ppermute with the
            # matmuls of the previous step.
            for j in range(S):
                k_idx = (s_idx - j) % S
                if causal:
                    k_pos = k_idx * t + jnp.arange(t)
                    mask = k_pos[None, :] <= q_pos[:, None]
                else:
                    mask = None
                m, denom, acc = _streaming_attention_block(
                    qh, k_cur, v_cur, mask, m, denom, acc
                )
                if j < S - 1:
                    k_cur = lax.ppermute(k_cur, tuple(s_entry), ring)
                    v_cur = lax.ppermute(v_cur, tuple(s_entry), ring)
            out = acc / denom[..., None]
            return self._merge_heads(out, dtype)

        return jax.shard_map(
            local_fn,
            mesh=plan.mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)

    def _ring_flash(self, qh, kh, vh, s_idx, S, s_entry, dtype):
        """Ring attention with the Pallas flash kernel per chunk.

        Step j computes this device's queries against the K/V chunk of
        device (s_idx - j) mod S with a local flash call, then merges
        the (out, lse) partials with the streaming-softmax combine.
        Chunk-level causality is exact: the own chunk (j=0) uses the
        in-kernel causal mask; rotated chunks are either fully visible
        (k_idx < s_idx) or discarded by forcing their lse to -inf.
        """
        causal = self.attrs["causal"]
        ring = [(i, (i + 1) % S) for i in range(S)]
        # _attend_ring's use_flash gate mirrors the dispatcher's own
        # support checks, so auto cannot return its None fallback here.
        res = pallas_kernels.flash_attention_lse_auto(qh, kh, vh, causal)
        assert res is not None, "gated caller: flash must be supported"
        o, lse = res
        o = o.astype(jnp.float32)
        k_cur, v_cur = kh, vh
        for j in range(1, S):
            k_cur = lax.ppermute(k_cur, tuple(s_entry), ring)
            v_cur = lax.ppermute(v_cur, tuple(s_entry), ring)

            def attend(kc=k_cur, vc=v_cur):
                r = pallas_kernels.flash_attention_lse_auto(qh, kc, vc, False)
                assert r is not None, "gated caller: flash must be supported"
                o_j, lse_j = r
                return o_j.astype(jnp.float32), lse_j

            if causal:
                # Chunk (s_idx - j) mod S is visible iff it precedes
                # this device's chunk; skip the kernel (fwd AND bwd)
                # entirely on devices where it is not.  The ppermute
                # still runs, so the ring stays in lockstep.
                def skip():
                    return (
                        jnp.zeros_like(o),
                        jnp.full(o.shape[:-1], _NEG_INF, jnp.float32),
                    )

                visible = ((s_idx - j) % S) < s_idx
                o_j, lse_j = lax.cond(visible, attend, skip)
            else:
                o_j, lse_j = attend()
            o, lse = _merge_lse(o, lse, o_j, lse_j)
        return self._merge_heads(o, dtype)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_frequencies(d: int, theta: float, scaling: Optional[dict] = None):
    """``(frequencies (d/2,) f32, scale of cos and sin, scale of the
    softmax)``.  Plain: pair i turns ``theta^(-2i/d)`` a position, both
    scales 1.  Under YaRN (``scaling``: a configuration's
    ``rope_scaling`` of ``type`` ``yarn``) a pair that turns more than
    ``beta_fast`` times in the ``original_max_position_embeddings``
    keeps its frequency, one that turns fewer than ``beta_slow`` times
    has it divided by ``factor``, and the pairs between (the range's
    ends rounded outward to whole pairs) are blended along a linear
    ramp; cos and sin are scaled by ``mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)`` and the softmax by
    ``mscale(factor, mscale_all_dim)^2`` (DeepSeek-V3's reading)."""
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)   # (d/2,)
    if scaling is None:
        return inv, 1.0, 1.0
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "yarn":
        raise ValueError(f"rope_scaling type {kind!r} is not built yet (only 'yarn')")
    factor = float(scaling["factor"])
    span = scaling["original_max_position_embeddings"]

    def pair_turning(turns: float) -> float:
        return d * math.log(span / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(pair_turning(scaling.get("beta_fast", 32))), 0)
    hi = min(math.ceil(pair_turning(scaling.get("beta_slow", 1))), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float32) - lo)
                   / max(hi - lo, 0.001), 0.0, 1.0)
    slowed = inv / factor
    # slowed where the ramp is 1, inv where it is 0 (and inv itself,
    # bit for bit, under a factor of 1).
    inv = slowed + (inv - slowed) * (1.0 - ramp)
    all_dim = scaling.get("mscale_all_dim", 0)
    if all_dim:
        wave = _yarn_mscale(factor, scaling.get("mscale", 1)) \
            / _yarn_mscale(factor, all_dim)
        return inv, wave, _yarn_mscale(factor, all_dim) ** 2
    return inv, _yarn_mscale(factor, 1), 1.0


def rope_interleaved(x, pos, inv, wave: float = 1.0):
    """Rotary embedding over adjacent pairs ``(2i, 2i+1)`` of the last
    dim (DeepSeek's ``rope_interleave``), in f32: pair i turns by
    ``pos * inv[i]`` (``rope_frequencies``), cos and sin scaled by
    ``wave``.  ``x``: (..., t, d) with ``pos`` (..., t) broadcasting
    against its leading dims."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[..., None] * inv                 # (..., t, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if wave != 1.0:
        cos, sin = cos * wave, sin * wave
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class LatentAttention(Op):
    """Multi-head latent attention (DeepSeek-V2/V3's MLA) over
    (batch, seq, dim), causal.

    Keys and values of every head are expanded from one low-rank latent
    a token: ``[c~ | k~_r] = x W_kva`` (``kv_rank + rope``), ``c =
    RMSNorm(c~)``, ``k_r = RoPE(k~_r)`` (one rotary key shared by every
    head), ``[k_nope | v] = c W_kvb``.  A head's score is ``(q_nope .
    k_nope + RoPE(q_rope) . k_r) / sqrt(nope + rope)``.

    Two formulations of the same attention:

    - **expanded** (training, eval, serving prefill): K and V are
      materialised a head and ordinary causal attention runs at q.k
      width ``nope + rope`` and v width ``v_dim``.
    - **absorbed** (serving decode): ``W_kvb`` is folded into the query
      and the output (``q^_h = q_nope,h W_K,h^T``, ``o_h = (sum_j p_j
      c_j) W_V,h``), so attention runs over the latent itself and the
      cache holds ``[c_j | k_r,j]``: ``kv_rank + rope`` values a token,
      shared by every head.

    Two more arguments, each absent by default (the op then declares the
    cache and lowers to the programs it always did).  ``gate="per_head"``:
    the attended values of a head times ``sigmoid(x W_g)``'s value for
    it, before the output projection.  ``select`` (``indexer_num_heads``,
    ``indexer_head_dim``, ``topk``): the learned token selector of
    ``ops/token_select.py``, DeepSeek-V3.2's reading of it: its query
    from the normed compressed query (``q_rank``), its rotary part the
    leading ``rope_dim`` of the selector's head at the layer's own
    frequencies.  The op then keeps a second cache entry (the selector's
    keys) and declares the latent cache positions-major, a position's
    ``kv_rank + rope`` values one row, filled up with zeros to whole
    128-lane tiles (576 -> 640: the chip stores a row that is not whole
    tiles positions-last whatever the declared order, and a decode
    superstep would relayout every layer's cache on its way in and out):
    a decode step scores the slot's
    keys, picks ``topk`` positions, gathers those rows (one gather
    serves every head) and runs the absorbed attention over them alone;
    a prefill runs the expanded attention with row ``t`` over its
    selected set (``_attend_selected``).  Padded, on one device: the
    paged pool, the offset prefill and a mesh raise
    ``NotImplementedError`` (ROADMAP B-M1).

    Strategy axes: ``c`` shards heads (the q, kv-expansion and output
    projections carry the tag on their head dim); ``n`` the batch.
    """

    serving_aware = True

    def __init__(self, name: str, x: TensorSpec, num_heads: int,
                 kv_rank: int, nope_dim: int, rope_dim: int, v_dim: int,
                 rope_theta: float = 10000.0, norm_eps: float = 1e-6,
                 q_rank: Optional[int] = None,
                 rope_scaling: Optional[dict] = None,
                 gate: Optional[str] = None,
                 select: Optional[dict] = None,
                 kernel_initializer=None):
        super().__init__(name, [x])
        assert x.ndim == 3, f"attention input must be (batch, seq, dim), got {x.shape}"
        assert rope_dim % 2 == 0, rope_dim
        if gate not in (None, "per_head"):
            raise ValueError(f"{name}: gate={gate!r} (only 'per_head')")
        self.attrs = dict(num_heads=num_heads, kv_rank=kv_rank,
                          nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
                          rope_theta=float(rope_theta), norm_eps=norm_eps,
                          q_rank=q_rank, rope_scaling=rope_scaling,
                          gate=gate, select=select, causal=True)
        #: What the scores are multiplied by before the softmax.
        self.scale = 1.0 / math.sqrt(nope_dim + rope_dim)
        inv, wave, soft = rope_frequencies(rope_dim, rope_theta, rope_scaling)
        if rope_scaling is not None:
            self.scale *= soft
        #: The token selector composed into this op, if any.
        self.select = None
        if select is not None:
            if wave != 1.0 or int(select["indexer_head_dim"]) < rope_dim:
                raise ValueError(
                    f"{name}: a token selector whose head is narrower than "
                    f"the rotary part ({rope_dim}), or under a rope scaling "
                    f"whose cos and sin scale ({wave!r}), is not built")
            self.select = TokenSelector(
                select, theta=rope_theta, eps=norm_eps, query_dim=q_rank,
                rotary_dim=rope_dim, inv=inv)
        self.kernel_initializer = kernel_initializer or GlorotUniform()
        self._make_output(x.shape, x.dtype, x.dim_axes)

    #: None = the decode kernel where the cache shape allows it.
    decode_kernel: Optional[bool] = None

    def param_specs(self) -> Dict[str, ParamSpec]:
        a = self.attrs
        d = self.inputs[0].shape[-1]
        h, r = a["num_heads"], a["kv_rank"]
        dt = self.outputs[0].dtype
        ki = self.kernel_initializer
        qr, qw = a["q_rank"], h * (a["nope_dim"] + a["rope_dim"])
        query = {"wq": ParamSpec((d, qw), dt, ki, (None, "c"))} if not qr else {
            "wq_a": ParamSpec((d, qr), dt, ki),
            "q_norm": ParamSpec((qr,), dt, OnesInitializer()),
            "wq_b": ParamSpec((qr, qw), dt, ki, (None, "c")),
        }
        specs = {
            **query,
            "wkv_a": ParamSpec((d, r + a["rope_dim"]), dt, ki),
            "kv_norm": ParamSpec((r,), dt, OnesInitializer()),
            "wkv_b": ParamSpec((r, h * (a["nope_dim"] + a["v_dim"])), dt, ki,
                               (None, "c")),
            "wo": ParamSpec((h * a["v_dim"], d), dt, ki, ("c", None)),
        }
        if a["gate"]:
            specs["wg"] = ParamSpec((d, h), dt, ki, (None, "c"))
        if self.select is not None:
            specs.update(self.select.param_specs(d, dt, ki))
        return specs

    @property
    def row_width(self) -> int:
        return self.attrs["kv_rank"] + self.attrs["rope_dim"]

    @property
    def row_pad(self) -> int:
        """Zeros behind a position's row in the positions-major cache."""
        return -self.row_width % 128 if self.select is not None else 0

    def cache_entries(self, max_seq: int) -> Dict[str, CacheEntry]:
        dt = self.outputs[0].dtype
        if self.select is not None:
            # A position a row: the row a decode step gathers.  The
            # selector's keys beside it.
            return {"ckr": CacheEntry((max_seq, self.row_width + self.row_pad), dt),
                    TokenSelector.ENTRY: self.select.cache_entry(max_seq, dt)}
        # One column a token, positions last: see pallas_kernels.mla_decode.
        return {"ckr": CacheEntry((self.row_width, max_seq), dt)}

    def serving_path(self, decode: bool) -> str:
        kind = "latent" if self.select is None else "latent_select"
        return f"{kind}_absorbed" if decode else f"{kind}_expanded"

    def decode_fetch_block(self, slots, max_seq, kernel, c=1):
        if self.select is not None:
            # Rows, not blocks: the gather fetches the chosen positions.
            return 1
        shape = (slots, self.row_width, max_seq)
        if kernel is not False and pallas_kernels.mla_decode_supported(
                shape, self.attrs["kv_rank"]):
            return pallas_kernels.mla_decode_chunk(max_seq)
        return max_seq

    # -- shared pieces -------------------------------------------------------

    def _query_source(self, params, x):
        """What the queries are projected from: the normed compressed
        query (b, t, q_rank), or ``x`` where the op has none."""
        if self.attrs["q_rank"]:
            return rms_norm(x @ params["wq_a"], params["q_norm"],
                            self.attrs["norm_eps"])
        return x

    def _query_heads(self, params, src):
        """``(q_nope, q_rope)`` (b, t, h, .) of ``_query_source``'s rows,
        the rotary part not yet turned."""
        a = self.attrs
        b, t, _ = src.shape
        q = src @ params["wq_b" if a["q_rank"] else "wq"]
        q = q.reshape(b, t, a["num_heads"], a["nope_dim"] + a["rope_dim"])
        return q[..., :a["nope_dim"]], q[..., a["nope_dim"]:]

    def _turn_queries(self, q_rope, pos):
        return self._rope(
            q_rope.transpose(0, 2, 1, 3), pos[:, None, :]
        ).transpose(0, 2, 1, 3)

    def _key_latent(self, params, x, pos):
        """``(c, k_r)``: the normalised latent (b, t, kv_rank) and the
        shared rotary key (b, t, rope) turned to ``pos`` (b, t)."""
        r = self.attrs["kv_rank"]
        ckr = x @ params["wkv_a"]
        c = rms_norm(ckr[..., :r], params["kv_norm"], self.attrs["norm_eps"])
        return c, self._rope(ckr[..., r:], pos)

    def _latent(self, params, x, pos):
        """``(q_nope, q_rope, c, k_r)``: queries a head (b, t, h, .),
        rotary parts turned to their positions ``pos`` (b, t); the
        normalised latent (b, t, kv_rank) and the shared rotary key
        (b, t, rope)."""
        q_nope, q_rope = self._query_heads(params,
                                           self._query_source(params, x))
        c, k_r = self._key_latent(params, x, pos)
        return q_nope, self._turn_queries(q_rope, pos), c, k_r

    def _rope(self, x, pos):
        a = self.attrs
        inv, wave, _ = rope_frequencies(x.shape[-1], a["rope_theta"],
                                        a["rope_scaling"])
        return rope_interleaved(x, pos, inv, wave)

    def causal_blocks(self, t: int) -> Dict[str, int]:
        """``causal_blocks`` of a serving prefill of ``t`` rows."""
        a = self.attrs
        if not _on_one_device(self):
            return {}
        return causal_blocks(self.select, t, a["num_heads"], a["num_heads"],
                             a["nope_dim"] + a["rope_dim"], a["v_dim"],
                             self.outputs[0].dtype)

    def _causal(self, q, k, v, serving: bool):
        """Causal attention on heads (b, h, t, .); (b, t, h * v_dim)."""
        if serving and _on_one_device(self) and \
                pallas_kernels.flash_uneven_supported(q.shape, self.attrs["v_dim"]):
            out = pallas_kernels.flash_fwd_uneven(q, k, v, self.scale)
        else:
            out = _einsum_attention(q, k, v, True, self.scale)
        b, _, t, _ = q.shape
        return out.transpose(0, 2, 1, 3).reshape(b, t, -1)

    def _expanded(self, params, q_nope, q_rope, c, k_r, serving: bool):
        """Causal attention with K and V expanded a head; (b, t, h*v)."""
        a = self.attrs
        b, t, h, _ = q_nope.shape
        kv = (c @ params["wkv_b"]).reshape(b, t, h, a["nope_dim"] + a["v_dim"])
        k_nope, v = kv[..., :a["nope_dim"]], kv[..., a["nope_dim"]:]
        q = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(0, 2, 1, 3)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r[:, :, None, :], (b, t, h, a["rope_dim"]))],
            axis=-1,
        ).transpose(0, 2, 1, 3)
        return self._causal(q, k, v.transpose(0, 2, 1, 3), serving)

    def _gate_logits(self, params, x):
        return x @ params["wg"] if self.attrs["gate"] else None

    def _output(self, params, out, gate):
        """The output gate (``_gate_logits``, where the op has one) and
        projection."""
        if gate is not None:
            out = _gate_heads(out, gate, self.attrs["v_dim"])
        return out @ params["wo"]

    def forward(self, params, xs, state, training):
        (x,) = xs
        if "cache_ckr" in state:
            return self._forward_cached(params, x, state)
        b, t, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        if self.select is not None:
            return [self._prefill_selected(params, x, pos, serving=False)[0]], state
        out = self._expanded(params, *self._latent(params, x, pos),
                             serving=False)
        return [self._output(params, out, self._gate_logits(params, x))], state

    # -- the latent cache (runtime/serving.py) -------------------------------

    def _forward_cached(self, params, x, state):
        """Prefill (t > 1): the expanded attention over the call's own
        tokens, writing post-norm ``c`` and post-RoPE ``k_r`` into cache
        columns ``0..t-1``.  Decode (t == 1): the token at ``pos``
        writes its column and attends columns ``<= pos`` absorbed."""
        if self.select is not None:
            return self._forward_selected(params, x, state)
        a = self.attrs
        cache = state["cache_ckr"]                      # (B, row, S)
        b, t, _ = x.shape
        h, r = a["num_heads"], a["kv_rank"]
        if "block_table" in state or "chunk" in state:
            raise NotImplementedError(
                f"{self.name}: the latent cache has no paged pool or "
                f"offset prefill yet (ROADMAP Queue B)")
        new_state = dict(state)
        if t > 1:
            pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
            q_nope, q_rope, c, k_r = self._latent(params, x, pos)
            col = jnp.concatenate([c, k_r], axis=-1).astype(cache.dtype)
            new_state["cache_ckr"] = cache.at[:, :, :t].set(
                col.transpose(0, 2, 1))
            out = self._expanded(params, q_nope, q_rope, c, k_r, serving=True)
            return [self._output(params, out, self._gate_logits(params, x))], \
                new_state
        pos = state["pos"]                              # (B,)
        q_nope, q_rope, c, k_r = self._latent(params, x, pos[:, None])
        col = jnp.concatenate([c, k_r], axis=-1)[:, 0].astype(cache.dtype)
        q_cat, w_v = self._absorb(params, q_nope, q_rope, x.dtype)
        use = self.decode_kernel
        supported = pallas_kernels.mla_decode_supported(cache.shape, r)
        if use is None or (use and not supported):
            use = supported
        if use:
            # The kernel writes the column itself, into the lane tile
            # that holds ``pos``.
            o_lat, cache = pallas_kernels.mla_decode(
                q_cat, col, cache, pos + 1, r, self.scale)
        else:
            # One in-place column write a slot.  A scatter along the
            # last axis makes the compiler hold the whole cache
            # positions-major and copy it back, every layer of every step.
            for i in range(b):
                cache = lax.dynamic_update_slice(
                    cache, col[i][None, :, None], (i, 0, pos[i]))
            o_lat = _latent_decode(q_cat, cache, pos, r, self.scale)
        new_state["cache_ckr"] = cache
        o = jnp.einsum("bhr,rhv->bhv", o_lat, w_v)
        return [self._output(params, o.reshape(b, 1, h * a["v_dim"]),
                             self._gate_logits(params, x))], new_state

    def _absorb(self, params, q_nope, q_rope, dtype):
        """A decode step's ``(q_cat (B, h, kv_rank + rope), w_v (kv_rank,
        h, v_dim))``: ``W_kvb``'s key half folded into the query, its
        value half for the output."""
        a = self.attrs
        wkv_b = params["wkv_b"].reshape(a["kv_rank"], a["num_heads"],
                                        a["nope_dim"] + a["v_dim"])
        w_k, w_v = wkv_b[..., :a["nope_dim"]], wkv_b[..., a["nope_dim"]:]
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_k)
        return jnp.concatenate([q_lat.astype(dtype), q_rope[:, 0]], axis=-1), w_v

    # -- a token selector over the latent cache (PR 48) ------------------------

    def _index(self, params, x, src, pos):
        """The selector's ``(q, k, w)`` of this call's tokens at ``pos``
        (b, t): the query from ``src`` (``_query_source``'s rows)."""
        with jax.named_scope("ff_index"):
            return self.select.project(params, x, pos, q_from=src)

    def _prefill_selected(self, params, x, pos, serving: bool):
        """The expanded attention of the tokens ``x`` at ``pos`` = ``0..
        t-1``, each row over its selected set; ``(out (b, t, h * v_dim),
        rows (b, t, .) for the latent cache, the selector's keys (b, t,
        .))``.  Sized for a 32k prefill at 64 heads beside the weights:
        the attention's and the selector's queries are made from the
        compressed query a chunk at a time (805 and 512 MB whole), the
        heads' keys hold their own ``nope`` part alone and the rotary key
        is scored once for all of them (K whole is 1 GB, its product with
        ``W_kvb`` in one piece another), and the gate's logits are taken
        before the attention so that the layer's input can go.  A
        serving program's masked chunks score the two key parts inside
        ``pallas_kernels.attend_kept`` (no head's 64 MB of float32 scores
        is written out); elsewhere a head at a time."""
        a, sel = self.attrs, self.select
        h, r, nope = a["num_heads"], a["kv_rank"], a["nope_dim"]
        src = self._query_source(params, x)
        c, k_r = self._key_latent(params, x, pos)
        with jax.named_scope("ff_index"):
            ik, iw = sel.keys(params, x, pos)
        gate = self._gate_logits(params, x)
        wkv_b = params["wkv_b"].reshape(r, h, nope + a["v_dim"])
        k_nope = jnp.einsum("btr,rhn->bhtn", c, wkv_b[..., :nope])
        v = jnp.einsum("btr,rhv->bhtv", c, wkv_b[..., nope:])

        def index_q(start, n):
            with jax.named_scope("ff_index"):
                return sel.queries(
                    params, lax.dynamic_slice_in_dim(src, start, n, axis=1),
                    lax.dynamic_slice_in_dim(pos, start, n, axis=1))

        def placed_q(start, n):
            q_nope, q_rope = self._query_heads(
                params, lax.dynamic_slice_in_dim(src, start, n, axis=1))
            q_rope = self._turn_queries(
                q_rope, lax.dynamic_slice_in_dim(pos, start, n, axis=1))
            return jnp.concatenate([q_nope, q_rope],
                                   axis=-1).transpose(0, 2, 1, 3)

        def dense(q, k, v, dtype):
            n = k.shape[2]
            whole = jnp.concatenate([k, jnp.broadcast_to(
                k_r[:, None, :n], k.shape[:3] + (a["rope_dim"],))], axis=-1)
            return self._causal(q, whole, v, serving).astype(dtype)

        out = _attend_selected(sel, x.shape[1], placed_q, k_nope, v,
                               (index_q, ik, iw), self.scale, dense, x.dtype,
                               shared_k=k_r, serving=serving)
        return self._output(params, out, gate), self._cache_rows(c, k_r), ik

    def kept_blocks(self, t: int) -> Dict[str, Any]:
        """``kept_blocks`` of a serving prefill of ``t`` rows."""
        a = self.attrs
        h, nope, rope = a["num_heads"], a["nope_dim"], a["rope_dim"]
        return kept_blocks(self.select, t, h, (1, h, t, nope), nope + rope,
                           a["v_dim"], rope)

    def _cache_rows(self, c, k_r):
        """``[c | k_r | zeros]`` (b, t, the cache's row)."""
        pad = jnp.zeros(c.shape[:-1] + (self.row_pad,), c.dtype)
        return jnp.concatenate([c, k_r, pad], axis=-1)

    def _forward_selected(self, params, x, state):
        """The cached forward of an op with a selector: caches ``ckr``
        (B, S, kv_rank + rope + row_pad), a position a row, and ``idx`` (B, S,
        selector head), the selector's keys.  Prefill (t > 1): both
        written at rows ``0..t-1`` and ``_prefill_selected``.  Decode
        (t == 1): the token at ``pos`` writes its two rows (a slice
        update a slot, in place), the selector scores every row of its
        small cache, keeps ``topk`` among the live ones, and the latent
        rows of those positions alone are gathered and attended,
        absorbed."""
        a, sel, plan = self.attrs, self.select, getattr(self, "_plan", None)
        if "block_table" in state or "chunk" in state or \
                (plan is not None and plan.num_devices > 1):
            raise NotImplementedError(
                f"{self.name}: selection over a paged pool, an offset "
                f"prefill or a sharded cache is not built (ROADMAP B-M1)")
        cache, ci = state["cache_ckr"], state[f"cache_{sel.ENTRY}"]
        b, t, _ = x.shape
        new_state = dict(state)
        if t > 1:
            pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
            y, rows, ik = self._prefill_selected(params, x, pos, serving=True)
            cache = cache.at[:, :t].set(rows.astype(cache.dtype))
            ci = ci.at[:, :t].set(ik.astype(ci.dtype))
        else:
            at = state["pos"]                           # (B,)
            pos = at[:, None]
            src = self._query_source(params, x)
            q_nope, q_rope = self._query_heads(params, src)
            c, k_r = self._key_latent(params, x, pos)
            q_rope = self._turn_queries(q_rope, pos)
            iq, ik, iw = self._index(params, x, src, pos)
            row = self._cache_rows(c, k_r)
            for i in range(b):
                cache, ci = (
                    lax.dynamic_update_slice(e, new[i][None].astype(e.dtype),
                                             (i, at[i], 0))
                    for e, new in ((cache, row), (ci, ik)))
            with jax.named_scope("ff_index"):
                scores = sel.scores(iq, iw, ci)[:, 0]             # (B, S)
            with jax.named_scope("ff_select"):
                idx, valid = sel.pick(scores, at)
                rows = jnp.take_along_axis(cache, idx[:, :, None], axis=1)
            q_cat, w_v = self._absorb(params, q_nope, q_rope, x.dtype)
            # Zeros against the rows' zeros, so that the product runs
            # over whole lane tiles and no row is cut.
            q_cat = jnp.pad(q_cat, ((0, 0), (0, 0), (0, self.row_pad)))
            o_lat = _latent_decode_rows(q_cat, rows, valid, a["kv_rank"],
                                        self.scale)
            out = jnp.einsum("bhr,rhv->bhv", o_lat, w_v).reshape(b, 1, -1)
            y = self._output(params, out, self._gate_logits(params, x))
        new_state["cache_ckr"] = cache
        new_state[f"cache_{sel.ENTRY}"] = ci
        return [y], new_state


def _latent_decode_rows(q_cat, rows, valid, v_width: int, scale: float):
    """The absorbed attention of one query a head ``q_cat`` (B, h, row)
    over a slot's gathered latent rows ``rows`` (B, k, row), the
    ``valid`` (B, k) ones: f32 scores and softmax, the products in the
    operands' dtype.  (B, h, v_width)."""
    s = jnp.einsum("bhr,bkr->bhk", q_cat, rows,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(valid[:, None, :], s, _NEG_INF), axis=-1)
    return jnp.einsum("bhk,bkv->bhv", p.astype(rows.dtype),
                      rows[..., :v_width],
                      preferred_element_type=jnp.float32).astype(q_cat.dtype)


def _latent_decode(q_cat, cache, pos, v_width: int, scale: float):
    """Dense oracle of ``pallas_kernels.mla_decode``: f32 scores over
    the whole (B, row, S) cache, masked to positions ``<= pos``."""
    cf = cache.astype(jnp.float32)
    s = jnp.einsum("bhr,brs->bhs", q_cat.astype(jnp.float32), cf) * scale
    mask = jnp.arange(cache.shape[-1])[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(mask[:, None, :], s, _NEG_INF), axis=-1)
    return jnp.einsum("bhs,bvs->bhv", p, cf[:, :v_width]).astype(q_cat.dtype)
