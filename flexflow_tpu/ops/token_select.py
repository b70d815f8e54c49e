"""A learned token selector over a slot's cache (DeepSeek Sparse
Attention's "lightning indexer"): which past positions an attention
layer reads.

Not an attention op: it owns a few small projections, ONE more cache
entry a slot (its keys, one head of ``head_dim`` a position: 128 B
where K and V of a grouped-query layer are 2 KB), a score a (query,
position) pair and a top-k over the scores.  An attention op composes
it (``MultiHeadAttention(select=...)`` today; the same selector sits
over latent attention in other models) and attends the selected
positions alone.  With ``a`` the attention layer's (normed) input,

    q^I_j = R'_p((a W_qI)_j)   j < heads         k^I = R'_p(LayerNorm(a W_kI))
    w     = (a W_w) * heads^-1/2 * head_dim^-1/2                    (float32)
    I(t, s) = sum_j w_j(t) ReLU(q^I_j(t) . k^I(s))                  s <= t
    S_t   = the ``topk`` positions s <= t of largest I(t, s)  (all while t < topk)

``R'`` turns half-split pairs ``(i, i + head_dim/2)`` of the whole head
by the token's index (``rope_half`` with one position component).  Two
arguments move it to DeepSeek-V3.2's own reading over latent attention:
``query_dim`` (the query is taken from another row than ``a``: the
layer's normed compressed query, handed to ``project`` as ``q_from``)
and ``rotary_dim`` with ``inv`` (the pairs ``(i, i + rotary_dim/2)`` of
the leading ``rotary_dim`` turn, at the layer's own frequencies; the
rest passes).
Queries, keys and the key cache are the compute dtype; ``w``, the
scores and the top-k are float32.  Ties go to the lower position
(``lax.top_k``'s order), in a decode step and in a prefill alike.

A decode step needs the ``topk`` indices and takes ``lax.top_k`` (a full
sort of the row).  A prefill needs each row's ``topk``-th largest score
alone, never the order of the rest, and sorts nothing: ``order_key``
turns a float32 into the int32 whose integer order is the float's total
order (``lax.top_k``'s own: ``-0.0`` below ``+0.0``, ``-inf`` below
every finite score), and ``kth_largest_key`` builds the ``topk``-th
largest key from its top bit down, a compare and a count of the row a
bit.  What is kept is compared in keys too, so the selected set is
``lax.top_k``'s position for position, zeros of both signs included.
The mask (``keep``: the causal edge is in it) is all the attention op
takes from a prefill chunk's selection: ``ops/attention.py::
_attend_selected`` hands it to ``pallas_kernels.attend_kept`` where
that kernel's gate takes the shapes, one mask for every head.
NaN scores are outside the contract (their keys lie beyond both
infinities).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from flexflow_tpu.initializers import OnesInitializer, ZeroInitializer
from flexflow_tpu.ops.base import CacheEntry, ParamSpec

_NEG_INF = -jnp.inf


def order_key(x):
    """The int32 key of a float32: ``key(a) < key(b)`` exactly where
    ``a`` lies below ``b`` in the float's total order (a negative's
    magnitude bits are flipped, so ``-0.0`` is -1 and ``+0.0`` is 0)."""
    b = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def kth_largest_key(keys, k: int):
    """The ``k``-th largest of each row of int32 ``keys`` (..., s), as
    (..., 1): the largest threshold that ``k`` of the row's keys still
    reach, exact and without a sort.  Built from the sign down: a
    candidate keeps its bit iff ``k`` keys are still ``>=`` it, 32
    counts of the row in all (on the chip one fused compare-and-reduce
    a round, the keys held in VMEM across the loop: PERF.md §6 PR 43).
    ``1 <= k <= s``."""
    assert 1 <= k <= keys.shape[-1], (k, keys.shape)

    def reached(cand):
        return jnp.sum(keys >= cand, axis=-1, keepdims=True,
                       dtype=jnp.int32) >= k

    zero = jnp.zeros(keys.shape[:-1] + (1,), jnp.int32)
    base = jnp.where(reached(zero), zero, jnp.iinfo(jnp.int32).min)

    def set_bit(i, t):
        cand = t | (jnp.int32(1) << (30 - i))
        return jnp.where(reached(cand), cand, t)

    return lax.fori_loop(0, 31, set_bit, base)


def rope_half(x, pos, theta: float, sections: Optional[Sequence[int]] = None,
              rotary_dim: Optional[int] = None, inv=None, wave: float = 1.0):
    """Rotary embedding over half-split pairs ``(i, i + d/2)`` of the
    last dim, in f32: pair ``i`` turns by ``p_c(i) * theta^(-2i/d)``.
    ``x``: (..., t, d).  ``pos``: (..., t) token indices, or with
    ``sections`` (multimodal rotary positions: how many pairs each
    position component turns, in order) optionally (..., t,
    len(sections)); one index stands for every component, which is plain
    rotary.  ``rotary_dim`` (r < d): the pairs ``(i, i + r/2)`` of the
    leading ``r`` turn and the rest passes through.  ``inv`` (r/2,) the
    pairs' frequencies where they are not ``theta``'s own, and ``wave``
    the scale of cos and sin (``ops/attention.py::rope_frequencies``)."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        turned = rope_half(x[..., :rotary_dim], pos, theta, sections,
                           inv=inv, wave=wave)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    if inv is None:
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)   # (d/2,)
    pos = jnp.asarray(pos).astype(jnp.float32)
    if sections is not None and pos.ndim == x.ndim:
        assert sum(sections) == d // 2, (sections, d)
        comp = np.repeat(np.arange(len(sections)), sections)
        pos = jnp.take(pos, comp, axis=-1)                          # (..., t, d/2)
    else:
        pos = pos[..., None]
    ang = pos * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if wave != 1.0:
        cos, sin = cos * wave, sin * wave
    xf = x.astype(jnp.float32)
    a, b = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


class TokenSelector:
    """The selector's parameters, cache entry, scores and top-k, for an
    attention op to compose.  ``config`` is a model's ``sa_config``
    (``indexer_num_heads``, ``indexer_head_dim``, ``topk``; one key head;
    ``q_chunk_size`` the query rows a prefill scores at a time, which
    changes no result).  ``query_dim``: the width of the row the query
    is projected from where that is not the layer's input;
    ``rotary_dim`` / ``inv``: ``rope_half``'s, for q and k alike."""

    #: The cache entry's name inside the composing op (the parameters
    #: there carry the prefix ``idx_``).
    ENTRY = "idx"

    def __init__(self, config: Dict[str, Any], theta: float, eps: float = 1e-6,
                 query_dim: Optional[int] = None,
                 rotary_dim: Optional[int] = None, inv=None):
        if config.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError(
                f"token selector: indexer_num_kv_heads="
                f"{config['indexer_num_kv_heads']!r} is not built (one key head)")
        self.heads = int(config["indexer_num_heads"])
        self.head_dim = int(config["indexer_head_dim"])
        self.topk = int(config["topk"])
        self.q_chunk = int(config.get("q_chunk_size", 512))
        assert self.head_dim % 2 == 0, self.head_dim
        self.theta, self.eps = float(theta), float(eps)
        self.query_dim = query_dim
        self.turn = dict(rotary_dim=rotary_dim, inv=inv)
        self.scale = 1.0 / math.sqrt(self.heads * self.head_dim)

    def param_specs(self, d: int, dtype, initializer) -> Dict[str, ParamSpec]:
        h, hd = self.heads, self.head_dim
        return {
            "idx_wq": ParamSpec((self.query_dim or d, h * hd), dtype,
                                initializer),
            "idx_wk": ParamSpec((d, hd), dtype, initializer),
            # Held and multiplied in f32, like a router.
            "idx_ww": ParamSpec((d, h), jnp.float32, initializer),
            "idx_k_scale": ParamSpec((hd,), dtype, OnesInitializer()),
            "idx_k_bias": ParamSpec((hd,), dtype, ZeroInitializer()),
        }

    def cache_entry(self, max_seq: int, dtype) -> CacheEntry:
        """The keys of every position, positions-major (a row a key)."""
        return CacheEntry((max_seq, self.head_dim), dtype)

    def project(self, params, a, pos, q_from=None):
        """``(q (b, t, heads, hd), k (b, t, hd), w (b, t, heads) f32)``
        of the tokens ``a`` (b, t, d) at indices ``pos`` (b, t); the
        query from ``q_from`` (b, t, query_dim) where the selector has
        one."""
        q = self.queries(params, a if q_from is None else q_from, pos)
        return (q, *self.keys(params, a, pos))

    def queries(self, params, src, pos):
        """``q`` (b, t, heads, hd) of the rows ``src`` at ``pos``."""
        b, t, _ = src.shape
        q = (src @ params["idx_wq"]).reshape(b, t, self.heads, self.head_dim)
        return rope_half(q.transpose(0, 2, 1, 3), pos[:, None], self.theta,
                         **self.turn).transpose(0, 2, 1, 3)

    def keys(self, params, a, pos):
        """``(k (b, t, hd), w (b, t, heads) f32)`` of the tokens ``a``."""
        kf = (a @ params["idx_wk"]).astype(jnp.float32)
        mean = jnp.mean(kf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(kf - mean), axis=-1, keepdims=True)
        kf = (kf - mean) * lax.rsqrt(var + self.eps) \
            * params["idx_k_scale"].astype(jnp.float32) \
            + params["idx_k_bias"].astype(jnp.float32)
        k = rope_half(kf.astype(a.dtype), pos, self.theta, **self.turn)
        w = jnp.dot(a.astype(jnp.float32), params["idx_ww"],
                    precision=lax.Precision.HIGHEST) * self.scale
        return k, w

    #: The f32 products of every head at once (``t x heads x s``) one
    #: call of ``scores`` may hold; past it the heads run one after
    #: another (a prefill chunk of 512 rows against 32k keys is 1 GiB
    #: for 16 heads at once, 64 MiB a head).
    DOTS_BYTES = 1 << 27

    def scores(self, q, w, keys):
        """``I`` (b, t, s) f32 of queries ``q`` (b, t, heads, hd) under
        weights ``w`` (b, t, heads) against ``keys`` (b, s, hd); no
        mask."""
        b, t, h, _ = q.shape
        if b * t * h * keys.shape[1] * 4 <= self.DOTS_BYTES:
            dots = jnp.einsum("bthd,bsd->bths", q, keys,
                              preferred_element_type=jnp.float32)
            return jnp.einsum("bths,bth->bts", jax.nn.relu(dots), w)

        def add_head(acc, qw):
            qj, wj = qw                                   # (b, t, hd), (b, t)
            dots = jnp.einsum("btd,bsd->bts", qj, keys,
                              preferred_element_type=jnp.float32)
            return acc + jax.nn.relu(dots) * wj[..., None], None

        acc = jnp.zeros((b, t, keys.shape[1]), jnp.float32)
        return lax.scan(add_head, acc, (q.transpose(2, 0, 1, 3),
                                        w.transpose(2, 0, 1)))[0]

    def pick(self, scores, pos):
        """A decode step's selection: ``(idx (B, k) int32, valid (B, k))``
        from ``scores`` (B, S) over each slot's whole cache and the
        query's own position ``pos`` (B,): the ``k = min(topk, S)``
        largest among positions ``<= pos``.  Where fewer are live,
        ``valid`` is false on the rest (their indices point anywhere)."""
        live = jnp.arange(scores.shape[-1])[None, :] <= pos[:, None]
        top, idx = lax.top_k(jnp.where(live, scores, _NEG_INF),
                             min(self.topk, scores.shape[-1]))
        return idx, top > _NEG_INF

    def keep(self, scores, q_pos):
        """A prefill's selection as a mask (b, t, s): of ``scores``
        (b, t, s) against key positions ``0..s-1``, query row ``i`` (at
        position ``q_pos[i]``) keeps the causal positions whose score
        reaches its ``topk``-th largest causal score (all of them while
        there are no more than ``topk``).  The ``topk``-th is found by
        ``kth_largest_key``'s threshold search over the scores' order
        keys, not by a sort, and every comparison after it is of keys:
        the total order is ``lax.top_k``'s (``+0.0`` above ``-0.0``), so
        with the lower position among equal keys the mask is the one
        scattered from ``lax.top_k``'s indices.  A row with fewer than
        ``topk`` causal positions finds the key of ``-inf`` and keeps
        its whole past."""
        s = scores.shape[-1]
        causal = jnp.arange(s)[None, :] <= q_pos[:, None]
        if s <= self.topk:
            return jnp.broadcast_to(causal[None], scores.shape)
        keys = order_key(jnp.where(causal[None], scores, _NEG_INF))
        kth = kth_largest_key(keys, self.topk)
        keep = causal[None] & (keys >= kth)

        def lowest_of_equals(keep):
            # Scores that tie with the topk-th (exact zeros, where every
            # head's ReLU is shut): the lowest positions, as top_k has it.
            above = keys > kth
            ties = keep & ~above
            room = self.topk - jnp.sum(above, axis=-1, keepdims=True)
            return above | (ties & (jnp.cumsum(ties, axis=-1) <= room))

        return lax.cond(jnp.any(jnp.sum(keep, axis=-1) > self.topk),
                        lowest_of_equals, lambda keep: keep, keep)
