"""The language model of Keye-VL-2.0 in plain float32 ``jax.numpy``: forward.

Pre-norm residual blocks (RMSNorm eps ``rms_norm_eps``, no bias, SiLU,
an untied head), every layer alike.  With ``a = RMSNorm(x)``, ``H``
query heads over ``G`` key/value heads of ``hd``:

    q_h = R_p(RMSNorm_hd(a W_q)_h)      k_g = R_p(RMSNorm_hd(a W_k)_g)      v_g = (a W_v)_g
    R_p: half-split rotary over the whole head, pair (i, i + hd/2), angle
         p_c(i) theta^(-2i/hd); c(i) the component ``mrope_section`` gives
         pair i; for text p_0 = p_1 = p_2 = the token's index
    indexer (``sa_config``: J heads of hdI over one key head):
         q^I_j = R'_p((a W_qI)_j)       k^I = R'_p(LayerNorm(a W_kI))
         w = (a W_w) J^-1/2 hdI^-1/2                      (float32)
         I(t, s) = sum_j w_j(t) ReLU(q^I_j(t) . k^I(s))   s <= t
         S_t = the ``topk`` positions s <= t of largest I(t, s), lowest
               position first among equals (all of them while t < topk)
    o_h(t) = sum_{s in S_t} softmax_{s in S_t}(q_h(t) . k_{h // (H/G)}(s) / sqrt(hd)) v_{h // (H/G)}(s)
    y = concat_h(o_h) W_o

then ``p = softmax(u W_r)`` over every expert in float32, top-k, the k
renormalised to sum 1 (``norm_topk_prob``), ``sum_e p_e W_down,e(SiLU(
W_gate,e u) * W_up,e u)``; no shared expert.  Residual adds, a last
RMSNorm, the head.

Departures from the two published descriptions this follows (Qwen3-MoE's
block for everything outside ``sa_config``, DeepSeek-V3.2-Exp's indexer
inside it), each because the configuration has no key for the thing:
the indexer's query is taken from the normed hidden state (no
compressed query exists here to take it from); both its rotary parts
turn over their whole width by the token's index at the layer's theta
(32 pairs cannot carry a 64-pair ``mrope_section``); no Hadamard
rotation (orthogonal: no product changes) and no fp8 (a precision
choice) in the indexer; ``q_chunk_size`` and ``kv_chunk_size`` are read
as tile sizes and appear nowhere below.  The vision tower is absent:
``tokens`` are ids.

No kernel, cache or batching, and nothing of the program is imported.
Leaves are named ``"<op>/<key>"`` after the recipe in ``leaf_spec`` and
drawn by ``benchmark/weights.py``, any leaf (or any expert of a leaf)
alone.  ``assumed.q_norm_gain`` (layer -> the centre of its query
norm's scale, 1 elsewhere) lets a configuration draw a layer's heads
peaked: under unit scales a softmax over thousands of seeded positions
is flat, every layer's output is one common average, and no logit
depends on which positions the selector kept.  The layers are one
scanned body, each drawing its leaves from its own keys and offsets,
an expert's inside the loop over experts, and attention
runs a block of query rows at a time, a key/value head at a time, the
selection as a mask scattered from ``lax.top_k`` of ``I``.  Matrix
products run at ``highest`` precision; ``quant`` (the control) rounds
both operands of every product the configuration computes in bfloat16
(the indexer's q . k among them) to fp8 e4m3 first, scaled by the
tensor's largest magnitude: the nearest precision below the one the
configuration states.  The router, ``w``, the norms and the softmax stay
in float32 there too, as the configuration states them.

What ``served_gaps`` hands the runner as the gap it judges is the MEAN
over the served positions of how far the served token's logit lies
below the reference's best, not the widest, for the reason the
DeepSeek-V3 reference gives: a top-k is a discontinuous function, here
twice over (8 of 128 experts, 2048 of up to 32k positions), and where
two candidates lie within the program's bfloat16 round-off of each
other the program and this float32 walk part by a whole expert's output
or a position's value.  The widest gap, the quantiles, the first served
token's gap, the share of (token, layer) expert selections that flip
under bfloat16 activations and the share of selected positions that a
bfloat16 evaluation of ``I`` replaces are printed beside it.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

_SQ3 = math.sqrt(3.0)
_Q_ROWS = 256  # query rows a block of the reference's attention


def leaf_spec(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    """``"op/key" -> (shape, half_width, offset)`` of every leaf, in the
    layout the program holds it in."""
    a, sa = cfg["assumed"], cfg["sa_config"]
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    j, hi = sa["indexer_num_heads"], sa["indexer_head_dim"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    w, ns = a["init_std"] * _SQ3, a["norm_scale_half_width"]
    gains = a.get("q_norm_gain", {})      # layer (as a string) -> the scale's centre
    spec = {
        "embed/table": ((v, d), w, 0.0),
        "ln_f/scale": ((d,), ns, 1.0),
        "lm_head/kernel": ((v, d), w, 0.0),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk{i}_"
        spec[f"{p}ln1/scale"] = ((d,), ns, 1.0)
        spec[f"{p}ln2/scale"] = ((d,), ns, 1.0)
        spec[f"{p}attn/wq"] = ((d, h * hd), w, 0.0)
        spec[f"{p}attn/wk"] = ((d, hkv * hd), w, 0.0)
        spec[f"{p}attn/wv"] = ((d, hkv * hd), w, 0.0)
        spec[f"{p}attn/wo"] = ((h * hd, d), w, 0.0)
        spec[f"{p}attn/q_norm"] = ((hd,), ns, float(gains.get(str(i), 1.0)))
        spec[f"{p}attn/k_norm"] = ((hd,), ns, 1.0)
        spec[f"{p}attn/idx_wq"] = ((d, j * hi), w, 0.0)
        spec[f"{p}attn/idx_wk"] = ((d, hi), w, 0.0)
        spec[f"{p}attn/idx_ww"] = ((d, j), w, 0.0)
        spec[f"{p}attn/idx_k_scale"] = ((hi,), ns, 1.0)
        spec[f"{p}attn/idx_k_bias"] = ((hi,), ns, 0.0)
        spec[f"{p}moe/gate"] = ((d, e), w, 0.0)
        spec[f"{p}moe/w_gate"] = ((e, d, f), w, 0.0)
        spec[f"{p}moe/w_up"] = ((e, d, f), w, 0.0)
        spec[f"{p}moe/w_down"] = ((e, f, d), w, 0.0)
    return spec


def parameter_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    """``{"total", "active"}`` from ``leaf_spec``: every leaf, and what
    one token passes (its row of the table, ``num_experts_per_tok`` of
    each layer's experts, everything else once)."""
    total = active = 0
    share = cfg["num_experts_per_tok"] / cfg["num_experts"]
    for name, (shape, _, _) in leaf_spec(cfg).items():
        n = int(np.prod(shape))
        total += n
        if name == "embed/table":
            active += shape[1]
        elif name.endswith(("moe/w_gate", "moe/w_up", "moe/w_down")):
            active += int(n * share)
        else:
            active += n
    return {"total": total, "active": active}


def stored_dtype(cfg: Dict[str, Any], name: str) -> str:
    if name.endswith(("moe/gate", "attn/idx_ww")):
        return cfg["assumed"]["router_dtype"]
    return cfg["assumed"]["param_dtype"]


class Leaves:
    """Seeded leaves under the name prefix ``at`` (``"blk3_"``; empty
    for the whole model's names), each made when asked for and rounded
    once to the dtype the configuration stores it in, held in f32.
    ``seed`` is a whole number or the (possibly traced) ``(low, high)``
    words of ``weights.split_seed``.  ``keys`` (local name -> (the leaf's
    32-bit key, its offset), possibly traced) stands in for the names
    where one traced body serves several layers: shapes and half
    widths are then ``at``'s."""

    def __init__(self, cfg: Dict[str, Any], seed, at: str = "", keys=None, spec=None):
        self.cfg, self.seed, self.prefix, self.keys = cfg, seed, at, keys
        self.spec = spec or leaf_spec(cfg)

    def at(self, prefix: str, keys=None) -> "Leaves":
        """The same leaves seen from under another prefix."""
        return Leaves(self.cfg, self.seed, prefix, keys, self.spec)

    def _values(self, name: str, rows, cols_n: int):
        full = self.prefix + name
        _, hw, off = self.spec[full]
        key, off = self.keys[name] if self.keys is not None else \
            (weights.leaf_key(self.seed, full, jnp), off)
        v = weights.unit_uniform(key, rows.astype(jnp.uint32)[:, None],
                                 jnp.arange(cols_n, dtype=jnp.uint32)[None, :], jnp)
        return weights.round_to(jnp.float32(off) + jnp.float32(hw) * v,
                                stored_dtype(self.cfg, full), jnp)

    def __call__(self, name: str):
        shape = self.spec[self.prefix + name][0]
        rows_n = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
        return self._values(name, jnp.arange(rows_n, dtype=jnp.uint32),
                            shape[-1]).reshape(shape)

    def expert(self, name: str, e):
        """Row ``e`` (may be traced) of the stacked leaf ``name``."""
        _, rows_n, cols_n = self.spec[self.prefix + name][0]
        rows = jnp.asarray(e, jnp.uint32) * jnp.uint32(rows_n) \
            + jnp.arange(rows_n, dtype=jnp.uint32)
        return self._values(name, rows, cols_n)


def _fp8(x):
    """Round to fp8 e4m3 (largest finite value 240) under the tensor's
    own scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    return weights.round_to(x / s, "float8_e4m3fn", jnp) * s


def _mm(a, b, quant: bool):
    if quant:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision="highest")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rotary(x, pos, theta: float, sections=None):
    """Half-split rotary over the last dim of ``x`` (t, ..., d): pair
    (i, i + d/2) of the token at row ``r`` turns by ``pos[r, c(i)]
    theta^(-2i/d)``.  ``pos``: (t,) indices, or (t, len(sections))
    components with ``sections`` pairs each, in order."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    pos = jnp.asarray(pos, jnp.float32)
    if pos.ndim == 2:
        comp = np.repeat(np.arange(len(sections)), sections)
        assert comp.shape[0] == d // 2, (sections, d)
        pos = pos[:, comp]                                  # (t, d/2)
    else:
        pos = pos[:, None]
    ang = (pos * inv).reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def indexer(cfg, get: Leaves, a, index, quant: bool = False, low: bool = False):
    """``(q^I (t, J, hdI), k^I (t, hdI), w (t, J))`` of the normed tokens
    ``a`` (t, d) at indices ``index`` (t,).  ``low`` evaluates the q and
    k sides as the program does: ``a`` and the rotated q and k rounded
    to bfloat16 (``w`` stays what the configuration says: float32)."""
    sa, theta = cfg["sa_config"], float(cfg["rope_theta"])
    j, hi = sa["indexer_num_heads"], sa["indexer_head_dim"]
    t = a.shape[0]
    r = (lambda z: weights.round_to(z, "bfloat16", jnp)) if low else (lambda z: z)
    q = r(rotary(r(_mm(r(a), get("attn/idx_wq"), quant)).reshape(t, j, hi), index, theta))
    k = r(_mm(r(a), get("attn/idx_wk"), quant))
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
    k = (k - mean) * jax.lax.rsqrt(var + cfg["rms_norm_eps"]) \
        * get("attn/idx_k_scale") + get("attn/idx_k_bias")
    k = r(rotary(r(k), index, theta))
    w = jnp.matmul(a, get("attn/idx_ww"), precision="highest") / math.sqrt(j * hi)
    return q, k, w


def index_scores(q, k, w, quant: bool = False):
    """``I`` (rows, t): no mask."""
    if quant:
        q, k = _fp8(q), _fp8(k)
    dots = jnp.einsum("qjd,td->qjt", q, k, precision="highest")
    return jnp.einsum("qjt,qj->qt", jax.nn.relu(dots), w, precision="highest")


def selected(scores, start, topk: int):
    """The selection of query rows ``start ..`` as a mask (rows, t) from
    their scores (rows, t): the ``topk`` causal positions of largest
    score (``lax.top_k``: the lower position among equals), or every
    causal position where there are no more."""
    rows, t = scores.shape
    causal = jnp.arange(t)[None, :] <= (start + jnp.arange(rows))[:, None]
    if t <= topk:
        return causal
    top, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    mask = jnp.zeros((rows, t), bool).at[jnp.arange(rows)[:, None], idx].set(
        top > -jnp.inf)
    return mask & causal


def attention(cfg, get: Leaves, a, quant: bool = False, positions=None,
              select: bool = True):
    """Causal grouped-query attention over one sequence ``a`` (t, d)
    with the head norm, rotary positions and the learned selection;
    ``(y (t, d), the share of selected positions that a bfloat16
    evaluation of I replaces)``.  ``positions`` (t, 3) are the
    multimodal components (default: the index, three times);
    ``select`` false attends the whole causal past (the tie to dense
    attention: the tests')."""
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    g, t, eps = h // hkv, a.shape[0], cfg["rms_norm_eps"]
    theta, topk = float(cfg["rope_theta"]), cfg["sa_config"]["topk"]
    sections = (cfg.get("rope_scaling") or {}).get("mrope_section")
    index = jnp.arange(t)
    pos = index if positions is None else jnp.asarray(positions)
    q = _rms(_mm(a, get("attn/wq"), quant).reshape(t, h, hd), get("attn/q_norm"), eps)
    k = _rms(_mm(a, get("attn/wk"), quant).reshape(t, hkv, hd), get("attn/k_norm"), eps)
    q = rotary(q, pos, theta, sections).reshape(t, hkv, g, hd)
    k = rotary(k, pos, theta, sections)
    v = _mm(a, get("attn/wv"), quant).reshape(t, hkv, hd)
    qi, ki, wi = indexer(cfg, get, a, index, quant)
    qil, kil, _ = indexer(cfg, get, a, index, quant, low=True)
    rows = min(_Q_ROWS, t)
    assert t % rows == 0, (t, rows)

    def block(args):
        qb, qib, qilb, wib, start = args
        causal = jnp.arange(t)[None, :] <= (start + jnp.arange(rows))[:, None]
        if select:
            mask = selected(index_scores(qib, ki, wib, quant), start, topk)
            other = selected(index_scores(qilb, kil, wib, quant), start, topk)
            replaced = jnp.sum(mask & ~other), jnp.sum(mask)
        else:
            mask, replaced = causal, (jnp.int32(0), jnp.sum(causal))

        def head(args):
            qh, kh, vh = args                                    # (rows, g, hd), (t, hd)
            s = jnp.einsum("qgd,td->gqt", qh, kh, precision="highest") / math.sqrt(hd)
            pr = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gqt,td->qgd", pr, vh, precision="highest")

        o = jax.lax.map(head, (qb.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                               v.transpose(1, 0, 2)))            # (hkv, rows, g, hd)
        return o.transpose(1, 0, 2, 3).reshape(rows, h * hd), replaced

    n = t // rows
    o, (gone, kept) = jax.lax.map(block, (
        q.reshape(n, rows, hkv, g, hd), qi.reshape((n, rows) + qi.shape[1:]),
        qil.reshape((n, rows) + qil.shape[1:]), wi.reshape(n, rows, -1),
        jnp.arange(0, t, rows)))
    share = jnp.sum(gone) / jnp.maximum(jnp.sum(kept), 1)
    return _mm(o.reshape(t, h * hd), get("attn/wo"), quant), share.astype(jnp.float32)


def _gated(u, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(u, w_gate, quant)) * _mm(u, w_up, quant), w_down, quant)


def route(cfg, get: Leaves, u):
    """``(idx (t, k), w (t, k))`` in f32, the product at full precision."""
    p = jax.nn.softmax(jnp.matmul(u, get("moe/gate"), precision="highest"), axis=-1)
    w, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w


def experts(cfg, get: Leaves, u, quant: bool = False):
    """The expert layer's output for ``u`` (t, d): a loop over every
    expert, each run on every token and weighed by what the router gave
    it there (zero where it was not chosen): the dense sum."""
    idx, w = route(cfg, get, u)

    def one(j, acc):
        gate = jnp.sum(jnp.where(idx == j, w, 0.0), axis=-1)      # (t,)
        y = _gated(u, get.expert("moe/w_gate", j), get.expert("moe/w_up", j),
                   get.expert("moe/w_down", j), quant)
        return acc + gate[:, None] * y

    return jax.lax.fori_loop(0, cfg["num_experts"], one, jnp.zeros_like(u))


def selection_flips(cfg, get: Leaves, u):
    """Of the tokens of ``u`` (an expert layer's input), the share whose
    chosen experts change when the router reads them rounded to
    bfloat16, as the program's activations are."""
    a = jnp.sort(route(cfg, get, u)[0], axis=-1)
    b = jnp.sort(route(cfg, get, weights.round_to(u, "bfloat16", jnp))[0], axis=-1)
    return jnp.mean(jnp.any(a != b, axis=-1).astype(jnp.float32))


def layer(cfg, get: Leaves, x, quant: bool = False, select: bool = True):
    """One block; ``(x, (share of flipped expert selections, share of
    replaced positions))``."""
    eps = cfg["rms_norm_eps"]
    y, replaced = attention(cfg, get, _rms(x, get("ln1/scale"), eps), quant,
                            select=select)
    x = x + y
    u = _rms(x, get("ln2/scale"), eps)
    return x + experts(cfg, get, u, quant), (selection_flips(cfg, get, u), replaced)


def hidden(cfg: Dict[str, Any], seed, tokens, quant: bool = False, select: bool = True):
    """``tokens (t,) -> (hidden (t, d) before the last norm, (the share
    of flipped expert selections, the share of replaced positions) of
    each layer)``.  The layers are one scanned body, each drawing its
    leaves from its own keys."""
    get = Leaves(cfg, seed)
    x = get("embed/table")[tokens]
    at, n = "blk0_", cfg["num_hidden_layers"]
    local = [name[len(at):] for name in get.spec if name.startswith(at)]
    keys = {name: (jnp.stack([weights.leaf_key(seed, f"blk{i}_{name}", jnp)
                              for i in range(n)]),
                   jnp.asarray([get.spec[f"blk{i}_{name}"][2] for i in range(n)],
                               jnp.float32)) for name in local}

    def body(x, layer_keys):
        return layer(cfg, get.at(at, layer_keys), x, quant, select)

    return jax.lax.scan(body, x, keys)


class Walk:
    """The two jitted programs of one walk of ``cfg``: the layers, and
    the last norm with the head.  The seed is an argument of both."""

    def __init__(self, cfg: Dict[str, Any], quant: bool = False, select: bool = True):
        self.hidden = jax.jit(lambda seed, tokens: hidden(cfg, seed, tokens, quant, select))

        def head(seed, x):
            g = Leaves(cfg, seed)
            return _mm(_rms(x, g("ln_f/scale"), cfg["rms_norm_eps"]),
                       g("lm_head/kernel").T, quant)

        self.head = jax.jit(head)


def logits_fn(cfg: Dict[str, Any], seed: int, tokens, quant: bool = False,
              select: bool = True):
    """``tokens (t,) -> logits (t, vocab)``, float32: the whole forward
    at once (small sizes: the tests)."""
    walk, words = Walk(cfg, quant, select), weights.split_seed(seed)
    return walk.head(words, walk.hidden(words, jnp.asarray(tokens))[0])


def served_gaps(cfg: Dict[str, Any], seed: int, max_seq: int,
                samples: List[Dict[str, Any]], quant: bool = False) -> Dict[str, Any]:
    """For each sample ``{"prompt", "tokens"}`` run the full forward once
    over prompt and served tokens and read, at every served position,
    how far the served token's logit lies below the reference's best.
    With ``quant`` the token read is the one the lower precision puts
    first at that position, not the served one (the control).
    ``widest_gap``, the number the runner judges, is the mean over the
    positions (see the module's text); the widest is ``max_gap``."""
    sound, low = Walk(cfg), Walk(cfg, True) if quant else None
    words = weights.split_seed(seed)
    gaps: List[float] = []
    first: List[float] = []
    flips: List[float] = []
    replaced: List[float] = []
    pad = int(cfg["assumed"].get("reference_pad", _Q_ROWS))
    width = -(-max(len(s["tokens"]) for s in samples) // 8) * 8
    for s in samples:
        prompt = np.asarray(s["prompt"], np.int32)
        served = np.asarray(s["tokens"], np.int32)
        full = np.concatenate([prompt, served])[:-1]
        t, lo = full.shape[0], len(prompt) - 1
        # Padded (no layer looks ahead) so that a few programs serve
        # every sample, and far enough that the rows read are a slice
        # of one size.
        size = -(-(lo + width) // pad) * pad
        padded = jnp.asarray(np.pad(full, (0, size - t)))

        def served_logits(walk):
            x, shares = walk.hidden(words, padded)
            rows = jax.lax.dynamic_slice_in_dim(x, lo, width, axis=0)
            return walk.head(words, rows)[:t - lo], shares

        lg, (flip, gone) = served_logits(sound)
        flips.extend(float(f) for f in flip)
        replaced.extend(float(f) for f in gone)
        read = jnp.argmax(served_logits(low)[0], axis=-1) if quant else jnp.asarray(served)
        gap = np.asarray(jnp.max(lg, axis=-1)
                         - jnp.take_along_axis(lg, read[:, None], axis=-1)[:, 0])
        gaps.extend(float(g) for g in gap)
        first.append(float(gap[0]))
    if not gaps:
        nan = float("nan")
        return {"widest_gap": nan, "mean_gap": nan, "max_gap": nan, "tokens": 0}
    q50, q90, q99 = (float(q) for q in np.percentile(gaps, [50, 90, 99]))
    out = {"widest_gap": float(np.mean(gaps)), "mean_gap": float(np.mean(gaps)),
           "max_gap": max(gaps), "tokens": len(gaps), "first_token_max_gap": max(first),
           "selection_flip_share": float(np.mean(flips)) if flips else float("nan"),
           "position_replaced_share": float(np.mean(replaced)) if replaced else float("nan")}
    print(f"[reference] {'control' if quant else 'served'} gaps over {len(gaps)} positions of "
          f"{len(samples)} requests: mean {out['mean_gap']:.6g} p50 {q50:.6g} p90 {q90:.6g} "
          f"p99 {q99:.6g} max {out['max_gap']:.6g}; over 0.1: "
          f"{float(np.mean(np.asarray(gaps) > 0.1)):.4f}; first tokens (prefill) max "
          f"{out['first_token_max_gap']:.6g}; (token, layer) expert selections that flip under "
          f"bfloat16 activations: {out['selection_flip_share']:.4f}; selected positions that a "
          f"bfloat16 evaluation of I replaces: {out['position_replaced_share']:.4f}", flush=True)
    return out
