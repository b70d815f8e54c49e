"""The A.X-K2 block family in plain float32 ``jax.numpy``: forward.

The DeepSeek-V3 block (RMSNorm pre-norm, latent attention with K and V
expanded a head, the query compressed, ``first_k_dense_replace``
gated-SiLU dense layers, then expert layers under a sigmoid router with a
selection bias and one shared expert on every token, no bias anywhere, an
untied head) with five changes.  ``a`` is a sublayer's normed input:

- *gated norms* (``gated_norm``, ``gated_norm_rank`` r) on a block's two
  norms and the last one:

      GN(x) = n * sigmoid((n W_d) W_u)      n = RMSNorm(x; w, eps)
      W_d (d, r), W_u (r, d), nothing between the two maps

  (the config gives the flag and the rank alone: the gate's input, its
  sigmoid and which norms carry it are the low-rank "GatedNorm" of the
  Qwen team's 2026 paper on attention and residual sinks, as recalled:
  there is no network here, so what is followed is written down);
- *a learned token selector* (DeepSeek-V3.2-Exp's indexer, on the keys
  ``index_n_heads`` J, ``index_head_dim`` hdI, ``index_topk``):

      c_q   = RMSNorm(a W_qa)                         (the compressed query)
      q^I_j = R((c_q W_qI)_j)   j < J        k^I = R(LayerNorm(a W_kI))
      w     = (a W_w) J^-1/2 hdI^-1/2                 (float32)
      I(t, s) = sum_j w_j(t) ReLU(q^I_j(t) . k^I(s))  s <= t
      S_t   = the ``index_topk`` positions s <= t of largest I(t, s), the
              lower position among equals (every position while t < topk)

  ``R`` turns the half-split pairs ``(i, i + rope/2)`` of the leading
  ``rope = qk_rope_head_dim`` of the 128 by the token's index at the
  layer's own (YaRN) frequencies and passes the rest;
- attention over ``S_t`` alone, then *a gate a head*
  (``attention_output_gate``):

      o_h = sum_{s in S_t} softmax_s(scale q_h . k_h(s)) v_h(s)
      g = sigmoid(a W_g) (one value a head)      y = concat_h(g_h o_h) W_o

- *group-limited routing* (``n_group`` G, ``topk_group``; DeepSeek-V3's
  ``noaux_tc``): ``s = sigmoid(u W_r)``, ``s' = s + b``; a group of
  ``E / G`` consecutive experts stands by the sum of its two largest
  ``s'``; the ``topk_group`` best groups stay (the lower index among
  equals); the top-k of ``s'`` is taken inside them; weights
  ``routed_scaling_factor s_e / sum s_e`` from the unbiased scores;
- rotary positions under ``rope_parameters`` (YaRN: a pair that turns
  more than ``beta_fast`` times in ``original_max_position_embeddings``
  keeps its frequency, one that turns fewer than ``beta_slow`` times has
  it divided by ``factor``, a linear blend between, the ends rounded
  outward to whole pairs; ``mscale = mscale_all_dim`` leaves cos and sin
  alone and scales the softmax by ``(0.1 ln factor + 1)^2``).

Departures, each also in the configuration's ``assumed``: RoPE of the
attention turns adjacent pairs ``(2i, 2i+1)`` in place (kanana's
reading); a dropped group's experts are out of the top-k altogether
(DeepSeek's own code fills them with ``-inf``; ``transformers`` fills 0);
no Hadamard rotation and no fp8 in the indexer; ``attn_gate_fused`` is a
storage layout and appears nowhere.  A chip that holds a share of the
experts (``held_experts``; the router keeps
``published.n_routed_experts`` outputs) adds its own experts' terms and
the shared expert and leaves the others' out; a sliced vocabulary is a
smaller vocabulary.

No kernel, cache or batching, and nothing of the program is imported.
Leaves are named ``"<op>/<key>"`` after the recipe in ``leaf_spec`` and
drawn by ``benchmark/weights.py``, any leaf (or any expert of a leaf)
alone; the expert layers are one scanned body, each drawing its leaves
from its own keys and offsets, an expert's inside the loop over experts.
Attention runs a block of query rows at a time and a head at a time, the
selection as a mask scattered from ``lax.top_k`` of ``I`` (the selector's
heads a few at a time), and a feed-forward a block of rows at a time.
Matrix products run at ``highest`` precision; ``quant`` (the control)
rounds both operands of every product the configuration computes in
bfloat16 (the indexer's q . k and the gated norm's two maps among them)
to fp8 e4m3 first, scaled by the tensor's largest magnitude: the nearest
precision below the one the configuration states.  The router, ``w``,
the norms, the sigmoids and the softmax stay in float32 there too.

What ``served_gaps`` hands the runner as the gap it judges is the MEAN
over the served positions of how far the served token's logit lies below
the reference's best, not the widest, for the reason the DeepSeek-V3
reference gives: a top-k is a discontinuous function, here three times
over (4 of 8 groups, 8 of their 128 experts, 2048 of up to 32k
positions).  The widest gap, the quantiles, the first served token's
gap, the share of (token, layer) expert selections that flip under
bfloat16 activations and the share of selected positions that a bfloat16
evaluation of ``I`` replaces are printed beside it.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

_SQ3 = math.sqrt(3.0)
_Q_ROWS = 256   # query rows a block of the reference's attention
_I_HEADS = 8    # selector heads whose products are held at once


def router_width(cfg: Dict[str, Any]) -> int:
    """Outputs of the router: the published number of experts where the
    configuration holds a share of them."""
    if cfg.get("held_experts") is None:
        return cfg["n_routed_experts"]
    return cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"])


def held_experts(cfg: Dict[str, Any]) -> List[int]:
    held = cfg.get("held_experts")
    return list(range(cfg["n_routed_experts"])) if held is None else list(held)


def leaf_spec(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    """``"op/key" -> (shape, half_width, offset)`` of every leaf, in the
    layout the program holds it in."""
    asm = cfg["assumed"]
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    r, rope, qr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"], cfg["q_lora_rank"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    j, hi, gr = cfg["index_n_heads"], cfg["index_head_dim"], cfg["gated_norm_rank"]
    e, eh, f = router_width(cfg), len(held_experts(cfg)), cfg["moe_intermediate_size"]
    fs, fd = cfg["n_shared_experts"] * f, cfg["intermediate_size"]
    w, ns = asm["init_std"] * _SQ3, asm["norm_scale_half_width"]
    up = asm["gated_norm_up_std"] * _SQ3
    gains = asm.get("q_norm_gain", {})      # layer (as a string) -> the scale's centre

    def gated_norm(name):
        return {f"{name}/scale": ((d,), ns, 1.0), f"{name}/w_down": ((d, gr), w, 0.0),
                f"{name}/w_up": ((gr, d), up, 0.0)}

    spec = {"embed/table": ((v, d), w, 0.0), "lm_head/kernel": ((v, d), w, 0.0),
            **gated_norm("ln_f")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk{i}_"
        spec.update(gated_norm(f"{p}ln1"))
        spec.update(gated_norm(f"{p}ln2"))
        spec[f"{p}attn/wq_a"] = ((d, qr), w, 0.0)
        spec[f"{p}attn/q_norm"] = ((qr,), ns, float(gains.get(str(i), 1.0)))
        spec[f"{p}attn/wq_b"] = ((qr, h * (nope + rope)), w, 0.0)
        spec[f"{p}attn/wkv_a"] = ((d, r + rope), w, 0.0)
        spec[f"{p}attn/kv_norm"] = ((r,), ns, 1.0)
        spec[f"{p}attn/wkv_b"] = ((r, h * (nope + vd)), w, 0.0)
        spec[f"{p}attn/wo"] = ((h * vd, d), w, 0.0)
        spec[f"{p}attn/wg"] = ((d, h), w, 0.0)
        spec[f"{p}attn/idx_wq"] = ((qr, j * hi), w, 0.0)
        spec[f"{p}attn/idx_wk"] = ((d, hi), w, 0.0)
        spec[f"{p}attn/idx_ww"] = ((d, j), w, 0.0)
        spec[f"{p}attn/idx_k_scale"] = ((hi,), ns, 1.0)
        spec[f"{p}attn/idx_k_bias"] = ((hi,), ns, 0.0)
        if i < cfg["first_k_dense_replace"]:
            spec[f"{p}mlp_gate/kernel"] = ((fd, d), w, 0.0)
            spec[f"{p}mlp_up/kernel"] = ((fd, d), w, 0.0)
            spec[f"{p}mlp_down/kernel"] = ((d, fd), w, 0.0)
        else:
            spec[f"{p}moe/gate"] = ((d, e), w, 0.0)
            spec[f"{p}moe/e_bias"] = ((e,), asm["e_bias_half_width"], 0.0)
            spec[f"{p}moe/w_gate"] = ((eh, d, f), w, 0.0)
            spec[f"{p}moe/w_up"] = ((eh, d, f), w, 0.0)
            spec[f"{p}moe/w_down"] = ((eh, f, d), w, 0.0)
            spec[f"{p}moe/s_gate"] = ((d, fs), w, 0.0)
            spec[f"{p}moe/s_up"] = ((d, fs), w, 0.0)
            spec[f"{p}moe/s_down"] = ((fs, d), w, 0.0)
    return spec


def parameter_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    """``{"total", "active"}`` from ``leaf_spec``: every leaf, and what
    one token passes: ``num_experts_per_tok`` of each expert layer's
    routed experts, everything else once, the head among it and the
    token table NOT (a row of it is read, none multiplied)."""
    total = active = 0
    eh = len(held_experts(cfg))
    for name, (shape, _, _) in leaf_spec(cfg).items():
        n = int(np.prod(shape))
        total += n
        if name == "embed/table":
            continue
        if name.endswith(("moe/w_gate", "moe/w_up", "moe/w_down")):
            active += n // eh * cfg["num_experts_per_tok"]
        else:
            active += n
    return {"total": total, "active": active}


def stored_dtype(cfg: Dict[str, Any], name: str) -> str:
    if name.endswith(("moe/gate", "moe/e_bias", "attn/idx_ww")):
        return cfg["assumed"]["router_dtype"]
    return cfg["assumed"]["param_dtype"]


class Leaves:
    """Seeded leaves under the name prefix ``at`` (``"blk3_"``; empty
    for the whole model's names), each made when asked for and rounded
    once to the dtype the configuration stores it in, held in f32.
    ``seed`` is a whole number or the (possibly traced) ``(low, high)``
    words of ``weights.split_seed``.  ``keys`` (local name -> (the leaf's
    32-bit key, its offset), possibly traced) stands in for the names
    where one traced body serves several layers: shapes and half widths
    are then ``at``'s."""

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


def gated_norm(cfg, get: Leaves, name: str, x, quant: bool = False):
    """``GN(x)`` of the norm ``name`` (``"ln1"``, ``"ln2"``, ``"ln_f"``)."""
    n = _rms(x, get(f"{name}/scale"), cfg["rms_norm_eps"])
    low = _mm(n, get(f"{name}/w_down"), quant)
    return n * jax.nn.sigmoid(_mm(low, get(f"{name}/w_up"), quant))


def _by_rows(fn, u, rows: int = 4 * _Q_ROWS):
    """``fn`` (rows of ``u`` -> rows) a block of rows at a time."""
    t = u.shape[0]
    if t <= rows or t % rows:
        return fn(u)
    return jax.lax.map(fn, u.reshape(t // rows, rows, -1)).reshape(t, -1)


# -- positions -------------------------------------------------------------------


def yarn(cfg):
    """``(inv_freq (rope/2,) float32, softmax scale)`` of the
    configuration's ``rope_parameters``.  The scale of cos and sin,
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, is
    asserted 1 (the configuration's)."""
    d, rp = cfg["qk_rope_head_dim"], cfg["rope_parameters"]
    assert rp["rope_type"] == "yarn", rp
    theta, factor = float(rp["rope_theta"]), float(rp["factor"])
    span = rp["original_max_position_embeddings"]

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    def pair_turning(turns):
        return d * math.log(span / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(rp["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(rp["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float32) - low) / max(high - low, 0.001), 0, 1)
    f = theta ** (-np.arange(0, d, 2, dtype=np.float32) / np.float32(d))
    inv = f / np.float32(factor) * ramp + f * (1 - ramp)
    assert mscale(rp["mscale"]) / mscale(rp["mscale_all_dim"]) == 1.0, rp
    scale = (cfg["qk_nope_head_dim"] + d) ** -0.5 * mscale(rp["mscale_all_dim"]) ** 2
    return jnp.asarray(inv, jnp.float32), scale


def _rope(x, pos, inv):
    """Adjacent pairs ``(2i, 2i+1)`` of the last dim turned by
    ``pos * inv[i]``; ``x`` (t, ..., d), ``pos`` (t,)."""
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _rope_leading(x, pos, inv):
    """``R``: the half-split pairs ``(i, i + r/2)`` of the leading ``r =
    2 len(inv)`` dims of ``x`` (t, ..., d) turned by ``pos * inv[i]``,
    the rest passed through."""
    r = 2 * inv.shape[0]
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


# -- the selector ------------------------------------------------------------------


def indexer(cfg, get: Leaves, a, cq, index, quant: bool = False, low: bool = False):
    """``(q^I (t, J, hdI), k^I (t, hdI), w (t, J))`` of the normed tokens
    ``a`` (t, d) and their compressed queries ``cq`` (t, q_lora_rank) at
    indices ``index`` (t,).  ``low`` evaluates the q and k sides as the
    program does: ``a``, ``cq`` and the rotated q and k rounded to
    bfloat16 (``w`` stays what the configuration says: float32)."""
    j, hi = cfg["index_n_heads"], cfg["index_head_dim"]
    inv = yarn(cfg)[0]
    assert hi >= 2 * inv.shape[0], (hi, inv.shape)
    t = a.shape[0]
    r = (lambda z: weights.round_to(z, "bfloat16", jnp)) if low else (lambda z: z)
    q = r(_rope_leading(r(_mm(r(cq), get("attn/idx_wq"), quant)).reshape(t, j, hi), index, inv))
    k = r(_mm(r(a), get("attn/idx_wk"), quant))
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
    k = (k - mean) * jax.lax.rsqrt(var + cfg["rms_norm_eps"]) \
        * get("attn/idx_k_scale") + get("attn/idx_k_bias")
    k = r(_rope_leading(r(k), index, inv))
    w = jnp.matmul(a, get("attn/idx_ww"), precision="highest") / math.sqrt(j * hi)
    return q, k, w


def index_scores(q, k, w, quant: bool = False):
    """``I`` (rows, t): no mask.  The heads ``_I_HEADS`` at a time (all 64
    against 32k keys are 2 GB a block of rows)."""
    if quant:
        q, k = _fp8(q), _fp8(k)
    rows, j, _ = q.shape
    n = j // _I_HEADS if j % _I_HEADS == 0 else 1

    def some(acc, qw):
        qs, ws = qw                                     # (rows, j/n, hdI), (rows, j/n)
        dots = jnp.einsum("qjd,td->qjt", qs, k, precision="highest")
        return acc + jnp.einsum("qjt,qj->qt", jax.nn.relu(dots), ws, precision="highest"), None

    split = lambda z: z.reshape((rows, n, j // n) + z.shape[2:]).swapaxes(0, 1)
    return jax.lax.scan(some, jnp.zeros((rows, k.shape[0]), jnp.float32),
                        (split(q), split(w)))[0]


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


# -- latent attention over the selected set ----------------------------------------


def attention(cfg, get: Leaves, a, quant: bool = False, select: bool = True):
    """Causal latent attention, expanded, over one sequence ``a`` (t, d)
    with the learned selection and the gate a head; ``(y (t, d), the
    share of selected positions that a bfloat16 evaluation of I
    replaces)``.  ``select`` false attends the whole causal past (the
    tests' tie to plain latent attention, and the control's other side)."""
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, topk, t = cfg["rms_norm_eps"], cfg["index_topk"], a.shape[0]
    pos = jnp.arange(t)
    inv, scale = yarn(cfg)
    cq = _rms(_mm(a, get("attn/wq_a"), quant), get("attn/q_norm"), eps)
    q = _mm(cq, get("attn/wq_b"), quant).reshape(t, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv)], axis=-1)
    ckr = _mm(a, get("attn/wkv_a"), quant)
    c = _rms(ckr[:, :r], get("attn/kv_norm"), eps)
    k_r = _rope(ckr[:, r:], pos, inv)                                 # (t, rope)
    kv = _mm(c, get("attn/wkv_b"), quant).reshape(t, h, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (t, h, rope))], axis=-1)
    v = kv[..., nope:]
    qi, ki, wi = indexer(cfg, get, a, cq, pos, quant)
    qil, kil, _ = indexer(cfg, get, a, cq, pos, quant, low=True)
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
            qh, kh, vh = args                                        # (rows, .), (t, .)
            s = jnp.einsum("qd,td->qt", qh, kh, precision="highest") * scale
            pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return jnp.einsum("qt,td->qd", pr, vh, precision="highest")

        o = jax.lax.map(head, (qb.transpose(1, 0, 2), k.transpose(1, 0, 2),
                               v.transpose(1, 0, 2)))                # (h, rows, vd)
        return o.transpose(1, 0, 2), replaced

    n = t // rows
    o, (gone, kept) = jax.lax.map(block, (
        q.reshape(n, rows, h, nope + rope), qi.reshape((n, rows) + qi.shape[1:]),
        qil.reshape((n, rows) + qil.shape[1:]), wi.reshape(n, rows, -1),
        jnp.arange(0, t, rows)))
    share = jnp.sum(gone) / jnp.maximum(jnp.sum(kept), 1)
    gate = jax.nn.sigmoid(_mm(a, get("attn/wg"), quant))             # (t, h)
    o = o.reshape(t, h, vd) * gate[:, :, None]
    return _mm(o.reshape(t, h * vd), get("attn/wo"), quant), share.astype(jnp.float32)


# -- the expert layer ------------------------------------------------------------


def _gated(u, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(u, w_gate, quant)) * _mm(u, w_up, quant), w_down, quant)


def route(cfg, get: Leaves, u, groups: bool = True):
    """``(idx (t, k), w (t, k))`` in f32, the product at full precision,
    over the router's whole width.  ``groups`` false takes the top-k over
    every expert (what a router that ignores the groups does: the tests')."""
    s = jax.nn.sigmoid(jnp.matmul(u, get("moe/gate"), precision="highest"))
    choice = s + get("moe/e_bias")
    g, keep = cfg.get("n_group", 1), cfg.get("topk_group", 1)
    if groups and g > 1:
        by_group = choice.reshape(choice.shape[0], g, -1)
        standing = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)   # (t, g)
        _, best = jax.lax.top_k(standing, keep)
        kept = jnp.zeros(standing.shape, bool).at[
            jnp.arange(standing.shape[0])[:, None], best].set(True)
        choice = jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(choice.shape)
    _, idx = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def experts(cfg, get: Leaves, u, quant: bool = False, shared: bool = True,
            groups: bool = True):
    """The expert layer's output for ``u`` (t, d) on this chip: a loop
    over the experts it holds, each run on every token and weighed by
    what the router gave it there (zero where it was not chosen), plus
    the shared expert.  The experts held elsewhere are left out."""
    idx, w = route(cfg, get, u, groups)
    held = jnp.asarray(held_experts(cfg))

    def one(j, acc):
        gate = jnp.sum(jnp.where(idx == held[j], w, 0.0), axis=-1)   # (t,)
        y = _gated(u, get.expert("moe/w_gate", j), get.expert("moe/w_up", j),
                   get.expert("moe/w_down", j), quant)
        return acc + gate[:, None] * y

    out = jax.lax.fori_loop(0, held.shape[0], one, jnp.zeros_like(u))
    if shared:
        out = out + _gated(u, get("moe/s_gate"), get("moe/s_up"), get("moe/s_down"), quant)
    return out


def selection_flips(cfg, get: Leaves, u):
    """Of the tokens of ``u`` (an expert layer's input), the share whose
    chosen experts change when the router reads them rounded to
    bfloat16, as the program's activations are."""
    a = jnp.sort(route(cfg, get, u)[0], axis=-1)
    b = jnp.sort(route(cfg, get, weights.round_to(u, "bfloat16", jnp))[0], axis=-1)
    return jnp.mean(jnp.any(a != b, axis=-1).astype(jnp.float32))


# -- the walk ----------------------------------------------------------------------


def layer(cfg, get: Leaves, x, dense: bool, quant: bool = False, select: bool = True):
    """One block; ``(x, (share of flipped expert selections, share of
    replaced positions))``."""
    y, replaced = attention(cfg, get, gated_norm(cfg, get, "ln1", x, quant), quant, select)
    x = x + y
    u = gated_norm(cfg, get, "ln2", x, quant)
    if dense:
        mlp = lambda rows: _gated(rows, get("mlp_gate/kernel").T, get("mlp_up/kernel").T,
                                  get("mlp_down/kernel").T, quant)
        return x + _by_rows(mlp, u), (jnp.float32(0.0), replaced)
    return x + experts(cfg, get, u, quant), (selection_flips(cfg, get, u), replaced)


def hidden(cfg: Dict[str, Any], seed, tokens, quant: bool = False, select: bool = True):
    """``tokens (t,) -> (hidden (t, d) before the last norm, (the share
    of flipped selections of each expert layer, the share of replaced
    positions of every layer))``.  The leading dense layers one by one,
    then one scanned body over the expert layers, which are alike: each
    draws its leaves from its own keys and offsets."""
    get = Leaves(cfg, seed)
    k, n = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    x = get("embed/table")[tokens]
    gone = []
    for i in range(k):
        x, (_, replaced) = layer(cfg, get.at(f"blk{i}_"), x, True, quant, select)
        gone.append(replaced)
    if n == k:
        return x, (jnp.zeros((0,), jnp.float32), jnp.stack(gone))
    first = f"blk{k}_"
    local = [name[len(first):] for name in get.spec if name.startswith(first)]
    keys = {name: (jnp.stack([weights.leaf_key(seed, f"blk{i}_{name}", jnp)
                              for i in range(k, n)]),
                   jnp.asarray([get.spec[f"blk{i}_{name}"][2] for i in range(k, n)],
                               jnp.float32)) for name in local}

    def body(x, layer_keys):
        return layer(cfg, get.at(first, layer_keys), x, False, quant, select)

    x, (flips, replaced) = jax.lax.scan(body, x, keys)
    return x, (flips, jnp.concatenate([jnp.stack(gone), replaced]) if gone else replaced)


class Walk:
    """The two jitted programs of one walk of ``cfg``: the layers, and
    the last norm with the head.  The seed is an argument of both."""

    def __init__(self, cfg: Dict[str, Any], quant: bool = False, select: bool = True):
        self.hidden = jax.jit(lambda seed, tokens: hidden(cfg, seed, tokens, quant, select))

        def head(seed, x):
            g = Leaves(cfg, seed)
            return _mm(gated_norm(cfg, g, "ln_f", x, quant), g("lm_head/kernel").T, quant)

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
