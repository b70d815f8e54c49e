"""The Xing4.0 block family in plain float32 ``jax.numpy``: forward.

The DeepSeek-V3 block (RMSNorm pre-norm, latent attention with K and V
expanded a head, ``first_k_dense_replace`` gated-SiLU dense layers, then
expert layers under a sigmoid router, top-k of ``score +
e_score_correction_bias``, the chosen scores normalised and scaled, one
shared expert on every token, no bias anywhere, an untied head) with
three changes:

- the residual path is ``n = hc_mult`` streams of width ``C`` a token,
  ``X`` (n, C), and each sublayer ``F`` (attention, feed-forward) sits in
  a manifold-constrained hyper-connection (DeepSeek's "mHC:
  Manifold-Constrained Hyper-Connections", arXiv:2512.24880 as recalled:
  there is no network here, so what is followed is written down), on the
  keys ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
  ``mhc_h_res_clamp_min/max``:

      x~      = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)        (all n C values)
      H~_pre  = a_pre  (x~ phi_pre)  + b_pre                   (n,)
      H~_post = a_post (x~ phi_post) + b_post                  (n,)
      H~_res  = a_res  mat(x~ phi_res) + b_res                 (n, n), row-major
      H_pre = sigmoid(H~_pre)     H_post = 2 sigmoid(H~_post)
      H_res = Sinkhorn(exp(clip(H~_res, clamp_min, clamp_max)))
      u  = H_pre X                                             (C,)
      X' = H_res X + H_post^T F(RMSNorm(u))                    (n, C)

  ``Sinkhorn``: ``hc_sinkhorn_iters`` rounds, each every row over its
  sum, then every column over its sum;
- the query is compressed: ``q = RMSNorm(u W_qa) W_qb``;
- rotary positions are YaRN's (``rope_scaling``): pair ``i``'s frequency
  is ``theta^(-2i/d)`` where the pair turns more than ``beta_fast`` times
  in ``original_max_position_embeddings`` positions, that over ``factor``
  where it turns fewer than ``beta_slow`` times, and a linear blend
  between; the softmax scale is ``(nope + rope)^(-1/2) (0.1
  mscale_all_dim ln(factor) + 1)^2``.

Departures from the published description, each also in the
configuration's ``assumed``: RoPE turns adjacent pairs ``(2i, 2i+1)`` in
place (``rope_interleave``, absent from the config: ``transformers``
moves the pairs apart first, a fixed permutation of q and k alike); the
table's row is copied into the ``n`` streams and the streams are summed
in front of the last norm; rows before columns in a Sinkhorn round,
``hc_eps`` added to each divisor, the clamp before ``exp``, no learned
scale on the hyper-connection's RMSNorm; the ramp's ends rounded outward
to whole pairs (DeepSeek-V3's ``yarn_find_correction_range``); the
multi-token-prediction module (``num_nextn_predict_layers``) adds
nothing to a served logit and is not walked.

No kernel, cache or batching, and nothing of the program is imported.
Leaves are named ``"<op>/<key>"`` after the recipe in ``leaf_spec`` and
drawn by ``benchmark/weights.py``, any leaf (or any expert of a leaf)
alone: a layer's weights are made when the walk reaches the layer (the
expert layers are alike, so one scanned body walks them, its leaves drawn
from the layer's own keys), an expert's inside the loop over experts, and
attention runs a block of query rows at a time.  Matrix products run at
``highest`` precision; ``quant`` (the control) rounds both operands of
every product the configuration computes in bfloat16 to fp8 e4m3 first,
scaled by the tensor's largest magnitude: the nearest precision below
the one the configuration states.  The router, the norms, the softmax
and the whole coefficient chain of the hyper-connection (its norm, its
three projections, the Sinkhorn rounds, both mixes) stay in float32
there too, as the configuration states them.

What ``served_gaps`` hands the runner as the gap it judges is the MEAN
over the served positions of how far the served token's logit lies below
the reference's best, not the widest, for the reason the DeepSeek-V3
reference gives: top-4 of 64 near-uniform sigmoid scores is a
discontinuous function, and where a token's 4th and 5th expert lie
within the program's bfloat16 round-off of each other the program and
this float32 walk part by a whole expert's output.  The widest gap, the
quantiles, the first served token's gap (the program's expanded prefill;
the others are its absorbed decode), the share of (token, layer)
selections that flip under bfloat16 activations and the largest defect
``|row or column sum - 1|`` the Sinkhorn rounds left on any ``H_res`` of
the walk are printed beside it.
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
    layout the program holds it in.  ``offset`` is one number, or for the
    hyper-connection's ``b_res`` one a matrix entry."""
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    r, rope, qr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"], cfg["q_lora_rank"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs, fd = cfg["n_shared_experts"] * f, cfg["intermediate_size"]
    n = cfg["hc_mult"]
    asm = cfg["assumed"]
    w, ns = asm["init_std"] * _SQ3, asm["norm_scale_half_width"]
    phi = _SQ3 / math.sqrt(n * d)           # x~ phi of unit spread
    a_lo, a_hi = asm["hc_alpha"]
    alpha = ((a_hi - a_lo) / 2, (a_hi + a_lo) / 2)
    bw = asm["hc_bias_half_width"]
    b_res = tuple(tuple(asm["hc_res_diagonal"] if i == j else 0.0 for j in range(n))
                  for i in range(n))
    spec = {
        "embed/table": ((v, d), w, 0.0),
        "ln_f/scale": ((d,), ns, 1.0),
        "lm_head/kernel": ((v, d), w, 0.0),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk{i}_"
        for k in (1, 2):
            spec[f"{p}hc{k}_pre/phi"] = ((n * d, n), phi, 0.0)
            spec[f"{p}hc{k}_pre/alpha"] = ((1,), *alpha)
            spec[f"{p}hc{k}_pre/bias"] = ((n,), bw, -math.log(n - 1.0))   # H_pre = 1/n
            spec[f"{p}hc{k}_post/phi_post"] = ((n * d, n), phi, 0.0)
            spec[f"{p}hc{k}_post/phi_res"] = ((n * d, n * n), phi, 0.0)
            spec[f"{p}hc{k}_post/alpha"] = ((2,), *alpha)
            spec[f"{p}hc{k}_post/b_post"] = ((n,), bw, 0.0)               # H_post = 1
            spec[f"{p}hc{k}_post/b_res"] = ((n, n), bw, b_res)
        spec[f"{p}ln1/scale"] = ((d,), ns, 1.0)
        spec[f"{p}ln2/scale"] = ((d,), ns, 1.0)
        spec[f"{p}attn/wq_a"] = ((d, qr), w, 0.0)
        spec[f"{p}attn/q_norm"] = ((qr,), ns, 1.0)
        spec[f"{p}attn/wq_b"] = ((qr, h * (nope + rope)), w, 0.0)
        spec[f"{p}attn/wkv_a"] = ((d, r + rope), w, 0.0)
        spec[f"{p}attn/kv_norm"] = ((r,), ns, 1.0)
        spec[f"{p}attn/wkv_b"] = ((r, h * (nope + vd)), w, 0.0)
        spec[f"{p}attn/wo"] = ((h * vd, d), w, 0.0)
        if i < cfg["first_k_dense_replace"]:
            spec[f"{p}mlp_gate/kernel"] = ((fd, d), w, 0.0)
            spec[f"{p}mlp_up/kernel"] = ((fd, d), w, 0.0)
            spec[f"{p}mlp_down/kernel"] = ((d, fd), w, 0.0)
        else:
            spec[f"{p}moe/gate"] = ((d, e), w, 0.0)
            spec[f"{p}moe/e_bias"] = ((e,), asm["e_bias_half_width"], 0.0)
            spec[f"{p}moe/w_gate"] = ((e, d, f), w, 0.0)
            spec[f"{p}moe/w_up"] = ((e, d, f), w, 0.0)
            spec[f"{p}moe/w_down"] = ((e, f, d), w, 0.0)
            spec[f"{p}moe/s_gate"] = ((d, fs), w, 0.0)
            spec[f"{p}moe/s_up"] = ((d, fs), w, 0.0)
            spec[f"{p}moe/s_down"] = ((fs, d), w, 0.0)
    return spec


def parameter_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    """``{"total", "active"}``: every leaf, and what one token's forward
    reads (of a layer's routed experts, the ``num_experts_per_tok``)."""
    total = active = 0
    share = cfg["num_experts_per_tok"] / cfg["n_routed_experts"]
    for name, (shape, _, _) in leaf_spec(cfg).items():
        n = int(np.prod(shape))
        total += n
        routed = name.endswith(("moe/w_gate", "moe/w_up", "moe/w_down"))
        active += int(round(n * share)) if routed else n
    return {"total": total, "active": active}


def stored_dtype(cfg: Dict[str, Any], name: str) -> str:
    if name.endswith(("moe/gate", "moe/e_bias")):
        return cfg["assumed"]["router_dtype"]
    if "_hc" in name:
        return cfg["assumed"]["hc_dtype"]
    return cfg["assumed"]["param_dtype"]


class Leaves:
    """Seeded leaves under the name prefix ``at`` (``"blk3_"``; empty
    for the whole model's names), each made when asked for and rounded
    once to the dtype the configuration stores it in, held in f32.
    ``seed`` is a whole number or the (possibly traced) ``(low, high)``
    words of ``weights.split_seed``.  ``keys`` (local name -> the leaf's
    32-bit key, possibly traced) stands in for the names where one
    traced body serves several layers: shapes are then ``at``'s."""

    def __init__(self, cfg: Dict[str, Any], seed, at: str = "", keys=None, spec=None):
        self.cfg, self.seed, self.prefix, self.keys = cfg, seed, at, keys
        self.spec = spec or leaf_spec(cfg)

    def at(self, prefix: str, keys=None) -> "Leaves":
        """The same leaves seen from under another prefix."""
        return Leaves(self.cfg, self.seed, prefix, keys, self.spec)

    def _values(self, name: str, rows, cols_n: int):
        full = self.prefix + name
        _, hw, off = self.spec[full]
        key = self.keys[name] if self.keys is not None else \
            weights.leaf_key(self.seed, full, jnp)
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
        """Expert ``e`` (may be traced) of the stacked leaf ``name``."""
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


def _gated(u, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(u, w_gate, quant)) * _mm(u, w_up, quant), w_down, quant)


# -- the hyper-connection ------------------------------------------------------


def sinkhorn_rounds(m, iters: int, eps: float) -> List[Any]:
    """``m`` (t, n, n), positive, after each of ``iters`` rounds: every
    row over its sum, then every column over its sum."""
    out = []
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        out.append(m)
    return out


def defect(m):
    """Largest ``|row sum - 1|`` or ``|column sum - 1|`` over ``m`` (t, n, n)."""
    return jnp.maximum(jnp.max(jnp.abs(jnp.sum(m, axis=-1) - 1.0)),
                       jnp.max(jnp.abs(jnp.sum(m, axis=-2) - 1.0)))


def hc_coefficients(cfg, get: Leaves, at: str, x):
    """``(H_pre (t, n), H_post (t, n), H_res (t, n, n))`` of the
    hyper-connection ``at`` (``"hc1"``, ``"hc2"``) for the streams ``x``
    (t, n, C), all float32."""
    t, n, c = x.shape
    flat = x.reshape(t, n * c)
    xt = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
                              + cfg["hc_eps"])

    def proj(name):
        return jnp.matmul(xt, get(name), precision="highest")

    a_post, a_res = get(f"{at}_post/alpha")
    h_pre = jax.nn.sigmoid(get(f"{at}_pre/alpha")[0] * proj(f"{at}_pre/phi")
                           + get(f"{at}_pre/bias"))
    h_post = 2.0 * jax.nn.sigmoid(a_post * proj(f"{at}_post/phi_post")
                                  + get(f"{at}_post/b_post"))
    logits = a_res * proj(f"{at}_post/phi_res").reshape(t, n, n) + get(f"{at}_post/b_res")
    m = jnp.exp(jnp.clip(logits, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
    return h_pre, h_post, sinkhorn_rounds(m, cfg["hc_sinkhorn_iters"], cfg["hc_eps"])[-1]


def hyper_connection(cfg, get: Leaves, at: str, x, sublayer):
    """``(X', defect of H_res)``: ``sublayer`` (``u (t, C) -> (t, C)``:
    the norm and ``F``) round the streams ``x`` (t, n, C)."""
    h_pre, h_post, h_res = hc_coefficients(cfg, get, at, x)
    u = jnp.einsum("tn,tnc->tc", h_pre, x, precision="highest")
    y = sublayer(u)
    new = jnp.einsum("tij,tjc->tic", h_res, x, precision="highest") \
        + h_post[:, :, None] * y[:, None, :]
    return new, defect(h_res)


# -- latent attention, compressed query, YaRN ------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(cfg):
    """``(inv_freq (rope/2,) float32, softmax scale)`` of the
    configuration's ``rope_scaling`` (``transformers``'
    ``_compute_yarn_parameters`` with DeepSeek-V3's softmax scale).  The
    scale of cos and sin, ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``, is asserted 1 (the configuration's)."""
    d, theta, rs = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]
    assert rs["type"] == "yarn", rs
    factor, span = rs["factor"], rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return d * math.log(span / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    pos_freqs = theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)
    wave = yarn_mscale(factor, rs["mscale"]) / yarn_mscale(factor, rs["mscale_all_dim"])
    assert wave == 1.0, wave
    scale = (cfg["qk_nope_head_dim"] + d) ** -0.5 * yarn_mscale(factor, rs["mscale_all_dim"]) ** 2
    return jnp.asarray(inv, jnp.float32), scale


def _rope(x, pos, inv):
    """Adjacent pairs ``(2i, 2i+1)`` of the last dim turned by
    ``pos * inv[i]``; ``x`` (t, ..., d), ``pos`` (t,)."""
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def attention(cfg, get: Leaves, u, quant: bool = False):
    """Causal latent attention, expanded, over one sequence ``u`` (t, d);
    ``get`` the layer's leaves."""
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    t = u.shape[0]
    pos = jnp.arange(t)
    inv, scale = yarn(cfg)
    q = _mm(_rms(_mm(u, get("attn/wq_a"), quant), get("attn/q_norm"), eps),
            get("attn/wq_b"), quant).reshape(t, h, nope + rope)
    ckr = _mm(u, get("attn/wkv_a"), quant)
    c = _rms(ckr[:, :r], get("attn/kv_norm"), eps)
    k_r = _rope(ckr[:, r:], pos, inv)                                 # (t, rope)
    kv = _mm(c, get("attn/wkv_b"), quant).reshape(t, h, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (t, h, rope))], axis=-1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv)], axis=-1)
    rows = min(_Q_ROWS, t)
    assert t % rows == 0, (t, rows)

    def block(args):
        qb, start = args                                             # (rows, h, .)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision="highest") * scale
        mask = jnp.arange(t)[None, :] <= (start + jnp.arange(rows))[:, None]
        pr = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v, precision="highest")

    o = jax.lax.map(block, (q.reshape(t // rows, rows, h, nope + rope),
                            jnp.arange(0, t, rows)))
    return _mm(o.reshape(t, h * vd), get("attn/wo"), quant)


# -- the expert layer ------------------------------------------------------------


def route(cfg, get: Leaves, u):
    """``(idx (t, k), w (t, k))`` in f32, the product at full precision."""
    s = jax.nn.sigmoid(jnp.matmul(u, get("moe/gate"), precision="highest"))
    _, idx = jax.lax.top_k(s + get("moe/e_bias"), cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def experts(cfg, get: Leaves, u, quant: bool = False):
    """The expert layer's output for ``u`` (t, d): a loop over the
    experts, each run on every token and weighed by what the router gave
    it there (zero where it was not chosen), plus the shared expert."""
    idx, w = route(cfg, get, u)

    def one(e, acc):
        gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)         # (t,)
        y = _gated(u, get.expert("moe/w_gate", e), get.expert("moe/w_up", e),
                   get.expert("moe/w_down", e), quant)
        return acc + gate[:, None] * y

    out = jax.lax.fori_loop(0, cfg["n_routed_experts"], one, jnp.zeros_like(u))
    return out + _gated(u, get("moe/s_gate"), get("moe/s_up"), get("moe/s_down"), quant)


def selection_flips(cfg, get: Leaves, u):
    """Of the tokens of ``u`` (an expert layer's input), the share whose
    chosen experts change when the router reads them rounded to
    bfloat16, as the program's activations are."""
    a = jnp.sort(route(cfg, get, u)[0], axis=-1)
    b = jnp.sort(route(cfg, get, weights.round_to(u, "bfloat16", jnp))[0], axis=-1)
    return jnp.mean(jnp.any(a != b, axis=-1).astype(jnp.float32))


# -- the walk ----------------------------------------------------------------------


def layer(cfg, get: Leaves, x, dense: bool, quant: bool = False):
    """One block over the streams ``x`` (t, n, C): ``(x, share of flipped
    selections, largest defect of its two H_res)``."""
    eps = cfg["rms_norm_eps"]
    flips = []

    def attend(u):
        return attention(cfg, get, _rms(u, get("ln1/scale"), eps), quant)

    def feed(u):
        u = _rms(u, get("ln2/scale"), eps)
        if dense:
            flips.append(jnp.float32(0.0))
            return _gated(u, get("mlp_gate/kernel").T, get("mlp_up/kernel").T,
                          get("mlp_down/kernel").T, quant)
        flips.append(selection_flips(cfg, get, u))
        return experts(cfg, get, u, quant)

    x, d1 = hyper_connection(cfg, get, "hc1", x, attend)
    x, d2 = hyper_connection(cfg, get, "hc2", x, feed)
    return x, flips[0], jnp.maximum(d1, d2)


def hidden(cfg: Dict[str, Any], seed, tokens, quant: bool = False):
    """``tokens (t,) -> (hidden (t, d) before the last norm: the streams'
    sum; the share of flipped selections of each expert layer; the
    largest defect of any H_res)``.  The leading dense layers one by one,
    then one scanned body over the expert layers, which are alike: each
    draws its leaves from its own keys."""
    get = Leaves(cfg, seed)
    k, n = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    row = get("embed/table")[tokens]
    x = jnp.broadcast_to(row[:, None, :], (row.shape[0], cfg["hc_mult"], row.shape[1]))
    worst = jnp.float32(0.0)
    for i in range(k):
        x, _, d = layer(cfg, get.at(f"blk{i}_"), x, True, quant)
        worst = jnp.maximum(worst, d)
    if n == k:
        return jnp.sum(x, axis=1), jnp.zeros((0,), jnp.float32), worst
    first = f"blk{k}_"
    local = [name[len(first):] for name in get.spec if name.startswith(first)]
    keys = {name: jnp.stack([weights.leaf_key(seed, f"blk{i}_{name}", jnp)
                             for i in range(k, n)]) for name in local}

    def body(x, layer_keys):
        x, flip, d = layer(cfg, get.at(first, layer_keys), x, False, quant)
        return x, (flip, d)

    x, (flips, ds) = jax.lax.scan(body, x, keys)
    return jnp.sum(x, axis=1), flips, jnp.maximum(worst, jnp.max(ds))


class Walk:
    """The two jitted programs of one walk of ``cfg``: the layers, and
    the last norm with the head.  The seed is an argument of both."""

    def __init__(self, cfg: Dict[str, Any], quant: bool = False):
        self.hidden = jax.jit(lambda seed, tokens: hidden(cfg, seed, tokens, quant))

        def head(seed, x):
            g = Leaves(cfg, seed)
            return _mm(_rms(x, g("ln_f/scale"), cfg["rms_norm_eps"]),
                       g("lm_head/kernel").T, quant)

        self.head = jax.jit(head)


def logits_fn(cfg: Dict[str, Any], seed: int, tokens, quant: bool = False):
    """``tokens (t,) -> logits (t, vocab)``, float32: the whole forward
    at once (small sizes: the tests)."""
    walk, words = Walk(cfg, quant), weights.split_seed(seed)
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
    worst = 0.0
    pad = int(cfg["assumed"].get("reference_pad", _Q_ROWS))
    width = -(-max(len(s["tokens"]) for s in samples) // 8) * 8
    for s in samples:
        prompt = np.asarray(s["prompt"], np.int32)
        served = np.asarray(s["tokens"], np.int32)
        full = np.concatenate([prompt, served])[:-1]
        t, lo = full.shape[0], len(prompt) - 1
        # Padded (causal attention never looks ahead) so that a few
        # programs serve every sample, and far enough that the rows
        # read are a slice of one size.
        size = -(-(lo + width) // pad) * pad
        padded = jnp.asarray(np.pad(full, (0, size - t)))

        def served_logits(walk):
            x, shares, d = walk.hidden(words, padded)
            rows = jax.lax.dynamic_slice_in_dim(x, lo, width, axis=0)
            return walk.head(words, rows)[:t - lo], shares, d

        lg, shares, d = served_logits(sound)
        flips.extend(float(f) for f in shares)
        worst = max(worst, float(d))
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
           "hc_defect": worst}
    print(f"[reference] {'control' if quant else 'served'} gaps over {len(gaps)} positions of "
          f"{len(samples)} requests: mean {out['mean_gap']:.6g} p50 {q50:.6g} p90 {q90:.6g} "
          f"p99 {q99:.6g} max {out['max_gap']:.6g}; over 0.1: "
          f"{float(np.mean(np.asarray(gaps) > 0.1)):.4f}; first tokens (prefill) max "
          f"{out['first_token_max_gap']:.6g}; (token, layer) selections that flip under "
          f"bfloat16 activations: {out['selection_flip_share']:.4f}; largest defect of an "
          f"H_res after its {cfg['hc_sinkhorn_iters']} rounds (padding's tokens too): "
          f"{worst:.4g}", flush=True)
    return out
