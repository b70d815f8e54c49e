"""The Laguna language model (poolside's Laguna-S-2.1) in plain float32
``jax.numpy``: forward.

Pre-norm residual blocks (RMSNorm eps ``rms_norm_eps``, no bias, SiLU,
an untied head).  Layer ``l`` is of kind ``layer_types[l]`` with ``H_l =
num_attention_heads_per_layer[l]`` query heads over ``G =
num_key_value_heads`` key/value heads of ``hd``.  With ``a = RMSNorm(x)``:

    q_h = R_p((a W_q)_h)        k_g = R_p((a W_k)_g)        v_g = (a W_v)_g
    R_p: rotary on the first ``r = hd * partial_rotary_factor`` dims of the
         head, half-split pairs (i, i + r/2), angle p * f_i, cos and sin
         times ``attention_factor``; the other hd - r dims pass through.
         ``rope_type`` ``default``: f_i = theta^(-2i/r), factor 1.
         ``yarn``: pair i turns n_i = original f_i / 2 pi times in the
         original context; f_i is kept where n_i > beta_fast, divided by
         ``factor`` where n_i < beta_slow, and blended along the linear
         ramp between the two pair indices (rounded outward, clamped to
         [0, r - 1]) in between.
    o_h(t) = sum_s softmax_s(q_h(t) . k_{h // (H_l/G)}(s) / sqrt(hd)) v_{h // (H_l/G)}(s)
         over s <= t, and on a ``sliding_attention`` layer also
         s > t - ``sliding_window``
    gamma = sigmoid(a W_g)  (one value a head)        y = concat_h(gamma_h o_h) W_o

Feed-forward: a ``dense`` layer of ``mlp_layer_types`` is a gated SiLU MLP
of width ``intermediate_size``; a ``sparse`` one scores ``s = sigmoid(u
W_r)`` over every expert in float32, takes the top ``num_experts_per_tok``,
divides the chosen scores by their sum (``norm_topk_prob``), multiplies by
``moe_routed_scaling_factor``, and adds ``sum_e w_e W_down,e(SiLU(W_gate,e
u) * W_up,e u)`` to one shared expert of ``shared_expert_intermediate_size``,
ungated.  Residual adds, a last RMSNorm, the head.

Departures from the published description, each because the catalog's
config has no key for the thing (the configuration file lists them under
``assumed``): the router is a sigmoid without a selection bias (the config
carries DeepSeek-V3's ``moe_routed_scaling_factor`` and ``norm_topk_prob``
and no bias key); the gate is ``sigmoid`` of a linear map of the normed
input, one value a head, applied before ``W_o`` (arXiv:2505.06708's
headwise form; the config says ``per_head`` and no more); no norm over the
heads of q and k; YaRN's ramp ends are rounded outward.  A configuration
that holds a share of the experts (``held_experts``; the router keeps
``published.num_experts`` outputs) adds its own experts' terms and the
shared expert and leaves the others' out; a sliced vocabulary is a smaller
vocabulary.

No kernel, cache or batching, and nothing of the program is imported.
Leaves are named ``"<op>/<key>"`` after the recipe in ``leaf_spec`` and
drawn by ``benchmark/weights.py``, any leaf (or any expert of a leaf)
alone.  ``assumed.attn_logit_gain`` (layer -> factor, 1 elsewhere) draws a
layer's ``W_q`` and ``W_k`` each ``sqrt(factor)`` wider, so that its
attention logits are ``factor`` times as large: under the plain init a
softmax over thousands of seeded positions is flat and no logit depends on
which of them a layer read.  Attention runs a block of query rows at a
time against an explicit mask, a key/value head at a time; a window layer's
block reads the slab of keys its band can reach (the mask is the same
mask, over fewer columns).  Matrix products run at ``highest`` precision;
``quant`` (the control) rounds both operands of every product the
configuration computes in bfloat16 to fp8 e4m3 first, scaled by the
tensor's largest magnitude: the nearest precision below the one the
configuration states.  The router, the gate's sigmoid, the norms and the
softmax stay in float32 there too.

What ``served_gaps`` hands the runner as the gap it judges is the MEAN
over the served positions of how far the served token's logit lies below
the reference's best, not the widest, for the reason the DeepSeek-V3
reference gives: a top-k (10 of 256 experts) is a discontinuous function,
and where two candidates lie within the program's bfloat16 round-off of
each other the program and this float32 walk part by a whole expert's
output.  The widest gap, the quantiles, the first served token's gap and
the share of (token, layer) expert selections that flip under bfloat16
activations are printed beside it.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

_SQ3 = math.sqrt(3.0)
_Q_ROWS = 256  # query rows a block of the reference's attention


def router_width(cfg: Dict[str, Any]) -> int:
    """Outputs of the router: the published number of experts where the
    configuration holds a share of them."""
    if cfg.get("held_experts") is None:
        return cfg["num_experts"]
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def held_experts(cfg: Dict[str, Any]) -> List[int]:
    held = cfg.get("held_experts")
    return list(range(cfg["num_experts"])) if held is None else list(held)


def layer_heads(cfg: Dict[str, Any], i: int) -> int:
    per = cfg.get("num_attention_heads_per_layer")
    return cfg["num_attention_heads"] if per is None else per[i]


def leaf_spec(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    """``"op/key" -> (shape, half_width, offset)`` of every leaf, in the
    layout the program holds it in."""
    a = cfg["assumed"]
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hkv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    e, eh, f = router_width(cfg), len(held_experts(cfg)), cfg["moe_intermediate_size"]
    fd, fs = cfg["intermediate_size"], cfg["shared_expert_intermediate_size"]
    w, ns = a["init_std"] * _SQ3, a["norm_scale_half_width"]
    gains = a.get("attn_logit_gain", {})      # layer (as a string) -> factor
    spec = {
        "embed/table": ((v, d), w, 0.0),
        "ln_f/scale": ((d,), ns, 1.0),
        "lm_head/kernel": ((v, d), w, 0.0),
    }
    for i in range(cfg["num_hidden_layers"]):
        p, h = f"blk{i}_", layer_heads(cfg, i)
        wide = w * math.sqrt(float(gains.get(str(i), 1.0)))
        spec[f"{p}ln1/scale"] = ((d,), ns, 1.0)
        spec[f"{p}ln2/scale"] = ((d,), ns, 1.0)
        spec[f"{p}attn/wq"] = ((d, h * hd), wide, 0.0)
        spec[f"{p}attn/wk"] = ((d, hkv * hd), wide, 0.0)
        spec[f"{p}attn/wv"] = ((d, hkv * hd), w, 0.0)
        spec[f"{p}attn/wo"] = ((h * hd, d), w, 0.0)
        spec[f"{p}attn/wg"] = ((d, h), w, 0.0)
        if cfg["mlp_layer_types"][i] == "dense":
            spec[f"{p}mlp_gate/kernel"] = ((fd, d), w, 0.0)
            spec[f"{p}mlp_up/kernel"] = ((fd, d), w, 0.0)
            spec[f"{p}mlp_down/kernel"] = ((d, fd), w, 0.0)
        else:
            spec[f"{p}moe/gate"] = ((d, e), w, 0.0)
            spec[f"{p}moe/w_gate"] = ((eh, d, f), w, 0.0)
            spec[f"{p}moe/w_up"] = ((eh, d, f), w, 0.0)
            spec[f"{p}moe/w_down"] = ((eh, f, d), w, 0.0)
            spec[f"{p}moe/s_gate"] = ((d, fs), w, 0.0)
            spec[f"{p}moe/s_up"] = ((d, fs), w, 0.0)
            spec[f"{p}moe/s_down"] = ((fs, d), w, 0.0)
    return spec


def parameter_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    """``{"total", "active"}`` from ``leaf_spec``: every leaf, and what
    one token passes: ``num_experts_per_tok`` of the ``router_width``
    experts of each expert layer, everything else once, the head among
    it and the token table NOT (a row of it is read, none multiplied)."""
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
    if name.endswith("moe/gate"):
        return cfg["assumed"]["router_dtype"]
    return cfg["assumed"]["param_dtype"]


class Leaves:
    """Seeded leaves under the name prefix ``at`` (``"blk3_"``; empty for
    the whole model's names), each made when asked for and rounded once
    to the dtype the configuration stores it in, held in f32.  ``seed`` is
    a whole number or the (possibly traced) ``(low, high)`` words of
    ``weights.split_seed``."""

    def __init__(self, cfg: Dict[str, Any], seed, at: str = "", spec=None):
        self.cfg, self.seed, self.prefix = cfg, seed, at
        self.spec = spec or leaf_spec(cfg)

    def at(self, prefix: str) -> "Leaves":
        """The same leaves seen from under another prefix."""
        return Leaves(self.cfg, self.seed, prefix, self.spec)

    def _values(self, name: str, rows, cols_n: int):
        full = self.prefix + name
        _, hw, off = self.spec[full]
        v = weights.unit_uniform(weights.leaf_key(self.seed, full, jnp),
                                 rows.astype(jnp.uint32)[:, None],
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


def rotary_frequencies(r: int, rope: Dict[str, Any]):
    """``(f (r/2,) float32, the scale of cos and sin)`` of one entry of
    ``rope_parameters`` over a rotary width ``r``."""
    theta = float(rope["rope_theta"])
    f = theta ** (-np.arange(0, r, 2, dtype=np.float32) / np.float32(r))
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return f.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}")
    factor, span = float(rope["factor"]), rope["original_max_position_embeddings"]

    def pair_turning(turns: float) -> float:
        return r * math.log(span / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(pair_turning(rope.get("beta_fast", 32))), 0)
    hi = min(math.ceil(pair_turning(rope.get("beta_slow", 1))), r - 1)
    ramp = np.clip((np.arange(r // 2, dtype=np.float32) - lo) / max(hi - lo, 0.001), 0.0, 1.0)
    f = f / factor * ramp + f * (1.0 - ramp)
    wave = rope.get("attention_factor")
    if wave is None:
        wave = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return f.astype(np.float32), float(wave)


def rotary(x, pos, r: int, f, wave: float):
    """``x`` (t, heads, hd): the first ``r`` dims of every head turned in
    half-split pairs (i, i + r/2) by ``pos[t] * f[i]``, cos and sin times
    ``wave``; the rest passed through."""
    ang = jnp.asarray(pos, jnp.float32)[:, None, None] * jnp.asarray(f)
    cos, sin = jnp.cos(ang) * wave, jnp.sin(ang) * wave
    a, b, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def attention(cfg, get: Leaves, a, i: int, quant: bool = False, window: bool = True):
    """Causal grouped-query attention of layer ``i`` over one sequence
    ``a`` (t, d), banded on a ``sliding_attention`` layer; ``window``
    false lets such a layer attend its whole causal past (the tests' tie
    to plain attention, and the control's other side)."""
    h, hkv, hd = layer_heads(cfg, i), cfg["num_key_value_heads"], cfg["head_dim"]
    g, t, kind = h // hkv, a.shape[0], cfg["layer_types"][i]
    rope = cfg["rope_parameters"][kind]
    r = int(round(hd * rope.get("partial_rotary_factor", 1)))
    f, wave = rotary_frequencies(r, rope)
    band: Optional[int] = cfg["sliding_window"] \
        if kind == "sliding_attention" and window else None
    pos = jnp.arange(t)
    q = rotary(_mm(a, get("attn/wq"), quant).reshape(t, h, hd), pos, r, f, wave)
    k = rotary(_mm(a, get("attn/wk"), quant).reshape(t, hkv, hd), pos, r, f, wave)
    v = _mm(a, get("attn/wv"), quant).reshape(t, hkv, hd)
    rows = min(_Q_ROWS, t)
    assert t % rows == 0, (t, rows)
    # The keys a block of rows starting at ``start`` can reach: all of
    # them, or under a band the ``span`` that end with the block's last
    # row (``lead`` rows of zeros in front, so that the slab is one size).
    span = t if band is None else min(t, rows + -(-(band - 1) // rows) * rows)
    lead = span - rows if band is not None else 0
    kp, vp = (jnp.pad(c, ((lead, 0), (0, 0), (0, 0))) for c in (k, v))

    def block(args):
        qb, start = args                                         # (rows, hkv, g, hd)
        first = start - lead if band is not None else 0          # the slab's first position
        ks, vs = (jax.lax.dynamic_slice_in_dim(c, first + lead, span, axis=0)
                  for c in (kp, vp))
        qpos = (start + jnp.arange(rows))[:, None]
        kpos = (first + jnp.arange(span))[None, :]
        mask = (kpos <= qpos) & (kpos >= 0)
        if band is not None:
            mask = mask & (kpos > qpos - band)

        def head(args):
            qh, kh, vh = args                                    # (rows, g, hd), (span, hd)
            s = jnp.einsum("qgd,td->gqt", qh, kh, precision="highest") / math.sqrt(hd)
            pr = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gqt,td->qgd", pr, vh, precision="highest")

        o = jax.lax.map(head, (qb.transpose(1, 0, 2, 3), ks.transpose(1, 0, 2),
                               vs.transpose(1, 0, 2)))           # (hkv, rows, g, hd)
        return o.transpose(1, 0, 2, 3).reshape(rows, h, hd)

    n = t // rows
    o = jax.lax.map(block, (q.reshape(n, rows, hkv, g, hd), jnp.arange(0, t, rows)))
    gate = jax.nn.sigmoid(_mm(a, get("attn/wg"), quant))         # (t, h)
    o = o.reshape(t, h, hd) * gate[:, :, None]
    return _mm(o.reshape(t, h * hd), get("attn/wo"), quant)


def _gated(u, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(u, w_gate, quant)) * _mm(u, w_up, quant), w_down, quant)


def route(cfg, get: Leaves, u):
    """``(idx (t, k), w (t, k))`` in f32, the product at full precision,
    over the router's whole width."""
    s = jax.nn.sigmoid(jnp.matmul(u, get("moe/gate"), precision="highest"))
    w, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg["moe_routed_scaling_factor"]


def experts(cfg, get: Leaves, u, quant: bool = False, shared: bool = True):
    """The expert layer's output for ``u`` (t, d) on this chip: a loop
    over the experts it holds, each run on every token and weighed by
    what the router gave it there (zero where it was not chosen), plus
    the shared expert.  The experts held elsewhere are left out."""
    idx, w = route(cfg, get, u)
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


def layer(cfg, get: Leaves, x, i: int, quant: bool = False, window: bool = True):
    """Block ``i``; ``(x, the share of flipped expert selections)``."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, get, _rms(x, get("ln1/scale"), eps), i, quant, window)
    u = _rms(x, get("ln2/scale"), eps)
    if cfg["mlp_layer_types"][i] == "dense":
        return x + _gated(u, get("mlp_gate/kernel").T, get("mlp_up/kernel").T,
                          get("mlp_down/kernel").T, quant), jnp.float32(0.0)
    return x + experts(cfg, get, u, quant), selection_flips(cfg, get, u)


def hidden(cfg: Dict[str, Any], seed, tokens, quant: bool = False, window: bool = True):
    """``tokens (t,) -> (hidden (t, d) before the last norm, the share of
    flipped expert selections of each expert layer)``."""
    get = Leaves(cfg, seed)
    x = get("embed/table")[tokens]
    flips = []
    for i in range(cfg["num_hidden_layers"]):
        x, flip = layer(cfg, get.at(f"blk{i}_"), x, i, quant, window)
        if cfg["mlp_layer_types"][i] != "dense":
            flips.append(flip)
    return x, jnp.stack(flips) if flips else jnp.zeros((0,), jnp.float32)


class Walk:
    """The two jitted programs of one walk of ``cfg``: the layers, and the
    last norm with the head.  The seed is an argument of both."""

    def __init__(self, cfg: Dict[str, Any], quant: bool = False, window: bool = True):
        self.hidden = jax.jit(lambda seed, tokens: hidden(cfg, seed, tokens, quant, window))

        def head(seed, x):
            g = Leaves(cfg, seed)
            return _mm(_rms(x, g("ln_f/scale"), cfg["rms_norm_eps"]),
                       g("lm_head/kernel").T, quant)

        self.head = jax.jit(head)


def logits_fn(cfg: Dict[str, Any], seed: int, tokens, quant: bool = False,
              window: bool = True):
    """``tokens (t,) -> logits (t, vocab)``, float32: the whole forward at
    once (small sizes: the tests)."""
    walk, words = Walk(cfg, quant, window), weights.split_seed(seed)
    return walk.head(words, walk.hidden(words, jnp.asarray(tokens))[0])


def served_gaps(cfg: Dict[str, Any], seed: int, max_seq: int,
                samples: List[Dict[str, Any]], quant: bool = False) -> Dict[str, Any]:
    """For each sample ``{"prompt", "tokens"}`` run the full forward once
    over prompt and served tokens and read, at every served position, how
    far the served token's logit lies below the reference's best.  With
    ``quant`` the token read is the one the lower precision puts first at
    that position, not the served one (the control).  ``widest_gap``, the
    number the runner judges, is the mean over the positions (see the
    module's text); the widest is ``max_gap``."""
    sound, low = Walk(cfg), Walk(cfg, True) if quant else None
    words = weights.split_seed(seed)
    gaps: List[float] = []
    first: List[float] = []
    flips: List[float] = []
    pad = int(cfg["assumed"].get("reference_pad", _Q_ROWS))
    width = -(-max(len(s["tokens"]) for s in samples) // 8) * 8
    for s in samples:
        prompt = np.asarray(s["prompt"], np.int32)
        served = np.asarray(s["tokens"], np.int32)
        full = np.concatenate([prompt, served])[:-1]
        t, lo = full.shape[0], len(prompt) - 1
        # Padded (no layer looks ahead) so that a few programs serve every
        # sample, and far enough that the rows read are a slice of one size.
        size = -(-(lo + width) // pad) * pad
        padded = jnp.asarray(np.pad(full, (0, size - t)))

        def served_logits(walk):
            x, flip = walk.hidden(words, padded)
            rows = jax.lax.dynamic_slice_in_dim(x, lo, width, axis=0)
            return walk.head(words, rows)[:t - lo], flip

        lg, flip = served_logits(sound)
        flips.extend(float(f) for f in flip)
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
           "selection_flip_share": float(np.mean(flips)) if flips else float("nan")}
    print(f"[reference] {'control' if quant else 'served'} gaps over {len(gaps)} positions of "
          f"{len(samples)} requests: mean {out['mean_gap']:.6g} p50 {q50:.6g} p90 {q90:.6g} "
          f"p99 {q99:.6g} max {out['max_gap']:.6g}; over 0.1: "
          f"{float(np.mean(np.asarray(gaps) > 0.1)):.4f}; first tokens (prefill) max "
          f"{out['first_token_max_gap']:.6g}; (token, layer) expert selections that flip under "
          f"bfloat16 activations: {out['selection_flip_share']:.4f}", flush=True)
    return out
