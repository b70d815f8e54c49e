"""LFM2-MoE (``model_type`` ``lfm2_moe``) in plain float32 ``jax.numpy``: forward.

Pre-norm residual blocks (RMSNorm eps ``norm_eps``, no bias anywhere),
``x <- x + Mixer_i(RMSNorm(x))``, ``x <- x + FFN_i(RMSNorm(x))``, one more
RMSNorm after the last layer and a head TIED to the token table:
``logits = x E^T``.  With ``a`` a sublayer's normed input:

    conv (``layer_types[i] == "conv"``, L = ``conv_L_cache`` taps):
        [B | C | z] = a W_in                  (three parts of the width)
        u = B * z
        c_t = sum_{j < L} w_j * u_{t - (L - 1) + j}     (u zero before 0)
        y = (C * c) W_out
    full_attention (H query heads over G cached heads of hd = d / H):
        q_h = R_p(RMSNorm_hd(a W_q)_h)   k_g = R_p(RMSNorm_hd(a W_k)_g)
        v_g = (a W_v)_g
        R_p: half-split rotary over the whole head, pair (i, i + hd/2),
             angle p theta^(-2i/hd)
        o_h(t) = sum_{s <= t} softmax_s(q_h(t) . k_{h // (H/G)}(s) / sqrt(hd))
                 v_{h // (H/G)}(s)
        y = concat_h(o_h) W_o
    feed-forward, layers below ``num_dense_layers``:
        (SiLU(a W_1) * a W_3) W_2                    (``intermediate_size``)
    feed-forward, the others:
        s = sigmoid(a W_r) over every expert, float32; the
        ``num_experts_per_tok`` experts of largest s + b (``use_expert_bias``:
        b chooses and does not weigh; the lower index among equals);
        weights s_e / (sum of the chosen s + 1e-6) (``norm_topk_prob``)
        times ``routed_scaling_factor``; each expert
        (SiLU(a W_1e) * a W_3e) W_2e of ``moe_intermediate_size``; no
        shared expert.

This follows ``transformers``' ``Lfm2Moe`` modules as the configuration's
keys name them (``Lfm2ShortConv``: ``B, C, x = in_proj(a).chunk(3)``,
``conv(B * x)``, ``out_proj(C * conv)``; ``Lfm2MoeSparseMoeBlock``'s
sigmoid router).  Departures: none known; the tie is the family's
default (``tie_embedding``), which the catalog row's ``config`` does not
carry (``assumed.tie_embedding``).

No kernel, cache or batching, and nothing of the program is imported.
Leaves are named ``"<op>/<key>"`` after the recipe in ``leaf_spec`` and
drawn by ``benchmark/weights.py``, any leaf (or any expert of a leaf)
alone: a layer's leaves are made as the layer is reached and an expert's
inside the loop over experts, so the 21 GB of float32 this stage holds
never stand at once; the table's rows are drawn for the tokens read and
the whole table once, for the head.  One jitted body a kind of layer
(mixer x feed-forward), each layer handing it its own leaf keys and
offsets.  Attention runs a block of query rows at a time, a cached head
at a time.  Matrix products run at ``highest`` precision; ``quant`` (the
control) rounds both operands of every matrix product the configuration
computes in bfloat16 to fp8 e4m3 first, scaled by the tensor's largest
magnitude: the nearest precision below the one the configuration states.
The router, the norms, the softmax and the convolution's elementwise
products stay in float32 there too.

``assumed.q_norm_gain`` (layer -> the centre of its query norm's scale, 1
elsewhere) draws an attention layer's heads peaked: under unit scales a
softmax over hundreds to thousands of seeded positions is flat, the
layer's output is one common average, and no logit depends on which
positions a decode step read.

What ``served_gaps`` hands the runner as the gap it judges is the MEAN
over the served positions of how far the served token's logit lies
below the reference's best, not the widest, for the reason the
DeepSeek-V3 reference gives: a top-k is a discontinuous function (4 of
64 experts in each of eight layers), and where two candidates lie within
the program's bfloat16 round-off of each other the program and this
float32 walk part by a whole expert's output.  The widest gap, the
quantiles, the first served token's gap and the share of (token, layer)
expert selections that flip under bfloat16 activations are printed
beside it.
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


def layer_kinds(cfg: Dict[str, Any]):
    """``[(mixer, feed-forward)]`` of the layers held: ``conv`` or
    ``attn``, ``mlp`` or ``moe``."""
    return [("conv" if cfg["layer_types"][i] == "conv" else "attn",
             "mlp" if i < cfg["num_dense_layers"] else "moe")
            for i in range(cfg["num_hidden_layers"])]


def leaf_spec(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    """``"op/key" -> (shape, half_width, offset)`` of every leaf, in the
    layout the program holds it in.  The head has none: it reads
    ``embed/table``."""
    a = cfg["assumed"]
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    e, fe, taps = cfg["num_experts"], cfg["moe_intermediate_size"], cfg["conv_L_cache"]
    w, ns = a["init_std"] * _SQ3, a["norm_scale_half_width"]
    gains = a.get("q_norm_gain", {})      # layer (as a string) -> the scale's centre
    spec = {"embed/table": ((v, d), w, 0.0), "ln_f/scale": ((d,), ns, 1.0)}
    for i, (mixer, ffn) in enumerate(layer_kinds(cfg)):
        p = f"blk{i}_"
        spec[f"{p}ln1/scale"] = ((d,), ns, 1.0)
        spec[f"{p}ln2/scale"] = ((d,), ns, 1.0)
        if mixer == "conv":
            spec[f"{p}conv/w_in"] = ((d, 3 * d), w, 0.0)
            spec[f"{p}conv/conv"] = ((taps, d), a["conv_tap_half_width"], 0.0)
            spec[f"{p}conv/w_out"] = ((d, d), w, 0.0)
        else:
            spec[f"{p}attn/wq"] = ((d, h * hd), w, 0.0)
            spec[f"{p}attn/wk"] = ((d, hkv * hd), w, 0.0)
            spec[f"{p}attn/wv"] = ((d, hkv * hd), w, 0.0)
            spec[f"{p}attn/wo"] = ((h * hd, d), w, 0.0)
            spec[f"{p}attn/q_norm"] = ((hd,), ns, float(gains.get(str(i), 1.0)))
            spec[f"{p}attn/k_norm"] = ((hd,), ns, 1.0)
        if ffn == "mlp":
            spec[f"{p}mlp_gate/kernel"] = ((f, d), w, 0.0)
            spec[f"{p}mlp_up/kernel"] = ((f, d), w, 0.0)
            spec[f"{p}mlp_down/kernel"] = ((d, f), w, 0.0)
        else:
            spec[f"{p}moe/gate"] = ((d, e), w, 0.0)
            if cfg.get("use_expert_bias"):
                spec[f"{p}moe/e_bias"] = ((e,), a["e_bias_half_width"], 0.0)
            spec[f"{p}moe/w_gate"] = ((e, d, fe), w, 0.0)
            spec[f"{p}moe/w_up"] = ((e, d, fe), w, 0.0)
            spec[f"{p}moe/w_down"] = ((e, fe, d), w, 0.0)
    return spec


def parameter_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    """``{"total", "active"}`` from ``leaf_spec``: every leaf, and what
    one token passes (``num_experts_per_tok`` of each expert layer's
    experts, everything else whole: the table is the head too)."""
    total = active = 0
    share = cfg["num_experts_per_tok"] / cfg["num_experts"]
    for name, (shape, _, _) in leaf_spec(cfg).items():
        n = int(np.prod(shape))
        total += n
        active += int(n * share) if name.endswith(
            ("moe/w_gate", "moe/w_up", "moe/w_down")) else n
    return {"total": total, "active": active}


def stored_dtype(cfg: Dict[str, Any], name: str) -> str:
    if name.endswith(("moe/gate", "moe/e_bias")):
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

    def rows(self, name: str, rows):
        """Rows ``rows`` (flattened leading index, may be traced) of the
        leaf ``name``."""
        full = self.prefix + name
        shape, hw, off = self.spec[full]
        key, off = self.keys[name] if self.keys is not None else \
            (weights.leaf_key(self.seed, full, jnp), off)
        v = weights.unit_uniform(key, jnp.asarray(rows).astype(jnp.uint32)[:, None],
                                 jnp.arange(shape[-1], dtype=jnp.uint32)[None, :], jnp)
        return weights.round_to(jnp.float32(off) + jnp.float32(hw) * v,
                                stored_dtype(self.cfg, full), jnp)

    def __call__(self, name: str):
        shape = self.spec[self.prefix + name][0]
        rows_n = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
        return self.rows(name, jnp.arange(rows_n, dtype=jnp.uint32)).reshape(shape)

    def expert(self, name: str, e):
        """Row ``e`` (may be traced) of the stacked leaf ``name``."""
        rows_n = self.spec[self.prefix + name][0][1]
        return self.rows(name, jnp.asarray(e, jnp.uint32) * jnp.uint32(rows_n)
                         + jnp.arange(rows_n, dtype=jnp.uint32))


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


def rotary(x, pos, theta: float):
    """Half-split rotary over the last dim of ``x`` (t, heads, d): pair
    (i, i + d/2) of the token at row ``r`` turns by ``pos[r]
    theta^(-2i/d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.asarray(pos, jnp.float32)[:, None] * inv)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def short_conv(cfg, get: Leaves, a, quant: bool = False, stateless: bool = False):
    """The gated short convolution over one sequence ``a`` (t, d).
    ``stateless`` keeps the newest tap alone (what a decode step with no
    window computes: the tests' picture of the control)."""
    gate_in, gate_out, z = jnp.split(_mm(a, get("conv/w_in"), quant), 3, axis=-1)
    u = gate_in * z
    taps = get("conv/conv")
    n, t = taps.shape[0], a.shape[0]
    ext = jnp.pad(u, ((n - 1, 0), (0, 0)))
    first = n - 1 if stateless else 0
    c = sum(taps[j] * ext[j:j + t] for j in range(first, n))
    return _mm(gate_out * c, get("conv/w_out"), quant)


def attention(cfg, get: Leaves, a, quant: bool = False):
    """Causal grouped-query attention over one sequence ``a`` (t, d)
    with the head norm and rotary positions."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["hidden_size"] // h, cfg["norm_eps"]
    g, t = h // hkv, a.shape[0]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    pos = jnp.arange(t)
    q = _rms(_mm(a, get("attn/wq"), quant).reshape(t, h, hd), get("attn/q_norm"), eps)
    k = _rms(_mm(a, get("attn/wk"), quant).reshape(t, hkv, hd), get("attn/k_norm"), eps)
    q = rotary(q, pos, theta).reshape(t, hkv, g, hd)
    k = rotary(k, pos, theta)
    v = _mm(a, get("attn/wv"), quant).reshape(t, hkv, hd)
    rows = min(_Q_ROWS, t)
    assert t % rows == 0, (t, rows)

    def block(args):
        qb, start = args
        causal = jnp.arange(t)[None, :] <= (start + jnp.arange(rows))[:, None]

        def head(args):
            qh, kh, vh = args                                    # (rows, g, hd), (t, hd)
            s = jnp.einsum("qgd,td->gqt", qh, kh, precision="highest") / math.sqrt(hd)
            pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gqt,td->qgd", pr, vh, precision="highest")

        o = jax.lax.map(head, (qb.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                               v.transpose(1, 0, 2)))            # (hkv, rows, g, hd)
        return o.transpose(1, 0, 2, 3).reshape(rows, h * hd)

    n = t // rows
    o = jax.lax.map(block, (q.reshape(n, rows, hkv, g, hd), jnp.arange(0, t, rows)))
    return _mm(o.reshape(t, h * hd), get("attn/wo"), quant)


def _gated(u, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(u, w_gate, quant)) * _mm(u, w_up, quant), w_down, quant)


def mlp(cfg, get: Leaves, u, quant: bool = False):
    """A leading layer's dense feed-forward (kernels held ``(out, in)``)."""
    return _gated(u, get("mlp_gate/kernel").T, get("mlp_up/kernel").T,
                  get("mlp_down/kernel").T, quant)


def route(cfg, get: Leaves, u):
    """``(idx (t, k), w (t, k))`` in f32, the product at full precision."""
    s = jax.nn.sigmoid(jnp.matmul(u, get("moe/gate"), precision="highest"))
    choice = s + get("moe/e_bias") if cfg.get("use_expert_bias") else s
    _, idx = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return idx, w * cfg["routed_scaling_factor"]


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


def layer(cfg, get: Leaves, x, mixer: str, ffn: str, quant: bool = False,
          stateless: bool = False):
    """One block; ``(x, share of flipped expert selections)`` (0 for a
    dense feed-forward)."""
    eps = cfg["norm_eps"]
    a = _rms(x, get("ln1/scale"), eps)
    x = x + (short_conv(cfg, get, a, quant, stateless) if mixer == "conv"
             else attention(cfg, get, a, quant))
    u = _rms(x, get("ln2/scale"), eps)
    if ffn == "mlp":
        return x + mlp(cfg, get, u, quant), jnp.float32(0.0)
    return x + experts(cfg, get, u, quant), selection_flips(cfg, get, u)


class Walk:
    """The jitted programs of one walk of ``cfg``: the table's rows, one
    body a kind of layer (a layer hands it its leaf keys and offsets),
    and the last norm with the tied head.  The seed is an argument of
    all of them."""

    def __init__(self, cfg: Dict[str, Any], quant: bool = False, stateless: bool = False):
        self.cfg, self.spec = cfg, leaf_spec(cfg)
        self.kinds = layer_kinds(cfg)
        first = {}
        for i, kind in enumerate(self.kinds):
            first.setdefault(kind, i)

        def body(kind):
            at = f"blk{first[kind]}_"

            def run(seed, keys, x):
                return layer(cfg, Leaves(cfg, seed, at, keys, self.spec), x, *kind,
                             quant=quant, stateless=stateless)

            return jax.jit(run)

        self.bodies = {kind: body(kind) for kind in first}
        self.embed = jax.jit(lambda seed, tokens: Leaves(cfg, seed, spec=self.spec).rows(
            "embed/table", tokens))

        def head(seed, x):
            g = Leaves(cfg, seed, spec=self.spec)
            return _mm(_rms(x, g("ln_f/scale"), cfg["norm_eps"]), g("embed/table").T, quant)

        self.head = jax.jit(head)

    def _keys(self, seed, i: int):
        at = f"blk{i}_"
        return {name[len(at):]: (weights.leaf_key(seed, name, jnp), jnp.float32(off))
                for name, (_, _, off) in self.spec.items() if name.startswith(at)}

    def hidden(self, seed, tokens):
        """``tokens (t,) -> (hidden (t, d) before the last norm, the share
        of flipped expert selections of each expert layer)``."""
        x = self.embed(seed, tokens)
        flips = []
        for i, kind in enumerate(self.kinds):
            x, flip = self.bodies[kind](seed, self._keys(seed, i), x)
            if kind[1] == "moe":
                flips.append(flip)
        return x, flips


def logits_fn(cfg: Dict[str, Any], seed: int, tokens, quant: bool = False,
              stateless: bool = False):
    """``tokens (t,) -> logits (t, vocab)``, float32: the whole forward
    at once (small sizes: the tests)."""
    walk, words = Walk(cfg, quant, stateless), weights.split_seed(seed)
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
