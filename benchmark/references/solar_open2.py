"""The Solar-Open2 block family in plain float32 ``jax.numpy``: forward.

Pre-norm residual blocks (RMSNorm, no bias, an untied head, no
positional signal anywhere: ``use_rope`` false).  Layer ``i`` mixes with
grouped-query softmax attention and an elementwise sigmoid output gate
if ``i`` is in ``gqa_layers``, else with Kimi Delta Attention (Kimi
Linear, arXiv:2510.26692): depthwise causal convolutions over the last
``short_conv_kernel_size`` positions of the q, k and v streams, SiLU, q
and k L2-normalised a head, a decay a key channel ``exp(g)``, ``g =
-exp(A_log) softplus(u W_f1 W_f2 + dt_bias)``, a write strength ``beta =
2 sigmoid(u W_beta)`` a head, and the recurrence, a token at a time (the
only formulation here: no chunks, no kernel),

    S' = diag(exp(g_t)) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T;
    o_t = S^T q_t

followed by a per-head RMSNorm, a low-rank sigmoid gate and ``W_o``.
Every layer is an expert layer: a sigmoid router over every expert,
top-k of ``score + e_bias``, the chosen scores normalised and scaled, a
loop over the experts this chip holds (``held_experts``: the rest are
left out, as in the program, and that partial result goes on), plus the
shared expert every token passes through.

No kernel, cache or batching, and nothing of the program is imported.
Leaves are named ``"<op>/<key>"`` after the recipe in ``leaf_spec`` and
drawn by ``benchmark/weights.py``, any leaf (or any expert of a leaf)
alone: a layer's weights are made when the walk reaches the layer (runs
of alike layers are one scanned body, its leaves drawn from the layer's
own keys), an expert's inside the loop over experts, and attention runs a
block of query rows at a time.  Matrix products run at ``highest``
precision; ``quant`` (the control) rounds both operands of every product
the configuration computes in bfloat16 to fp8 e4m3 first, scaled by the
tensor's largest magnitude: the nearest precision below the one the
configuration states.  The router, the norms, the softmax, the
convolutions, the decay and the recurrence stay in float32 there too, as
the configuration states them.

What ``served_gaps`` hands the runner as the gap it judges is the MEAN
over the served positions of how far the served token's logit lies below
the reference's best, not the widest, for the reason the DeepSeek-V3
reference gives: top-8 of 320 near-uniform sigmoid scores is a
discontinuous function, and where a token's 8th and 9th expert lie
within the program's bfloat16 round-off of each other, and one of them
is held here, the program and this float32 walk part by a whole expert's
output.  The widest gap, the quantiles, the first served token's gap
(the program's chunked prefill; the others are its recurrent decode) and
the share of (token, layer) selections that flip under bfloat16
activations are printed beside it.
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
    a = cfg["assumed"]
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    lin = cfg["linear_attn_config"]
    lw, r = lin["num_heads"] * lin["head_dim"], a["gate_rank"]
    e, eh, f = router_width(cfg), len(held_experts(cfg)), cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    w, ns = a["init_std"] * _SQ3, a["norm_scale_half_width"]
    spec = {
        "embed/table": ((v, d), w, 0.0),
        "ln_f/scale": ((d,), ns, 1.0),
        "lm_head/kernel": ((v, d), w, 0.0),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk{i}_"
        spec[f"{p}ln1/scale"] = ((d,), ns, 1.0)
        spec[f"{p}ln2/scale"] = ((d,), ns, 1.0)
        if i in cfg["gqa_layers"]:
            spec[f"{p}attn/wq"] = ((d, h * hd), w, 0.0)
            spec[f"{p}attn/wk"] = ((d, hkv * hd), w, 0.0)
            spec[f"{p}attn/wv"] = ((d, hkv * hd), w, 0.0)
            spec[f"{p}attn/wo"] = ((h * hd, d), w, 0.0)
            if cfg["use_gqa_gate"]:
                spec[f"{p}attn/wg"] = ((d, h * hd), w, 0.0)
        else:
            for n in "qkv":
                spec[f"{p}kda/w{n}"] = ((d, lw), w, 0.0)
                spec[f"{p}kda/conv_{n}"] = (
                    (lin["short_conv_kernel_size"], lw), a["conv_half_width"], 0.0)
            spec[f"{p}kda/w_f1"] = ((d, r), w, 0.0)
            spec[f"{p}kda/w_f2"] = ((r, lw), w, 0.0)
            spec[f"{p}kda/a_log"] = ((lin["num_heads"],), 1.3863, 1.3863)
            spec[f"{p}kda/dt_bias"] = ((lw,), 2.3, -4.6)
            spec[f"{p}kda/w_beta"] = ((d, lin["num_heads"]), w, 0.0)
            spec[f"{p}kda/w_g1"] = ((d, r), w, 0.0)
            spec[f"{p}kda/w_g2"] = ((r, lw), w, 0.0)
            spec[f"{p}kda/o_norm"] = ((lin["head_dim"],), ns, 1.0)
            spec[f"{p}kda/wo"] = ((lw, d), w, 0.0)
        spec[f"{p}moe/gate"] = ((d, e), w, 0.0)
        spec[f"{p}moe/e_bias"] = ((e,), a["e_bias_half_width"], 0.0)
        spec[f"{p}moe/w_gate"] = ((eh, d, f), w, 0.0)
        spec[f"{p}moe/w_up"] = ((eh, d, f), w, 0.0)
        spec[f"{p}moe/w_down"] = ((eh, f, d), w, 0.0)
        spec[f"{p}moe/s_gate"] = ((d, fs), w, 0.0)
        spec[f"{p}moe/s_up"] = ((d, fs), w, 0.0)
        spec[f"{p}moe/s_down"] = ((fs, d), w, 0.0)
    return spec


def stored_dtype(cfg: Dict[str, Any], name: str) -> str:
    if name.endswith(("moe/gate", "moe/e_bias", "kda/a_log", "kda/dt_bias")):
        return cfg["assumed"]["router_dtype"]
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
        """Row ``e`` (may be traced) of the stacked leaf ``name``: the
        ``e``-th expert this chip holds."""
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


def attention(cfg, get: Leaves, u, quant: bool = False):
    """Causal grouped-query attention with a sigmoid output gate over one
    sequence ``u`` (t, d): query head ``j`` reads key/value head ``j //
    group``; no positions."""
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    g, t = h // hkv, u.shape[0]
    q = _mm(u, get("attn/wq"), quant).reshape(t, hkv, g, hd)
    k = _mm(u, get("attn/wk"), quant).reshape(t, hkv, hd)
    v = _mm(u, get("attn/wv"), quant).reshape(t, hkv, hd)
    rows = min(_Q_ROWS, t)
    assert t % rows == 0, (t, rows)

    def block(args):
        qb, start = args                                             # (rows, hkv, g, hd)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision="highest") / math.sqrt(hd)
        mask = jnp.arange(t)[None, :] <= (start + jnp.arange(rows))[:, None]
        pr = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", pr, v, precision="highest")

    o = jax.lax.map(block, (q.reshape(t // rows, rows, hkv, g, hd),
                            jnp.arange(0, t, rows))).reshape(t, h * hd)
    if cfg["use_gqa_gate"]:
        o = o * jax.nn.sigmoid(_mm(u, get("attn/wg"), quant))
    return _mm(o, get("attn/wo"), quant)


def delta_streams(cfg, get: Leaves, u, quant: bool = False):
    """``(q, k, v, g, beta)`` of one sequence ``u`` (t, d): q, k, v
    (t, H, hd) after the convolution, SiLU and (q, k) normalisation, q
    scaled; ``g`` (t, H, hd) the log decay; ``beta`` (t, H)."""
    lin = cfg["linear_attn_config"]
    nh, hd, kc = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    t = u.shape[0]

    def stream(n):
        x = jnp.pad(_mm(u, get(f"kda/w{n}"), quant), ((kc - 1, 0), (0, 0)))
        w = get(f"kda/conv_{n}")                 # row j: the input kc-1-j back
        y = sum(x[j:j + t] * w[j] for j in range(kc))
        return jax.nn.silu(y).reshape(t, nh, hd)

    def unit(z):
        return z * jax.lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)

    q, k, v = unit(stream("q")) * hd ** -0.5, unit(stream("k")), stream("v")
    f = _mm(_mm(u, get("kda/w_f1"), quant), get("kda/w_f2"), quant)
    g = -jnp.exp(get("kda/a_log"))[:, None] * jax.nn.softplus(
        f + get("kda/dt_bias")).reshape(t, nh, hd)
    beta = jax.nn.sigmoid(_mm(u, get("kda/w_beta"), quant))
    return q, k, v, g, beta * (2.0 if cfg["kda_allow_neg_eigval"] else 1.0)


def delta_rule(q, k, v, g, beta, state=None):
    """The recurrence, a token at a time: ``(o (t, H, hd), S (H, d_k,
    d_v))`` from the state ``S`` (default zero)."""
    nh, hd = q.shape[1], q.shape[2]
    state = jnp.zeros((nh, hd, hd), jnp.float32) if state is None else state

    def step(s, x):
        q, k, v, g, b = x
        s = jnp.exp(g)[:, :, None] * s
        err = v - jnp.einsum("hkv,hk->hv", s, k, precision="highest")
        s = s + b[:, None, None] * k[:, :, None] * err[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q, precision="highest")

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def delta_attention(cfg, get: Leaves, u, quant: bool = False):
    """Kimi Delta Attention over one sequence ``u`` (t, d)."""
    t = u.shape[0]
    o, _ = delta_rule(*delta_streams(cfg, get, u, quant))
    o = _rms(o, get("kda/o_norm"), cfg["rms_norm_eps"]).reshape(t, -1)
    gate = jax.nn.sigmoid(_mm(_mm(u, get("kda/w_g1"), quant), get("kda/w_g2"), quant))
    return _mm(o * gate, get("kda/wo"), quant)


def route(cfg, get: Leaves, u):
    """``(idx (t, k), w (t, k))`` in f32, the product at full precision,
    over the router's whole width."""
    s = jax.nn.sigmoid(jnp.matmul(u, get("moe/gate"), precision="highest"))
    _, idx = jax.lax.top_k(s + get("moe/e_bias"), cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


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


def layer(cfg, get: Leaves, x, gqa: bool, quant: bool = False):
    """One block; ``(x, share of flipped selections)``."""
    eps = cfg["rms_norm_eps"]
    mix = attention if gqa else delta_attention
    x = x + mix(cfg, get, _rms(x, get("ln1/scale"), eps), quant)
    u = _rms(x, get("ln2/scale"), eps)
    return x + experts(cfg, get, u, quant), selection_flips(cfg, get, u)


def layer_runs(cfg: Dict[str, Any]):
    """``[(first layer, layers, grouped-query?)]``: runs of alike layers."""
    runs: List[list] = []
    for i in range(cfg["num_hidden_layers"]):
        gqa = i in cfg["gqa_layers"]
        if runs and runs[-1][2] == gqa:
            runs[-1][1] += 1
        else:
            runs.append([i, 1, gqa])
    return [tuple(r) for r in runs]


def hidden(cfg: Dict[str, Any], seed, tokens, quant: bool = False):
    """``tokens (t,) -> (hidden (t, d) before the last norm, the share
    of flipped selections of each layer)``.  A run of alike layers is
    one scanned body, each layer drawing its leaves from its own keys."""
    get = Leaves(cfg, seed)
    x = get("embed/table")[tokens]
    shares = []
    for first, n, gqa in layer_runs(cfg):
        at = f"blk{first}_"
        if n == 1:
            x, s = layer(cfg, get.at(at), x, gqa, quant)
            shares.append(s[None])
            continue
        local = [name[len(at):] for name in get.spec if name.startswith(at)]
        keys = {name: jnp.stack([weights.leaf_key(seed, f"blk{i}_{name}", jnp)
                                 for i in range(first, first + n)]) for name in local}

        def body(x, layer_keys, at=at, gqa=gqa):
            return layer(cfg, get.at(at, layer_keys), x, gqa, quant)

        x, s = jax.lax.scan(body, x, keys)
        shares.append(s)
    return x, jnp.concatenate(shares)


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

        lg, shares = served_logits(sound)
        flips.extend(float(f) for f in shares)
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
          f"{out['first_token_max_gap']:.6g}; (token, layer) selections that flip under "
          f"bfloat16 activations: {out['selection_flip_share']:.4f}", flush=True)
    return out
