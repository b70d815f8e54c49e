"""The DeepSeek-V3 block family in plain float32 ``jax.numpy``: forward.

Follows ``transformers``' ``DeepseekV3`` (the published description of
Kanana-2's ``model_type``): RMSNorm pre-norm blocks; latent attention
with K and V expanded a head (the only formulation here: no absorbed
path, no cache); ``first_k_dense_replace`` gated-SiLU dense layers, then
expert layers: a sigmoid router over every expert, top-k of ``score +
e_score_correction_bias``, the chosen scores normalised and scaled, a
loop over the experts, shared experts on every token; a final RMSNorm
and an untied head.  No bias anywhere.  Departure: RoPE turns adjacent
pairs ``(2i, 2i+1)`` in place (``rope_interleave``; ``transformers``
first moves the pairs apart, a fixed permutation of q and k alike that
leaves every score as it is).

No kernel, cache or batching, and nothing of the program is imported.
Leaves are named ``"<op>/<key>"`` after the recipe in ``leaf_spec`` and
drawn by ``benchmark/weights.py``, any leaf (or any expert of a leaf)
alone: at the published widths the model does not fit a chip in f32, so
a layer's weights are made when the walk reaches the layer (the expert
layers are alike, so one scanned body walks them, its leaves drawn from
the layer's own keys), an expert's inside the loop over experts, and
attention runs a block of query rows at a time.  Matrix products run at ``highest`` precision; ``quant`` (the
control) rounds both operands of every product the configuration
computes in bfloat16 to fp8 e4m3 first, scaled by the tensor's largest
magnitude: the nearest precision below the one the configuration states.
The router, the norms and the softmax stay in float32 there too, as the
configuration states them.

What ``served_gaps`` hands the runner as the gap it judges is the MEAN
over the served positions of how far the served token's logit lies below
the reference's best, not the widest.  The reason is the router: top-k
of 128 near-uniform scores is a discontinuous function, and where a
token's k-th and (k+1)-th expert lie within the program's bfloat16
round-off of each other the program and this float32 walk choose
different experts and their logits part by a whole expert's output
(measured: the widest gap of a sound run is over 1 while nine positions
in ten stay within a few hundredths).  No finite limit on the widest gap
separates a sound run from the fp8 control; the mean does, and still
moves when a few positions in a hundred are wrong.  The widest gap, the
quantiles, the first served token's gap (the program's expanded prefill;
the others are its absorbed decode) and the share of (token, layer)
selections that flip under bfloat16 activations are printed beside it.
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
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs, fd = cfg["n_shared_experts"] * f, cfg["intermediate_size"]
    w = cfg["assumed"]["init_std"] * _SQ3
    ns = cfg["assumed"]["norm_scale_half_width"]
    spec = {
        "embed/table": ((v, d), w, 0.0),
        "ln_f/scale": ((d,), ns, 1.0),
        "lm_head/kernel": ((v, d), w, 0.0),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk{i}_"
        spec[f"{p}ln1/scale"] = ((d,), ns, 1.0)
        spec[f"{p}ln2/scale"] = ((d,), ns, 1.0)
        spec[f"{p}attn/wq"] = ((d, h * (nope + rope)), w, 0.0)
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
            spec[f"{p}moe/e_bias"] = ((e,), cfg["assumed"]["e_bias_half_width"], 0.0)
            spec[f"{p}moe/w_gate"] = ((e, d, f), w, 0.0)
            spec[f"{p}moe/w_up"] = ((e, d, f), w, 0.0)
            spec[f"{p}moe/w_down"] = ((e, f, d), w, 0.0)
            spec[f"{p}moe/s_gate"] = ((d, fs), w, 0.0)
            spec[f"{p}moe/s_up"] = ((d, fs), w, 0.0)
            spec[f"{p}moe/s_down"] = ((fs, d), w, 0.0)
    return spec


def stored_dtype(cfg: Dict[str, Any], name: str) -> str:
    if name.endswith(("moe/gate", "moe/e_bias")):
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


def _rope(x, pos, theta):
    """Adjacent pairs ``(2i, 2i+1)`` of the last dim turned by
    ``pos * theta^(-2i/d)``; ``x`` (t, ..., d), ``pos`` (t,)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _gated(u, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(u, w_gate, quant)) * _mm(u, w_up, quant), w_down, quant)


def attention(cfg, get: Leaves, u, quant: bool = False):
    """Causal latent attention, expanded, over one sequence ``u`` (t, d);
    ``get`` the layer's leaves."""
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    t = u.shape[0]
    pos = jnp.arange(t)
    q = _mm(u, get("attn/wq"), quant).reshape(t, h, nope + rope)
    ckr = _mm(u, get("attn/wkv_a"), quant)
    c = _rms(ckr[:, :r], get("attn/kv_norm"), cfg["rms_norm_eps"])
    k_r = _rope(ckr[:, r:], pos, cfg["rope_theta"])                  # (t, rope)
    kv = _mm(c, get("attn/wkv_b"), quant).reshape(t, h, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (t, h, rope))], axis=-1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, cfg["rope_theta"])], axis=-1)
    rows = min(_Q_ROWS, t)
    assert t % rows == 0, (t, rows)

    def block(args):
        qb, start = args                                             # (rows, h, .)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision="highest") / math.sqrt(nope + rope)
        mask = jnp.arange(t)[None, :] <= (start + jnp.arange(rows))[:, None]
        pr = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v, precision="highest")

    o = jax.lax.map(block, (q.reshape(t // rows, rows, h, nope + rope),
                            jnp.arange(0, t, rows)))
    return _mm(o.reshape(t, h * vd), get("attn/wo"), quant)


def route(cfg, get: Leaves, u):
    """``(idx (t, k), w (t, k))`` in f32, the product at full precision."""
    s = jax.nn.sigmoid(jnp.matmul(u, get("moe/gate"), precision="highest"))
    _, idx = jax.lax.top_k(s + get("moe/e_bias"), cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def experts(cfg, get: Leaves, u, quant: bool = False, held=None, shared: bool = True):
    """The expert layer's output for ``u`` (t, d): a loop over the
    experts ``held`` (default all), each run on every token and weighed
    by what the router gave it there (zero where it was not chosen),
    plus the shared experts."""
    idx, w = route(cfg, get, u)
    held = jnp.arange(cfg["n_routed_experts"]) if held is None else jnp.asarray(held)

    def one(j, acc):
        e = held[j]
        gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)         # (t,)
        y = _gated(u, get.expert("moe/w_gate", e), get.expert("moe/w_up", e),
                   get.expert("moe/w_down", e), quant)
        return acc + gate[:, None] * y

    out = jnp.zeros_like(u)
    if held.shape[0]:
        out = jax.lax.fori_loop(0, held.shape[0], one, out)
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


def layer(cfg, get: Leaves, x, dense: bool, quant: bool = False):
    """One block; ``(x, share of flipped selections)``."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, get, _rms(x, get("ln1/scale"), eps), quant)
    u = _rms(x, get("ln2/scale"), eps)
    if dense:
        return x + _gated(u, get("mlp_gate/kernel").T, get("mlp_up/kernel").T,
                          get("mlp_down/kernel").T, quant), jnp.float32(0.0)
    return x + experts(cfg, get, u, quant), selection_flips(cfg, get, u)


def hidden(cfg: Dict[str, Any], seed, tokens, quant: bool = False):
    """``tokens (t,) -> (hidden (t, d) before the last norm, the share
    of flipped selections of each expert layer)``.  The leading dense
    layers one by one, then one scanned body over the expert layers,
    which are alike: each draws its leaves from its own keys."""
    get = Leaves(cfg, seed)
    k, n = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    x = get("embed/table")[tokens]
    for i in range(k):
        x, _ = layer(cfg, get.at(f"blk{i}_"), x, True, quant)
    if n == k:
        return x, jnp.zeros((0,), jnp.float32)
    first = f"blk{k}_"
    local = [name[len(first):] for name in get.spec if name.startswith(first)]
    keys = {name: jnp.stack([weights.leaf_key(seed, f"blk{i}_{name}", jnp)
                             for i in range(k, n)]) for name in local}

    def body(x, layer_keys):
        return layer(cfg, get.at(first, layer_keys), x, False, quant)

    return jax.lax.scan(body, x, keys)


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
        # Padded (causal attention never looks ahead) so that a few
        # programs serve every sample, and far enough that the rows
        # read are a slice of one size.
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
