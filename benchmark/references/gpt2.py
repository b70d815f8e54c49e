"""GPT-2 in plain float32 ``jax.numpy``: forward, loss, gradients, Adam.

Follows the published model (Radford et al. 2019; Hugging Face
``modeling_gpt2``): learned token and position tables, pre-LN blocks of
causal multi-head attention and a 4x ``gelu_new`` MLP, a final
LayerNorm and a linear head.  Departures, all listed under ``assumed``
in the configuration: the head is untied from the token table and has a
bias; weights are uniform with GPT-2's 0.02 standard deviation, drawn by
``benchmark/weights.py`` and rounded once to the dtype the configuration
stores them in.

No kernel, cache or batching, and nothing of the program is imported.
Leaves are named ``"<op>/<key>"`` after the recipe in ``leaf_spec``.
Matrix products run at ``highest`` precision; ``quant`` (the control)
rounds both operands of every product to fp8 e4m3 first, scaled by the
tensor's largest magnitude, which is the nearest precision below the
bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

_SQ3 = math.sqrt(3.0)


def leaf_spec(cfg: Dict[str, Any], seq_len: int) -> Dict[str, tuple]:
    """``"op/key" -> (shape, half_width, offset)`` of every leaf."""
    d, v, L = cfg["n_embd"], cfg["vocab_size"], cfg["n_layer"]
    w = cfg["assumed"]["init_std"] * _SQ3
    b = cfg["assumed"]["bias_half_width"]
    spec = {
        "embed/table": ((v, d), w, 0.0),
        "pos/table": ((seq_len, d), w, 0.0),
        "ln_f/scale": ((d,), 0.05, 1.0), "ln_f/bias": ((d,), b, 0.0),
        "lm_head/kernel": ((v, d), w, 0.0), "lm_head/bias": ((v,), b, 0.0),
    }
    for i in range(L):
        p = f"blk{i}_"
        for ln in ("ln1", "ln2"):
            spec[f"{p}{ln}/scale"] = ((d,), 0.05, 1.0)
            spec[f"{p}{ln}/bias"] = ((d,), b, 0.0)
        for m in "qkvo":
            spec[f"{p}attn/w{m}"] = ((d, d), w, 0.0)
            spec[f"{p}attn/b{m}"] = ((d,), b, 0.0)
        spec[f"{p}mlp_up/kernel"] = ((4 * d, d), w, 0.0)
        spec[f"{p}mlp_up/bias"] = ((4 * d,), b, 0.0)
        spec[f"{p}mlp_down/kernel"] = ((d, 4 * d), w, 0.0)
        spec[f"{p}mlp_down/bias"] = ((d,), b, 0.0)
    return spec


def stored_dtype(cfg: Dict[str, Any], name: str) -> str:
    if name == "embed/table":
        return cfg["assumed"]["token_table_dtype"]
    return cfg["assumed"]["param_dtype"]


def init(cfg: Dict[str, Any], seed: int, seq_len: int) -> Dict[str, jax.Array]:
    """Seeded weights, rounded to the dtype they are stored in, held in f32."""
    spec = leaf_spec(cfg, seq_len)

    def make(seed):
        return {
            name: weights.round_to(weights.leaf_values(seed, name, shape, hw, off, jnp),
                                   stored_dtype(cfg, name), jnp)
            for name, (shape, hw, off) in spec.items()
        }

    return jax.jit(make)(weights.split_seed(seed))


def _fp8(x):
    """Round to fp8 e4m3 (4 exponent bits, 3 of mantissa, largest finite
    value 240) under the tensor's own scale; the gradient passes
    straight through, as a low-precision kernel's would."""
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0)
    q = weights.round_to(x / s, "float8_e4m3fn", jnp) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quant: bool):
    if quant:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision="highest")


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


_BLOCK_LEAVES = tuple(f"{ln}/{k}" for ln in ("ln1", "ln2") for k in ("scale", "bias")) \
    + tuple(f"attn/{w}{m}" for w in "wb" for m in "qkvo") \
    + tuple(f"{m}/{k}" for m in ("mlp_up", "mlp_down") for k in ("kernel", "bias"))


def _block(p: Dict[str, jax.Array], x, n_head: int, eps: float, quant: bool):
    """One pre-LN block; ``p`` holds its leaves without the ``blk<i>_``."""
    b, t, d = x.shape
    hd = d // n_head
    a = _ln(x, p["ln1/scale"], p["ln1/bias"], eps)
    q, k, v = (
        (_mm(a, p[f"attn/w{m}"], quant) + p[f"attn/b{m}"])
        .reshape(b, t, n_head, hd).transpose(0, 2, 1, 3)
        for m in "qkv"
    )
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v, precision="highest")
    o = o.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + _mm(o, p["attn/wo"], quant) + p["attn/bo"]
    m = _ln(x, p["ln2/scale"], p["ln2/bias"], eps)
    m = _gelu_new(_mm(m, p["mlp_up/kernel"].T, quant) + p["mlp_up/bias"])
    return x + _mm(m, p["mlp_down/kernel"].T, quant) + p["mlp_down/bias"]


def logits_fn(cfg: Dict[str, Any], p: Dict[str, jax.Array], tokens, quant: bool = False):
    """``tokens (b, t) -> logits (b, t, vocab)``, float32."""
    eps, n_head = cfg["layer_norm_epsilon"], cfg["n_head"]
    t = tokens.shape[1]
    x = p["embed/table"][tokens] + p["pos/table"][:t]
    # The layers are alike, so they are walked by one scanned block (one
    # compiled body, not n_layer copies), each recomputed in the backward
    # pass so that float32 activations fit.
    layers = {k: jnp.stack([p[f"blk{i}_{k}"] for i in range(cfg["n_layer"])])
              for k in _BLOCK_LEAVES}
    blk = jax.checkpoint(lambda lp, x: _block(lp, x, n_head, eps, quant))
    x, _ = jax.lax.scan(lambda x, lp: (blk(lp, x), None), x, layers)
    x = _ln(x, p["ln_f/scale"], p["ln_f/bias"], eps)
    return _mm(x, p["lm_head/kernel"].T, quant) + p["lm_head/bias"]


def _nll_sum(cfg, p, tokens, labels, quant):
    lg = logits_fn(cfg, p, tokens, quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    return jnp.sum(lse - jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0])


def train(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int,
          batches: List[Dict[str, np.ndarray]], quant: bool = False) -> Dict[str, Any]:
    """Follow ``len(batches)`` optimizer steps from the seed.  Returns
    the loss of each step, the norm of every leaf's first gradient, and
    the norm of every leaf's change after the last step.  The batch is
    walked in blocks of rows so that the f32 activations fit beside
    nothing else on one chip."""
    opt = traffic["optimizer"]
    assert opt["name"] == "adam", opt
    lr, b1, b2, eps = opt["lr"], opt["b1"], opt["b2"], opt["eps"]
    rows = int(traffic.get("reference_rows", 2))
    seq = batches[0]["tokens"].shape[1]
    p = init(cfg, seed, seq)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    m, v = zeros(p), zeros(p)

    @jax.jit
    def block_grad(p, tokens, labels, n_total):
        f = lambda p: _nll_sum(cfg, p, tokens, labels, quant) / n_total
        return jax.value_and_grad(f)(p)

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(x))) for k, x in t.items()})

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def adam(p, g, m, v, t):
        # Moments and the step in f32; the new weight is stored in the
        # dtype the configuration keeps weights in, as the model states.
        c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g), v, g)
        p = {k: weights.round_to(p[k] - lr * (m[k] / c1) / (jnp.sqrt(v[k] / c2) + eps),
                                 stored_dtype(cfg, k), jnp) for k in p}
        return p, m, v

    out: Dict[str, Any] = {"losses": []}
    for step, batch in enumerate(batches, start=1):
        tok, lab = batch["tokens"], batch["label"]
        n_total = float(tok.size)
        loss, grads = 0.0, None
        for r in range(0, tok.shape[0], rows):
            l, g = block_grad(p, tok[r:r + rows], lab[r:r + rows], n_total)
            loss += float(l)
            grads = g if grads is None else add(grads, g)
        out["losses"].append(loss)
        if step == 1:
            out["grad_norms"] = {k: float(x) for k, x in jax.device_get(norms(grads)).items()}
        p, m, v = adam(p, grads, m, v, jnp.float32(step))
    del m, v, grads
    # The seeded weights are made again rather than kept beside the run.
    delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b), donate_argnums=0)(
        p, init(cfg, seed, seq))
    out["delta_norms"] = {k: float(x) for k, x in jax.device_get(norms(delta)).items()}
    return out


def served_gaps(cfg: Dict[str, Any], seed: int, max_seq: int,
                samples: List[Dict[str, Any]], quant: bool = False) -> Dict[str, Any]:
    """For each sample ``{"prompt", "tokens"}`` run the full forward once
    over prompt and served tokens and read, at every served position,
    how far the served token's logit lies below the reference's best.
    With ``quant`` the token read is the one the lower precision puts
    first at that position, not the served one (the control)."""
    p = init(cfg, seed, max_seq)
    fwd = jax.jit(lambda p, t, q: logits_fn(cfg, p, t, q), static_argnums=2)
    n = 0
    gaps: List[float] = []
    for s in samples:
        prompt = np.asarray(s["prompt"], np.int32)
        served = np.asarray(s["tokens"], np.int32)
        full = np.concatenate([prompt, served])[None, :-1]
        # Padded to the longest context so that one program serves every
        # sample; causal attention never looks at the padding.
        t = full.shape[1]
        full = np.pad(full, ((0, 0), (0, max_seq - t)))
        lg = fwd(p, full, False)[0, len(prompt) - 1:t]
        best = jnp.max(lg, axis=-1)
        if quant:
            read = jnp.argmax(fwd(p, full, True)[0, len(prompt) - 1:t], axis=-1)
        else:
            read = jnp.asarray(served)
        gap = np.asarray(best - jnp.take_along_axis(lg, read[:, None], axis=-1)[:, 0])
        gaps.extend(float(x) for x in gap)
        n += len(served)
    worst = max(gaps) if gaps else float("nan")
    return {"widest_gap": worst, "tokens": n,
            "mean_gap": float(np.mean(gaps)) if gaps else float("nan")}
