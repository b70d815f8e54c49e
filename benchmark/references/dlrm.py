"""DLRM in plain float32 ``jax.numpy``: forward, loss, gradients, SGD.

Follows the reference's ``examples/DLRM/dlrm.cc``: a bottom MLP over the
dense features (ReLU after every layer), one embedding row a table a
sample, the interaction by concatenation, a top MLP (ReLU, and a sigmoid
after its last layer), mean squared error.  Weights are uniform with the
reference's standard deviations (``sqrt(2/(in+out))`` for a kernel,
``sqrt(2/out)`` for a bias, ``U(+-1/sqrt(rows))`` for a table), drawn by
``benchmark/weights.py``; the one bias under the sigmoid is held away
from zero (``leaf_spec``).

The tables are never held whole: the reference makes only the rows the
followed batches name, as a compact table, and trains on that.  Plain
SGD with no momentum and no decay leaves every other row as the seed
made it.  Nothing of the program is imported.  Matrix products run at
``highest`` precision; ``quant`` (the control) keeps every weight, row
and activation in bfloat16, the nearest precision below the float32 the
configuration states.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

_SQ3 = math.sqrt(3.0)


def _mlps(cfg):
    return (("bot", cfg["mlp_bot"]), ("top", cfg["mlp_top"]))


def leaf_spec(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    """``"op/key" -> (shape, half_width, offset)`` of every leaf."""
    spec = {}
    for tag, ln in _mlps(cfg):
        for i in range(len(ln) - 1):
            spec[f"{tag}_linear{i}/kernel"] = (
                (ln[i + 1], ln[i]), math.sqrt(2.0 / (ln[i] + ln[i + 1])) * _SQ3, 0.0)
            sd = math.sqrt(2.0 / ln[i + 1])
            if tag == "top" and i == len(ln) - 2:
                # The bias under the sigmoid sets every prediction at
                # seeded weights (the rest of the last layer adds +-0.16).
                # Drawn around 0 it puts one seed in twenty at 0.5, where
                # the gradient over random labels cancels to round-off and
                # no comparison is conditioned; so it is held to sd * [0.75, 1.25].
                spec[f"{tag}_linear{i}/bias"] = ((ln[i + 1],), sd / 4.0, sd)
            else:
                spec[f"{tag}_linear{i}/bias"] = ((ln[i + 1],), sd * _SQ3, 0.0)
    t, rows, d = cfg["num_tables"], cfg["rows_per_table"], cfg["sparse_feature_size"]
    spec["embeddings/tables"] = ((t, rows, d), 1.0 / math.sqrt(rows), 0.0)
    return spec


def _forward(cfg, p, table, dense, idx, dt):
    """``idx (b, tables)`` indexes the compact ``table``."""
    prec = "highest" if dt == jnp.float32 else None

    def mlp(tag, ln, x, last_sigmoid):
        for i in range(len(ln) - 1):
            x = jnp.matmul(x, p[f"{tag}_linear{i}/kernel"].T, precision=prec) \
                + p[f"{tag}_linear{i}/bias"]
            if last_sigmoid and i == len(ln) - 2:
                x = 1.0 / (1.0 + jnp.exp(-x))
            else:
                x = jnp.maximum(x, 0)
        return x

    x = mlp("bot", cfg["mlp_bot"], dense.astype(dt), False)
    emb = table[idx].reshape(idx.shape[0], -1)
    z = jnp.concatenate([x, emb], axis=1)
    return mlp("top", cfg["mlp_top"], z, True)


def train(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int,
          batches: List[Dict[str, np.ndarray]], quant: bool = False) -> Dict[str, Any]:
    """Follow ``len(batches)`` SGD steps from the seed; see
    ``references/gpt2.py::train`` for what comes back."""
    opt = traffic["optimizer"]
    assert opt["name"] == "sgd" and not opt.get("momentum") and not opt.get("wd"), opt
    lr = opt["lr"]
    dt = jnp.bfloat16 if quant else jnp.float32
    spec = leaf_spec(cfg)
    rows_n, d = cfg["rows_per_table"], cfg["sparse_feature_size"]
    # The rows the batches name, as flat (table * rows + id), once each.
    flat = [b["sparse_input"].astype(np.int64)
            + np.arange(cfg["num_tables"], dtype=np.int64)[None, :] * rows_n for b in batches]
    uniq = np.unique(np.concatenate([f.ravel() for f in flat]))
    # The compact table is padded to the most rows the batches could name
    # (copies of the last row, which nothing indexes and no gradient
    # reaches), so that its shape, and with it every program below, is
    # the same for every seed and compiles once.
    rows = np.concatenate([uniq, np.full(sum(f.size for f in flat) - uniq.size, uniq[-1])])
    shape, hw, off = spec["embeddings/tables"]
    p0 = {"embeddings/tables": weights.leaf_rows(seed, "embeddings/tables", rows, d, hw, off, np)}
    for name, (shape, hw, off) in spec.items():
        if name != "embeddings/tables":
            p0[name] = weights.leaf_values(seed, name, shape, hw, off, np)
    p0 = jax.jit(lambda t: {k: v.astype(dt) for k, v in t.items()})(p0)

    def loss_fn(p, dense, idx, label):
        q = {k: v for k, v in p.items() if k != "embeddings/tables"}
        pred = _forward(cfg, q, p["embeddings/tables"], dense, idx, dt)
        return jnp.mean(jnp.square(pred.astype(jnp.float32) - label))

    step_fn = jax.jit(jax.value_and_grad(loss_fn))
    sgd = jax.jit(lambda p, g: jax.tree.map(lambda p, g: (p - lr * g).astype(p.dtype), p, g))
    norms_j = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                                 for k, x in t.items()})
    norms = lambda t: {k: float(x) for k, x in jax.device_get(norms_j(t)).items()}
    out: Dict[str, Any] = {"losses": []}
    p = p0
    for step, (b, f) in enumerate(zip(batches, flat), start=1):
        idx = jnp.asarray(np.searchsorted(uniq, f).astype(np.int32))
        loss, g = step_fn(p, jnp.asarray(b["dense_input"]), idx, jnp.asarray(b["label"]))
        out["losses"].append(float(loss))
        if step == 1:
            out["grad_norms"] = norms(g)
        p = sgd(p, g)
    out["delta_norms"] = norms(jax.jit(lambda p, p0: jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))(p, p0))
    return out
