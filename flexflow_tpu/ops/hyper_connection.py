"""Manifold-constrained hyper-connections: the residual path as ``n``
streams (DeepSeek's mHC, the ``hc_*`` / ``mhc_*`` keys of a model's
configuration).

Where a pre-norm block adds a sublayer's output to one residual vector,
this family carries ``X`` of ``n`` streams of width ``C`` a token, and
each sublayer ``F`` has a pair of ops round it:

- :class:`HyperConnectionPre` reads a learned, token-dependent mixture
  of the streams, ``u = H_pre X`` (what the sublayer's norm then sees);
- :class:`HyperConnectionPost` writes back, ``X' = H_res X + H_post^T
  F(norm(u))``, through a token-dependent ``n x n`` matrix made doubly
  stochastic by Sinkhorn rounds.

All three coefficient sets come from the token's own stream: ``x~ =
RMSNorm(vec(X))`` over all ``n C`` values, ``H~ = alpha (x~ phi) + b``,
``H_pre = sigmoid(H~_pre)``, ``H_post = 2 sigmoid(H~_post)``, ``H_res =
Sinkhorn(exp(clip(H~_res)))``.  The coefficient chain runs in float32;
the streams stay in the activation dtype.  Neither op keeps anything for
a slot while serving (the streams are activations, not state), so
prefill and decode run the same ``forward``.

The chain is written tokens-last (a coefficient is one array over the
tokens, ``n + n + n^2`` of them) so that the forty normalisations of a
Sinkhorn run are elementwise work on whole registers and fuse into one
loop; laid out ``(tokens, n, n)`` each would be a reduction over a
4-wide axis padded to a register tile.

Strategy axes: a norm's (``n`` the batch, ``s`` the sequence; the
streams and the width are not split).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.initializers import Initializer, NormInitializer, ZeroInitializer
from flexflow_tpu.ops.base import Op, ParamSpec, TensorSpec

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class _Constant(Initializer):
    """A fixed array (broadcast to the leaf's shape)."""

    value: object

    def __call__(self, key, shape, dtype):
        return jnp.broadcast_to(jnp.asarray(self.value, dtype), tuple(shape))


def _open(x, streams: int):
    """The stream of a token: ``x`` itself, or its ``streams`` copies
    where ``x`` is still the table's one row (the first block)."""
    if x.ndim == 4:
        return x
    b, t, c = x.shape
    return jnp.broadcast_to(x[:, :, None, :], (b, t, streams, c))


def _projected(x, phis: Sequence, eps: float):
    """``[(x~ phi)^T for phi in phis]``, each ``(k, b, t)`` float32 with
    the tokens last, for ``x`` (b, t, n, C): ``x~`` is ``x`` over its
    root mean square across all ``n C`` values (one scalar a token, so
    it is applied to the product and ``x~`` is never held)."""
    b, t, n, c = x.shape
    xf = x.astype(jnp.float32).reshape(b, t, n * c)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1) + eps)
    return [jnp.einsum("btc,ck->kbt", xf, phi, precision=_HIGHEST) * inv
            for phi in phis]


@functools.partial(jax.jit, static_argnames=("iters", "eps"))
def sinkhorn(m, iters: int, eps: float):
    """``iters`` rounds on positive matrices ``m`` (n, n, ...): every
    row over its sum, then every column over its sum, ``eps`` added to
    each divisor.  Plain adds and multiplies of the ``n^2`` entries' own
    arrays, no reduction: XLA makes some 46 loops of the twenty rounds
    on the chip where the reductions of the array form make 81 (and one
    reciprocal a sum, not a divide an entry: the CPU's compiler takes 16
    s over the chain of divides).  Jitted, so that the 1,500 operations
    are traced and lowered once a shape and not once an op."""
    n = m.shape[0]
    rows = [[m[i, j] for j in range(n)] for i in range(n)]
    for _ in range(iters):
        for i in range(n):
            s = 1.0 / (_total(rows[i]) + eps)
            rows[i] = [v * s for v in rows[i]]
        for j in range(n):
            s = 1.0 / (_total([rows[i][j] for i in range(n)]) + eps)
            for i in range(n):
                rows[i][j] = rows[i][j] * s
    return jnp.stack([jnp.stack(r) for r in rows])


def _total(vals):
    out = vals[0]
    for v in vals[1:]:
        out = out + v
    return out


def stochastic_defect(m):
    """How far ``m`` (n, n, ...) is from doubly stochastic: the largest
    ``|row sum - 1|`` or ``|column sum - 1|``, a scalar."""
    return jnp.maximum(jnp.max(jnp.abs(jnp.sum(m, axis=1) - 1.0)),
                       jnp.max(jnp.abs(jnp.sum(m, axis=0) - 1.0)))


class _HyperConnection(Op):
    """What both halves share: the stream's shape and the configuration's
    constants."""

    def __init__(self, name: str, inputs, streams: int, iters: int = 20,
                 eps: float = 1e-6, clamp: Tuple[float, float] = (-30.0, 30.0)):
        super().__init__(name, inputs)
        x = inputs[0]
        assert x.ndim in (3, 4), \
            f"{name}: a stream is (batch, seq, [streams,] dim), got {x.shape}"
        if x.ndim == 4 and x.shape[2] != streams:
            raise ValueError(
                f"{name}: the input carries {x.shape[2]} streams, not {streams}")
        self.attrs = dict(streams=int(streams), iters=int(iters),
                          eps=float(eps), clamp=(float(clamp[0]), float(clamp[1])))
        self.width = x.shape[-1]

    def _phi(self, k: int) -> ParamSpec:
        """A projection of the whole stream onto ``k`` coefficients, at
        the spread that gives ``x~ phi`` a unit one."""
        nc = self.attrs["streams"] * self.width
        return ParamSpec((nc, k), jnp.float32,
                         NormInitializer(0.0, 1.0 / math.sqrt(nc)))


class HyperConnectionPre(_HyperConnection):
    """``u = H_pre X``: (batch, seq, n, C) -> (batch, seq, C), ``H_pre =
    sigmoid(alpha (x~ phi) + bias)``.  A (batch, seq, C) input is the
    table's row, read as ``n`` equal streams.  ``bias`` starts where
    ``H_pre`` is ``1/n`` (the streams' mean) and ``alpha`` small, so that
    an untrained op is a plain residual's read."""

    def __init__(self, name: str, x: TensorSpec, streams: int, **kw):
        super().__init__(name, [x], streams, **kw)
        self._make_output(x.shape[:2] + (self.width,), x.dtype,
                          x.dim_axes[:2] + (None,))

    def param_specs(self) -> Dict[str, ParamSpec]:
        n = self.attrs["streams"]
        return {
            "phi": self._phi(n),
            "alpha": ParamSpec((1,), jnp.float32, _Constant(0.01)),
            "bias": ParamSpec((n,), jnp.float32, _Constant(-math.log(n - 1.0))),
        }

    def forward(self, params, xs, state, training):
        a = self.attrs
        x = _open(xs[0], a["streams"])
        (z,) = _projected(x, [params["phi"]], a["eps"])
        h = jax.nn.sigmoid(params["alpha"][0] * z
                           + params["bias"][:, None, None])          # (n, b, t)
        u = _total([h[j][..., None] * x[:, :, j].astype(jnp.float32)
                    for j in range(a["streams"])])
        return [u.astype(x.dtype)], state


class HyperConnectionPost(_HyperConnection):
    """``X' = H_res X + H_post^T y``: the stream (batch, seq, n, C) and a
    sublayer's output ``y`` (batch, seq, C) -> the stream; with ``close``
    the new streams are summed into (batch, seq, C) (the last block, in
    front of the final norm).  ``H_post = 2 sigmoid(alpha[0] (x~
    phi_post) + b_post)``, ``H_res = Sinkhorn(exp(clip(alpha[1] mat(x~
    phi_res) + b_res)))``, ``mat`` row-major.  The biases start where
    ``H_post`` is 1 and ``H_res`` all but the identity.

    Serving, it reports ``hc_defect``: the largest ``|row sum - 1|`` or
    ``|column sum - 1|`` of any token's ``H_res`` in the forward."""

    serving_aware = True
    serving_stats = ("hc_defect",)

    def __init__(self, name: str, x: TensorSpec, y: TensorSpec, streams: int,
                 close: bool = False, **kw):
        super().__init__(name, [x, y], streams, **kw)
        assert y.shape == x.shape[:2] + (self.width,), (x.shape, y.shape)
        self.close = bool(close)
        lead, axes = x.shape[:2], x.dim_axes[:2]
        if self.close:
            self._make_output(lead + (self.width,), x.dtype, axes + (None,))
        else:
            self._make_output(lead + (self.attrs["streams"], self.width),
                              x.dtype, axes + (None, None))

    def param_specs(self) -> Dict[str, ParamSpec]:
        n = self.attrs["streams"]
        return {
            "phi_post": self._phi(n),
            "phi_res": self._phi(n * n),
            "alpha": ParamSpec((2,), jnp.float32, _Constant(0.01)),
            "b_post": ParamSpec((n,), jnp.float32, ZeroInitializer()),
            "b_res": ParamSpec((n, n), jnp.float32,
                               _Constant(8.0 * (np.eye(n) - 1.0))),
        }

    def forward(self, params, xs, state, training):
        a = self.attrs
        n = a["streams"]
        x, y = _open(xs[0], n), xs[1].astype(jnp.float32)
        z_post, z_res = _projected(
            x, [params["phi_post"], params["phi_res"]], a["eps"])
        h_post = 2.0 * jax.nn.sigmoid(params["alpha"][0] * z_post
                                      + params["b_post"][:, None, None])
        logits = params["alpha"][1] * z_res.reshape((n, n) + z_res.shape[1:]) \
            + params["b_res"][:, :, None, None]
        h_res = sinkhorn(jnp.exp(jnp.clip(logits, *a["clamp"])),
                         a["iters"], a["eps"])                       # (n, n, b, t)
        xf = [x[:, :, j].astype(jnp.float32) for j in range(n)]
        new = [_total([h_res[i, j][..., None] * xf[j] for j in range(n)])
               + h_post[i][..., None] * y for i in range(n)]
        out = _total(new) if self.close else jnp.stack(new, axis=2)
        if not state.get("serving"):
            return [out.astype(x.dtype)], state
        new_state = dict(state)
        new_state["stats"] = {"hc_defect": stochastic_defect(h_res)}
        return [out.astype(x.dtype)], new_state
