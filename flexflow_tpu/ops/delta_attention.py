"""Kimi Delta Attention: the gated delta rule with a decay a key channel.

A linear-attention layer (Kimi Linear, arXiv:2510.26692) that keeps,
instead of a cache that grows with the sequence, one **recurrent state**
``S`` (d_k x d_v, float32) a head and the last ``conv_size - 1`` inputs
of its three short convolutions (the window ``ops/short_conv.py``'s two
helpers walk and hand on).  For a token with normed input ``u``:

    q, k, v = SiLU(conv(u W_q)), SiLU(conv(u W_k)), SiLU(conv(u W_v))
        (depthwise, causal over the last ``conv_size`` positions; per
        head q and k are L2-normalised, q scaled by d_k^-1/2)
    g = -exp(A_log[h]) * softplus(u W_f1 W_f2 + dt_bias)   (<= 0, a channel)
    beta = 2 sigmoid(u W_beta)                              (a head)
    S' = diag(exp(g)) S;  S = S' + beta k (v - S'^T k)^T;  o = S^T q
    y = (RMSNorm(o) * sigmoid(u W_g1 W_g2)) W_o

Three formulations of the same recurrence: :func:`kda_recurrence` (a
``lax.scan`` a token: the oracle, the differentiable path, and the path
where a gate refuses the shape), ``pallas_kernels.kda_chunk`` (a prefill
in chunks of 64: one kernel that reads q, k, v, g and beta where
``_qkv`` and ``_decay`` leave them and makes the chunk's decays, Gram
matrices and triangular solve beside the state in VMEM) and
``pallas_kernels.kda_decode`` (one token a slot, the state read and
written in place).

Strategy axes: ``c`` tags the head dimension of every parameter and of
the state, so a later sharded placement needs no new declaration; no
sharded path is built.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from flexflow_tpu.initializers import (
    GlorotUniform, OnesInitializer, UniformInitializer,
)
from flexflow_tpu.ops import pallas_kernels
from flexflow_tpu.ops.base import CacheEntry, Op, ParamSpec, TensorSpec
from flexflow_tpu.ops.short_conv import causal_taps, window_at

#: Tokens a segment of a long prefill: projections, convolutions and
#: the scan's operands exist for one segment at a time.
_SEGMENT = 2048


def kda_recurrence(q, k, v, g, beta, state):
    """The recurrence as written, a token at a time in f32: ``q``, ``k``,
    ``g`` (T, N, d_k), ``v`` (T, N, d_v), ``beta`` (T, N), ``state``
    (N, d_v, d_k) (``S`` transposed).  Returns ``(o (T, N, d_v), state)``."""
    f32 = jnp.float32

    def step(st, xs):
        q, k, v, g, b = xs
        st = st * jnp.exp(g)[:, None, :]
        err = b[:, None] * (v - jnp.einsum("nvk,nk->nv", st, k))
        st = st + err[:, :, None] * k[:, None, :]
        return st, jnp.einsum("nvk,nk->nv", st, q)

    state, o = lax.scan(
        step, state.astype(f32),
        tuple(x.astype(f32) for x in (q, k, v, g, beta)))
    return o, state


class KimiDeltaAttention(Op):
    """Gated delta-rule linear attention over (batch, seq, dim), causal
    by construction; see the module's text for the equations."""

    #: The state cannot live in a block pool, and has no rows to share
    #: or to roll back: the paged pool, the offset prefill and the
    #: speculative step refuse the op by name.
    cache_paged = False
    #: None = the kernels where the head widths allow them.
    decode_kernel: Optional[bool] = None

    def __init__(self, name: str, x: TensorSpec, num_heads: int,
                 head_dim: int, conv_size: int = 4,
                 gate_rank: Optional[int] = None, norm_eps: float = 1e-5,
                 neg_eigval: bool = True, kernel_initializer=None):
        super().__init__(name, [x])
        assert x.ndim == 3, f"attention input must be (batch, seq, dim), got {x.shape}"
        assert conv_size >= 2, conv_size
        self.attrs = dict(num_heads=num_heads, head_dim=int(head_dim),
                          conv_size=int(conv_size),
                          gate_rank=int(gate_rank or head_dim),
                          norm_eps=float(norm_eps), neg_eigval=bool(neg_eigval),
                          causal=True)
        self.kernel_initializer = kernel_initializer or GlorotUniform()
        self._make_output(x.shape, x.dtype, x.dim_axes)

    @property
    def width(self) -> int:
        return self.attrs["num_heads"] * self.attrs["head_dim"]

    def param_specs(self) -> Dict[str, ParamSpec]:
        a = self.attrs
        d, w, r = self.inputs[0].shape[-1], self.width, a["gate_rank"]
        dt, ki = self.outputs[0].dtype, self.kernel_initializer
        f32 = jnp.float32
        col, row = (None, "c"), ("c", None)
        conv = UniformInitializer(-0.5, 0.5)
        specs = {n: ParamSpec((d, w), dt, ki, col) for n in ("wq", "wk", "wv")}
        specs.update({f"conv_{n}": ParamSpec((a["conv_size"], w), dt, conv, col)
                      for n in "qkv"})
        specs.update({
            "w_f1": ParamSpec((d, r), dt, ki),
            "w_f2": ParamSpec((r, w), dt, ki, col),
            # Decay rates exp(A_log) in 1..16 a head and a softplus bias
            # whose steps lie in 0.001..0.1, held in f32 (Mamba-2's ranges,
            # which the public implementation takes over).
            "a_log": ParamSpec((a["num_heads"],), f32,
                               UniformInitializer(0.0, 2.7726), ("c",)),
            "dt_bias": ParamSpec((w,), f32, UniformInitializer(-6.9, -2.3), ("c",)),
            "w_beta": ParamSpec((d, a["num_heads"]), dt, ki, col),
            "w_g1": ParamSpec((d, r), dt, ki),
            "w_g2": ParamSpec((r, w), dt, ki, col),
            "o_norm": ParamSpec((a["head_dim"],), dt, OnesInitializer()),
            "wo": ParamSpec((w, d), dt, ki, row),
        })
        return specs

    # -- serving ---------------------------------------------------------------

    def cache_entries(self, max_seq: int) -> Dict[str, CacheEntry]:
        a = self.attrs
        hd = a["head_dim"]
        return {
            "state": CacheEntry((a["num_heads"], hd, hd), jnp.float32,
                                ("c", None, None), sequence=False),
            "conv": CacheEntry((a["conv_size"] - 1, 3 * self.width),
                               self.outputs[0].dtype, (None, "c"),
                               sequence=False),
        }

    def serving_path(self, decode: bool) -> str:
        return "delta_recurrent" if decode else "delta_chunked"

    def decode_fetch_block(self, slots, max_seq, kernel, c=1):
        return 0  # nothing of a sequence: the state is the whole read

    # -- pieces ----------------------------------------------------------------

    def _kernel(self) -> bool:
        hd = self.attrs["head_dim"]
        return self.decode_kernel is not False and \
            pallas_kernels.kda_supported(hd, hd)

    def _conv_weight(self, params):
        return jnp.concatenate(
            [params["conv_q"], params["conv_k"], params["conv_v"]], axis=1
        ).astype(jnp.float32)

    def _streams(self, params, x):
        """The three pre-convolution streams, side by side: (..., 3 w).
        Three products: a decode step's few rows read each weight once
        either way, and a concatenated weight would be made anew (201 MB
        at the published widths) every step of the scan."""
        return jnp.concatenate(
            [x @ params["wq"], x @ params["wk"], x @ params["wv"]], axis=-1)

    def _qkv(self, params, ext, t: int):
        """``ext`` (..., t + conv_size - 1, 3 w): ``t`` rows of the streams
        behind the window before them.  Returns q, k, v (..., t, H, hd)
        in f32: convolved, SiLU, q and k normalised a head, q scaled."""
        a = self.attrs
        y = causal_taps(ext, self._conv_weight(params), t)
        y = y * jax.nn.sigmoid(y)
        q, k, v = (s.reshape(s.shape[:-1] + (a["num_heads"], a["head_dim"]))
                   for s in jnp.split(y, 3, axis=-1))

        def unit(z):
            return z * lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)

        return unit(q) * a["head_dim"] ** -0.5, unit(k), v

    def _decay(self, params, x):
        """``(g (..., H, hd), beta (..., H))`` in f32."""
        a = self.attrs
        f = ((x @ params["w_f1"]) @ params["w_f2"]).astype(jnp.float32)
        g = jax.nn.softplus(f + params["dt_bias"]).reshape(
            f.shape[:-1] + (a["num_heads"], a["head_dim"]))
        g = -jnp.exp(params["a_log"])[:, None] * g
        beta = jax.nn.sigmoid((x @ params["w_beta"]).astype(jnp.float32))
        return g, beta * (2.0 if a["neg_eigval"] else 1.0)

    def _output(self, params, x, o):
        """``o`` (..., H, hd) f32 -> the layer's output (..., dim)."""
        a = self.attrs
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + a["norm_eps"])
        o = o * params["o_norm"].astype(jnp.float32)
        gate = jax.nn.sigmoid(
            ((x @ params["w_g1"]) @ params["w_g2"]).astype(jnp.float32))
        o = o.reshape(o.shape[:-2] + (self.width,)) * gate
        return o.astype(x.dtype) @ params["wo"]

    # -- a whole sequence ------------------------------------------------------

    def _sequence(self, params, x, st, tail, length, kernel: bool):
        """``x`` (b, t, dim) from the state ``st`` (b, H, hd, hd) and the
        window ``tail`` (b, conv_size - 1, 3 w).  Tokens at positions
        ``>= length`` (a scalar; None = all real) are pad: they get ``g =
        0`` and ``beta = 0``, which leaves the state as it is, and the
        window handed back is that of the last real rows.  Returns ``(y,
        st, window)``."""
        a = self.attrs
        b, t, _ = x.shape
        h, hd = a["num_heads"], a["head_dim"]
        length = jnp.int32(t) if length is None else length.astype(jnp.int32)
        chunk = pallas_kernels.KDA_CHUNK
        pad = -t % chunk if kernel else 0
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        tp = t + pad
        seg = tp
        if kernel and tp > _SEGMENT:
            seg = next(s for s in range(_SEGMENT, 0, -chunk) if tp % s == 0)
        scan_fn = pallas_kernels.kda_chunk if kernel else kda_recurrence

        def segment(carry, xs):
            st, tail, window = carry
            xseg, start = xs                                  # (b, seg, dim)
            ext = jnp.concatenate([tail, self._streams(params, xseg)], axis=1)
            q, k, v = self._qkv(params, ext, seg)
            g, beta = self._decay(params, xseg)
            live = (start + jnp.arange(seg) < length)[None, :, None]
            g = jnp.where(live[..., None], g, 0.0)
            beta = jnp.where(live, beta, 0.0)

            def fold(z):                                      # -> (seg, b*H, .)
                return jnp.moveaxis(z, 1, 0).reshape((seg, b * h) + z.shape[3:])

            o, st = scan_fn(fold(q), fold(k), fold(v), fold(g), fold(beta),
                            st.reshape(b * h, hd, hd))
            o = jnp.moveaxis(o.reshape(seg, b, h, hd), 0, 1)
            # The window of the last real rows, where they end here.
            window = window_at(ext, window, length - start, seg)
            return ((st.reshape(b, h, hd, hd), ext[:, seg:], window),
                    self._output(params, xseg, o))

        n = tp // seg
        xs = (jnp.moveaxis(x.reshape(b, n, seg, x.shape[-1]), 1, 0),
              jnp.arange(n, dtype=jnp.int32) * seg)
        carry = (st.astype(jnp.float32), tail, tail)
        if n == 1:
            (st, _, window), y = segment(carry, (xs[0][0], xs[1][0]))
        else:
            (st, _, window), ys = lax.scan(segment, carry, xs)
            y = jnp.moveaxis(ys, 0, 1).reshape(b, tp, -1)
        return y[:, :t], st, window

    def forward(self, params, xs, state, training):
        (x,) = xs
        if "cache_state" in state:
            return self._forward_cached(params, x, state)
        a = self.attrs
        b = x.shape[0]
        st = jnp.zeros((b, a["num_heads"], a["head_dim"], a["head_dim"]),
                       jnp.float32)
        tail = jnp.zeros((b, a["conv_size"] - 1, 3 * self.width), x.dtype)
        y, _, _ = self._sequence(params, x, st, tail, None, kernel=False)
        return [y], state

    # -- the recurrent state (runtime/serving.py) -------------------------------

    def _forward_cached(self, params, x, state):
        """Prefill (t > 1): the sequence from an empty state, ending at
        ``state["length"]`` (the prompt's length inside its padded
        bucket); the state and the window of the last real rows go to
        the cache.  Decode (t == 1): one token a slot from the cached
        state and window."""
        if "block_table" in state or "chunk" in state:
            raise NotImplementedError(
                f"{self.name}: a recurrent state has no paged pool or "
                f"offset prefill (ROADMAP Queue B)")
        st, conv = state["cache_state"], state["cache_conv"]
        b, t, _ = x.shape
        new_state = dict(state)
        if t > 1:
            y, st, conv = self._sequence(
                params, x, jnp.zeros_like(st), jnp.zeros_like(conv),
                state.get("length"), kernel=self._kernel())
            new_state["cache_state"] = st
            new_state["cache_conv"] = conv.astype(state["cache_conv"].dtype)
            return [y], new_state
        ext = jnp.concatenate([conv, self._streams(params, x)], axis=1)
        q, k, v = (z[:, 0] for z in self._qkv(params, ext, 1))   # (b, H, hd)
        g, beta = (z[:, 0] for z in self._decay(params, x))
        if self._kernel():
            o, st = pallas_kernels.kda_decode(q, k, v, g, beta, st)
        else:
            fold = lambda z: z.reshape((1, -1) + z.shape[2:])
            o, st = kda_recurrence(fold(q), fold(k), fold(v), fold(g),
                                   fold(beta), st.reshape((-1,) + st.shape[2:]))
            o, st = o.reshape(v.shape), st.reshape(state["cache_state"].shape)
        new_state["cache_state"] = st
        new_state["cache_conv"] = ext[:, 1:]
        return [self._output(params, x, o[:, None])], new_state

