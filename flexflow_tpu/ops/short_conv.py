"""The gated short convolution, and the window every short convolution keeps.

A mixer with no cache that grows with the sequence (LFM2, Liquid AI:
``transformers``' ``Lfm2ShortConv``).  For a token with normed input
``a`` and a depthwise causal filter ``w`` of ``L`` taps:

    [B | C | z] = a W_in                  (three parts of the width)
    u = B * z
    c_t = sum_{j < L} w_j * u_{t - (L - 1) + j}       (u zero before 0)
    y = (C * c) W_out

No activation inside, no bias.  What a slot keeps while serving is the
last ``L - 1`` rows of ``u``: its **window**.

The window is not this op's alone: ``KimiDeltaAttention`` carries one for
its three streams (``ops/delta_attention.py``).  The two pieces both ops
need are here once: :func:`causal_taps`, the depthwise product of ``t``
rows behind their window, and :func:`window_at`, the window a padded
prefill hands on, which ends at the prompt's true length and not at the
bucket's end.

Strategy axes: ``c`` tags the channel dimension of the taps, of ``W_in``'s
columns and of the window, so a later sharded placement needs no new
declaration; no sharded path is built.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from flexflow_tpu.initializers import GlorotUniform, UniformInitializer
from flexflow_tpu.ops.base import CacheEntry, Op, ParamSpec, TensorSpec


def causal_taps(ext, taps, t: int):
    """``ext`` (..., t + L - 1, w): ``t`` rows behind the ``L - 1`` rows
    before them; ``taps`` (L, w) float32.  Row ``r`` of the result
    (..., t, w), float32, is ``sum_j taps[j] * ext[r + j]``: the
    depthwise causal product, the newest row under the last tap."""
    ext = ext.astype(jnp.float32)
    return sum(ext[..., j:j + t, :] * taps[j] for j in range(taps.shape[0]))


def window_at(ext, window, at, seg: int):
    """The window after the last real row, where that row lies here.
    ``ext`` (b, seg + kc, w) holds ``seg`` rows behind the ``kc`` before
    them, and the sequence's real rows end ``at`` rows into these
    ``seg`` (a traced scalar: a prompt's length less the segment's
    start).  Inside (``0 < at <= seg``) the ``kc`` rows that end there
    are the window; else ``window`` (b, kc, w) stays what it was."""
    kc = window.shape[1]
    cand = lax.dynamic_slice_in_dim(ext, jnp.clip(at, 0, seg), kc, axis=1)
    return jnp.where((at > 0) & (at <= seg), cand, window)


class GatedShortConv(Op):
    """The gated short convolution over (batch, seq, dim), causal by
    construction; see the module's text for the equations."""

    #: A window has no rows to page, share or roll back: the paged pool,
    #: a shared prefix, the offset prefill and the speculative step refuse
    #: the op by name (``ServingExecutor.stateful_ops``).
    cache_paged = False

    def __init__(self, name: str, x: TensorSpec, kernel_size: int = 3,
                 kernel_initializer=None):
        super().__init__(name, [x])
        assert x.ndim == 3, f"short_conv input must be (batch, seq, dim), got {x.shape}"
        assert kernel_size >= 2, kernel_size
        self.attrs = dict(kernel_size=int(kernel_size), causal=True)
        self.kernel_initializer = kernel_initializer or GlorotUniform()
        self._make_output(x.shape, x.dtype, x.dim_axes)

    def param_specs(self) -> Dict[str, ParamSpec]:
        d, dt, ki = self.inputs[0].shape[-1], self.outputs[0].dtype, \
            self.kernel_initializer
        return {
            "w_in": ParamSpec((d, 3 * d), dt, ki, (None, "c")),
            "conv": ParamSpec((self.attrs["kernel_size"], d), dt,
                              UniformInitializer(-0.5, 0.5), (None, "c")),
            "w_out": ParamSpec((d, d), dt, ki, ("c", None)),
        }

    # -- serving ---------------------------------------------------------------

    def cache_entries(self, max_seq: int) -> Dict[str, CacheEntry]:
        d = self.inputs[0].shape[-1]
        return {"conv": CacheEntry((self.attrs["kernel_size"] - 1, d),
                                   self.outputs[0].dtype, (None, "c"),
                                   sequence=False)}

    def serving_path(self, decode: bool) -> str:
        return "short_conv"

    def decode_fetch_block(self, slots, max_seq, kernel, c=1):
        return 0  # nothing of a sequence: the window is the whole read

    # -- execution -------------------------------------------------------------

    def forward(self, params, xs, state, training):
        (x,) = xs
        cached = "cache_conv" in state
        if cached and ("block_table" in state or "chunk" in state):
            raise NotImplementedError(
                f"{self.name}: a convolution window has no paged pool or "
                f"offset prefill (ROADMAP B-M)")
        b, t, d = x.shape
        gate_in, gate_out, z = jnp.split(x @ params["w_in"], 3, axis=-1)
        u = gate_in * z
        decode = cached and t == 1
        before = state["cache_conv"] if decode else \
            jnp.zeros((b, self.attrs["kernel_size"] - 1, d), u.dtype)
        ext = jnp.concatenate([before, u], axis=1)
        c = causal_taps(ext, params["conv"].astype(jnp.float32), t)
        y = (gate_out * c.astype(x.dtype)) @ params["w_out"]
        if not cached:
            return [y], state
        new_state = dict(state)
        with jax.named_scope("ff_conv_state"):
            if decode:
                window = ext[:, 1:]
            else:
                # A prefill inside its padded bucket: the rows that end at
                # the prompt's length, not at the bucket's end.
                length = state.get("length")
                at = jnp.int32(t) if length is None else length.astype(jnp.int32)
                window = window_at(ext, before, at, t)
            new_state["cache_conv"] = window.astype(state["cache_conv"].dtype)
        return [y], new_state
