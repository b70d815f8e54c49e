"""Batch normalization, and RMS normalization.

Reference: ``src/ops/batch_norm.cu`` — cudnnBatchNormalizationForward
Training/Backward with per-shard running mean/var cached in
``BatchNormMeta`` (``model.h:428-436``).  Here batch statistics are
computed over (n, h, w); under a sharded batch XLA turns the mean/var
reductions into cross-replica psums automatically, which fixes a
subtle reference deficiency (per-shard-only statistics).  Running
stats live in the op state pytree and are updated functionally.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from flexflow_tpu.initializers import GlorotUniform, OnesInitializer, ZeroInitializer
from flexflow_tpu.ops.activations import apply_activation
from flexflow_tpu.ops.base import Op, ParamSpec, TensorSpec


class BatchNorm(Op):
    def __init__(
        self,
        name: str,
        x: TensorSpec,
        relu: bool = False,
        momentum: float = 0.9,
        eps: float = 1e-5,
    ):
        super().__init__(name, [x])
        assert x.ndim == 4
        self.attrs = dict(relu=relu, momentum=momentum, eps=eps)
        self.channels = x.shape[3]
        self._make_output(x.shape, x.dtype, ("n", "h", "w", "c"))

    def param_specs(self) -> Dict[str, ParamSpec]:
        c = self.channels
        dt = self.outputs[0].dtype
        return {
            "scale": ParamSpec((c,), dt, OnesInitializer(), ("c",)),
            "bias": ParamSpec((c,), dt, ZeroInitializer(), ("c",)),
        }

    def state_specs(self) -> Dict[str, ParamSpec]:
        c = self.channels
        dt = self.outputs[0].dtype
        return {
            "running_mean": ParamSpec((c,), dt, ZeroInitializer(), ("c",)),
            "running_var": ParamSpec((c,), dt, OnesInitializer(), ("c",)),
        }

    def forward(self, params, xs, state, training):
        (x,) = xs
        eps = self.attrs["eps"]
        if training:
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=(0, 1, 2))
            var = jnp.mean(jnp.square(xf), axis=(0, 1, 2)) - jnp.square(mean)
            m = self.attrs["momentum"]
            new_state = {
                "running_mean": (m * state["running_mean"] + (1 - m) * mean).astype(x.dtype),
                "running_var": (m * state["running_var"] + (1 - m) * var).astype(x.dtype),
            }
        else:
            mean = state["running_mean"].astype(jnp.float32)
            var = state["running_var"].astype(jnp.float32)
            new_state = state
        inv = jnp.reciprocal(jnp.sqrt(var + eps))
        y = (x.astype(jnp.float32) - mean) * inv * params["scale"].astype(
            jnp.float32
        ) + params["bias"].astype(jnp.float32)
        y = y.astype(x.dtype)
        if self.attrs["relu"]:
            y = apply_activation(y, "relu")
        return [y], new_state


class RMSNorm(Op):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last dim, in f32
    (no mean, no bias: the norm of the Llama/DeepSeek block family).

    ``gate_rank`` (r > 0) makes it a gated norm: with ``n`` the norm's
    output, ``n * sigmoid((n W_down) W_up)``, ``W_down`` (d, r) and
    ``W_up`` (r, d) with nothing between them; the two maps in the
    compute dtype with f32 accumulation, the sigmoid in f32, the product
    in the compute dtype.  Under the scope ``ff_gnorm``."""

    def __init__(self, name: str, x: TensorSpec, eps: float = 1e-6,
                 gate_rank: int = 0, kernel_initializer=None):
        super().__init__(name, [x])
        self.attrs = dict(eps=eps, gate_rank=int(gate_rank))
        self.kernel_initializer = kernel_initializer or GlorotUniform()
        self._make_output(x.shape, x.dtype, x.dim_axes)

    def param_specs(self) -> Dict[str, ParamSpec]:
        d, dt = self.inputs[0].shape[-1], self.outputs[0].dtype
        specs = {"scale": ParamSpec((d,), dt, OnesInitializer())}
        r = self.attrs["gate_rank"]
        if r:
            specs["w_down"] = ParamSpec((d, r), dt, self.kernel_initializer)
            specs["w_up"] = ParamSpec((r, d), dt, self.kernel_initializer)
        return specs

    def forward(self, params, xs, state, training):
        (x,) = xs
        n = rms_norm(x, params["scale"], self.attrs["eps"])
        if self.attrs["gate_rank"]:
            with jax.named_scope("ff_gnorm"):
                low = n @ params["w_down"]
                logits = jnp.dot(low, params["w_up"],
                                 preferred_element_type=jnp.float32)
                n = n * jax.nn.sigmoid(logits).astype(n.dtype)
        return [n], state


def rms_norm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (xf * inv * scale.astype(jnp.float32)).astype(x.dtype)
