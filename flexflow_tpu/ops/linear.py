"""Fully-connected (dense) operator.

Reference: ``src/ops/linear.cu`` — a 2-D ``(c_out, n)`` task grid (TP×DP),
kernel stored out-dim-major, input broadcast to c-shards via an aliased
partition (``linear.cu:100-138``) and replica input-grads reduced by a
second backward task (``linear.cu:494-520``).  On TPU the whole dance is
one ``dot_general``: sharding the kernel's out-dim over the ``c`` mesh
axes makes XLA all-gather the input and reduce-scatter/psum the input
gradient — the ``backward2`` Saxpy tree for free.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from flexflow_tpu.initializers import GlorotUniform, ZeroInitializer
from flexflow_tpu.ops.activations import apply_activation, check_activation
from flexflow_tpu.ops.base import Op, ParamSpec, TensorSpec


class Linear(Op):
    def __init__(
        self,
        name: str,
        x: TensorSpec,
        out_dim: int,
        activation: Optional[str] = None,
        use_bias: bool = True,
        kernel_initializer=None,
        bias_initializer=None,
        tied_to: Optional[str] = None,
    ):
        """``tied_to`` (an embedding op's name): the kernel is that op's
        ``table`` (``(out_dim, in_dim)``, the layout this op keeps its own
        in), read and never owned: a tied head.  The op then declares no
        kernel, and the leaf exists once in the parameter tree."""
        super().__init__(name, [x])
        assert x.ndim >= 2, f"linear input must be (batch, ..., features), got {x.shape}"
        check_activation(activation)
        cin = x.shape[-1]
        self.in_dim = cin
        self.attrs = dict(out_dim=out_dim, activation=activation, use_bias=use_bias)
        if tied_to is not None:
            self.tied = {"kernel": (tied_to, "table")}
        self.kernel_initializer = kernel_initializer or GlorotUniform()
        self.bias_initializer = bias_initializer or ZeroInitializer()
        # ND inputs (e.g. (batch, seq, features) in the NMT vocab
        # projection, ``nmt/linear.cu``) contract the last dim only.
        self._make_output(
            x.shape[:-1] + (out_dim,), x.dtype, x.dim_axes[:-1] + ("c",)
        )

    def param_specs(self) -> Dict[str, ParamSpec]:
        out_dim = self.attrs["out_dim"]
        # Kernel is (out, in) — out-dim-major like the reference
        # (``linear.cu`` stores the kernel transposed) — and sharded on
        # its out-dim under a c-split.
        specs = {} if self.tied else {
            "kernel": ParamSpec(
                (out_dim, self.in_dim),
                self.outputs[0].dtype,
                self.kernel_initializer,
                ("c", None),
            )
        }
        if self.attrs["use_bias"]:
            specs["bias"] = ParamSpec(
                (out_dim,), self.outputs[0].dtype, self.bias_initializer, ("c",)
            )
        return specs

    def forward(self, params, xs, state, training):
        (x,) = xs
        plan = getattr(self, "_plan", None)
        if plan is not None and plan.assign(self._pc).get("c"):
            if self.tied:
                raise NotImplementedError(
                    f"{self.name}: a kernel tied to {self.tied['kernel'][0]!r} "
                    f"lies as that op holds it; no 'c' split of a tied head "
                    f"is built (ROADMAP B-M)")
            # Pin the input REPLICATED along its contraction dim before
            # the dot.  Under a c-split the input arrives feature-
            # sharded, and GSPMD then has two algebraically-equal
            # lowerings: all-gather + full-K dot (this op's documented
            # design, the reference's aliased input partition,
            # ``linear.cu:100-138``) or partial-K dot + all-reduce.
            # Its cost model picks PER MESH LAYOUT — measured: the
            # compiled-pipeline mesh flipped to partial-K while the
            # stand-alone submesh gathers, a 1-ulp gradient drift that
            # breaks the compiled-pipeline bit-identity gate.  The
            # constraint removes the partial-K option, making Linear's
            # reduction order mesh-invariant.
            spec = plan.spec(
                self._pc,
                tuple(self.inputs[0].dim_axes[:-1]) + (None,),
                x.shape,
            )
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(plan.mesh, spec)
            )
        # bf16 operands accumulate in f32 on the MXU by default.
        kernel = params["kernel"]
        if self.tied:
            # The table keeps its owner's dtype (f32 under the embedding
            # family's policy); the product runs in the activations'.
            kernel = kernel.astype(x.dtype)
        y = jnp.dot(x, kernel.T)
        if self.attrs["use_bias"]:
            y = y + params["bias"]
        return [apply_activation(y, self.attrs["activation"])], state
