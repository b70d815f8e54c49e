"""Activation fusion helper.

The reference fuses activations into conv/linear leaf tasks via cuDNN
activation descriptors (``conv_2d.cu:524-537``, ``linear.cu:271-333``);
here they are plain jnp ops and XLA fuses them into the preceding
matmul/conv — no descriptor plumbing needed.
"""

from __future__ import annotations

import jax.numpy as jnp

VALID_ACTIVATIONS = (None, "none", "relu", "sigmoid", "tanh", "gelu", "silu")


def check_activation(activation) -> None:
    """Validate at graph-build time (op ctor), not first trace."""
    if activation not in VALID_ACTIVATIONS:
        raise ValueError(
            f"unknown activation {activation!r}; valid: {VALID_ACTIVATIONS}"
        )


def apply_activation(x, activation):
    if activation is None or activation == "none":
        return x
    if activation == "relu":
        return jnp.maximum(x, 0)
    if activation == "sigmoid":
        return jnp.reciprocal(1 + jnp.exp(-x))
    if activation == "tanh":
        return jnp.tanh(x)
    if activation == "gelu":
        import jax

        return jax.nn.gelu(x)
    if activation == "silu":
        import jax

        return jax.nn.silu(x)
    raise ValueError(f"unknown activation {activation!r}")
