"""Structural tensor operators (concat, reshape).

Reference: ``src/ops/concat.cu`` — strided-copy kernels over an n-D
task grid (``concat.cu:194-215`` fwd, bwd splits back).  Here concat is
``jnp.concatenate`` (XLA fuses the copies); the backward split is its
autodiff transpose.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from flexflow_tpu.ops.base import Op, TensorSpec


class Concat(Op):
    def __init__(self, name: str, inputs: Sequence[TensorSpec], axis: int):
        super().__init__(name, inputs)
        ndim = inputs[0].ndim
        if axis < 0:
            axis += ndim
        self.axis = axis
        for t in inputs:
            assert t.ndim == ndim
            for d in range(ndim):
                if d != axis:
                    assert t.shape[d] == inputs[0].shape[d], (
                        f"concat {name}: mismatched dim {d}: "
                        f"{t.shape} vs {inputs[0].shape}"
                    )
        out_shape = list(inputs[0].shape)
        out_shape[axis] = sum(t.shape[axis] for t in inputs)
        # The concatenated dim inherits no sharding tag (safe under
        # unequal part sizes); other dims keep the first input's tags.
        dim_axes = list(inputs[0].dim_axes)
        dim_axes[axis] = None
        self._make_output(tuple(out_shape), inputs[0].dtype, tuple(dim_axes))

    def forward(self, params, xs, state, training):
        return [jnp.concatenate(list(xs), axis=self.axis)], state


class Add(Op):
    """Elementwise sum (residual connections in transformer blocks)."""

    def __init__(self, name: str, a: TensorSpec, b: TensorSpec):
        super().__init__(name, [a, b])
        assert a.shape == b.shape, (a.shape, b.shape)
        assert a.dtype == b.dtype, (a.dtype, b.dtype)
        self._make_output(a.shape, a.dtype, a.dim_axes)

    def forward(self, params, xs, state, training):
        a, b = xs
        return [a + b], state


class Multiply(Op):
    """Elementwise product (the gate of a gated MLP)."""

    def __init__(self, name: str, a: TensorSpec, b: TensorSpec):
        super().__init__(name, [a, b])
        assert a.shape == b.shape, (a.shape, b.shape)
        assert a.dtype == b.dtype, (a.dtype, b.dtype)
        self._make_output(a.shape, a.dtype, a.dim_axes)

    def forward(self, params, xs, state, training):
        a, b = xs
        return [a * b], state


class Reshape(Op):
    """Free-form reshape; batch dim must be preserved."""

    def __init__(self, name: str, x: TensorSpec, shape: Sequence[int],
                 dim_axes: Optional[Sequence[Optional[str]]] = None):
        super().__init__(name, [x])
        shape = tuple(shape)
        assert shape[0] == x.shape[0], "reshape must preserve the batch dim"
        import numpy as np
        assert int(np.prod(shape)) == int(np.prod(x.shape))
        if dim_axes is None:
            dim_axes = ("n",) + tuple(None for _ in shape[1:])
        self._make_output(shape, x.dtype, tuple(dim_axes))

    def forward(self, params, xs, state, training):
        (x,) = xs
        return [x.reshape(self.outputs[0].shape)], state


class DotInteraction(Op):
    """DLRM pairwise-dot feature interaction.

    The reference ships only the concat interaction and leaves dot as a
    TODO (``examples/DLRM/dlrm.cc:49-65`` "TODO: implement dot
    attention"); this op completes the --arch-interaction-op surface.
    Inputs: dense features (batch, d) and stacked embeddings
    (batch, T, d).  Output: dense features concatenated with the
    strictly-lower-triangular pairwise dot products of the T+1 feature
    vectors — (batch, d + (T+1)T/2), the standard DLRM formulation.
    One batched (T+1, d)x(d, T+1) matmul per sample on the MXU.
    """

    def __init__(self, name: str, dense: TensorSpec, sparse: TensorSpec):
        super().__init__(name, [dense, sparse])
        assert dense.ndim == 2 and sparse.ndim == 3, (dense.shape, sparse.shape)
        assert dense.shape[0] == sparse.shape[0]
        assert dense.shape[1] == sparse.shape[2], (
            f"{name}: dense dim {dense.shape[1]} != feature dim {sparse.shape[2]}"
        )
        b, t, d = sparse.shape
        f = t + 1
        out_dim = d + (f * (f - 1)) // 2
        self._make_output((b, out_dim), dense.dtype, ("n", None))

    def forward(self, params, xs, state, training):
        dense, sparse = xs
        feats = jnp.concatenate([dense[:, None, :], sparse], axis=1)  # (b,F,d)
        dots = jnp.einsum("bfd,bgd->bfg", feats, feats)  # (b,F,F)
        f = feats.shape[1]
        li, lj = jnp.tril_indices(f, k=-1)
        pairs = dots[:, li, lj]  # (b, F(F-1)/2)
        return [jnp.concatenate([dense, pairs.astype(dense.dtype)], axis=1)], state


class Dropout(Op):
    """Inverted dropout with a deterministic state-threaded RNG.

    The reference applies dropout through the cuDNN RNN descriptor in
    the NMT LSTM stack (rate 0.2, ``nmt/lstm.cu:152-174``) with cuDNN
    managing the random states; here the op owns its PRNG key as op
    STATE (like batchnorm's running stats), splitting it each training
    step — so masks are reproducible from the seed, advance with the
    step chain, and are identical under every sharding (threefry is
    counter-based: the DP=strategy numerics invariant holds).  Eval
    and rate 0 are the identity.
    """

    def __init__(self, name: str, x: TensorSpec, rate: float):
        super().__init__(name, [x])
        if not 0.0 <= rate < 1.0:  # also rejects nan
            raise ValueError(
                f"dropout {name}: rate must be in [0, 1), got {rate}"
            )
        self.attrs = dict(rate=rate)
        self._make_output(x.shape, x.dtype, x.dim_axes)

    def state_specs(self):
        from flexflow_tpu.initializers import RngKeyInitializer
        from flexflow_tpu.ops.base import ParamSpec

        return {"rng": ParamSpec((2,), jnp.uint32, RngKeyInitializer())}

    def forward(self, params, xs, state, training):
        (x,) = xs
        rate = self.attrs["rate"]
        if not training or rate == 0.0:
            return [x], state
        new_key, sub = jax.random.split(state["rng"])
        keep = jax.random.bernoulli(sub, 1.0 - rate, x.shape)
        y = jnp.where(keep, x / (1.0 - rate), 0).astype(x.dtype)
        return [y], {"rng": new_key}
