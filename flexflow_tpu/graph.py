"""The FFModel graph-builder API.

Mirrors the reference's ``FFModel`` (``include/model.h:197-307``): apps
call ``conv2d/dense/embedding/...`` to append ops to ``self.layers``
(each ctor in the reference creates regions/partitions and no compute —
here each builder infers shapes and no compute), then hand the model to
the runtime (``flexflow_tpu/runtime``) which compiles the whole graph +
strategy into one jitted train step — the TPU equivalent of the
reference's per-op Legion index launches wrapped in a captured trace
(``dlrm.cc:151-156``).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp

from flexflow_tpu.config import FFConfig
from flexflow_tpu.ops import (
    LSTM,
    Add,
    BatchNorm,
    Concat,
    DotInteraction,
    Dropout,
    Conv2D,
    Embedding,
    Flat,
    GatedShortConv,
    HeteroEmbedding,
    HyperConnectionPost,
    HyperConnectionPre,
    KimiDeltaAttention,
    LatentAttention,
    LayerNorm,
    Linear,
    MixtureOfExperts,
    MSELoss,
    MultiEmbedding,
    MultiHeadAttention,
    Multiply,
    Op,
    Pool2D,
    PositionEmbedding,
    Reshape,
    RMSNorm,
    SoftmaxCrossEntropy,
    TensorSpec,
    WordEmbedding,
)


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        self.config = config or FFConfig()
        self.layers: List[Op] = []
        self.input_tensors: List[TensorSpec] = []
        self._name_counts: Dict[str, int] = {}

    # -- naming -----------------------------------------------------------

    def _unique(self, base: str, name: Optional[str]) -> str:
        existing = {op.name for op in self.layers} | {t.name for t in self.input_tensors}
        if name is not None:
            assert name not in existing, f"duplicate op name {name!r}"
            return name
        while True:
            i = self._name_counts.get(base, 0)
            self._name_counts[base] = i + 1
            candidate = f"{base}{i}"
            if candidate not in existing:
                return candidate

    def _add(self, op: Op) -> TensorSpec:
        self.layers.append(op)
        return op.outputs[0]

    # -- inputs -----------------------------------------------------------

    def create_tensor(
        self,
        shape: Sequence[int],
        dtype=None,
        name: Optional[str] = None,
        dim_axes: Optional[Sequence[Optional[str]]] = None,
    ) -> TensorSpec:
        """Declare an input placeholder (reference:
        ``create_tensor<NDIM>`` ``model.cc:213-280``).  4-D shapes are
        NHWC.  Default sharding tags: batch on dim 0, and NHWC tags for
        4-D tensors.  Default dtype is ``config.compute_dtype``."""
        if dtype is None:
            dtype = jnp.dtype(self.config.compute_dtype)
        shape = tuple(shape)
        if dim_axes is None:
            if len(shape) == 4:
                dim_axes = ("n", "h", "w", "c")
            else:
                dim_axes = ("n",) + tuple(None for _ in shape[1:])
        t = TensorSpec(
            name=self._unique("input", name),
            shape=shape,
            dtype=dtype,
            dim_axes=tuple(dim_axes),
            producer=None,
        )
        self.input_tensors.append(t)
        return t

    # -- op builders (reference: model.h:197-307) --------------------------

    def conv2d(
        self,
        x: TensorSpec,
        out_channels: int,
        kernel_h: int,
        kernel_w: int,
        stride_h: int,
        stride_w: int,
        padding_h: int,
        padding_w: int,
        activation: Optional[str] = None,
        use_bias: bool = True,
        name: Optional[str] = None,
        **kw,
    ) -> TensorSpec:
        return self._add(
            Conv2D(
                self._unique("conv2d", name), x, out_channels,
                kernel_h, kernel_w, stride_h, stride_w, padding_h, padding_w,
                activation=activation, use_bias=use_bias, **kw,
            )
        )

    def pool2d(
        self,
        x: TensorSpec,
        kernel_h: int,
        kernel_w: int,
        stride_h: int,
        stride_w: int,
        padding_h: int,
        padding_w: int,
        pool_type: str = "max",
        activation: Optional[str] = None,
        name: Optional[str] = None,
    ) -> TensorSpec:
        return self._add(
            Pool2D(
                self._unique("pool2d", name), x,
                kernel_h, kernel_w, stride_h, stride_w, padding_h, padding_w,
                pool_type=pool_type, activation=activation,
            )
        )

    def batch_norm(self, x: TensorSpec, relu: bool = False, name: Optional[str] = None) -> TensorSpec:
        return self._add(BatchNorm(self._unique("batchnorm", name), x, relu=relu))

    def dense(
        self,
        x: TensorSpec,
        out_dim: int,
        activation: Optional[str] = None,
        use_bias: bool = True,
        name: Optional[str] = None,
        **kw,
    ) -> TensorSpec:
        """``tied_to`` names an embedding op built before this one whose
        ``table`` is this op's kernel (a tied head): one leaf, that op's."""
        tied_to = kw.get("tied_to")
        if tied_to is not None:
            table = self.find_op(tied_to).param_specs().get("table")
            if table is None or tuple(table.shape) != (out_dim, x.shape[-1]):
                raise ValueError(
                    f"dense {name!r}: tied_to={tied_to!r} needs that op's "
                    f"table to be ({out_dim}, {x.shape[-1]}), got "
                    f"{None if table is None else tuple(table.shape)}")
        return self._add(
            Linear(self._unique("dense", name), x, out_dim,
                   activation=activation, use_bias=use_bias, **kw)
        )

    # The reference calls this ``linear`` in places; keep an alias.
    linear = dense

    def embedding(
        self,
        x: TensorSpec,
        num_entries: int,
        out_dim: int,
        aggr: str = "sum",
        name: Optional[str] = None,
        **kw,
    ) -> TensorSpec:
        self._embedding_dtypes(kw)
        # --shard-embeddings: flip the table to its row-range-sharded
        # layout (vocab over c).  Multi/Hetero embeddings are already
        # leading-dim 'c'-tagged, so only the single-table ops switch.
        kw.setdefault("shard_rows", self.config.shard_embeddings)
        return self._add(
            Embedding(self._unique("embedding", name), x, num_entries, out_dim,
                      aggr=aggr, **kw)
        )

    def _embedding_dtypes(self, kw) -> None:
        """Dtype policy for the embedding family: activations follow
        ``compute_dtype``; the TABLE stays f32 while sparse updates are
        enabled (the row-DMA kernels are f32-only — Mosaic cannot prove
        dynamic one-row slices aligned on packed bf16 sublanes) and
        lookups are gather-bound, so a low-precision table would buy
        nothing while knocking big-table training onto the full-sweep
        XLA scatter."""
        out = jnp.dtype(self.config.compute_dtype)
        kw.setdefault("out_dtype", out)
        kw.setdefault(
            "dtype",
            jnp.float32 if self.config.sparse_embedding_updates else out,
        )

    def multi_embedding(
        self,
        x: TensorSpec,
        num_tables: int,
        num_entries: int,
        out_dim: int,
        name: Optional[str] = None,
        **kw,
    ) -> TensorSpec:
        self._embedding_dtypes(kw)
        return self._add(
            MultiEmbedding(self._unique("embeddings", name), x, num_tables,
                           num_entries, out_dim, **kw)
        )

    def hetero_embedding(
        self,
        x: TensorSpec,
        vocab_sizes,
        out_dim: int,
        name: Optional[str] = None,
        **kw,
    ) -> TensorSpec:
        """T different-vocab tables, row-concatenated and row-range
        sharded (heterogeneous table parallelism; reference:
        ``dlrm.cc:230-330`` + ``dlrm_strategy.cc:5-36``)."""
        self._embedding_dtypes(kw)
        return self._add(
            HeteroEmbedding(self._unique("embeddings", name), x, vocab_sizes,
                            out_dim, **kw)
        )

    def word_embedding(
        self,
        x: TensorSpec,
        num_entries: int,
        out_dim: int,
        name: Optional[str] = None,
        **kw,
    ) -> TensorSpec:
        """Token embedding (batch, seq) -> (batch, seq, dim) (reference:
        the NMT embed op, ``nmt/embed.cu``)."""
        self._embedding_dtypes(kw)
        kw.setdefault("shard_rows", self.config.shard_embeddings)
        return self._add(
            WordEmbedding(self._unique("word_embedding", name), x, num_entries,
                          out_dim, **kw)
        )

    def lstm(
        self,
        x: TensorSpec,
        hidden_size: int,
        initial_state=None,
        name: Optional[str] = None,
        **kw,
    ):
        """LSTM over (batch, seq, features); returns (y, hT, cT)
        (reference: the NMT LSTM op family, ``nmt/lstm.cu``; sequence
        chunking + pipelining is the 's' strategy axis — see
        ``ops/rnn.py``)."""
        op = LSTM(self._unique("lstm", name), x, hidden_size,
                  initial_state=initial_state, **kw)
        self.layers.append(op)
        return op.outputs[0], op.outputs[1], op.outputs[2]

    def multihead_attention(
        self,
        x: TensorSpec,
        num_heads: int,
        causal: bool = True,
        name: Optional[str] = None,
        **kw,
    ) -> TensorSpec:
        """Self-attention; under an 's' strategy degree this runs ring
        attention over the mesh (see ``ops/attention.py``)."""
        return self._add(
            MultiHeadAttention(self._unique("attention", name), x, num_heads,
                               causal=causal, **kw)
        )

    def moe(
        self,
        x: TensorSpec,
        num_experts: int,
        ffn_dim: int,
        capacity_factor: float = 1.25,
        name: Optional[str] = None,
        **kw,
    ) -> TensorSpec:
        """Mixture-of-experts FFN (``top_k=1`` switch routing, the
        default; ``top_k=2`` GShard top-2 with renormalized gates;
        ``dispatch="sorted"`` the dropless grouped-product formulation
        with its routers, gated and shared experts and
        ``held_experts``); a
        'c' strategy degree shards experts across the mesh (the
        reference's per-table expert placement, ``dlrm_strategy.cc:5-36``,
        generalized — see ``ops/moe.py``)."""
        return self._add(
            MixtureOfExperts(self._unique("moe", name), x, num_experts,
                             ffn_dim, capacity_factor=capacity_factor, **kw)
        )

    def latent_attention(self, x: TensorSpec, num_heads: int,
                         name: Optional[str] = None, **kw) -> TensorSpec:
        """Causal multi-head latent attention (``ops/attention.py``
        ``LatentAttention``: ``kv_rank``, ``nope_dim``, ``rope_dim``,
        ``v_dim``, ``rope_theta``; ``q_rank`` for a compressed query,
        ``rope_scaling`` for YaRN's frequencies)."""
        return self._add(
            LatentAttention(self._unique("latent_attention", name), x,
                            num_heads, **kw)
        )

    def delta_attention(self, x: TensorSpec, num_heads: int, head_dim: int,
                        name: Optional[str] = None, **kw) -> TensorSpec:
        """Gated delta-rule linear attention (``ops/delta_attention.py``
        ``KimiDeltaAttention``: ``conv_size``, ``gate_rank``,
        ``norm_eps``, ``neg_eigval``)."""
        return self._add(
            KimiDeltaAttention(self._unique("delta_attention", name), x,
                               num_heads, head_dim, **kw)
        )

    def short_conv(self, x: TensorSpec, kernel_size: int = 3,
                   name: Optional[str] = None, **kw) -> TensorSpec:
        """Gated short convolution (``ops/short_conv.py``
        ``GatedShortConv``: a depthwise causal filter of ``kernel_size``
        taps between two gates)."""
        return self._add(
            GatedShortConv(self._unique("short_conv", name), x,
                           kernel_size=kernel_size, **kw)
        )

    def hyper_connection_pre(self, x: TensorSpec, streams: int,
                             name: Optional[str] = None, **kw) -> TensorSpec:
        """What a sublayer reads of an ``streams``-stream residual
        (``ops/hyper_connection.py`` ``HyperConnectionPre``: ``iters``,
        ``eps``, ``clamp``); a (batch, seq, dim) ``x`` opens the stream."""
        return self._add(
            HyperConnectionPre(self._unique("hc_pre", name), x, streams, **kw)
        )

    def hyper_connection_post(self, x: TensorSpec, y: TensorSpec, streams: int,
                              name: Optional[str] = None, **kw) -> TensorSpec:
        """The stream ``x`` after a sublayer's output ``y`` is written
        back (``HyperConnectionPost``); ``close=True`` sums the streams
        into (batch, seq, dim)."""
        return self._add(
            HyperConnectionPost(self._unique("hc_post", name), x, y, streams,
                                **kw)
        )

    def rms_norm(self, x: TensorSpec, name: Optional[str] = None, **kw) -> TensorSpec:
        return self._add(RMSNorm(self._unique("rmsnorm", name), x, **kw))

    def multiply(self, a: TensorSpec, b: TensorSpec, name: Optional[str] = None) -> TensorSpec:
        return self._add(Multiply(self._unique("multiply", name), a, b))

    def layer_norm(self, x: TensorSpec, name: Optional[str] = None, **kw) -> TensorSpec:
        return self._add(LayerNorm(self._unique("layernorm", name), x, **kw))

    def position_embedding(self, x: TensorSpec, name: Optional[str] = None, **kw) -> TensorSpec:
        return self._add(PositionEmbedding(self._unique("pos_embedding", name), x, **kw))

    def add(self, a: TensorSpec, b: TensorSpec, name: Optional[str] = None) -> TensorSpec:
        return self._add(Add(self._unique("add", name), a, b))

    def dropout(self, x: TensorSpec, rate: float, name: Optional[str] = None) -> TensorSpec:
        """Inverted dropout (reference: cuDNN RNN dropout in the NMT
        LSTM, ``nmt/lstm.cu:152-174``); identity at eval/rate 0."""
        return self._add(Dropout(self._unique("dropout", name), x, rate))

    def concat(self, inputs: Sequence[TensorSpec], axis: int, name: Optional[str] = None) -> TensorSpec:
        return self._add(Concat(self._unique("concat", name), inputs, axis))

    def flat(self, x: TensorSpec, name: Optional[str] = None) -> TensorSpec:
        return self._add(Flat(self._unique("flat", name), x))

    def dot_interaction(self, dense: TensorSpec, sparse: TensorSpec,
                        name: Optional[str] = None) -> TensorSpec:
        """DLRM pairwise-dot interaction (completes the reference's
        --arch-interaction-op TODO, ``dlrm.cc:49-65``)."""
        return self._add(DotInteraction(self._unique("interact", name), dense, sparse))

    def reshape(self, x: TensorSpec, shape: Sequence[int], name: Optional[str] = None) -> TensorSpec:
        return self._add(Reshape(self._unique("reshape", name), x, shape))

    def softmax(self, logits: TensorSpec, labels: TensorSpec,
                label_smoothing: float = 0.0,
                name: Optional[str] = None) -> TensorSpec:
        """Fused softmax + cross-entropy loss (reference: softmax op is
        fused with the loss, ``src/ops/softmax.cu:91-160``);
        ``label_smoothing`` mixes in the uniform distribution."""
        return self._add(SoftmaxCrossEntropy(
            self._unique("softmax", name), logits, labels,
            label_smoothing=label_smoothing,
        ))

    def mse_loss(self, pred: TensorSpec, label: TensorSpec, reduction: str = "mean",
                 name: Optional[str] = None) -> TensorSpec:
        return self._add(MSELoss(self._unique("mseloss", name), pred, label, reduction))

    # -- introspection ----------------------------------------------------

    @property
    def loss_ops(self) -> List[Op]:
        return [op for op in self.layers if op.is_loss]

    def find_op(self, name: str) -> Op:
        for op in self.layers:
            if op.name == name:
                return op
        raise KeyError(name)

    def summary(self) -> str:
        lines = []
        for t in self.input_tensors:
            lines.append(f"input   {t.name:24s} {t.shape}")
        for op in self.layers:
            outs = ", ".join(str(o.shape) for o in op.outputs)
            lines.append(f"{type(op).__name__:8s}{op.name:24s} -> {outs}")
        return "\n".join(lines)
