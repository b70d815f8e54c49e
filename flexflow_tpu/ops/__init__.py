from flexflow_tpu.ops.attention import (
    LatentAttention,
    LayerNorm,
    MultiHeadAttention,
    PositionEmbedding,
)
from flexflow_tpu.ops.base import CacheEntry, Op, ParamSpec, TensorSpec, op_params
from flexflow_tpu.ops.conv import Conv2D, Flat, Pool2D
from flexflow_tpu.ops.delta_attention import KimiDeltaAttention
from flexflow_tpu.ops.hyper_connection import HyperConnectionPost, HyperConnectionPre
from flexflow_tpu.ops.embedding import Embedding, HeteroEmbedding, MultiEmbedding, WordEmbedding
from flexflow_tpu.ops.linear import Linear
from flexflow_tpu.ops.losses import MSELoss, SoftmaxCrossEntropy
from flexflow_tpu.ops.moe import MixtureOfExperts
from flexflow_tpu.ops.norm import BatchNorm, RMSNorm
from flexflow_tpu.ops.rnn import LSTM
from flexflow_tpu.ops.short_conv import GatedShortConv
from flexflow_tpu.ops.tensor_ops import (
    Add,
    Concat,
    DotInteraction,
    Dropout,
    Multiply,
    Reshape,
)

__all__ = [
    "CacheEntry",
    "Op",
    "ParamSpec",
    "TensorSpec",
    "op_params",
    "Conv2D",
    "Pool2D",
    "Flat",
    "BatchNorm",
    "Linear",
    "Embedding",
    "HeteroEmbedding",
    "MultiEmbedding",
    "WordEmbedding",
    "LSTM",
    "Add",
    "Concat",
    "DotInteraction",
    "Dropout",
    "GatedShortConv",
    "HyperConnectionPost",
    "HyperConnectionPre",
    "KimiDeltaAttention",
    "LatentAttention",
    "LayerNorm",
    "MixtureOfExperts",
    "MultiHeadAttention",
    "PositionEmbedding",
    "Reshape",
    "RMSNorm",
    "Multiply",
    "SoftmaxCrossEntropy",
    "MSELoss",
]
