"""Operator base classes.

The reference gives every op one Legion task family (init/fwd/bwd) and
makes each op own its output region + partitions (reference:
``include/model.h:141-156``, pattern described at ``src/ops/*.cu``).
Here an op is a pure-function node in the graph: it declares its
parameters (shape/dtype/initializer/sharding axes), infers its output
specs, and implements ``forward`` in jax.  Backward is jax autodiff —
there are no hand-written bwd tasks; XLA emits the transposed kernels
the reference wrote by hand (e.g. ``linear.cu:388-488``).

Semantic sharding axes: each tensor dim is tagged 'n' (sample), 'c'
(channel/feature), 'h', 'w', 's' (sequence) or None; the mesh plan
maps tags to mesh axes per the op's ParallelConfig (see
parallel/mesh.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from flexflow_tpu.initializers import Initializer


@dataclasses.dataclass
class ParamSpec:
    shape: Tuple[int, ...]
    dtype: Any
    initializer: Initializer
    # Semantic axis per dim, for sharded parameters (TP linear kernels,
    # table-parallel embeddings).  None => replicated dim.
    dim_axes: Tuple[Optional[str], ...] = ()

    def __post_init__(self):
        if not self.dim_axes:
            self.dim_axes = tuple(None for _ in self.shape)


@dataclasses.dataclass(frozen=True)
class CacheEntry:
    """One array an attention-like op keeps for a slot while serving
    (``Op.cache_entries``): the serving executor allocates ``(slots,) +
    shape`` of ``dtype`` for it (SERVING.md "Cache layout") and hands it
    to ``forward`` as ``state["cache_<entry>"]``.  Where the sequence
    axis lies in ``shape`` is the op's business (keys and values are
    ``(max_seq, heads, d_head)``; the latent cache puts the sequence
    last, the order the chip stores a 576-wide row in anyway).  ``axes``
    tags the dims for sharded decode ('c' = heads).  ``sequence`` is
    false for an entry with no sequence axis at all (a recurrent state,
    a convolution window): its bytes do not grow with ``max_seq``, and
    the op that declares it is told a prefill's true ``length``."""

    shape: Tuple[int, ...]
    dtype: Any
    axes: Tuple[Optional[str], ...] = ()
    sequence: bool = True

    def __post_init__(self):
        if not self.axes:
            object.__setattr__(self, "axes", tuple(None for _ in self.shape))


#: Serving counters (``Op.serving_stats``) that say the worst of what was
#: seen: folded over layers and steps by their largest, where every other
#: counter is folded by its mean.
SERVING_STATS_LARGEST = frozenset({"hc_defect"})


@dataclasses.dataclass
class TensorSpec:
    """Symbolic tensor in the op graph (the reference's ``Tensor`` /
    LogicalRegion handle, ``include/model.h:141-156``).  4-D activations
    are NHWC — the TPU-native layout (the reference is NCHW; the lane
    dimension on TPU wants channels last)."""

    name: str
    shape: Tuple[int, ...]
    dtype: Any
    dim_axes: Tuple[Optional[str], ...]
    producer: Optional["Op"] = None

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def __repr__(self):
        return f"TensorSpec({self.name}, {self.shape}, {self.dtype}, axes={self.dim_axes})"


class Op:
    """Graph node: owns name, inputs, outputs, params."""

    #: Set True for ops producing a scalar loss contribution + metrics.
    is_loss = False
    #: Loss-contributing ops are normally exempt from per-layer remat
    #: (terminal losses are cheap); heavy non-terminal loss ops (MoE's
    #: aux-loss byproduct) opt back in with True.
    allow_remat = False

    def __init__(self, name: str, inputs: Sequence[TensorSpec]):
        self.name = name
        self.inputs: List[TensorSpec] = list(inputs)
        self.outputs: List[TensorSpec] = []

    # -- static structure -------------------------------------------------

    def param_specs(self) -> Dict[str, ParamSpec]:
        return {}

    #: Leaves of OTHER ops this op reads, under its own keys: ``{key:
    #: (op name, that op's key)}`` (a head tied to the token table).  Such
    #: a leaf exists once, in its owner's subtree; whoever walks the graph
    #: hands ``forward`` the op's view through :func:`op_params`.
    tied: Dict[str, Tuple[str, str]] = {}

    def state_specs(self) -> Dict[str, ParamSpec]:
        """Non-trained mutable state (e.g. batchnorm running stats)."""
        return {}

    # -- serving ----------------------------------------------------------

    #: Whether the op's cache entries can live in the paged block pool
    #: (``kv_block > 0``); an op that cannot is refused there by name.
    cache_paged = False
    #: Ops that take a forward-only kernel path, or report counters,
    #: when serving get ``state["serving"] = True`` from the serving
    #: executor (training and eval never set it).
    serving_aware = False
    #: Names of the scalar counters a serving-aware op hands back as
    #: ``new_state["stats"]`` when serving (empty = none): the serving
    #: programs stack them beside the tokens, and ``Server.run`` writes
    #: them on the step's event.
    serving_stats: Tuple[str, ...] = ()

    def cache_entries(self, max_seq: int) -> Dict[str, "CacheEntry"]:
        """What the op keeps for a slot of ``max_seq`` positions while
        serving: entry name -> array (empty = the op holds no cache).
        The serving executor holds and carries whatever is declared,
        by name."""
        return {}

    #: Positions of a slot's past a decode step reads at most (an
    #: attention window), None = every live one.
    decode_window: Optional[int] = None

    def decode_fetch_block(self, slots: int, max_seq: int,
                           kernel: Optional[bool], c: int = 1) -> int:
        """Positions of a slot's padded cache one decode step fetches
        at a time on a device holding ``slots`` slots and a ``c``-th of
        the heads: a kernel's block where the op decodes through one
        that fetches live blocks only (``kernel`` as ``decode_kernel``:
        None = where supported), else the whole cache; 0 for an op whose
        entries have no sequence axis.  What
        ``decode_superstep.kv_rows_fetched`` rounds lengths up to."""
        return max_seq

    # -- mesh binding -----------------------------------------------------

    def bind_mesh(self, plan, pc) -> None:
        """Called by the executor before tracing ``forward`` with the
        MeshPlan and this op's ParallelConfig.  Most ops ignore it —
        GSPMD places them from sharding constraints alone.  Ops that
        need *explicit* collectives (pipelined sequence-parallel scans,
        ring attention) stash the mesh axes here and issue
        ``shard_map``/``ppermute`` themselves — the analogue of the
        reference ops that talk to the mapper directly
        (``RnnMapper::assign_to_gpu``, ``rnn_mapper.cc:131-135``)."""
        self._plan = plan
        self._pc = pc

    # -- sparse-gradient protocol -----------------------------------------
    #
    # Embedding-style ops (output == gathered rows, up to a linear
    # aggregation) opt in by returning their table keys from
    # ``sparse_keys``.  The executor then differentiates w.r.t. the
    # GATHERED ROWS instead of the table and applies the row cotangent
    # with a scatter-add — donation makes the table update in place, so
    # neither a table-sized gradient nor a table-sized copy ever
    # materializes.  This is the TPU-native answer to the reference's
    # atomicAdd scatter backward (``embedding.cu:128-158``) *and* to
    # its skip-the-embedding-update hack (``model.cc:566-574``): the
    # update is exact plain-SGD, just row-sparse.

    def sparse_keys(self) -> Tuple[str, ...]:
        """Param keys eligible for row-sparse updates ('' = none)."""
        return ()

    def sparse_ok(self, plan, pc) -> bool:
        """Whether the sparse path is valid under this placement."""
        return True

    def sparse_rows(self, params, xs):
        """Gather: params + graph inputs -> rows pytree (small)."""
        raise NotImplementedError

    def sparse_forward(self, rows, xs, state, training):
        """Forward given pre-gathered rows; must not touch the table."""
        raise NotImplementedError

    def sparse_apply(self, params, xs, row_grads, lr):
        """Scatter row cotangents: p.at[ids].add(-lr * g)."""
        raise NotImplementedError

    def sparse_flat_ids(self, params, xs):
        """Row ids of every gathered row into the ``(R, D)`` flat view
        of the (single) sparse table — ``table.reshape(-1, last_dim)``.
        Shape matches ``row_grads[..., 0]``.  Lets the executor compute
        duplicate-id row sums generically (exact global-norm clipping;
        unique-row lazy momentum/Adam updates)."""
        raise NotImplementedError

    # -- execution --------------------------------------------------------

    def forward(
        self,
        params: Dict[str, jax.Array],
        xs: Sequence[jax.Array],
        state: Dict[str, jax.Array],
        training: bool,
    ):
        """Returns (ys: list of arrays, new_state dict).

        Loss ops instead return ((loss_scalar, metrics_dict), new_state).
        """
        raise NotImplementedError

    def _make_output(self, shape, dtype, dim_axes, idx: int = 0) -> TensorSpec:
        t = TensorSpec(
            name=f"{self.name}:out{idx}" if idx else f"{self.name}:out",
            shape=tuple(shape),
            dtype=dtype,
            dim_axes=tuple(dim_axes),
            producer=self,
        )
        self.outputs.append(t)
        return t


def op_params(op: Op, params: Dict[str, Dict[str, Any]]) -> Dict[str, Any]:
    """What ``op.forward`` reads of the parameter tree ``{op: {key:
    leaf}}``: the op's own subtree and, under the op's keys, the leaves
    it is tied to (``Op.tied``)."""
    own = params.get(op.name, {})
    if not op.tied:
        return own
    missing = [owner for owner, _ in op.tied.values() if owner not in params]
    if missing:
        raise KeyError(
            f"{op.name}: tied to {missing}, whose leaves are not in this "
            f"parameter tree (a pipeline stage holds its own ops' alone)")
    return {**own, **{k: params[owner][key]
                      for k, (owner, key) in op.tied.items()}}
