"""Embedding operators.

Reference: ``src/ops/embedding.cu`` — custom gather fwd / atomicAdd
scatter bwd kernels (``embedding.cu:128-158``) over a sample-dim-only
task grid, with *table* parallelism done purely by mapper placement
(DLRM pins each table to one GPU, ``dlrm_strategy.cc:11-19``).

TPU-native design: the gather is ``jnp.take``; the scatter-add gradient
is XLA's gather transpose (deterministic, no atomics).  Table/expert
parallelism is first-class via :class:`MultiEmbedding`, which stacks
all tables into one (T, vocab, dim) parameter sharded T-ways on the
``c`` axis — the GSPMD equivalent of per-table placement, with the
all-to-all the mapper's copies implied now emitted by XLA.  The
executor's sparse protocol (``sparse_rows`` / ``sparse_apply``, outside
autodiff) reaches the rows with the Pallas row kernels instead where
``_row_addressing`` finds a form for the array a device holds: the
whole table on one chip, its shard inside the row-sharded
``shard_map``, always in the shape and order it is stored.

Row sharding (SHARDING.md "Sharded embedding tables"): any table whose
LEADING param dim is tagged ``c`` (``MultiEmbedding``'s stacked T dim,
``HeteroEmbedding``'s row-concat dim, ``Embedding``/``WordEmbedding``
under ``shard_rows=True`` / ``--shard-embeddings``) is range-sharded
over the mesh c group — per-device HBM holds ``rows/c`` of it, the
capacity move past a replicated table that exceeds
``FF_DEVICE_MEM_BYTES``.  The lookup then runs as an explicit
``shard_map``: the OWNING shard resolves each id
(``id // rows_per_shard`` routing as a masked, clipped local take) and
a ``psum`` over the c group assembles full rows — never a full-table
all-gather (fflint FFH001 checks the compiled HLO for exactly that).
Its transpose is a LOCAL masked scatter-add into the owning shard
(the reference's atomicAdd backward, ``embedding.cu:128-158``, without
atomics and without any collective), so the row-sparse update path
composes with sharding unchanged.  Both directions are value-exact vs
the replicated forms: the psum adds structural zeros and the local
scatter applies the same per-occurrence adds in the same order.  A
stacked ``MultiEmbedding`` under the sparse protocol needs neither mask
nor psum: column ``t`` of its ids addresses table ``t``, so the ids
split over ``c`` with the tables and each shard resolves its own
columns (``by_table`` below).
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from flexflow_tpu.initializers import NormInitializer
from flexflow_tpu.ops.base import Op, ParamSpec, TensorSpec


def _row_sharding(op: Op, key: str):
    """``(c_axes, c_deg, local_rows)`` when ``op``'s param ``key`` is
    row-RANGE sharded over the mesh c group, else None.

    Row-sharded means: the param's LEADING dim is tagged ``c``, the
    bound strategy gives the op a c degree > 1, and the leading extent
    divides evenly (GSPMD would pad otherwise and the range routing
    would misattribute rows).  ``local_rows`` is in FLAT rows — the
    ``(prod(shape[:-1]), D)`` view all the id/scatter math uses, so a
    ``MultiEmbedding``'s per-shard T/c tables are ``(T/c)*V`` flat
    rows."""
    spec = op.param_specs().get(key)
    if spec is None or not spec.dim_axes or spec.dim_axes[0] != "c":
        return None
    plan = getattr(op, "_plan", None)
    pc = getattr(op, "_pc", None)
    if plan is None or pc is None:
        return None
    (c_axes, c_deg), = plan.local_degrees(pc, "c")
    if c_deg <= 1 or not c_axes:
        return None
    if spec.shape[0] % c_deg:
        return None
    nrows = 1
    for s in spec.shape[:-1]:
        nrows *= int(s)
    return c_axes, c_deg, nrows // c_deg


def _note_shard_event(op: Op, event: str, **fields) -> None:
    """One build-time telemetry counter per (op, event): the sharded
    gather/combine programs announce themselves when first traced —
    host-side only, nothing lands in the jitted program."""
    noted = getattr(op, "_shard_events", None)
    if noted is None:
        noted = op._shard_events = set()
    if event in noted:
        return
    noted.add(event)
    from flexflow_tpu.runtime import telemetry as _telemetry

    _telemetry.current().emit(event, op=op.name, **fields)


def _shard_offset(plan, c_axes, local_rows):
    """First flat row owned by this shard: the linearized c-group
    coordinate times the shard extent (the ``id // rows_per_shard``
    routing, solved from the owning side)."""
    import jax

    k = 0
    for ax in c_axes:
        k = k * plan.mesh.shape[ax] + jax.lax.axis_index(ax)
    return k * local_rows


def _table_spec(c_axes, ndim):
    from jax.sharding import PartitionSpec

    return PartitionSpec(c_axes, *(None,) * (ndim - 1))


def _local_addressing(op: Op, table, n_ids: int, c_deg: int, kind: str) -> str:
    """``_row_addressing`` of one shard of ``table`` (leading dim over
    ``c_deg`` devices) for the ``n_ids`` ids a device resolves there."""
    local_shape = (table.shape[0] // c_deg,) + tuple(table.shape[1:])
    return _row_addressing(op, n_ids, local_shape, table.dtype, kind,
                           in_shard_map=True)


def _take_rows(tbl, loc, how: str):
    """Rows ``loc`` (local flat ids, any shape) of the shard ``tbl`` as
    it lies, ``(R, D)`` or stacked ``(T, V, D)``."""
    d = tbl.shape[-1]
    if how == "xla":
        return jnp.take(tbl.reshape(-1, d), loc, axis=0)
    from flexflow_tpu.ops import pallas_kernels as pk

    return pk.gather_rows(tbl, loc.reshape(-1)).reshape(loc.shape + (d,))


def _add_rows(tbl, loc, upd, how: str):
    """``tbl.at[loc].add(upd)`` over the shard's flat rows, in the
    shard's own shape (in place through the kernels)."""
    d = tbl.shape[-1]
    if how == "xla":
        return tbl.reshape(-1, d).at[loc].add(upd).reshape(tbl.shape)
    from flexflow_tpu.ops import pallas_kernels as pk

    return pk.scatter_add_rows(tbl, loc.reshape(-1), upd.reshape(-1, d))


def _sharded_gather(op: Op, table, flat_ids, shard, *, sparse=False,
                    by_table=False):
    """Row-range-sharded ``table[flat_ids]`` (``flat_ids`` over the
    table's flat rows; the table ``(R, D)`` or stacked ``(T, V, D)``, as
    it lies): the owning shard resolves each id inside a ``shard_map``,
    never a full-table all-gather, values bit-identical to the
    replicated ``jnp.take``.

    ``by_table`` (``MultiEmbedding``: ids ``(B, T)``, column ``t``
    addresses stacked table ``t``, the stacked dim on ``c``): the ids'
    table axis splits over ``c`` like the tables, so a shard sees only
    its own columns, masks nothing, and the result stays on ``c`` along
    that axis, which is the op's output tag.  Otherwise the owner
    depends on the id's value: every shard takes all ids (masked,
    clipped) and a ``psum`` over the c group, adding structural zeros,
    assembles full rows.

    Differentiable (``shard_map`` + ``jnp.take`` + psum) as ``forward``
    calls it.  ``sparse`` (the executor's sparse protocol, outside
    autodiff) lets the shard's shape pick the Pallas row kernels."""
    import jax
    from jax.sharding import PartitionSpec

    c_axes, c_deg, local_rows = shard
    plan = op._plan
    (n_axes, n_deg), = plan.local_degrees(op._pc, "n")
    # Batch-shaped ids keep their leading dim on n; 1-D id vectors
    # (the stateful sparse path's unique rows) replicate.
    n_entry = n_axes if (n_axes and flat_ids.ndim > 1) else None
    how = "xla"
    if sparse:
        n_ids = flat_ids.size // (n_deg if n_entry else 1)
        how = _local_addressing(
            op, table, n_ids // (c_deg if by_table else 1), c_deg, "gather")

    def local_fn(tbl, ids):
        loc = ids - _shard_offset(plan, c_axes, local_rows)
        if by_table:  # this shard's own columns: every id is in range
            return _take_rows(tbl, loc, how)
        ok = (loc >= 0) & (loc < local_rows)
        got = _take_rows(tbl, jnp.clip(loc, 0, local_rows - 1), how)
        got = jnp.where(ok[..., None], got, 0.0)
        return jax.lax.psum(got, c_axes)

    _note_shard_event(op, "embedding_gather", shards=int(c_deg),
                      rows_per_shard=int(local_rows),
                      combine="table_axis" if by_table else "psum",
                      addressing=how)
    id_spec = (n_entry, c_axes) if by_table else (
        (n_entry,) + (None,) * (flat_ids.ndim - 1))
    return jax.shard_map(
        local_fn,
        mesh=plan.mesh,
        in_specs=(_table_spec(c_axes, table.ndim), PartitionSpec(*id_spec)),
        out_specs=PartitionSpec(*id_spec, None),
        check_vma=False,
    )(table, flat_ids)


def _sharded_scatter_add(op: Op, table, flat_ids, upd, shard, *,
                         by_table=False):
    """Transpose of :func:`_sharded_gather`: each shard scatter-adds
    the updates whose ids fall in its row range into its own rows, in
    the table's own shape — a LOCAL read-modify-write, no collective
    (only the table stays sharded).  ``by_table``: ids and updates
    split over ``c`` along their table axis, so a shard applies its own
    columns and nothing else.  Otherwise ids and updates replicate into
    the ``shard_map`` and out-of-range slots add exact zeros to local
    row 0, the same no-op-compatible convention the stateful sparse
    path uses for its padding slots.  Executor sparse path only: the
    shard's shape may pick the in-place Pallas row kernel."""
    import jax
    from jax.sharding import PartitionSpec

    c_axes, c_deg, local_rows = shard
    plan = op._plan
    d = table.shape[-1]
    how = _local_addressing(
        op, table, flat_ids.size // (c_deg if by_table else 1), c_deg,
        "scatter")

    def local_fn(tbl, ids, u):
        loc = ids.reshape(-1) - _shard_offset(plan, c_axes, local_rows)
        u = u.reshape(-1, d)
        if not by_table:  # another shard's ids: exact zeros to local row 0
            ok = (loc >= 0) & (loc < local_rows)
            loc, u = jnp.where(ok, loc, 0), jnp.where(ok[:, None], u, 0.0)
        return _add_rows(tbl, loc, u, how)

    _note_shard_event(op, "embedding_combine", shards=int(c_deg),
                      rows_per_shard=int(local_rows),
                      combine="local_scatter_add", addressing=how)
    split = (None, c_axes) if by_table else (None,) * flat_ids.ndim
    tspec = _table_spec(c_axes, table.ndim)
    return jax.shard_map(
        local_fn,
        mesh=plan.mesh,
        in_specs=(
            tspec,
            PartitionSpec(*split),
            PartitionSpec(*split, *(None,) * (upd.ndim - len(split))),
        ),
        out_specs=tspec,
        check_vma=False,
    )(table, flat_ids, upd)


def _row_addressing(op: Op, n_ids: int, shape, dtype, kind: str,
                    in_shard_map: bool = False) -> str:
    """How the executor's sparse protocol reaches the rows of the
    ``shape``/``dtype`` array a device sees: ``"lane_major"`` or
    ``"row_major"`` — the Pallas row kernels (pallas_kernels.gather_rows
    / scatter_add_rows) in the order the chip stores a table of this
    shape — or ``"xla"``.  XLA's TPU lowering of gather/scatter over a
    big table is a full-table sweep, the kernels touch only the
    addressed rows.  TPU only; on one device, or ``in_shard_map`` on the
    shard a device holds of a row-sharded table (a plain local array
    there).  A multi-device table outside a ``shard_map`` keeps the jnp
    path: GSPMD places that op, and cannot partition a kernel.  Only
    outside autodiff — jax has no AD rule for scalar-prefetch
    pallas_call, so ONLY the executor's sparse protocol (never
    ``forward``) may dispatch here.  Announced once an op at build
    (``embedding_rows``)."""
    import jax
    from flexflow_tpu.ops import pallas_kernels as pk

    plan = getattr(op, "_plan", None)
    rows = 1
    for s in shape[:-1]:
        rows *= s
    how = None
    placed_by_gspmd = (plan is not None and plan.num_devices > 1
                       and not in_shard_map)
    if (jax.default_backend() == "tpu" and not placed_by_gspmd
            and rows < 2**31):  # kernel ids are int32 (SMEM)
        how = pk.rows_addressing(n_ids, tuple(shape), dtype, kind)
    how = how or "xla"
    _note_shard_event(op, "embedding_rows", addressing=how, kind=kind,
                      dim=int(shape[-1]), ids=int(n_ids))
    return how


def _gather_dispatch(op: Op, table, flat_ids, by_table: bool = False):
    """``table[(R, D)][flat_ids] -> flat_ids.shape + (D,)`` — the
    row-sharded ``shard_map`` gather when the op's table is range
    sharded, else the Pallas row kernel when eligible, else
    ``jnp.take``.  ``table`` may arrive stacked, ``(T, V, D)`` with
    ``flat_ids`` over its ``T*V`` rows, and stays as it lies on every
    path but XLA's own: flattening a narrow-row table is a copy of it
    on the chip.  ``by_table``: see ``_sharded_gather``.  Executor
    sparse path only (the Pallas branches are not differentiable
    through)."""
    shard = _row_sharding(op, op.sparse_keys()[0])
    if shard is not None:
        return _sharded_gather(op, table, flat_ids, shard, sparse=True,
                               by_table=by_table)
    how = _row_addressing(op, flat_ids.size, table.shape, table.dtype,
                          "gather")
    return _take_rows(table, flat_ids, how)


def _scatter_add_dispatch(op: Op, table, flat_ids, upd,
                          by_table: bool = False):
    """``table.at[flat_ids].add(upd)`` in ``table``'s own shape (2-D or
    stacked, see ``_gather_dispatch``) — the local per-shard scatter
    when the op's table is row-sharded, else the in-place Pallas row
    kernel when eligible.  Executor sparse path only."""
    upd = upd.astype(table.dtype)
    shard = _row_sharding(op, op.sparse_keys()[0])
    if shard is not None:
        return _sharded_scatter_add(op, table, flat_ids, upd, shard,
                                    by_table=by_table)
    how = _row_addressing(op, flat_ids.size, table.shape, table.dtype,
                          "scatter")
    return _add_rows(table, flat_ids, upd, how)


class Embedding(Op):
    """Single-table embedding lookup with bag aggregation.

    Input: int indices (batch, bag); output (batch, out_dim) after
    sum/avg over the bag dim (the reference's aggr modes).

    ``shard_rows=True`` (``--shard-embeddings``) retags the table's
    dims from column-split ``(None, "c")`` to row-range-sharded
    ``("c", None)``: a c degree then shards the VOCAB so per-device
    HBM holds ``num_entries/c`` rows, the lookup becomes the
    shard_map gather+psum, and the output loses its 'c' tag (full
    rows are assembled by the psum).
    """

    def __init__(
        self,
        name: str,
        x: TensorSpec,
        num_entries: int,
        out_dim: int,
        aggr: str = "sum",
        dtype=jnp.float32,
        out_dtype=None,
        kernel_initializer=None,
        shard_rows: bool = False,
    ):
        super().__init__(name, [x])
        assert x.ndim == 2, f"embedding input must be (batch, bag), got {x.shape}"
        assert aggr in ("sum", "avg")
        self.attrs = dict(num_entries=num_entries, out_dim=out_dim, aggr=aggr)
        self.kernel_initializer = kernel_initializer or NormInitializer(0.0, 0.01)
        # ``dtype`` is the TABLE dtype; ``out_dtype`` (default: same)
        # lets f32 tables — required by the row-sparse update kernels —
        # emit activations in the model's compute dtype.
        self.table_dtype = jnp.dtype(dtype)
        self.shard_rows = bool(shard_rows)
        self._make_output((x.shape[0], out_dim), out_dtype or dtype,
                          ("n", None) if self.shard_rows else ("n", "c"))

    def param_specs(self) -> Dict[str, ParamSpec]:
        a = self.attrs
        return {
            "table": ParamSpec(
                (a["num_entries"], a["out_dim"]),
                self.table_dtype,
                self.kernel_initializer,
                ("c", None) if self.shard_rows else (None, "c"),
            )
        }

    def forward(self, params, xs, state, training):
        # Pure jnp (differentiable): the dense-grad path traces this
        # under value_and_grad.
        (idx,) = xs
        shard = _row_sharding(self, "table")
        if shard is not None:
            rows = _sharded_gather(self, params["table"], idx, shard)
        else:
            rows = jnp.take(params["table"], idx, axis=0)  # (batch, bag, dim)
        return self.sparse_forward(rows, xs, state, training)

    def sparse_keys(self):
        return ("table",)

    def sparse_rows(self, params, xs):
        (idx,) = xs
        return _gather_dispatch(self, params["table"], idx)

    def sparse_forward(self, rows, xs, state, training):
        if self.attrs["aggr"] == "sum":
            y = jnp.sum(rows, axis=1)
        else:
            y = jnp.mean(rows, axis=1)
        return [y.astype(self.outputs[0].dtype)], state

    def sparse_apply(self, params, xs, row_grads, lr):
        (idx,) = xs
        table = _scatter_add_dispatch(
            self, params["table"], idx, -lr * row_grads
        )
        return {**params, "table": table}

    def sparse_flat_ids(self, params, xs):
        (idx,) = xs
        return idx


class MultiEmbedding(Op):
    """T same-shaped tables stacked into one sharded parameter — the
    expert/table-parallel form used by DLRM.

    Input: int indices (batch, T); output (batch, T, out_dim).  The
    stacked dim is tagged 'c', so a strategy ``{"c": T}`` gives exactly
    the reference's one-table-per-device placement
    (``dlrm_strategy.cc:5-36``) with XLA generating the resulting
    gather/all-to-all over ICI.
    """

    def __init__(
        self,
        name: str,
        x: TensorSpec,
        num_tables: int,
        num_entries: int,
        out_dim: int,
        dtype=jnp.float32,
        out_dtype=None,
        kernel_initializer=None,
    ):
        super().__init__(name, [x])
        assert x.ndim == 2 and x.shape[1] == num_tables
        self.attrs = dict(
            num_tables=num_tables, num_entries=num_entries, out_dim=out_dim
        )
        self.kernel_initializer = kernel_initializer or NormInitializer(0.0, 0.01)
        self.table_dtype = jnp.dtype(dtype)
        self._make_output((x.shape[0], num_tables, out_dim), out_dtype or dtype,
                          ("n", "c", None))

    def param_specs(self) -> Dict[str, ParamSpec]:
        a = self.attrs
        return {
            "tables": ParamSpec(
                (a["num_tables"], a["num_entries"], a["out_dim"]),
                self.table_dtype,
                self.kernel_initializer,
                ("c", None, None),
            )
        }

    def forward(self, params, xs, state, training):
        # Pure jnp (differentiable).  Gather row idx[b, t] from table
        # t: one_hot-free take_along_axis.  (T, vocab, dim) indexed by
        # (batch, T) → (batch, T, dim).  When the stacked dim is
        # c-sharded (and c | T — leading-axis sharding survives the
        # flat-view merge) the lookup routes through the explicit
        # sharded gather over the (T*V, D) view: each shard resolves
        # the ids whose tables it owns, a psum assembles full rows —
        # the fancy-index form would leave GSPMD free to all-gather
        # the whole stacked table.
        (idx,) = xs  # (batch, T)
        tables = params["tables"]  # (T, vocab, dim)
        shard = _row_sharding(self, "tables")
        if shard is not None:
            T, V, D = tables.shape
            rows = _sharded_gather(
                self, tables.reshape(T * V, D),
                self._flat_ids(tables, idx), shard,
            )
            return [rows.astype(self.outputs[0].dtype)], state
        t_range = jnp.arange(tables.shape[0])[None, :]  # (1, T)
        return [tables[t_range, idx].astype(self.outputs[0].dtype)], state

    def sparse_keys(self):
        return ("tables",)

    def _flat_ids(self, tables, idx):
        # Global row id t*V + idx[b, t] into the (T*V, D) bitcast view.
        T, V, _ = tables.shape
        return jnp.arange(T, dtype=idx.dtype)[None, :] * V + idx

    def sparse_rows(self, params, xs):
        (idx,) = xs  # (batch, T)
        tables = params["tables"]  # (T, vocab, dim)
        return _gather_dispatch(
            self, tables, self._flat_ids(tables, idx), by_table=True
        )

    def sparse_forward(self, rows, xs, state, training):
        return [rows.astype(self.outputs[0].dtype)], state

    def sparse_apply(self, params, xs, row_grads, lr):
        (idx,) = xs  # (batch, T)
        tables = params["tables"]
        new = _scatter_add_dispatch(
            self, tables, self._flat_ids(tables, idx), -lr * row_grads,
            by_table=True,
        )
        return {**params, "tables": new}

    def sparse_flat_ids(self, params, xs):
        (idx,) = xs
        return self._flat_ids(params["tables"], idx)


class HeteroEmbedding(Op):
    """T *different-vocab* tables as one row-concatenated parameter —
    heterogeneous expert/table parallelism (the real 26-table Criteo
    case, ``examples/DLRM/dlrm.cc:230-330``).

    The reference pins each table whole to one GPU
    (``dlrm_strategy.cc:5-36``), which load-balances badly when vocabs
    are skewed (Criteo spans 10^1..10^7 rows).  TPU-native redesign:
    concatenate all tables along the ROW dim into a single
    ``(sum_vocab, dim)`` parameter with per-table row offsets folded
    into the ids, tag the row dim ``c``, and shard row-RANGES — each
    device owns an equal slice of rows regardless of table boundaries,
    so placement is balanced by construction.  Under ``c > 1`` the
    lookup runs as an explicit ``shard_map``: each shard gathers the
    ids that fall in its row range (masked, clipped) and a ``psum``
    over the ``c`` group assembles full rows — the standard
    sharded-gather pattern; its transpose is a local scatter-add into
    the owning shard (the reference's atomicAdd backward,
    ``embedding.cu:128-158``, without atomics).

    Rows are padded to a multiple of ``pad_to`` so any ``c`` degree
    dividing ``pad_to`` shards evenly; padded rows are never indexed,
    so their gradient is structurally zero.
    """

    def __init__(
        self,
        name: str,
        x: TensorSpec,
        vocab_sizes,
        out_dim: int,
        dtype=jnp.float32,
        out_dtype=None,
        pad_to: int = 128,
    ):
        super().__init__(name, [x])
        vocab_sizes = tuple(int(v) for v in vocab_sizes)
        assert x.ndim == 2 and x.shape[1] == len(vocab_sizes), (
            f"ids must be (batch, {len(vocab_sizes)}), got {x.shape}"
        )
        total = sum(vocab_sizes)
        rows = ((total + pad_to - 1) // pad_to) * pad_to
        offsets = []
        acc = 0
        for v in vocab_sizes:
            offsets.append(acc)
            acc += v
        self.attrs = dict(
            vocab_sizes=vocab_sizes, out_dim=out_dim, rows=rows,
            offsets=tuple(offsets),
        )
        self.table_dtype = jnp.dtype(dtype)
        self._make_output(
            (x.shape[0], len(vocab_sizes), out_dim), out_dtype or dtype,
            ("n", None, None)
        )

    def _init_table(self, key, shape, dtype):
        """Per-table U(-1/sqrt(V_t), 1/sqrt(V_t)) rows (``dlrm.cc:41-47``),
        zeros for padding — one uniform draw scaled by a per-row range."""
        import jax

        a = self.attrs
        scale = jnp.zeros((a["rows"],), jnp.float32)
        for off, v in zip(a["offsets"], a["vocab_sizes"]):
            scale = scale.at[off:off + v].set(1.0 / (v ** 0.5))
        u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
        return (u * scale[:, None]).astype(dtype)

    def param_specs(self) -> Dict[str, ParamSpec]:
        a = self.attrs
        return {
            "table": ParamSpec(
                (a["rows"], a["out_dim"]),
                self.table_dtype,
                self._init_table,
                ("c", None),
            )
        }

    def sparse_keys(self):
        return ("table",)

    def sparse_rows(self, params, xs):
        (idx,) = xs
        offsets = jnp.asarray(self.attrs["offsets"], idx.dtype)
        return _gather_dispatch(self, params["table"], idx + offsets[None, :])

    def sparse_forward(self, rows, xs, state, training):
        return [rows.astype(self.outputs[0].dtype)], state

    def sparse_apply(self, params, xs, row_grads, lr):
        (idx,) = xs
        offsets = jnp.asarray(self.attrs["offsets"], idx.dtype)
        table = _scatter_add_dispatch(
            self, params["table"], idx + offsets[None, :], -lr * row_grads
        )
        return {**params, "table": table}

    def sparse_flat_ids(self, params, xs):
        (idx,) = xs
        offsets = jnp.asarray(self.attrs["offsets"], idx.dtype)
        return idx + offsets[None, :]

    def forward(self, params, xs, state, training):
        (idx,) = xs  # (batch, T)
        table = params["table"]
        offsets = jnp.asarray(self.attrs["offsets"], idx.dtype)
        flat = idx + offsets[None, :]  # global row ids

        out_dtype = self.outputs[0].dtype
        shard = _row_sharding(self, "table")
        if shard is None:
            return [jnp.take(table, flat, axis=0).astype(out_dtype)], state
        gathered = _sharded_gather(self, table, flat, shard)
        return [gathered.astype(out_dtype)], state


class WordEmbedding(Op):
    """Token embedding over (batch, seq) int ids → (batch, seq, dim).

    Reference: the NMT word-embedding op (``nmt/embed.cu`` — custom
    gather fwd / scatter-add bwd kernels, ``embed.cu:152-186``).  The
    scatter-add gradient is XLA's gather transpose; sequence sharding
    (axis tag 's') flows straight through the lookup.
    """

    def __init__(
        self,
        name: str,
        x: TensorSpec,
        num_entries: int,
        out_dim: int,
        dtype=jnp.float32,
        out_dtype=None,
        kernel_initializer=None,
        shard_rows: bool = False,
    ):
        super().__init__(name, [x])
        assert x.ndim == 2, f"word embedding input must be (batch, seq), got {x.shape}"
        self.attrs = dict(num_entries=num_entries, out_dim=out_dim)
        self.kernel_initializer = kernel_initializer or NormInitializer(0.0, 0.01)
        self.table_dtype = jnp.dtype(dtype)
        # shard_rows (--shard-embeddings): vocab-range-shard the table
        # over c — per-device HBM holds num_entries/c rows, the lookup
        # runs the shard_map gather+psum (the replicated table stays
        # the default: LM vocabs usually fit, and replication keeps
        # the lookup collective-free).
        self.shard_rows = bool(shard_rows)
        self._make_output((x.shape[0], x.shape[1], out_dim), out_dtype or dtype,
                          ("n", "s", None))

    def param_specs(self) -> Dict[str, ParamSpec]:
        a = self.attrs
        return {
            "table": ParamSpec(
                (a["num_entries"], a["out_dim"]),
                self.table_dtype,
                self.kernel_initializer,
                ("c", None) if self.shard_rows else None,
            )
        }

    def forward(self, params, xs, state, training):
        (idx,) = xs
        shard = _row_sharding(self, "table")
        if shard is not None:
            rows = _sharded_gather(self, params["table"], idx, shard)
        else:
            rows = jnp.take(params["table"], idx, axis=0)
        return [rows.astype(self.outputs[0].dtype)], state

    def sparse_keys(self):
        return ("table",)

    def sparse_rows(self, params, xs):
        (idx,) = xs
        return _gather_dispatch(self, params["table"], idx)

    def sparse_forward(self, rows, xs, state, training):
        return [rows.astype(self.outputs[0].dtype)], state

    def sparse_apply(self, params, xs, row_grads, lr):
        (idx,) = xs
        table = _scatter_add_dispatch(
            self, params["table"], idx, -lr * row_grads
        )
        return {**params, "table": table}

    def sparse_flat_ids(self, params, xs):
        (idx,) = xs
        return idx
