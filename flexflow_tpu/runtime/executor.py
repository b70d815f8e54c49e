"""Graph → jitted-step compiler.

This is the TPU-native replacement for the reference's runtime layer:
``FFModel::forward/backward/update/zero_gradients``
(``src/runtime/model.cc:538-595``) driving per-op Legion index launches
through the FFMapper.  Here the whole step — forward over the op graph,
autodiff backward, SGD update, metric reduction — is ONE traced program
under ``jax.jit`` (the reference's ``begin_trace/end_trace`` around the
DLRM step, ``dlrm.cc:151-156``, made total), and the per-op
``(n,c,h,w)`` strategy becomes a ``with_sharding_constraint`` on every
op output so GSPMD places compute and inserts the ICI collectives that
Legion coherence + the mapper produced on GPUs.
"""

from __future__ import annotations

import contextlib
import functools
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.ops.base import Op, TensorSpec, op_params
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.parallel.mesh import MeshPlan, build_mesh_plan
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore


_log = logging.getLogger("ff.executor")


def _unique_row_sums(flat_ids, flat_g):
    """Sum duplicate-id row cotangents: returns ``(uids, gsum, mask)``
    with one summed row per unique id in the first ``nuniq`` slots
    (zeros beyond).  This is exactly what the dense scatter-add
    gradient holds per touched row (the reference's atomicAdd backward,
    ``embedding.cu:144-158``), computed at batch size instead of table
    size: sort ids, segment-sum adjacent equals."""
    n = flat_ids.shape[0]
    order = jnp.argsort(flat_ids)
    sid = jnp.take(flat_ids, order)
    sg = jnp.take(flat_g, order, axis=0)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), (sid[1:] != sid[:-1]).astype(jnp.int32)]
    )
    seg = jnp.cumsum(starts)
    gsum = jax.ops.segment_sum(sg, seg, num_segments=n)
    uids = jnp.zeros((n,), sid.dtype).at[seg].set(sid)
    mask = jnp.arange(n) <= seg[-1]
    return uids, gsum, mask


@contextlib.contextmanager
def _op_scope(op: Op):
    """``jax.named_scope(op.name)``; a terminal loss op (not MoE, whose
    loss term is a byproduct of its FFN) also under ``ff_loss``, so its
    forward and its transpose are found as one phase of the step
    (``obs/events.py::SCOPE_CATALOG``)."""
    with contextlib.ExitStack() as stack:
        if op.is_loss and not op.allow_remat:
            stack.enter_context(jax.named_scope("ff_loss"))
        stack.enter_context(jax.named_scope(op.name))
        yield


def _merge_metrics(acc: Dict[str, jax.Array], m: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    out = dict(acc)
    for k, v in m.items():
        out[k] = out[k] + v if k in out else v
    return out


def mean_metrics(
    metrics: Dict[str, jax.Array],
    count: Optional[int] = None,
    stacked: bool = False,
) -> Dict[str, jax.Array]:
    """Count-aware per-microbatch metric reduction, shared by every
    multi-microbatch execution path (``Executor._build_accum_step``'s
    stacked scan output, ``PipelineExecutor._finish_step``'s summed
    accumulator): integer-dtype metrics are COUNTS (samples, correct
    predictions) and sum across microbatches; float metrics are means
    and average.  ``stacked=True`` reduces a leading microbatch axis;
    otherwise ``metrics`` are already summed and the float entries are
    averaged by an EXPLICIT reciprocal multiply, not a division: the
    count path runs both eagerly (host pipeline ``_finish_step``) and
    inside the compiled whole-step pipeline program, and XLA's
    algebraic simplifier rewrites an in-program division by a non-
    power-of-two literal into multiply-by-reciprocal while the eager
    dispatch keeps the true (1-ulp-different) division — writing the
    multiply ourselves makes the two runtimes share one formula
    (``optimization_barrier`` cannot pin it: this XLA vintage strips
    barriers before the simplifier runs, measured 2026-08-04)."""
    if stacked:
        return {
            k: jnp.sum(v, axis=0)
            if jnp.issubdtype(v.dtype, jnp.integer)
            else jnp.mean(v, axis=0)
            for k, v in metrics.items()
        }
    inv = np.float32(1.0) / np.float32(count)
    return {
        k: v if jnp.issubdtype(v.dtype, jnp.integer) else v * inv
        for k, v in metrics.items()
    }


class Executor:
    """Compiles an FFModel + StrategyStore onto a MeshPlan."""

    def __init__(
        self,
        model: FFModel,
        config: Optional[FFConfig] = None,
        strategy: Optional[StrategyStore] = None,
        mesh_plan: Optional[MeshPlan] = None,
        optimizer: Optional[SGDOptimizer] = None,
        devices: Optional[Sequence[jax.Device]] = None,
    ):
        self.model = model
        self.config = config or model.config
        if mesh_plan is None:
            nd = self.config.resolve_num_devices() if devices is None else len(devices)
            mesh_plan = build_mesh_plan(nd, devices=devices)
        self.plan = mesh_plan
        self.strategy = strategy or StrategyStore.data_parallel(self.plan.num_devices)
        # Loudly reject (never silently drop) placements this executor
        # cannot realize: a proper-subset device list is layer-wise
        # placement, which is PipelineExecutor's job (reference
        # ``config.h:39-48`` gpu[]; ``nmt.cc:269-308``).
        full = set(range(self.plan.num_devices))
        for name, pc in self.strategy.table.items():
            ids = pc.device_ids
            if ids is not None and set(ids) != full:
                raise ValueError(
                    f"strategy for {name!r} places on devices "
                    f"{sorted(set(ids))} but this Executor's mesh is "
                    f"devices 0..{self.plan.num_devices - 1}; Executor "
                    f"runs every op on the full mesh — use "
                    f"flexflow_tpu.runtime.pipeline.PipelineExecutor (or "
                    f"make_executor) for layer-wise placement"
                )
        self.optimizer = optimizer or SGDOptimizer(
            lr=self.config.learning_rate, weight_decay=self.config.weight_decay
        )
        self._consumer: Dict[str, Op] = {}
        for op in model.layers:
            for t in op.inputs:
                self._consumer.setdefault(t.name, op)
        self._accum_cache: Dict[int, Any] = {}
        self._superstep_cache: Dict[Tuple[int, int], Any] = {}

    # -- sharding assembly -------------------------------------------------

    def _pc(self, op: Op) -> ParallelConfig:
        return self.strategy.find(op.name)

    def output_sharding(self, op: Op, t: TensorSpec) -> NamedSharding:
        return self.plan.sharding(self._pc(op), t.dim_axes, t.shape)

    def param_sharding(self, op: Op, spec) -> NamedSharding:
        return self.plan.sharding(self._pc(op), spec.dim_axes, spec.shape)

    def input_sharding(self, t: TensorSpec) -> NamedSharding:
        """An input placeholder is sharded the way its first consumer
        wants it — the analogue of the mapper slicing the loader launch
        over the consumer op's task index space (``dlrm.cc:447-512``)."""
        consumer = self._consumer.get(t.name)
        if consumer is None:
            return self.plan.replicated()
        return self.plan.sharding(self._pc(consumer), t.dim_axes, t.shape)

    def params_shardings(self):
        return {
            op.name: {
                k: self.param_sharding(op, spec)
                for k, spec in op.param_specs().items()
            }
            for op in self.model.layers
            if op.param_specs()
        }

    def state_shardings(self):
        return {
            op.name: {
                k: self.param_sharding(op, spec)
                for k, spec in op.state_specs().items()
            }
            for op in self.model.layers
            if op.state_specs()
        }

    @functools.cached_property
    def _batch_shardings(self) -> Dict[str, NamedSharding]:
        return {t.name: self.input_sharding(t) for t in self.model.input_tensors}

    def batch_shardings(self) -> Dict[str, NamedSharding]:
        return self._batch_shardings

    # -- initialization ----------------------------------------------------

    def init(self, seed: Optional[int] = None) -> Tuple[Any, Any, Any]:
        """Materialize (params, opt_state, op_state) directly in their
        target shardings (reference: initializer index tasks over the
        weight partitions, ``initializer_kernel.cu:24-179``)."""
        seed = self.config.seed if seed is None else seed
        out_sh = (self.params_shardings(), self.state_shardings())
        params, state = jax.jit(self._init_fn, out_shardings=out_sh)(
            jax.random.PRNGKey(seed)
        )
        if self.config.zero_sharded_optimizer:
            # Moments are BORN sharded: creating them replicated first
            # would OOM at exactly the scale the flag exists for.
            avals = jax.eval_shape(self.optimizer.init, params)
            if avals is None:
                opt_state = None
            else:
                zsh = self._zero_opt_shardings
                out_sh = self.optimizer.map_param_states(
                    avals,
                    lambda tree: jax.tree.map(lambda _, s: s, tree, zsh),
                )
                out_sh = jax.tree.map(
                    lambda x: x if isinstance(x, NamedSharding) else None,
                    out_sh,
                )
                opt_state = jax.jit(
                    self.optimizer.init, out_shardings=out_sh
                )(params)
        else:
            opt_state = self.optimizer.init(params)
        return params, opt_state, state

    # -- ZeRO-1 optimizer-state sharding -----------------------------------

    def _zero_sharding(self, op: Op, spec) -> NamedSharding:
        """The param's own sharding with its leading dim additionally
        split over the op's data-parallel mesh axes (the replica group
        the moments would otherwise be replicated across) — ZeRO-1:
        each DP rank stores and updates 1/dp of the optimizer state,
        GSPMD inserting the update all-gather."""
        pc = self._pc(op)
        return NamedSharding(
            self.plan.mesh,
            self.plan.spec(
                pc, spec.dim_axes, spec.shape,
                extra_leading_axes=self.plan.assign(pc).get("n", ()),
            ),
        )

    @functools.cached_property
    def _zero_opt_shardings(self):
        """Params-structured tree of ZeRO shardings for moment leaves."""
        return {
            op.name: {
                k: self._zero_sharding(op, spec)
                for k, spec in op.param_specs().items()
            }
            for op in self.model.layers
            if op.param_specs()
        }

    def _constrain_zero_opt(self, new_opt):
        if not self.config.zero_sharded_optimizer or new_opt is None:
            return new_opt
        return self.optimizer.map_param_states(
            new_opt,
            lambda tree: jax.tree.map(
                jax.lax.with_sharding_constraint, tree, self._zero_opt_shardings
            ),
        )

    # -- sparse embedding updates ------------------------------------------

    @functools.cached_property
    def _sparse_ops(self) -> List[Op]:
        """Ops taking the row-sparse update path (ops/base.py protocol):
        opted-in embedding ops whose inputs are all graph inputs, when
        the config enables it and the optimizer's update rule is exactly
        reproducible row-wise."""
        if not getattr(self.config, "sparse_embedding_updates", False):
            return []
        if not getattr(self.optimizer, "supports_sparse_rows", False):
            return []
        input_names = {t.name for t in self.model.input_tensors}
        # A table another op reads too (a tied head) gets that op's
        # dense gradient as well: it stays on the dense path.
        read_elsewhere = {owner for op in self.model.layers
                          for owner, _ in op.tied.values()}
        out = []
        for op in self.model.layers:
            keys = op.sparse_keys()
            if not keys or op.name in read_elsewhere:
                continue
            if set(keys) != set(op.param_specs().keys()):
                continue  # mixed dense+sparse params: keep dense
            if any(
                spec.dtype != jnp.float32
                for spec in op.param_specs().values()
            ):
                # Sub-f32 tables round per-duplicate in the scatter
                # RMW, which is not bit-identical to the dense path's
                # single post-sum rounding — keep those dense.
                continue
            if not all(t.name in input_names for t in op.inputs):
                continue  # ids must come straight from the batch
            if not op.sparse_ok(self.plan, self._pc(op)):
                continue
            out.append(op)
        return out

    def _init_fn(self, key):
        """Pure initializer over the op graph — jitted by :meth:`init`
        and eval_shape'd by :meth:`abstract_step`, so the two cannot
        diverge.  ``config.parameter_all_ones`` (--ones-init) swaps
        every PARAMETER initializer for ones — the reference's
        deterministic-numerics build (``PARAMETER_ALL_ONES``,
        ``conv_2d.cu:394-399``); op state (e.g. batchnorm running
        stats) keeps its own initializers, which are already
        deterministic."""
        ones = None
        if self.config.parameter_all_ones:
            from flexflow_tpu.initializers import OnesInitializer

            ones = OnesInitializer()
        params: Dict[str, Dict[str, jax.Array]] = {}
        state: Dict[str, Dict[str, jax.Array]] = {}
        for op in self.model.layers:
            pspecs = op.param_specs()
            if pspecs:
                params[op.name] = {}
                for k, spec in sorted(pspecs.items()):
                    key, sub = jax.random.split(key)
                    init = ones or spec.initializer
                    params[op.name][k] = init(sub, spec.shape, spec.dtype)
            sspecs = op.state_specs()
            if sspecs:
                state[op.name] = {}
                for k, spec in sorted(sspecs.items()):
                    key, sub = jax.random.split(key)
                    state[op.name][k] = spec.initializer(sub, spec.shape, spec.dtype)
        return params, state

    # -- forward -----------------------------------------------------------

    def forward(self, params, state, batch, training: bool, rows_override=None):
        """Run the op graph.  Returns (loss, metrics, new_state, env).

        ``rows_override`` maps op name -> pre-gathered embedding rows;
        those ops run ``sparse_forward`` (never touching their table)
        so autodiff produces row-sized cotangents."""
        env: Dict[str, jax.Array] = {}
        env_spec: Dict[str, PartitionSpec] = {}
        for t in self.model.input_tensors:
            x = batch[t.name]
            # The sample dim may shrink (pipeline microbatching splits
            # the declared batch); feature dims are structural.
            strict_from = 1 if (t.dim_axes and t.dim_axes[0] == "n") else 0
            assert x.shape[strict_from:] == t.shape[strict_from:], (
                f"input {t.name}: expected {t.shape}, got {x.shape}"
            )
            sh = self.input_sharding(t)
            env[t.name] = jax.lax.with_sharding_constraint(x, sh)
            env_spec[t.name] = sh.spec
        total_loss = jnp.float32(0.0)
        metrics: Dict[str, jax.Array] = {}
        new_state: Dict[str, Dict[str, jax.Array]] = {}
        for op in self.model.layers:
            op.bind_mesh(self.plan, self._pc(op))
            # The named scope lands in HLO instruction metadata
            # (op_name="…/opname/…"), which is what lets the post-SPMD
            # audit attribute collectives — and their bytes — to model
            # ops (analysis/hlo.py collective_bytes_by_op), and a trace
            # reader device time (obs/trace.py).
            with _op_scope(op):
                xs = [
                    self._reshard_input(env[t.name], env_spec.get(t.name), t, op)
                    for t in op.inputs
                ]
                p = op_params(op, params)
                s = state.get(op.name, {})
                if rows_override is not None and op.name in rows_override:
                    result, s_new = op.sparse_forward(
                        rows_override[op.name], xs, s, training
                    )
                elif self.config.remat and training and (
                    not op.is_loss or op.allow_remat
                ):
                    # Per-layer rematerialization: drop this op's
                    # activations after forward and recompute them in the
                    # backward pass (jax.checkpoint) — HBM for FLOPs.
                    fwd = jax.checkpoint(
                        lambda p, xs, s, _op=op: _op.forward(p, xs, s, training)
                    )
                    result, s_new = fwd(p, xs, s)
                else:
                    result, s_new = op.forward(p, xs, s, training)
                if op.is_loss:
                    loss, m, ys = result
                    total_loss = total_loss + loss
                    metrics = _merge_metrics(metrics, m)
                else:
                    ys = result
                for t, y in zip(op.outputs, ys):
                    sh = self.output_sharding(op, t)
                    y = jax.lax.with_sharding_constraint(y, sh)
                    env[t.name] = y
                    env_spec[t.name] = sh.spec
            if s_new is not s and s_new:
                new_state[op.name] = s_new
            elif s:
                new_state[op.name] = s
        return total_loss, metrics, new_state, env

    def _reshard_input(self, x, frm_spec, t: TensorSpec, op: Op):
        """Reshard a consumer's input through explicit decomposed hops
        when the producer/consumer strategy boundary moves mesh axes
        across tensor dims — the transitions GSPMD otherwise handles by
        involuntary full rematerialization (replicate + repartition).
        The reverse chain constrains the cotangent in the backward pass,
        so both directions reshard with subgroup collectives.  The
        reference analogue is Legion materializing explicit copies for
        arbitrary repartitions between ops (``flat.cu:81-124``)."""
        if frm_spec is None:
            return x
        to_spec = self.plan.spec(self._pc(op), t.dim_axes, t.shape)
        # Full chain ending with `to_spec` when a mover decomposition
        # exists; [] for pure add/drop (GSPMD's own single collective)
        # or undecomposable transitions (warned on ff.mesh).
        for spec in self.plan.reshard_hops(frm_spec, to_spec, len(t.shape)):
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(self.plan.mesh, spec)
            )
        return x

    # -- steps -------------------------------------------------------------

    def _loss_fn(self, params, state, batch):
        loss, metrics, new_state, _ = self.forward(params, state, batch, training=True)
        return loss, (metrics, new_state)

    def _clip_scale(self, grads, extra_sq=0.0):
        """--clip-norm scale factor from the global L2 norm of ``grads``
        plus ``extra_sq`` (the sparse ops' per-unique-row squared sums).
        One formula for every execution path, so the clip decision is
        identical under dense, sparse and accumulated gradients."""
        c = self.config.clip_norm
        sq = extra_sq + sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads)
        )
        return jnp.minimum(1.0, c * jax.lax.rsqrt(jnp.maximum(sq, 1e-30)))

    def _clip_grads(self, grads):
        """--clip-norm: global-L2 gradient clipping before the update
        (identical under every sharding: the norm reduces over the
        fully-reduced gradient tree)."""
        c = self.config.clip_norm
        if not c or c <= 0.0:
            return grads
        scale = self._clip_scale(grads)
        return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)

    def build_train_step(self):
        """The whole iteration — fwd, bwd (autodiff), SGD — as one pure
        function.  Reference equivalent: forward() + zero_gradients() +
        backward() + update() (``model.cc:538-595``) under a Legion
        trace."""
        sparse_ops = self._sparse_ops
        if not sparse_ops:

            def train_step(params, opt_state, state, batch):
                (loss, (metrics, new_state)), grads = jax.value_and_grad(
                    self._loss_fn, has_aux=True
                )(params, state, batch)
                with jax.named_scope("ff_opt"):
                    grads = self._clip_grads(grads)
                    new_params, new_opt = self.optimizer.update(params, opt_state, grads)
                    new_opt = self._constrain_zero_opt(new_opt)
                return new_params, new_opt, new_state, metrics

            return train_step

        sparse_names = {op.name for op in sparse_ops}
        stateless = getattr(self.optimizer, "stateless_sparse", True)
        clip = self.config.clip_norm

        def sparse_train_step(params, opt_state, state, batch):
            rows = {}
            for op in sparse_ops:
                op.bind_mesh(self.plan, self._pc(op))
                xs = [batch[t.name] for t in op.inputs]
                with jax.named_scope(op.name):
                    rows[op.name] = op.sparse_rows(params[op.name], xs)
            dense = {k: v for k, v in params.items() if k not in sparse_names}

            def loss_fn(dense_params, rows):
                loss, metrics, new_state, _ = self.forward(
                    dense_params, state, batch, training=True,
                    rows_override=rows,
                )
                return loss, (metrics, new_state)

            (loss, (metrics, new_state)), (dg, rg) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True
            )(dense, rows)

            with jax.named_scope("ff_opt"):
                # Duplicate-id row sums per sparse op — needed by exact
                # global-norm clipping (the dense gradient's norm sums
                # duplicate-id cotangents BEFORE squaring) and by stateful
                # (lazy momentum/Adam) row updates (nonlinear in g, so one
                # update per unique row).
                uniq = {}
                if clip > 0.0 or not stateless:
                    for op in sparse_ops:
                        xs = [batch[t.name] for t in op.inputs]
                        ids = op.sparse_flat_ids(params[op.name], xs)
                        g = rg[op.name]
                        uniq[op.name] = _unique_row_sums(
                            ids.reshape(-1), g.reshape(-1, g.shape[-1])
                        )

                scale = None
                if clip > 0.0:
                    extra_sq = sum(
                        jnp.sum(jnp.square(gsum.astype(jnp.float32)))
                        for (_, gsum, _) in uniq.values()
                    )
                    scale = self._clip_scale(dg, extra_sq)
                    dg = jax.tree.map(
                        lambda g: (g * scale).astype(g.dtype), dg
                    )

                # Dense update over the non-sparse params; sparse subtrees
                # of the optimizer state are filtered out and row-updated
                # below (SGD: None state passes through untouched).
                opt_dense = self.optimizer.map_param_states(
                    opt_state,
                    lambda tree: {
                        k: v for k, v in tree.items() if k not in sparse_names
                    },
                )
                new_params, new_opt = self.optimizer.update(dense, opt_dense, dg)
                new_opt = self.optimizer.restore_param_states(
                    new_opt, opt_state, sparse_names
                ) if new_opt is not None else None

                lr = self.optimizer.lr
                for op in sparse_ops:
                    with jax.named_scope(op.name):
                        if stateless:
                            xs = [batch[t.name] for t in op.inputs]
                            g = rg[op.name]
                            if scale is not None:
                                g = g * scale
                            # Linear update: per-occurrence scatter-add
                            # (duplicates distribute), Pallas row-DMA kernels.
                            new_params[op.name] = op.sparse_apply(
                                params[op.name], xs, g, lr
                            )
                        else:
                            new_params[op.name], new_opt = self._sparse_stateful_apply(
                                op, params[op.name], new_opt, uniq[op.name], scale
                            )
                new_opt = self._constrain_zero_opt(new_opt)
            return new_params, new_opt, new_state, metrics

        return sparse_train_step

    def _sparse_stateful_apply(self, op: Op, op_params, opt_state, uniq, scale):
        """Lazy momentum/Adam row update for one sparse op: gather the
        unique rows' param + optimizer-state rows, run the optimizer's
        row step, scatter-add the deltas back (unique ids: add ==
        assign; padding slots carry zero deltas into row 0 — a no-op
        compatible with both the jnp and Pallas scatter paths)."""
        from flexflow_tpu.ops.embedding import (
            _gather_dispatch,
            _scatter_add_dispatch,
        )

        uids, gsum, mask = uniq
        if scale is not None:
            gsum = gsum * scale
        key = op.sparse_keys()[0]
        table = op_params[key]
        safe = jnp.where(mask, uids, 0)
        p_rows = _gather_dispatch(op, table, safe)
        bufs = self.optimizer.sparse_state_buffers(opt_state, op.name, key)
        buf_rows = {k: _gather_dispatch(op, b, safe) for k, b in bufs.items()}
        t = self.optimizer.sparse_step_count(opt_state)
        d_p, d_bufs = self.optimizer.sparse_row_step(
            p_rows, gsum, buf_rows, t=t
        )
        m = mask[:, None]
        new_table = _scatter_add_dispatch(
            op, table, safe, jnp.where(m, d_p, 0)
        )
        new_bufs = {
            k: _scatter_add_dispatch(op, b, safe, jnp.where(m, d_bufs[k], 0))
            for k, b in bufs.items()
        }
        new_params = {**op_params, key: new_table}
        if new_bufs:
            opt_state = self.optimizer.with_sparse_state_buffers(
                opt_state, op.name, key, new_bufs
            )
        return new_params, opt_state

    @functools.cached_property
    def train_step(self):
        return jax.jit(self.build_train_step(), donate_argnums=(0, 1, 2))

    # -- gradient accumulation ---------------------------------------------

    def accum_train_step(self, accum_steps: int):
        """A train step over ``accum_steps`` stacked microbatches: one
        optimizer update from the mean of per-microbatch gradients.

        Each input tensor arrives shaped ``(accum_steps,) + t.shape``
        (see :meth:`stack_microbatches`).  Losses are batch means, so
        averaging microbatch gradients is exactly the full-batch
        gradient; HBM holds one microbatch of activations at a time
        (``lax.scan``), which is how batch sizes beyond memory run.
        Count-like metrics (integer dtypes) are summed across
        microbatches, means are averaged.

        Note: this path always uses dense gradients — the row-sparse
        embedding protocol (``_sparse_ops``) applies to ``train_step``
        only, so accumulating steps over very large embedding tables
        materializes table-sized gradients per microbatch.
        """
        cached = self._accum_cache.get(accum_steps)
        if cached is not None:
            return cached
        fn = jax.jit(self._build_accum_step(accum_steps), donate_argnums=(0, 1, 2))
        self._accum_cache[accum_steps] = fn
        return fn

    def _build_accum_step(self, accum_steps: int):
        """The unjitted accumulated step (see :meth:`accum_train_step`)
        — also the per-step body :meth:`build_superstep` scans over when
        superstep execution composes with gradient accumulation."""
        for op in self.model.layers:
            if op.is_loss and getattr(op, "reduction", "mean") != "mean":
                # Sum-reduced losses would need grad SUM across
                # microbatches; the mean below would shrink the step by
                # accum_steps silently.
                raise ValueError(
                    f"gradient accumulation requires mean-reduction "
                    f"losses; {op.name!r} uses {op.reduction!r}"
                )

        def step(params, opt_state, state, stacked):
            def micro(carry_state, batch):
                (loss, (metrics, new_state)), grads = jax.value_and_grad(
                    self._loss_fn, has_aux=True
                )(params, carry_state, batch)
                return new_state, (metrics, grads)

            new_state, (metrics, grads) = jax.lax.scan(micro, state, stacked)
            m = mean_metrics(metrics, stacked=True)
            with jax.named_scope("ff_opt"):
                g = self._clip_grads(
                    jax.tree.map(lambda x: jnp.mean(x, axis=0), grads)
                )
                new_params, new_opt = self.optimizer.update(params, opt_state, g)
                new_opt = self._constrain_zero_opt(new_opt)
            return new_params, new_opt, new_state, m

        return step

    def stack_microbatches(self, batch: Dict[str, Any], accum_steps: int):
        """Reshape a ``(accum*b, ...)`` host batch into the
        ``(accum, b, ...)`` layout ``accum_train_step`` scans over."""
        out = {}
        for k, v in batch.items():
            assert v.shape[0] % accum_steps == 0, (k, v.shape, accum_steps)
            out[k] = v.reshape((accum_steps, v.shape[0] // accum_steps) + v.shape[1:])
        return out

    # -- superstep execution -------------------------------------------------

    @property
    def superstep_fused(self) -> bool:
        """Whether ``steps_per_call > 1`` fuses into one compiled
        dispatch here — always true for this executor (its constructor
        rejects layer-wise placement).  ``PipelineExecutor`` exposes
        the same property (true on the compiled-step path); the
        trainer and resilience layer route on it."""
        return self.strategy.superstep_capable()

    def build_superstep(self, k: int, accum_steps: int = 1):
        """K full train steps compiled into ONE jitted dispatch.

        The per-step host round-trip is the largest remaining overhead
        at dispatch-bound shapes (not measured on the chip, ROADMAP A2);
        the reference amortizes it by letting Legion batch and pipeline
        operator tasks across iterations.  Here the training LOOP itself
        moves into XLA: a ``lax.scan`` of the train step over a stacked
        batch queue shaped ``(k,) + batch`` (see :meth:`stack_steps`),
        with the ``(params, opt_state, op_state)`` carry donated — op
        state carries the dropout RNG chain, so stochastic layers
        advance exactly as in k sequential steps.  Per-step metrics come
        back stacked ``(k, ...)`` in one host readback, so loss curves
        unstack bit-identically to k=1 execution.

        Composes with gradient accumulation (``accum_steps > 1`` scans
        the accumulated step, whose own inner microbatch scan nests
        inside) and with ZeRO optimizer sharding (the step body re-pins
        moment shardings every iteration).  Layer-wise (device-subset)
        strategies dispatch per-stage programs from the host and cannot
        fuse — Executor's constructor already rejects them, and
        :meth:`StrategyStore.superstep_mode` tells callers which
        superstep form a strategy supports: this FUSED one, or the
        pipeline's fence-amortized form
        (``Trainer._fit_superstep_pipeline``: k steps dispatched
        back-to-back under one ``device_get``).
        """
        if k < 1:
            raise ValueError(f"steps_per_call must be >= 1, got {k}")
        if not self.strategy.superstep_capable():
            raise ValueError(
                "superstep execution requires full-mesh strategies; "
                "layer-wise (device-subset) placement dispatches "
                "per-stage programs the scan cannot fuse"
            )
        cached = self._superstep_cache.get((k, accum_steps))
        if cached is not None:
            return cached
        inner = (
            self._build_accum_step(accum_steps)
            if accum_steps > 1
            else self.build_train_step()
        )

        def superstep(params, opt_state, state, stacked):
            def body(carry, batch):
                p, o, s = carry
                p, o, s, m = inner(p, o, s, batch)
                return (p, o, s), m

            (p, o, s), ms = jax.lax.scan(
                body, (params, opt_state, state), stacked
            )
            return p, o, s, ms

        fn = jax.jit(superstep, donate_argnums=(0, 1, 2))
        self._superstep_cache[(k, accum_steps)] = fn
        return fn

    @staticmethod
    def metrics_row(ms: Dict[str, Any], j: int) -> Dict[str, Any]:
        """Unstack step ``j``'s metrics from a superstep's stacked
        ``(k, ...)`` metrics (host or device) — the per-step view both
        the trainer's loss curve and the resilience layer's finiteness
        scan consume at the single superstep fence."""
        return {key: v[j] for key, v in ms.items()}

    def stack_steps(self, batches: Sequence[Dict[str, Any]], accum_steps: int = 1):
        """Stack k per-step host batches into the device-resident
        ``(k, ...)`` queue :meth:`build_superstep` scans over, placed
        with each input's consumer sharding under unsharded leading
        step (and microbatch) dims.  With ``accum_steps > 1`` each
        element first takes the ``(accum, b, ...)`` microbatch layout
        (:meth:`stack_microbatches`)."""
        import numpy as np

        if accum_steps > 1:
            batches = [self.stack_microbatches(b, accum_steps) for b in batches]
        lead = 1 + (1 if accum_steps > 1 else 0)
        sh = self._batch_shardings
        out = {}
        # Integer inputs (embedding/label id queues) stage FIRST:
        # device_put returns with the H2D copy in flight, so the id
        # transfer overlaps the host-side np.stack of the (much
        # larger) float inputs instead of queueing behind it.  Stable
        # sort — within each dtype class the input order is unchanged.
        names = sorted(
            batches[0],
            key=lambda n: 0 if np.issubdtype(
                batches[0][n].dtype, np.integer
            ) else 1,
        )
        for name in names:
            vals = [b[name] for b in batches]
            if all(isinstance(v, np.ndarray) for v in vals):
                stacked = np.stack(vals)
            else:
                # Already-placed device batches (caller-owned loaders):
                # one on-device concat, still a single dispatch.
                stacked = jnp.stack([jnp.asarray(v) for v in vals])
            if name in sh:
                spec = PartitionSpec(*([None] * lead), *sh[name].spec)
                stacked = jax.device_put(
                    stacked, NamedSharding(self.plan.mesh, spec)
                )
            out[name] = stacked
        return out

    @functools.cached_property
    def eval_step(self):
        def eval_step(params, state, batch):
            loss, metrics, _, env = self.forward(params, state, batch, training=False)
            return loss, metrics

        return jax.jit(eval_step)

    @functools.cached_property
    def forward_step(self):
        """Inference forward over the graph returning every op output —
        the compile-check entry used by __graft_entry__."""

        def fwd(params, state, batch):
            loss, metrics, _, env = self.forward(params, state, batch, training=False)
            outs = {
                t.name: env[t.name]
                for op in self.model.layers
                for t in op.outputs
            }
            return loss, outs

        return jax.jit(fwd)

    # -- compute-free modes --------------------------------------------------
    #
    # The reference's DISABLE_COMPUTATION build compiles the whole
    # task/partition machinery with the kernels stubbed out
    # (``ops.h:19``, ``model.h:573-575``) — its "fake backend" for
    # exercising the runtime without GPUs.  The jax analogues: trace
    # the full train step under eval_shape (zero FLOPs, validates the
    # graph, shardings and dtypes), or AOT-lower it to stablehlo text.

    def _abstract_batch(self):
        return {
            t.name: jax.ShapeDtypeStruct(t.shape, t.dtype)
            for t in self.model.input_tensors
        }

    def _abstract_init(self):
        """(params, opt_state, state) avals via eval_shape of the REAL
        init path — no device is touched (even the PRNG key stays
        abstract)."""
        key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        params, state = jax.eval_shape(self._init_fn, key)
        opt_state = jax.eval_shape(self.optimizer.init, params)
        return params, opt_state, state

    def abstract_step(self):
        """``jax.eval_shape`` over init + one train step: returns the
        (params, opt_state, state, metrics) avals without touching any
        device."""
        params, opt_state, state = self._abstract_init()
        return jax.eval_shape(
            self.build_train_step(), params, opt_state, state,
            self._abstract_batch(),
        )

    def lower_train_step(self):
        """AOT-lower the cached jitted train step (the exact function
        :meth:`train_step` runs): the returned ``Lowered`` exposes
        ``.as_text()`` (stablehlo) and ``.compile()`` — the inspection
        path the reference lacked."""
        params, opt_state, state = self._abstract_init()
        return self.train_step.lower(
            params, opt_state, state, self._abstract_batch()
        )

    # -- data placement ----------------------------------------------------

    def shard_batch(self, batch: Dict[str, Any]) -> Dict[str, jax.Array]:
        """Device-put each declared input in its consumer's sharding;
        keys that are not model inputs pass through untouched (forward
        ignores them)."""
        sh = self._batch_shardings
        return {
            k: jax.device_put(v, sh[k]) if k in sh else v for k, v in batch.items()
        }
