"""Inter-op (layer-wise) pipeline parallelism over device subsets.

The reference places individual ops on explicit device subsets — the
``gpu[1024]`` list in ``ParallelConfig`` (``include/config.h:39-48``)
— and its NMT app pins the embed layer to GPUs {0,1} and each LSTM
chunk to its own device set (``nmt/nmt.cc:269-308``,
``nmt/rnn_mapper.cc:131-135``), so different layers of one model run
on different workers with Legion's dataflow runtime overlapping their
execution across iterations.

TPU-native redesign: a strategy's ``device_ids`` partitions the op
graph into *stages*.  Each stage compiles (via its own
:class:`~flexflow_tpu.runtime.executor.Executor`) onto a submesh built
from exactly its device subset; intra-stage dp/tp/spatial degrees
still apply within the submesh.  Stage boundaries are plain
``jax.device_put`` transfers between submeshes (ICI, async).  The
backward pass is remat-style — each stage stores only its *inputs*
and recomputes activations inside its backward jit (``jax.vjp``), the
standard memory-optimal schedule for pipeline stages.  When stages
occupy disjoint devices, asynchronous jax dispatch of the microbatched
stage programs in dependency order yields GPipe-like fill/drain
overlap without an explicit schedule: microbatch ``i`` on stage ``k``
runs concurrently with microbatch ``i+1`` on stage ``k-1``.  Stages
MAY share devices (the reference permits arbitrary per-op device
lists, ``config.h:39-48``); overlapping stages serialize on the shared
devices — Legion's semantics — and a warning notes the lost overlap.

Numerics are exactly the single-executor step: mean-reduction losses
make the microbatch-mean gradient equal the full-batch gradient (the
same invariant ``Executor.accum_train_step`` relies on).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.ops.base import Op, TensorSpec
from flexflow_tpu.ops.linear import Linear
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.parallel.mesh import (
    InfeasibleStrategyError,
    build_stage_mesh_plan,
    check_stage_mesh_feasible,
)
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime import telemetry as _telemetry
from flexflow_tpu.runtime.executor import (
    Executor,
    _merge_metrics,
    _unique_row_sums,
    mean_metrics,
)

_log = logging.getLogger("ff.pipeline")


class PlacementError(ValueError):
    pass


class CompiledPipelineUnsupported(PlacementError):
    """The compiled whole-step path cannot realize this model/strategy
    combination; callers (``make_executor``) fall back LOUDLY to the
    host-driven pipeline, which supports everything."""


@dataclasses.dataclass
class Stage:
    index: int
    device_ids: Tuple[int, ...]
    ops: List[Op]
    #: tensors flowing INTO this stage from earlier stages or the host
    in_names: List[str]
    #: tensors this stage produces that later stages consume
    out_names: List[str]


def _clip_scale_f32(total_sq, clip: float):
    """Clip-norm scale from the summed per-stage squared norms, all in
    float32 (traced form).  ``_clip_scale_f32_host`` is the bit-exact
    numpy mirror the host-driven path applies after its fence — one
    formula, two runtimes, so the compiled step (which folds this into
    the program, fence-free) stays bit-identical to the host path.
    sqrt/divide/min are correctly-rounded IEEE f32 in both numpy and
    XLA:CPU, which is what makes the mirror exact; ``rsqrt`` (an
    approximate op) is deliberately avoided."""
    return jnp.minimum(
        jnp.float32(1.0),
        jnp.float32(clip)
        / jnp.maximum(jnp.sqrt(total_sq), jnp.float32(1e-15)),
    )


def _clip_scale_f32_host(sqs, clip: float) -> float:
    """Host mirror of :func:`_clip_scale_f32`: fold the fenced per-stage
    squared norms in stage order with f32 arithmetic."""
    total = np.float32(sqs[0])
    for x in sqs[1:]:
        total = total + np.float32(x)
    return float(np.minimum(
        np.float32(1.0),
        np.float32(clip)
        / np.maximum(np.sqrt(total), np.float32(1e-15)),
    ))


class _StageModel:
    """Duck-typed FFModel slice: exactly the attributes Executor reads."""

    def __init__(self, config: FFConfig, layers: List[Op],
                 input_tensors: List[TensorSpec]):
        self.config = config
        self.layers = layers
        self.input_tensors = input_tensors


def derive_stages(model: FFModel, strategy: StrategyStore) -> List[Stage]:
    """Group ops into pipeline stages by their ``device_ids`` placement.

    Ops without an explicit placement inherit their (first) producer's
    placement — graph inputs' consumers default to the first placed
    list — mirroring the reference mapper's "same device as producer"
    default (``mapper.cc:54-197``).  A stage is a maximal CONSECUTIVE
    run of ops (graph order) sharing one placement, so interleaved
    placements (A B A) form separate stages rather than an invalid
    grouping; stages must be closed under dataflow: an op may only
    consume tensors from its own or earlier stages.
    """
    producer: Dict[str, Op] = {}
    for op in model.layers:
        for t in op.outputs:
            producer[t.name] = op

    explicit: Dict[str, Tuple[int, ...]] = {}
    for op in model.layers:
        ids = strategy.find(op.name).device_ids
        if ids is not None:
            explicit[op.name] = tuple(ids)
    if not explicit:
        raise PlacementError("no op in the strategy carries device_ids")
    first_list = next(iter(explicit.values()))

    # Placement list per op: unplaced ops inherit from their MOST
    # DOWNSTREAM input producer (greatest graph position — the
    # successor of the old max-stage rule), so a multi-input op joins
    # the latest stage feeding it instead of spawning a spurious
    # earlier-placement stage.
    order = {op.name: i for i, op in enumerate(model.layers)}
    list_of_op: Dict[str, Tuple[int, ...]] = {}
    for op in model.layers:
        if op.name in explicit:
            list_of_op[op.name] = explicit[op.name]
            continue
        inherited = None
        best = -1
        for t in op.inputs:
            p = producer.get(t.name)
            if p is not None and p.name in list_of_op and order[p.name] > best:
                best = order[p.name]
                inherited = list_of_op[p.name]
        list_of_op[op.name] = inherited if inherited is not None else first_list

    # Stages = maximal consecutive runs of one placement.
    placements: List[Tuple[int, ...]] = []
    stage_of_op: Dict[str, int] = {}
    for op in model.layers:
        ids = list_of_op[op.name]
        if not placements or placements[-1] != ids:
            placements.append(ids)
        stage_of_op[op.name] = len(placements) - 1

    # Overlap check — a device serving two stages serializes them, so
    # the GPipe fill/drain overlap vanishes there.  The reference
    # permits arbitrary per-op device lists (``config.h:39-48``; its
    # README AlexNet table reuses GPU 0 in five layers) with Legion
    # serializing on data dependencies — sequential dispatch of the
    # stage programs gives exactly those semantics, so overlap is
    # legal here too, just not pipelined.
    for si, ids in enumerate(placements):
        if len(set(ids)) != len(ids):
            raise PlacementError(
                f"stage {si} repeats a device in its device_ids {ids}; "
                f"each device may appear once per stage"
            )
    seen: Dict[int, int] = {}
    overlaps: List[Tuple[int, int, int]] = []
    for si, ids in enumerate(placements):
        for d in ids:
            if d in seen and seen[d] != si:
                overlaps.append((d, seen[d], si))
            else:
                seen[d] = si
    if overlaps:
        d, a, b = overlaps[0]
        _log.warning(
            "stage device sets overlap (device %d serves stages %d and %d"
            "%s): stages sharing devices serialize — layer-wise placement "
            "semantics are preserved but there is no pipeline overlap "
            "between them",
            d, a, b,
            f", +{len(overlaps) - 1} more" if len(overlaps) > 1 else "",
        )

    # Dataflow monotonicity holds by construction: stages are
    # consecutive runs in graph order and producers precede consumers.

    graph_inputs = {t.name for t in model.input_tensors}
    stages: List[Stage] = []
    for si, ids in enumerate(placements):
        ops = [op for op in model.layers if stage_of_op[op.name] == si]
        if not ops:
            raise PlacementError(f"stage {si} ({ids}) has no ops")
        local_out = {t.name for op in ops for t in op.outputs}
        in_names: List[str] = []
        for op in ops:
            for t in op.inputs:
                if t.name not in local_out and t.name not in in_names:
                    in_names.append(t.name)
        # Outputs consumed by later stages.
        later_needs = {
            t.name
            for op in model.layers
            if stage_of_op[op.name] > si
            for t in op.inputs
        }
        out_names = [n for n in local_out if n in later_needs]
        stages.append(Stage(si, ids, ops, in_names, sorted(out_names)))
    del graph_inputs
    return stages


def compiled_unsupported_reason(
    model: FFModel,
    strategy: StrategyStore,
    stages: Optional[List[Stage]] = None,
) -> Optional[str]:
    """``None`` when the compiled whole-step pipeline can realize this
    model/strategy, else the blocker string ``PipelineExecutor``
    raises as :class:`CompiledPipelineUnsupported`.

    The SINGLE implementation of the compiled-pipeline eligibility
    ladder — the constructor's gate AND the execution-config searcher's
    legality predicate (``search/execution.py``), so the search never
    simulates a compiled config the executor would refuse into the
    loud host-driven fallback."""
    if stages is None:
        try:
            stages = derive_stages(model, strategy)
        except PlacementError as e:
            return str(e)
    for st in stages:
        for op in st.ops:
            pc = strategy.find(op.name)
            if pc.s > 1:
                return (
                    "compiled pipeline step does not support s-degree "
                    "(explicit-collective sequence ops) inside stages yet"
                )
            if pc.h > 1 or pc.w > 1:
                # Spatial partials reduce across devices; their
                # reduction order on the shared stage mesh is
                # unverified against the submesh (the c-degree needed
                # an explicit pin in Linear.forward — same hazard
                # class).
                return (
                    f"compiled pipeline step: spatial (h/w) degree on "
                    f"{op.name!r} is unverified against the host "
                    f"path's submesh numerics"
                )
            if pc.c > 1 and not isinstance(op, Linear):
                # Linear pins its contraction operand so the dot
                # lowers identically on both meshes (ops/linear.py);
                # other c-sharded ops keep partitioner-chosen
                # reduction orders.
                return (
                    f"compiled pipeline step: c-degree on non-Linear "
                    f"op {op.name!r} is unverified against the host "
                    f"path's submesh numerics"
                )
    try:
        check_stage_mesh_feasible([st.device_ids for st in stages])
    except InfeasibleStrategyError as e:
        return f"compiled pipeline step: {e}"
    return None


class PipelineExecutor:
    """Executes an FFModel whose strategy places op groups on device
    subsets (disjoint or overlapping) — the runtime realization of
    ``device_ids`` (simulator-only in round 1).

    ``microbatches`` splits the batch GPipe-style; 1 reproduces the
    reference's plain layer-wise placement (compute still pipelined
    across *iterations* by async dispatch, as Legion's dataflow did).

    ``chunk`` is the microbatch chunk factor ``c``: each stage's
    forward (and backward, with in-scan gradient accumulation) runs as
    ONE jitted ``lax.scan`` over ``c`` stacked microbatches, cutting
    host programs per step from ``2*S*m`` to ``2*S*ceil(m/c)`` — the
    pipeline's answer to the per-program dispatch floor
    (PIPELINE_OVERHEAD.md; ~1.4-1.6 ms/program on the CPU mesh, not
    measured on the chip).  ``c=1`` reproduces the per-microbatch
    event loop exactly; ``c=m`` is the dispatch-minimal GPipe-shaped
    limit.  Numerics are bit-identical across ``c``: the scan carries
    the running per-stage gradient (and last-stage metrics) sum, so
    accumulation order is microbatch order regardless of chunking.
    The memory tradeoff is explicit: the 1F1B live-activation bound
    becomes chunk-granular (at most ``(S-si)*c`` microbatch
    activations live per stage instead of ``S-si``).

    ``compiled=True`` (``--pipeline-compiled``) replaces the
    host-orchestrated event loop with ONE jitted whole-step program on
    a shared stage-shaped mesh (:func:`~flexflow_tpu.parallel.mesh.
    build_stage_mesh_plan`): every stage's microbatch ``lax.scan``
    (forward AND remat backward), the boundary activation/cotangent
    exchange, global clip-norm, and the per-stage optimizer updates
    are a single compiled dispatch — host programs per step drop from
    ``2*S*ceil(m/c)`` to 1, and the step becomes fence-free compiled
    IR, which is what lets :meth:`build_superstep` wrap it in the
    donated-carry ``lax.scan`` (one dispatch + one ``device_get`` per
    k steps; ``StrategyStore.superstep_mode(compiled=True)`` ==
    ``"fused"``).  Numerics are BIT-identical to the host-driven path:
    the compiled trace reuses the exact per-stage chunked-scan bodies
    at ``c=m`` — same accumulation carries, same microbatch order,
    same cotangent-summation order — and every stage keeps the exact
    submesh axis factorization (and thus reduction orders) of the
    host path via the shared stage plan
    (tests/test_pipeline_chunk.py pins parity incl. dropout, clip-norm
    and skip connections).  Tradeoffs, stated honestly: ALL stages'
    params/grads/compute live on ONE stage-group-sized mesh (per-device
    memory = the sum of every stage's shard — identical to replicating
    along a stage axis, which each device of a stage-major mesh also
    pays), and the whole-step program sequences stages as data
    dependencies rather than overlapping them across device subsets.
    A manual ``shard_map`` over a stage axis with ``lax.ppermute``
    boundary exchange would confine each stage's compute to its own
    devices, but on the baked-in jax 0.4.37/XLA the required
    partial-auto mode hard-crashes the SPMD partitioner
    (CollectivePermute/AllGather with manual subgroups:
    ``spmd_partitioner.cc:512 Check failed:
    target.IsManualSubgroup()``; reading back a scan-carried remat
    stash: ``hlo_sharding_util.cc:2750``) — measured 2026-08-04,
    revisit on the next jax upgrade (ROADMAP; the interim stage-major
    GSPMD form was measured S x slower — see build_stage_mesh_plan).

    ``accum_steps > 1`` (``--accum-steps`` on layer-wise strategies)
    lowers gradient accumulation onto the same microbatch machinery:
    accumulating ``a`` groups of ``m`` microbatches IS the pipeline
    loop over ``a*m`` microbatches (mean-reduction losses make the
    microbatch-mean gradient the full-batch gradient either way), so
    the executor simply multiplies the microbatch count and every
    execution path — event loop, chunked scan, compiled step —
    composes unchanged.
    """

    def __init__(
        self,
        model: FFModel,
        strategy: StrategyStore,
        config: Optional[FFConfig] = None,
        optimizer: Optional[SGDOptimizer] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        microbatches: int = 1,
        schedule: str = "1f1b",
        chunk: int = 1,
        compiled: bool = False,
        accum_steps: int = 1,
    ):
        self.model = model
        self.config = config or model.config
        if getattr(self.config, "zero_sharded_optimizer", False):
            # Loudly reject rather than half-apply: stage init would
            # shard moments but this executor's update path would not
            # re-pin them (Executor.__init__ rejects unrealizable
            # placements the same way).
            raise PlacementError(
                "--zero-opt supports the full-mesh Executor only: ZeRO "
                "moment sharding is per-op over the op's data-parallel "
                "mesh axes, and layer-wise strategies would need it "
                "PER-SUBMESH (each stage's moments split over that "
                "stage's own devices) — not implemented; layer-wise "
                "strategies keep replicated optimizer state"
            )
        self.optimizer = optimizer or SGDOptimizer(
            lr=self.config.learning_rate, weight_decay=self.config.weight_decay
        )
        # Row-sparse embedding updates (--sparse-embeddings /
        # --lazy-sparse-opt) ride the per-stage sparse carry: each
        # stage Executor's _sparse_ops gate runs against the STAGE
        # model (ids entering the stage are stage graph-inputs), the
        # stage backward differentiates (dense_params, xs, rows) and
        # emits (flat_ids, row_grads) per sparse op, the host loop
        # concatenates them in microbatch order, and _finish_step /
        # _compiled_step_impl apply the executor's row update
        # (_stage_update_sparse) on the stage's own submesh.
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        self.accum_steps = accum_steps
        if accum_steps > 1:
            # Lowering, not a separate path: accumulating a groups of m
            # microbatches == the microbatch loop over a*m microbatches
            # (see class docstring).
            _log.info(
                "accum_steps=%d on a layer-wise strategy: lowered onto "
                "the microbatch loop (%d x %d = %d microbatches per "
                "optimizer step)",
                accum_steps, accum_steps, microbatches,
                accum_steps * microbatches,
            )
            microbatches = accum_steps * microbatches
        self.microbatches = microbatches
        if chunk < 1:
            raise ValueError(f"pipeline chunk must be >= 1, got {chunk}")
        if chunk > microbatches:
            _log.warning(
                "pipeline chunk %d exceeds microbatches %d; clamping "
                "(c=m is already the dispatch-minimal limit)",
                chunk, microbatches,
            )
            chunk = microbatches
        self.chunk = chunk
        self.compiled = bool(compiled)
        if schedule not in ("1f1b", "gpipe"):
            raise ValueError(f"unknown pipeline schedule {schedule!r}")
        self.schedule = schedule
        #: dispatch-order event trace of the last train_step — a list of
        #: ("F"|"B", stage, unit) where a unit is a microbatch (chunk=1)
        #: or a CHUNK of ``chunk`` stacked microbatches; tests and the
        #: dry run verify the schedule by EVENT ORDER, not wall clock
        #: (the virtual mesh multiplexes one core,
        #: PIPELINE_OVERHEAD.md).  len(last_schedule) is exactly the
        #: fwd+bwd host program count of the step: 2*S*ceil(m/c).
        self.last_schedule: List[Tuple[str, int, int]] = []
        self._zero_douts: Dict[Tuple, jax.Array] = {}
        self._zero_grads_cache: Dict[int, Any] = {}
        self._zero_metrics_cache: Dict[int, Any] = {}
        all_devices = list(devices) if devices is not None else jax.devices()
        self.stages = derive_stages(model, strategy)

        spec_of = {t.name: t for op in model.layers for t in op.outputs}
        for t in model.input_tensors:
            spec_of[t.name] = t
        self._spec_of = spec_of
        self._producer: Dict[str, Op] = {
            t.name: op for op in model.layers for t in op.outputs
        }

        for st in self.stages:
            for d in st.device_ids:
                if d >= len(all_devices):
                    raise PlacementError(
                        f"stage {st.index} places on device {d} but only "
                        f"{len(all_devices)} devices exist"
                    )

        self._stage_plan = None
        if self.compiled:
            # Eligibility gate for the compiled whole-step path; every
            # refusal names the blocker so make_executor can fall back
            # loudly to the host-driven runtime.  ONE implementation
            # (compiled_unsupported_reason) shared with the
            # execution-config searcher, so a config the search emits
            # is never one this constructor refuses into fallback.
            reason = compiled_unsupported_reason(
                model, strategy, stages=self.stages
            )
            if reason is not None:
                raise CompiledPipelineUnsupported(reason)
            self._stage_plan = build_stage_mesh_plan(
                [st.device_ids for st in self.stages],
                devices=all_devices,
            )
            self._compiled_step_fn = None
            self._compiled_superstep_cache: Dict[int, Any] = {}

        self.stage_ex: List[Executor] = []
        for st in self.stages:
            sub_devices = [all_devices[d] for d in st.device_ids]
            # Intra-stage strategy: same degrees, no placement, DP
            # fallback sized to the submesh.
            table = {
                op.name: dataclasses.replace(
                    strategy.find(op.name), device_ids=None
                )
                for op in st.ops
                if op.name in strategy
            }
            sub_store = StrategyStore(len(sub_devices), table)
            sub_model = _StageModel(
                self.config, st.ops, [spec_of[n] for n in st.in_names]
            )
            self.stage_ex.append(
                Executor(
                    sub_model,
                    config=self.config,
                    strategy=sub_store,
                    optimizer=self.optimizer,
                    # Compiled mode: every stage compiles against the
                    # SAME compact stage-shaped mesh, with the exact
                    # axis factorization a stand-alone submesh gets —
                    # the per-op strategy mapping is preserved, only
                    # the device identity changes.  Host mode keeps
                    # the per-stage submeshes.
                    mesh_plan=self._stage_plan if self.compiled else None,
                    devices=None if self.compiled else sub_devices,
                )
            )

    # -- init --------------------------------------------------------------

    def init(self, seed: Optional[int] = None):
        params, opt_state, state = {}, {}, {}
        for si, ex in enumerate(self.stage_ex):
            p, o, s = ex.init(None if seed is None else seed + si)
            params[si] = p
            opt_state[si] = o
            state[si] = s
        return params, opt_state, state

    # -- per-stage compiled pieces ----------------------------------------

    def _stage_fwd(self, si: int):
        """(params, state, inputs) -> (outs, loss, metrics, new_state)."""
        ex, st = self.stage_ex[si], self.stages[si]

        def fwd(params, state, inputs):
            loss, metrics, new_state, env = ex.forward(
                params, state, inputs, training=True
            )
            outs = {n: env[n] for n in st.out_names}
            return outs, loss, metrics, new_state

        return jax.jit(fwd)

    @functools.cached_property
    def _stage_sparse(self) -> List[List[Op]]:
        """Per-stage row-sparse ops (the executor's ``_sparse_ops``
        gate run against the STAGE model: ids flowing into the stage
        are stage graph-inputs, the plan/pc checks use the stage's own
        submesh).  Non-empty entries switch that stage's backward to
        the sparse carry and its update to the row form."""
        return [ex._sparse_ops for ex in self.stage_ex]

    def _dense_stage_params(self, si: int, params_si):
        """The subtree the stage backward differentiates: full params
        minus the sparse ops' tables (those get row cotangents)."""
        names = {op.name for op in self._stage_sparse[si]}
        if not names:
            return params_si
        return {k: v for k, v in params_si.items() if k not in names}

    def _stage_bwd(self, si: int):
        """(params, state, inputs, douts, dloss) -> (dparams, dinputs,
        metrics, new_state, sparse).  Recomputes the stage forward
        (remat at stage boundaries) so the fwd pass stores only stage
        inputs.  ``sparse`` maps each sparse op's name to its
        ``(flat_ids, flat_row_grads)`` for this microbatch (``{}`` on
        dense stages); ``dparams`` then spans only the dense subtree —
        the table never materializes a dense gradient."""
        ex, st = self.stage_ex[si], self.stages[si]
        diffable = self._diffable_inputs(si)
        sparse_ops = self._stage_sparse[si]
        sparse_names = {op.name for op in sparse_ops}

        def bwd(params, state, inputs, douts, dloss):
            const = {k: v for k, v in inputs.items() if k not in diffable}
            xs = {k: v for k, v in inputs.items() if k in diffable}
            rows, ids = {}, {}
            for op in sparse_ops:
                op.bind_mesh(ex.plan, ex._pc(op))
                op_xs = [inputs[t.name] for t in op.inputs]
                rows[op.name] = op.sparse_rows(params[op.name], op_xs)
                ids[op.name] = op.sparse_flat_ids(params[op.name], op_xs)
            dense = {k: v for k, v in params.items()
                     if k not in sparse_names}

            def f(p, x, r):
                loss, metrics, new_state, env = ex.forward(
                    p, state, {**const, **x}, training=True,
                    rows_override=r or None,
                )
                outs = {n: env[n] for n in st.out_names}
                return (outs, loss), (metrics, new_state)

            (_, _), vjp, (metrics, new_state) = jax.vjp(
                f, dense, xs, rows, has_aux=True
            )
            dparams, dxs, drows = vjp((douts, dloss))
            sparse = {
                n: (ids[n].reshape(-1),
                    drows[n].reshape(-1, drows[n].shape[-1]))
                for n in drows
            }
            return dparams, dxs, metrics, new_state, sparse

        return jax.jit(bwd)

    def _diffable_inputs(self, si: int) -> set:
        """Stage inputs that need cotangents: those produced by an
        earlier stage AND float-typed (ids/labels carry no gradient)."""
        graph_inputs = {t.name for t in self.model.input_tensors}
        out = set()
        for n in self.stages[si].in_names:
            if n in graph_inputs:
                continue
            if jnp.issubdtype(self._spec_of[n].dtype, jnp.floating):
                out.add(n)
        return out

    @functools.cached_property
    def _fwd_fns(self):
        return [self._stage_fwd(i) for i in range(len(self.stages))]

    @functools.cached_property
    def _bwd_fns(self):
        return [self._stage_bwd(i) for i in range(len(self.stages))]

    # -- chunked-scan stage programs ----------------------------------------
    #
    # One jitted lax.scan per (stage, chunk) instead of one program per
    # (stage, microbatch): the scan body is EXACTLY the per-microbatch
    # program, state/gradient/metric accumulation threads through the
    # carry in microbatch order, so numerics are bit-identical to the
    # chunk=1 event loop (pinned by tests/test_pipeline_chunk.py).

    def _stage_fwd_chunk(self, si: int):
        """(params, state, stacked_inputs) -> (stacked_outs,
        stacked_prestates, new_state).  ``stacked_inputs`` carries a
        leading chunk dim; the scan threads stage state (BN stats,
        dropout RNG) through the microbatches in order and emits each
        microbatch's PRE-forward state for the backward's remat."""
        ex, st = self.stage_ex[si], self.stages[si]

        def fwd(params, state, stacked):
            def body(s, xs):
                _, _, new_s, env = ex.forward(params, s, xs, training=True)
                outs = {n: env[n] for n in st.out_names}
                return new_s, (outs, s)

            new_state, (outs, prestates) = jax.lax.scan(body, state, stacked)
            return outs, prestates, new_state

        return jax.jit(fwd)

    def _stage_bwd_chunk(self, si: int):
        """(params, prestates, stacked_inputs, stacked_douts, dloss,
        grads_acc, metrics_acc) -> (grads, metrics, stacked_dxs).

        The scan carries the RUNNING per-stage gradient sum (and, for
        the last stage, the running metrics sum): the caller passes the
        accumulated value from the previous chunk (zeros for the
        first), so cross-chunk accumulation order is microbatch order —
        the bit-identity-across-``c`` invariant.  ``metrics_acc=None``
        (every stage but the last) drops metrics from the carry."""
        ex, st = self.stage_ex[si], self.stages[si]
        diffable = self._diffable_inputs(si)
        sparse_ops = self._stage_sparse[si]
        sparse_names = {op.name for op in sparse_ops}

        def bwd(params, prestates, inputs, douts, dloss, grads_acc,
                metrics_acc):
            const_in = {k: v for k, v in inputs.items() if k not in diffable}
            xs_in = {k: v for k, v in inputs.items() if k in diffable}
            dense = {k: v for k, v in params.items()
                     if k not in sparse_names}

            def body(carry, per_mb):
                s, const, xs, dd = per_mb
                rows, ids = {}, {}
                for op in sparse_ops:
                    op.bind_mesh(ex.plan, ex._pc(op))
                    mb_in = {**const, **xs}
                    op_xs = [mb_in[t.name] for t in op.inputs]
                    rows[op.name] = op.sparse_rows(params[op.name], op_xs)
                    ids[op.name] = op.sparse_flat_ids(
                        params[op.name], op_xs
                    )

                def f(p, x, r):
                    loss, metrics, new_state, env = ex.forward(
                        p, s, {**const, **x}, training=True,
                        rows_override=r or None,
                    )
                    outs = {n: env[n] for n in st.out_names}
                    return (outs, loss), (metrics, new_state)

                (_, _), vjp, (metrics, _) = jax.vjp(
                    f, dense, xs, rows, has_aux=True
                )
                dparams, dxs, drows = vjp((dd, dloss))
                sparse = {
                    n: (ids[n].reshape(-1),
                        drows[n].reshape(-1, drows[n].shape[-1]))
                    for n in drows
                }
                if metrics_acc is None:
                    g = jax.tree.map(jnp.add, carry, dparams)
                    return g, (dxs, sparse)
                g, macc = carry
                g = jax.tree.map(jnp.add, g, dparams)
                macc = {k: macc[k] + metrics[k] for k in macc}
                return (g, macc), (dxs, sparse)

            init = (
                grads_acc if metrics_acc is None
                else (grads_acc, metrics_acc)
            )
            carry, (dxs, sparse) = jax.lax.scan(
                body, init, (prestates, const_in, xs_in, douts)
            )
            # Stacked (L, n, ...) per-microbatch sparse carries flatten
            # to the concatenation in microbatch order — the same order
            # the chunk=1 event loop appends in.
            sparse = {
                n: (i.reshape(-1), g.reshape(-1, g.shape[-1]))
                for n, (i, g) in sparse.items()
            }
            if metrics_acc is None:
                return carry, None, dxs, sparse
            g, macc = carry
            return g, macc, dxs, sparse

        return jax.jit(bwd)

    @functools.cached_property
    def _fwd_chunk_fns(self):
        return [self._stage_fwd_chunk(i) for i in range(len(self.stages))]

    @functools.cached_property
    def _bwd_chunk_fns(self):
        return [self._stage_bwd_chunk(i) for i in range(len(self.stages))]

    def _zero_grads(self, si: int, params_si):
        """Cached zero gradient tree for stage ``si`` — the first
        chunk's carry init.  NEVER donated (the same buffers seed every
        step); adding 0 to the first microbatch's gradient is bit-exact
        (the chunk=1 path starts from the gradient itself)."""
        z = self._zero_grads_cache.get(si)
        if z is None:
            # Sparse stages carry gradients only for the DENSE subtree
            # (tables flow as (flat_ids, row_grads) instead).
            z = self._zero_grads_cache[si] = jax.jit(
                lambda p: jax.tree.map(jnp.zeros_like, p)
            )(self._dense_stage_params(si, params_si))
        return z

    def _abstract_zero_metrics(self, si: int, params_si, prestates, inputs):
        """Zero metrics tree for stage ``si``'s backward-scan carry:
        structure from an eval_shape of the stage forward at microbatch
        shapes (leading chunk dim stripped) — no device compute, and
        trace-safe (``jax.eval_shape`` only reads shapes/dtypes, so the
        compiled step can call this on tracers)."""
        elem = lambda v: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
        p_avals = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), params_si
        )
        s_avals = jax.tree.map(elem, prestates)
        x_avals = jax.tree.map(elem, inputs)

        def f(p, s, xs):
            _, metrics, _, _ = self.stage_ex[si].forward(
                p, s, xs, training=True
            )
            return metrics

        m_avals = jax.eval_shape(f, p_avals, s_avals, x_avals)
        return {
            k: jnp.zeros(a.shape, a.dtype) for k, a in m_avals.items()
        }

    def _zero_metrics(self, si: int, params_si, prestates, inputs):
        """Cached device-resident zero metrics (host chunked path) —
        computed once per stage, never donated."""
        z = self._zero_metrics_cache.get(si)
        if z is None:
            z = self._zero_metrics_cache[si] = self._abstract_zero_metrics(
                si, params_si, prestates, inputs
            )
        return z

    @functools.cached_property
    def _grad_sq_fns(self):
        def make(si):
            def sq(grads):
                return sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(grads)
                )

            return jax.jit(sq)

        return [make(i) for i in range(len(self.stages))]

    @functools.cached_property
    def _scale_fns(self):
        def make(si):
            def scale(grads, s):
                return jax.tree.map(
                    lambda g: (g * s).astype(g.dtype), grads
                )

            return jax.jit(scale)

        return [make(i) for i in range(len(self.stages))]

    @functools.cached_property
    def _opt_fns(self):
        def make(si):
            def upd(params, opt_state, grads):
                return self.optimizer.update(params, opt_state, grads)

            return jax.jit(upd, donate_argnums=(0, 1))

        return [make(i) for i in range(len(self.stages))]

    # -- per-stage sparse carry ---------------------------------------------
    #
    # Sparse stages never materialize a table-sized gradient: the stage
    # backward emits (flat_ids, row_grads) per sparse op, the host loop
    # (or the chunk scan) concatenates them in microbatch order, and
    # the tail below applies the executor's row update on the stage's
    # own submesh.  One traced body (_stage_update_sparse /
    # _stage_sq_sparse) serves both the host-driven jits and the
    # compiled whole-step trace, so host-vs-compiled bit-identity holds
    # by construction.

    def _stage_sq_sparse(self, si: int, grads, sparse):
        """Stage clip-norm squared term with the sparse carries folded
        in: each sparse op's UNIQUE-row summed cotangent squares (the
        dense table gradient sums duplicate-id cotangents BEFORE
        squaring) plus the dense leaves' squares — `extra_sq` first,
        the same fold order as ``Executor._clip_scale``."""
        extra = sum(
            jnp.sum(jnp.square(
                _unique_row_sums(ids, g)[1].astype(jnp.float32)
            ))
            for ids, g in sparse.values()
        )
        return extra + self._grad_sq_fns[si](grads)

    def _stage_update_sparse(self, si: int, params, opt_state, grads,
                             sparse, scale):
        """Sparse-stage optimizer tail (mirrors the full-mesh
        ``Executor.build_train_step`` sparse tail): dense update over
        the filtered param/optimizer-state trees, then one row update
        per sparse op — stateless: per-occurrence scatter of
        ``-lr*g``; stateful (lazy momentum/Adam): unique-row sums into
        the optimizer's row step.  ``scale`` is the clip factor for
        the ROW grads (the dense grads arrive pre-scaled), or None
        when clip is off."""
        from flexflow_tpu.ops.embedding import _scatter_add_dispatch

        ex = self.stage_ex[si]
        sparse_ops = [
            op for op in self._stage_sparse[si] if op.name in sparse
        ]
        sparse_names = {op.name for op in sparse_ops}
        stateless = getattr(self.optimizer, "stateless_sparse", True)
        dense = {k: v for k, v in params.items() if k not in sparse_names}
        opt_dense = self.optimizer.map_param_states(
            opt_state,
            lambda tree: {k: v for k, v in tree.items()
                          if k not in sparse_names},
        )
        new_params, new_opt = self.optimizer.update(dense, opt_dense, grads)
        new_params = dict(new_params)
        new_opt = self.optimizer.restore_param_states(
            new_opt, opt_state, sparse_names
        ) if new_opt is not None else None
        lr = self.optimizer.lr
        for op in sparse_ops:
            op.bind_mesh(ex.plan, ex._pc(op))
            ids, g = sparse[op.name]
            if stateless:
                if scale is not None:
                    g = g * scale
                key = op.sparse_keys()[0]
                table = params[op.name][key]
                new_params[op.name] = {
                    **params[op.name],
                    key: _scatter_add_dispatch(op, table, ids, -lr * g),
                }
            else:
                uniq = _unique_row_sums(ids, g)
                new_params[op.name], new_opt = ex._sparse_stateful_apply(
                    op, params[op.name], new_opt, uniq, scale
                )
        return new_params, new_opt

    @functools.cached_property
    def _sparse_sq_fns(self):
        def make(si):
            def sq(grads, sparse):
                return self._stage_sq_sparse(si, grads, sparse)

            return jax.jit(sq)

        return [make(i) for i in range(len(self.stages))]

    @functools.cached_property
    def _sparse_opt_fns(self):
        def make(si):
            def upd(params, opt_state, grads, sparse, scale):
                return self._stage_update_sparse(
                    si, params, opt_state, grads, sparse, scale
                )

            return jax.jit(upd, donate_argnums=(0, 1))

        return [make(i) for i in range(len(self.stages))]

    @functools.cached_property
    def _sparse_concat_fns(self):
        """Per-stage jitted concat of the per-unit (ids, row_grads)
        carries in microbatch order — ONE host dispatch per sparse
        stage per step (PIPELINE_OVERHEAD.md: dispatch cost is per
        call)."""
        def make(si):
            # Pin the carry REPLICATED on the stage submesh: the
            # per-microbatch loop hands over batch-sharded pieces while
            # the chunked scan's flatten hands over replicated ones —
            # without one canonical spec the row-update program
            # partitions its duplicate-id scatter differently per
            # producer and chunk invariance loses bit-identity.
            rep = self.stage_ex[si].plan.replicated()

            def cat(pieces):
                return {
                    n: (
                        jax.lax.with_sharding_constraint(
                            jnp.concatenate([p[n][0] for p in pieces]),
                            rep,
                        ),
                        jax.lax.with_sharding_constraint(
                            jnp.concatenate(
                                [p[n][1] for p in pieces], axis=0
                            ),
                            rep,
                        ),
                    )
                    for n in pieces[0]
                }

            return jax.jit(cat)

        return [make(i) for i in range(len(self.stages))]

    def _concat_sparse(self, sparse_acc: Dict[int, List[Any]]):
        """Fold the per-unit sparse carries collected by the event
        loops into per-stage ``{op: (ids, row_grads)}`` concatenations
        (microbatch order — the accumulation-order invariant).  Single
        pieces still route through the jitted concat for its canonical
        replicated output sharding."""
        out = {}
        for si, pieces in sparse_acc.items():
            if not pieces:
                continue
            out[si] = self._sparse_concat_fns[si](tuple(pieces))
        return out

    # -- data movement ------------------------------------------------------

    def _put_stage(self, si: int, name: str, x):
        """Place tensor ``name`` into stage ``si``'s submesh with the
        sharding its consumer there wants."""
        ex = self.stage_ex[si]
        spec = self._spec_of[name]
        return jax.device_put(x, ex.input_sharding(spec))

    @functools.cached_property
    def _in_shardings(self) -> List[Dict[str, Any]]:
        """Per-stage input shardings, precomputed so a stage's whole
        input set moves in ONE ``jax.device_put`` call (host dispatch is
        the pipeline's measured bottleneck, PIPELINE_OVERHEAD.md)."""
        return [
            {n: self.stage_ex[si].input_sharding(self._spec_of[n])
             for n in st.in_names}
            for si, st in enumerate(self.stages)
        ]

    def _put_stage_many(self, si: int, values: Dict[str, Any]) -> Dict[str, Any]:
        sh = self._in_shardings[si]
        return jax.device_put(values, {n: sh[n] for n in values})

    @staticmethod
    def _stacked(sh: NamedSharding) -> NamedSharding:
        """The same sharding under an unsharded leading chunk dim."""
        return NamedSharding(sh.mesh, PartitionSpec(None, *sh.spec))

    @functools.cached_property
    def _chunk_in_shardings(self) -> List[Dict[str, Any]]:
        """Per-stage input shardings with the leading chunk dim
        unsharded — the chunked analogue of ``_in_shardings``."""
        return [
            {n: self._stacked(sh) for n, sh in per_stage.items()}
            for per_stage in self._in_shardings
        ]

    def _put_stage_many_chunk(self, si: int, values: Dict[str, Any]):
        sh = self._chunk_in_shardings[si]
        return jax.device_put(values, {n: sh[n] for n in values})

    def shard_batch(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        """Graph inputs land on the stage that consumes them — one
        batched ``device_put`` per stage (dispatch cost is per call,
        not per array, the round-5 train_step fix)."""
        graph_inputs = {t.name for t in self.model.input_tensors}
        out = dict(batch)
        for si, st in enumerate(self.stages):
            vals = {
                n: batch[n]
                for n in st.in_names
                if n in graph_inputs and n in batch
            }
            if vals:
                out.update(self._put_stage_many(si, vals))
        return out

    # -- the step -----------------------------------------------------------

    def _split_micro(self, batch, m):
        if m == 1:
            return [batch]
        outs = []
        for i in range(m):
            piece = {}
            for k, v in batch.items():
                assert v.shape[0] % m == 0, (k, v.shape, m)
                sz = v.shape[0] // m
                piece[k] = v[i * sz:(i + 1) * sz]
            outs.append(piece)
        return outs

    def build_schedule(self, S: int, m: int) -> List[Tuple[str, int, int]]:
        """Dispatch-order event list ``("F"|"B", stage, unit)`` where a
        unit is a microbatch (``chunk=1``) or a chunk of stacked
        microbatches (``train_step`` passes ``ceil(m/c)`` units) — one
        event == one host program either way, so ``len(...)`` audits
        the per-step dispatch count.

        ``gpipe``: all forwards (fill), then all backwards (drain) —
        every microbatch's activations live simultaneously.

        ``1f1b``: each stage runs ``min(m, S-1-si)`` warmup forwards,
        then alternates one-backward-one-forward, then drains — at most
        ``S-si`` activations live per stage, and backwards start before
        the fill completes (Megatron-LM's non-interleaved schedule; the
        reference gets the equivalent overlap from Legion dataflow,
        ``rnn.cu:519-557``).  Per-stage sequences are merged by a
        discrete-slot simulation: an op dispatches in the first slot
        after its dependency (F on F of the previous stage, B on B of
        the next stage, same microbatch) so the emitted order is a
        valid async-dispatch order for the per-device program queues.
        """
        if self.schedule == "gpipe":
            return (
                [("F", si, mi) for mi in range(m) for si in range(S)]
                + [("B", si, mi) for mi in range(m)
                   for si in range(S - 1, -1, -1)]
            )
        seqs: List[List[Tuple[str, int]]] = []
        for si in range(S):
            w = min(m, S - 1 - si)
            seq = [("F", j) for j in range(w)]
            for j in range(m - w):
                seq.append(("F", j + w))
                seq.append(("B", j))
            seq += [("B", j) for j in range(m - w, m)]
            seqs.append(seq)
        done: set = set()
        ptr = [0] * S
        events: List[Tuple[str, int, int]] = []
        while any(ptr[si] < len(seqs[si]) for si in range(S)):
            fired: List[Tuple[str, int, int]] = []
            for si in range(S):
                if ptr[si] >= len(seqs[si]):
                    continue
                kind, mi = seqs[si][ptr[si]]
                dep = (
                    None if (kind == "F" and si == 0)
                    or (kind == "B" and si == S - 1)
                    else (kind, si - 1 if kind == "F" else si + 1, mi)
                )
                if dep is None or dep in done:
                    fired.append((kind, si, mi))
                    ptr[si] += 1
            if not fired:  # cannot happen for well-formed sequences
                raise RuntimeError("pipeline schedule deadlock")
            events.extend(fired)
            done.update(fired)
        return events

    def _zero_dout(self, si: int, name: str, y, stacked: bool = False):
        """Cached zero cotangent for an output with no downstream
        gradient — identical every microbatch and step, so one device
        buffer serves all of them (never donated).  ``stacked`` keys a
        chunk-shaped buffer (leading chunk dim unsharded)."""
        key = (si, name, tuple(y.shape), str(y.dtype), stacked)
        z = self._zero_douts.get(key)
        if z is None:
            sh = self.stage_ex[si].output_sharding(
                self._producer[name], self._spec_of[name]
            )
            if stacked:
                sh = self._stacked(sh)
            z = self._zero_douts[key] = jax.device_put(
                jnp.zeros(y.shape, y.dtype), sh
            )
        return z

    def _collect_douts(self, si: int, dout_acc: Dict[str, List[Any]],
                       boundary_u: Dict[str, Any], stacked: bool):
        """Assemble the backward's output cotangents for one unit
        (microbatch, or chunk when ``stacked``): sum the downstream
        contributions on the producer's mesh — a skip connection
        consumed by several later stages contributes several — or use
        the cached zero cotangent (shape from the actual unit value,
        not the full-batch spec).  Consumed here: every later stage's
        backward (the only writers) already fired, so drop the
        cotangent list AND this output's activation — without this,
        peak memory scales with m and the 1F1B bound is fiction (all
        of a unit's forwards precede its first backward, so no later
        event reads the activation)."""
        ex, st = self.stage_ex[si], self.stages[si]
        douts = {}
        for n in st.out_names:
            contribs = dout_acc.pop(n, None)
            if contribs:
                sh = ex.output_sharding(self._producer[n], self._spec_of[n])
                if stacked:
                    sh = self._stacked(sh)
                parts = [jax.device_put(g, sh) for g in contribs]
                total = parts[0]
                for p in parts[1:]:
                    total = total + p
                douts[n] = total
            else:
                douts[n] = self._zero_dout(si, n, boundary_u[n],
                                           stacked=stacked)
            boundary_u.pop(n, None)
        return douts

    def train_step(self, params, opt_state, state, batch):
        """One optimizer step: microbatched pipelined fwd+bwd, grads
        meaned over microbatches, per-stage optimizer updates.  Stage
        programs dispatch in ``build_schedule`` order (1F1B by
        default); numerics are schedule-invariant AND chunk-invariant —
        per-stage gradient accumulation always runs in microbatch
        order.  With ``clip_norm == 0`` the step is FENCE-FREE (no
        ``device_get``), which is what lets ``Trainer.fit`` amortize
        the host fence over ``steps_per_call`` pipeline steps; with
        ``clip_norm > 0`` one batched fence per step remains (the
        global norm couples all stages host-side — the documented
        one-fence-per-step floor).  ``compiled=True`` replaces all of
        this with ONE jitted whole-step program (clip-norm included,
        no fence floor at all)."""
        if self.compiled:
            fn = self.build_compiled_step()
            _telemetry.current().program_cost(
                "pipeline_compiled_step", fn,
                (params, opt_state, state, batch), S=len(self.stages))
            self.note_fused_dispatch()
            return fn(params, opt_state, state, batch)
        if self.chunk > 1:
            grads, stage_state, metrics_acc, sparse = self._run_chunked(
                params, state, batch
            )
        else:
            grads, stage_state, metrics_acc, sparse = self._run_microbatched(
                params, state, batch
            )
        return self._finish_step(params, opt_state, stage_state, grads,
                                 metrics_acc, sparse)

    def _run_microbatched(self, params, state, batch):
        """The chunk=1 event loop: one fwd/bwd program per (stage,
        microbatch) event."""
        m = self.microbatches
        S = len(self.stages)
        micros = self._split_micro(batch, m)
        graph_inputs = {t.name for t in self.model.input_tensors}

        # Stage state threads sequentially through microbatches (BN
        # running stats) — both schedules fire a stage's forwards in
        # microbatch order, so the threading is schedule-invariant.
        stage_state = dict(state)
        stage_inputs: List[List[Dict[str, Any]]] = [[None] * S for _ in range(m)]
        fwd_state: List[List[Any]] = [[None] * S for _ in range(m)]
        boundary: List[Dict[str, Any]] = [dict() for _ in range(m)]
        dloss_seed = jnp.float32(1.0 / m)
        grads = {si: None for si in range(S)}
        metrics_acc: Dict[str, jax.Array] = {}
        # Per-stage per-microbatch sparse carries, appended in B-event
        # order == microbatch order (both schedules fire a stage's
        # backwards in microbatch order).
        sparse_acc: Dict[int, List[Any]] = {si: [] for si in range(S)}
        # name -> list of cotangent contributions per microbatch (one
        # per consumer stage; a skip connection consumed by several
        # later stages contributes several — they SUM, on the
        # producer's mesh).
        dout_back: List[Dict[str, List[Any]]] = [dict() for _ in range(m)]

        events = self.build_schedule(S, m)
        self.last_schedule = events
        # Run telemetry folds the schedule into host-programs-per-step
        # counters (len(events) == 2*S*m fwd/bwd programs this step).
        _telemetry.current().add_programs(len(events))
        for kind, si, mi in events:
            st = self.stages[si]
            if kind == "F":
                vals = {
                    n: (micros[mi][n] if n in graph_inputs
                        else boundary[mi][n])
                    for n in st.in_names
                }
                # One device_put moves the whole input set (dispatch
                # cost is per call, not per array).
                inputs = self._put_stage_many(si, vals)
                stage_inputs[mi][si] = inputs
                fwd_state[mi][si] = stage_state[si]
                _telemetry.current().program_cost(
                    "pipeline_stage_fwd", self._fwd_fns[si],
                    (params[si], stage_state[si], inputs), stage=si)
                outs, _, _, new_state = self._fwd_fns[si](
                    params[si], stage_state[si], inputs
                )
                stage_state[si] = new_state
                boundary[mi].update(outs)
                continue
            douts = self._collect_douts(si, dout_back[mi], boundary[mi],
                                        stacked=False)
            _telemetry.current().program_cost(
                "pipeline_stage_bwd", self._bwd_fns[si],
                (params[si], fwd_state[mi][si], stage_inputs[mi][si],
                 douts, dloss_seed), stage=si)
            dparams, dxs, mets, _, sp = self._bwd_fns[si](
                params[si], fwd_state[mi][si], stage_inputs[mi][si],
                douts, dloss_seed,
            )
            # Release the remat inputs/state the backward just consumed
            # (1F1B's memory win depends on it).
            stage_inputs[mi][si] = None
            fwd_state[mi][si] = None
            if grads[si] is None:
                grads[si] = dparams
            else:
                grads[si] = jax.tree.map(jnp.add, grads[si], dparams)
            if sp:
                sparse_acc[si].append(sp)
            for n, g in dxs.items():
                dout_back[mi].setdefault(n, []).append(g)
            if si == S - 1:
                metrics_acc = _merge_metrics(metrics_acc, {
                    k: v for k, v in mets.items()
                })
        return grads, stage_state, metrics_acc, self._concat_sparse(sparse_acc)

    def _chunk_plan(self, m: int, c: int) -> List[int]:
        """Chunk lengths covering ``m`` microbatches: ``ceil(m/c)``
        chunks of ``c``, the last possibly shorter."""
        n = -(-m // c)
        return [min(c, m - ci * c) for ci in range(n)]

    def _chunk_slice(self, v, ci: int, m: int, c: int, length: int):
        """Microbatches ``[ci*c, ci*c+length)`` of a full-batch tensor,
        stacked ``(length, mb, ...)``."""
        assert v.shape[0] % m == 0, (v.shape, m)  # _split_micro's contract
        sz = v.shape[0] // m
        lo = ci * c * sz
        return v[lo:lo + length * sz].reshape(
            (length, sz) + tuple(v.shape[1:])
        )

    def _run_chunked(self, params, state, batch):
        """The chunked-scan event loop: one fwd/bwd *scan* program per
        (stage, chunk) event — ``2*S*ceil(m/c)`` host programs per
        step.  Cross-chunk gradient/metric accumulation threads the
        previous chunk's sums into the next scan's carry, so the
        summation order is microbatch order — bit-identical to
        ``_run_microbatched``."""
        m, c = self.microbatches, self.chunk
        S = len(self.stages)
        lengths = self._chunk_plan(m, c)
        n_chunks = len(lengths)
        graph_inputs = {t.name for t in self.model.input_tensors}

        stage_state = dict(state)
        stage_inputs: List[List[Any]] = [[None] * S for _ in range(n_chunks)]
        pre_states: List[List[Any]] = [[None] * S for _ in range(n_chunks)]
        boundary: List[Dict[str, Any]] = [dict() for _ in range(n_chunks)]
        dout_back: List[Dict[str, List[Any]]] = [dict() for _ in range(n_chunks)]
        dloss_seed = jnp.float32(1.0 / m)
        grads = {si: None for si in range(S)}
        metrics_acc = None
        # Per-stage per-chunk sparse carries (each already flattened in
        # microbatch order by the scan), appended in chunk order.
        sparse_acc: Dict[int, List[Any]] = {si: [] for si in range(S)}

        events = self.build_schedule(S, n_chunks)
        self.last_schedule = events
        # len(events) == 2*S*ceil(m/c) scan programs this step.
        _telemetry.current().add_programs(len(events))
        for kind, si, ci in events:
            st = self.stages[si]
            if kind == "F":
                vals = {
                    n: (self._chunk_slice(batch[n], ci, m, c, lengths[ci])
                        if n in graph_inputs else boundary[ci][n])
                    for n in st.in_names
                }
                inputs = self._put_stage_many_chunk(si, vals)
                stage_inputs[ci][si] = inputs
                _telemetry.current().program_cost(
                    "pipeline_stage_fwd_chunk", self._fwd_chunk_fns[si],
                    (params[si], stage_state[si], inputs), stage=si)
                outs, pres, new_state = self._fwd_chunk_fns[si](
                    params[si], stage_state[si], inputs
                )
                pre_states[ci][si] = pres
                stage_state[si] = new_state
                boundary[ci].update(outs)
                continue
            douts = self._collect_douts(si, dout_back[ci], boundary[ci],
                                        stacked=True)
            g_acc = (grads[si] if grads[si] is not None
                     else self._zero_grads(si, params[si]))
            m_acc = None
            if si == S - 1:
                m_acc = (metrics_acc if metrics_acc is not None
                         else self._zero_metrics(
                             si, params[si], pre_states[ci][si],
                             stage_inputs[ci][si]))
            _telemetry.current().program_cost(
                "pipeline_stage_bwd_chunk", self._bwd_chunk_fns[si],
                (params[si], pre_states[ci][si], stage_inputs[ci][si],
                 douts, dloss_seed, g_acc, m_acc), stage=si)
            g, mets, dxs, sp = self._bwd_chunk_fns[si](
                params[si], pre_states[ci][si], stage_inputs[ci][si],
                douts, dloss_seed, g_acc, m_acc,
            )
            grads[si] = g
            if si == S - 1:
                metrics_acc = mets
            if sp:
                sparse_acc[si].append(sp)
            # Release the remat inputs/states this backward consumed.
            stage_inputs[ci][si] = None
            pre_states[ci][si] = None
            for n, gx in dxs.items():
                dout_back[ci].setdefault(n, []).append(gx)
        return (grads, stage_state, metrics_acc or {},
                self._concat_sparse(sparse_acc))

    def _finish_step(self, params, opt_state, stage_state, grads,
                     metrics_acc, sparse=None):
        """Shared step tail: global clip-norm (ONE batched fence), the
        per-stage optimizer updates (row updates on sparse stages), and
        count-aware metric means."""
        m = self.microbatches
        S = len(self.stages)
        sparse = sparse or {}
        # --clip-norm: the global L2 norm spans ALL stages' gradients;
        # per-stage squared norms combine on the host (the per-stage
        # grads live on different submeshes), then each stage scales.
        # The combine is the shared f32 formula (_clip_scale_f32_host),
        # bit-identical to the compiled step's in-program hierarchical
        # clip — and the fetch is ONE device_get of all S squared norms
        # (each separate fetch is its own host round trip).  Sparse
        # stages fold their unique-row sums into the SAME fence.  The
        # compiled path has no fence here at all.
        scale_arr = None
        if self.config.clip_norm > 0.0:
            sqs = _telemetry.current().fence(
                [
                    self._sparse_sq_fns[si](grads[si], sparse[si])
                    if si in sparse
                    else self._grad_sq_fns[si](grads[si])
                    for si in range(S)
                ],
                "clip_norm",
            )
            scale = _clip_scale_f32_host(sqs, self.config.clip_norm)
            # Sparse row grads always multiply (x1.0 is bit-exact —
            # the compiled path's unconditional form); dense grads
            # keep the skip-at-1.0 fast path.
            scale_arr = jnp.float32(scale)
            if scale < 1.0:
                for si in range(S):
                    grads[si] = self._scale_fns[si](grads[si], scale_arr)

        # Optimizer (per stage, concurrent across submeshes).
        new_params, new_opt = {}, {}
        for si in range(S):
            if si in sparse:
                new_params[si], new_opt[si] = self._sparse_opt_fns[si](
                    params[si], opt_state[si], grads[si], sparse[si],
                    scale_arr,
                )
            else:
                new_params[si], new_opt[si] = self._opt_fns[si](
                    params[si], opt_state[si], grads[si]
                )
        m_out = mean_metrics(metrics_acc, count=m)
        return new_params, new_opt, stage_state, m_out

    # -- compiled whole-step path --------------------------------------------
    #
    # ONE jitted program per train step on the shared stage mesh: the
    # exact _run_chunked structure at c=m — per-stage forward scans in
    # stage order, per-stage remat-backward scans in reverse with the
    # same cotangent-summation order, the same gradient/metric carries
    # — plus the clip-norm combine and per-stage optimizer updates,
    # all inside the trace.  Bit-identity to the host-driven path is
    # BY CONSTRUCTION (same op sequence through the same stage-fn
    # bodies; sharding differs only in mesh layout, which the
    # DP≡strategy invariant — and tests/test_pipeline_chunk.py's
    # parity suite — pin as numerics-neutral).

    @property
    def superstep_fused(self) -> bool:
        """Whether ``steps_per_call > 1`` fuses into one compiled
        dispatch here (``Executor`` exposes the same property; the
        trainer and resilience layer route on it)."""
        return self.compiled

    def note_fused_dispatch(self, steps: int = 1) -> None:
        """Record ONE compiled host program covering ``steps`` train
        steps: the ``("C", 0, 0)`` sentinel is the compiled analogue of
        the ``2*S*ceil(m/c)`` event list, and the telemetry counter
        makes programs/step honestly read ``1/k`` on the fused
        superstep path.  Single owner of both pieces — ``train_step``
        calls it with the default, ``Trainer._fit_superstep`` after
        each fused k-step dispatch."""
        self.last_schedule = [("C", 0, 0)]
        _telemetry.current().add_programs(1, steps=steps)

    def _require_compiled(self, what: str) -> None:
        if not self.compiled:
            raise ValueError(
                f"{what} requires the compiled pipeline step "
                f"(PipelineExecutor(compiled=True) / --pipeline-compiled); "
                f"the host-driven pipeline amortizes the fence instead "
                f"(Trainer._fit_superstep_pipeline)"
            )

    def build_compiled_step(self):
        """The whole multi-stage train step as ONE jitted program —
        donated ``(params, opt_state, state)``, same signature and
        numerics as :meth:`train_step`.  Host programs per step drop
        from ``2*S*ceil(m/c)`` to 1, and the program is fence-free
        (clip-norm included), which is what makes layer-wise
        strategies genuinely superstep-capable
        (:meth:`build_superstep`)."""
        self._require_compiled("build_compiled_step")
        if self._compiled_step_fn is None:
            self._compiled_step_fn = jax.jit(
                self._compiled_step_impl, donate_argnums=(0, 1, 2)
            )
            _telemetry.current().emit(
                "compiled_step", mode="compiled", S=len(self.stages),
                m=self.microbatches, k=1,
            )
        return self._compiled_step_fn

    def _compiled_step_impl(self, params, opt_state, state, batch):
        """The traced whole-step body (see section comment: mirrors
        ``_run_chunked`` at ``c=m`` exactly, with ``_finish_step``'s
        tail folded in)."""
        m = self.microbatches
        S = len(self.stages)
        graph_inputs = {t.name for t in self.model.input_tensors}

        stacked: Dict[str, Any] = {}
        for name in graph_inputs:
            if name not in batch:
                continue
            v = jnp.asarray(batch[name])
            if v.shape[0] % m:
                raise PlacementError(
                    f"batch dim {v.shape[0]} of input {name!r} is not "
                    f"divisible by microbatches={m}"
                )
            # Row-major reshape == _split_micro's row slices.
            stacked[name] = v.reshape((m, v.shape[0] // m) + v.shape[1:])

        stage_state = dict(state)
        boundary: Dict[str, Any] = {}
        stage_inputs: List[Any] = [None] * S
        pre_states: List[Any] = [None] * S
        for si, st in enumerate(self.stages):
            # Pin each stage's stacked inputs to EXACTLY the host
            # path's placement (_put_stage_many_chunk): GSPMD's
            # propagation through the in-trace reshape is otherwise
            # free to leave a microbatch replicated where the host path
            # shards it, and a replicated mean reduces in a different
            # tree order than a sharded one — a 1-ulp loss drift the
            # bit-identity gate forbids (observed; the constraint is
            # the fix, not a nicety).
            sh = self._chunk_in_shardings[si]
            vals = {
                n: jax.lax.with_sharding_constraint(
                    stacked[n] if n in graph_inputs else boundary[n],
                    sh[n],
                )
                for n in st.in_names
            }
            stage_inputs[si] = vals
            # optimization_barrier at every stage-program boundary is a
            # best-effort isolation HINT only: this XLA vintage strips
            # barriers before the algebraic simplifier runs (stablehlo
            # carries 8, the optimized HLO zero — measured 2026-08-04),
            # so bit-identity does NOT rest on them.  It rests on the
            # explicit sharding pins here, the mesh-invariant Linear
            # contraction (ops/linear.py), and mean_metrics' explicit
            # reciprocal multiply (executor.py).  Kept because a TPU
            # backend that honors barriers only gets safer.
            outs, pres, new_state = jax.lax.optimization_barrier(
                self._fwd_chunk_fns[si](params[si], stage_state[si], vals)
            )
            pre_states[si] = pres
            stage_state[si] = new_state
            boundary.update(outs)

        dloss_seed = jnp.float32(1.0 / m)
        dout_back: Dict[str, List[Any]] = {}
        grads: Dict[int, Any] = {}
        sparse: Dict[int, Any] = {}
        metrics_acc = None
        for si in range(S - 1, -1, -1):
            st = self.stages[si]
            douts = {}
            for n in st.out_names:
                # The producer's stacked output placement — the
                # compiled mirror of _collect_douts' device_put (same
                # reasoning as the forward constraints above).
                sh = self._stacked(self.stage_ex[si].output_sharding(
                    self._producer[n], self._spec_of[n]
                ))
                contribs = dout_back.pop(n, None)
                if contribs:
                    # Same summation order as _collect_douts: reverse
                    # consumer-stage order (later stages' backwards
                    # appended first), each contribution pinned to the
                    # producer's placement before the sum.
                    parts = [
                        jax.lax.with_sharding_constraint(g, sh)
                        for g in contribs
                    ]
                    total = parts[0]
                    for p in parts[1:]:
                        total = total + p
                    douts[n] = total
                else:
                    ref = boundary[n]
                    douts[n] = jax.lax.with_sharding_constraint(
                        jnp.zeros(ref.shape, ref.dtype), sh
                    )
            g_acc = jax.tree.map(
                jnp.zeros_like, self._dense_stage_params(si, params[si])
            )
            m_acc = None
            if si == S - 1:
                m_acc = self._abstract_zero_metrics(
                    si, params[si], pre_states[si], stage_inputs[si]
                )
            g, mets, dxs, sp = jax.lax.optimization_barrier(
                self._bwd_chunk_fns[si](
                    params[si], pre_states[si], stage_inputs[si],
                    douts, dloss_seed, g_acc, m_acc,
                )
            )
            grads[si] = g
            sparse[si] = sp
            if si == S - 1:
                metrics_acc = mets
            for n, gx in dxs.items():
                dout_back.setdefault(n, []).append(gx)

        # Device-side hierarchical clip-norm: per-stage squared norms
        # (the same _grad_sq_fns / _stage_sq_sparse bodies as the host
        # path) combined in stage order with the shared f32 formula —
        # the host path's one-fence-per-step floor simply does not
        # exist here.
        scale = None
        if self.config.clip_norm > 0.0:
            def term(si):
                if sparse[si]:
                    return self._stage_sq_sparse(si, grads[si], sparse[si])
                return self._grad_sq_fns[si](grads[si])

            total = term(0)
            for si in range(1, S):
                total = total + term(si)
            scale = _clip_scale_f32(total, self.config.clip_norm)
            for si in range(S):
                grads[si] = self._scale_fns[si](grads[si], scale)

        new_params, new_opt = {}, {}
        for si in range(S):
            if sparse[si]:
                new_params[si], new_opt[si] = self._stage_update_sparse(
                    si, params[si], opt_state[si], grads[si],
                    sparse[si], scale,
                )
            else:
                new_params[si], new_opt[si] = self.optimizer.update(
                    params[si], opt_state[si], grads[si]
                )
        m_out = mean_metrics(metrics_acc or {}, count=m)
        return new_params, new_opt, stage_state, m_out

    def build_superstep(self, k: int, accum_steps: int = 1):
        """K whole pipeline steps in ONE compiled dispatch: the
        compiled step wrapped in the donated-carry ``lax.scan`` over a
        stacked ``(k,) + batch`` queue (:meth:`stack_steps`) — exactly
        ``Executor.build_superstep``'s shape, so ``Trainer
        ._fit_superstep`` and ``ResilientTrainer`` drive layer-wise
        strategies through the same fused path as full-mesh ones (one
        dispatch + one ``jax.device_get`` per k steps; host programs
        per step = 1/k)."""
        self._require_compiled("build_superstep (fused pipeline supersteps)")
        if k < 1:
            raise ValueError(f"steps_per_call must be >= 1, got {k}")
        if accum_steps != 1:
            raise ValueError(
                "pipeline gradient accumulation is lowered at "
                "construction (PipelineExecutor(accum_steps=...)); "
                "build_superstep composes with it at accum_steps=1"
            )
        if self._compiled_superstep_cache.get(k) is None:
            step = self._compiled_step_impl

            def superstep(params, opt_state, state, stacked):
                def body(carry, batch):
                    p, o, s = carry
                    p, o, s, m = step(p, o, s, batch)
                    return (p, o, s), m

                (p, o, s), ms = jax.lax.scan(
                    body, (params, opt_state, state), stacked
                )
                return p, o, s, ms

            self._compiled_superstep_cache[k] = jax.jit(
                superstep, donate_argnums=(0, 1, 2)
            )
            _telemetry.current().emit(
                "compiled_step", mode="compiled", S=len(self.stages),
                m=self.microbatches, k=k,
            )
        return self._compiled_superstep_cache[k]

    @functools.cached_property
    def _compiled_batch_shardings(self) -> Dict[str, NamedSharding]:
        """Graph-input shardings on the shared stage mesh (each input's
        consuming stage's placement) — the superstep stacking analogue
        of ``Executor._batch_shardings``."""
        graph_inputs = {t.name for t in self.model.input_tensors}
        out: Dict[str, NamedSharding] = {}
        for si, st in enumerate(self.stages):
            for n in st.in_names:
                if n in graph_inputs and n not in out:
                    out[n] = self._in_shardings[si][n]
        return out

    def stack_steps(self, batches: Sequence[Dict[str, Any]],
                    accum_steps: int = 1):
        """Stack k per-step host batches into the device-resident
        ``(k, ...)`` queue :meth:`build_superstep` scans over (mirrors
        ``Executor.stack_steps``; the leading step dim is unsharded,
        everything else takes the consuming stage's placement)."""
        self._require_compiled("stack_steps")
        if accum_steps != 1:
            raise ValueError(
                "pipeline gradient accumulation is lowered at "
                "construction (PipelineExecutor(accum_steps=...)); "
                "stack_steps composes with it at accum_steps=1"
            )
        sh = self._compiled_batch_shardings
        out = {}
        # Ids-first H2D staging, mirroring Executor.stack_steps: the
        # async device_put of integer id queues overlaps the host
        # np.stack of the float inputs.
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
                stacked = jnp.stack([jnp.asarray(v) for v in vals])
            if name in sh:
                spec = PartitionSpec(None, *sh[name].spec)
                stacked = jax.device_put(
                    stacked, NamedSharding(sh[name].mesh, spec)
                )
            out[name] = stacked
        return out

    # -- compute-free mode ---------------------------------------------------

    def abstract_step(self):
        """Per-stage ``jax.eval_shape`` of init + forward + backward
        (stage vjp) + optimizer update — the compute-free
        DISABLE_COMPUTATION analogue, mirroring Executor.abstract_step
        over the pipeline's actual per-stage programs.  Returns
        (params, opt_state, state, metrics) avals keyed by stage
        index; cross-stage activations are threaded abstractly and
        metrics come from the final stage, matching train_step.  Stage
        programs are validated at MICROBATCH shapes (batch split by
        ``self.microbatches``), the shapes train_step actually runs."""
        params, opt_state, state = {}, {}, {}
        metrics: Dict[str, Any] = {}
        boundary: Dict[str, Any] = {}
        graph_inputs = {t.name for t in self.model.input_tensors}
        S = len(self.stages)
        m = self.microbatches
        stage_inputs: List[Dict[str, Any]] = []
        for si, st in enumerate(self.stages):
            ex = self.stage_ex[si]
            p, o, s = ex._abstract_init()
            params[si], opt_state[si], state[si] = p, o, s
            inputs = {}
            for n in st.in_names:
                spec = self._spec_of[n]
                if n in graph_inputs:
                    if spec.shape[0] % m:
                        raise PlacementError(
                            f"batch dim {spec.shape[0]} of input "
                            f"{n!r} is not divisible by "
                            f"microbatches={m}"
                        )
                    shape = (spec.shape[0] // m,) + tuple(spec.shape[1:])
                    inputs[n] = jax.ShapeDtypeStruct(shape, spec.dtype)
                else:
                    inputs[n] = boundary[n]
            stage_inputs.append(inputs)

            def fwd(p, s, xs, _ex=ex, _st=st):
                loss, mets, new_state, env = _ex.forward(
                    p, s, xs, training=True
                )
                return {n: env[n] for n in _st.out_names}, loss, mets

            outs, loss, mets = jax.eval_shape(fwd, p, s, inputs)
            boundary.update(outs)
            if si == S - 1:
                metrics = mets
        # Backward + optimizer, reverse order — the vjp and update
        # trees must also be shape-valid for DRY RUN OK to mean "the
        # whole step compiles".
        dloss = jax.ShapeDtypeStruct((), jnp.float32)
        for si in range(S - 1, -1, -1):
            st = self.stages[si]
            douts = {n: boundary[n] for n in st.out_names}
            dparams, dxs, _, _, sparse = jax.eval_shape(
                self._bwd_fns[si], params[si], state[si],
                stage_inputs[si], douts, dloss,
            )
            if sparse:
                jax.eval_shape(
                    lambda p, o, g, sp, _si=si:
                        self._stage_update_sparse(_si, p, o, g, sp, None),
                    params[si], opt_state[si], dparams, sparse,
                )
            else:
                jax.eval_shape(
                    self.optimizer.update, params[si], opt_state[si],
                    dparams,
                )
        return params, opt_state, state, metrics

    @functools.cached_property
    def _compiled_eval_fn(self):
        """Compiled-mode eval: the whole read-only pass as ONE jitted
        program (per-stage losses/metrics combine in stage order inside
        the trace — no per-stage fetches at all)."""
        graph_inputs = {t.name for t in self.model.input_tensors}

        def ev(params, state, batch):
            boundary: Dict[str, Any] = {}
            total = jnp.float32(0.0)
            metrics: Dict[str, Any] = {}
            for si, st in enumerate(self.stages):
                inputs = {
                    n: (batch[n] if n in graph_inputs else boundary[n])
                    for n in st.in_names
                }
                loss, mets, _, env = self.stage_ex[si].forward(
                    params[si], state[si], inputs, training=False
                )
                total = total + loss
                metrics = _merge_metrics(metrics, mets)
                boundary.update({n: env[n] for n in st.out_names})
            return total, metrics

        return jax.jit(ev)

    def eval_step(self, params, state, batch):
        if self.compiled:
            loss, mets = self._compiled_eval_fn(params, state, batch)
            loss, mets = _telemetry.current().fence((loss, mets), "eval")
            return float(loss), mets
        graph_inputs = {t.name for t in self.model.input_tensors}
        boundary: Dict[str, Any] = {}
        losses: List[Any] = []
        mets_list: List[Dict[str, Any]] = []
        for si, st in enumerate(self.stages):
            inputs = self._put_stage_many(si, {
                n: (batch[n] if n in graph_inputs else boundary[n])
                for n in st.in_names
            })
            loss, mets, _, env = self._eval_fns[si](
                params[si], state[si], inputs
            )
            losses.append(loss)
            mets_list.append(mets)
            boundary.update({n: env[n] for n in st.out_names})
        # ONE host sync for the whole pass: per-stage losses/metrics
        # live on different submeshes (device arithmetic across meshes
        # is invalid), so they are summed host-side — but fetching
        # inside the loop serialized every stage on a device_get
        # (pipeline-overhead finding, PIPELINE_OVERHEAD.md).
        losses, mets_list = _telemetry.current().fence(
            (losses, mets_list), "eval"
        )
        metrics: Dict[str, Any] = {}
        for mets in mets_list:
            metrics = _merge_metrics(metrics, mets)
        return float(sum(losses)), metrics

    @functools.cached_property
    def _eval_fns(self):
        def make(si):
            ex, st = self.stage_ex[si], self.stages[si]

            def ev(params, state, inputs):
                loss, metrics, _, env = ex.forward(
                    params, state, inputs, training=False
                )
                return loss, metrics, None, {n: env[n] for n in st.out_names}

            return jax.jit(ev)

        return [make(i) for i in range(len(self.stages))]


def make_executor(
    model: FFModel,
    strategy: Optional[StrategyStore] = None,
    **kwargs,
):
    """Choose the runtime for a strategy: plain Executor when every op
    spans the whole mesh, PipelineExecutor when ``device_ids`` carve
    out proper subsets (the reference's layer-wise placement).
    ``compiled=True`` (--pipeline-compiled) requests the compiled
    whole-step pipeline; combinations it cannot realize fall back
    LOUDLY to the host-driven pipeline (the numerics oracle, which
    supports everything)."""
    if strategy is not None and any(
        pc.device_ids is not None for pc in strategy.table.values()
    ):
        nd = strategy.num_devices
        subsets = {
            pc.device_ids
            for pc in strategy.table.values()
            if pc.device_ids is not None
        }
        if any(len(set(ids)) < nd for ids in subsets):
            mb = kwargs.pop("microbatches", 1)
            sched = kwargs.pop("schedule", "1f1b")
            chunk = kwargs.pop("chunk", 1)
            compiled = kwargs.pop("compiled", False)
            accum = kwargs.pop("accum_steps", 1)
            kwargs.pop("mesh_plan", None)
            if compiled:
                try:
                    return PipelineExecutor(
                        model, strategy, microbatches=mb, schedule=sched,
                        chunk=chunk, compiled=True, accum_steps=accum,
                        **kwargs
                    )
                except CompiledPipelineUnsupported as e:
                    _log.warning(
                        "--pipeline-compiled unavailable for this "
                        "model/strategy (%s); falling back to the "
                        "host-driven pipeline", e,
                    )
            return PipelineExecutor(
                model, strategy, microbatches=mb, schedule=sched,
                chunk=chunk, accum_steps=accum, **kwargs
            )
        _log.warning(
            "strategy device_ids span the full mesh; explicit ordering is "
            "realized by mesh coordinates (placement-equivalent)"
        )
    kwargs.pop("microbatches", None)
    kwargs.pop("schedule", None)
    kwargs.pop("chunk", None)
    kwargs.pop("compiled", None)
    kwargs.pop("accum_steps", None)
    return Executor(model, strategy=strategy, **kwargs)
