"""FFModel graph → ffsim problem serialization.

Builds the text problem the native simulator consumes (see
``flexflow_tpu/native/ffsim.cc``): per-op candidate ``(n,c,h,w,s)``
degree vectors with roofline shard costs and mesh-consistent device
placements, plus producer→consumer tensor edges whose shard-rect
intersections the simulator costs as communication (the reference's
``intersect(rect)/bandwidth`` comm tasks, ``simulator.cc:896-908``).
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

_log = logging.getLogger("ff.search")

from flexflow_tpu.graph import FFModel
from flexflow_tpu.ops import Op
from flexflow_tpu.parallel.mesh import InfeasibleStrategyError, MeshPlan, _prime_factors
from flexflow_tpu.parallel.strategy import AXES, ParallelConfig, StrategyStore
from flexflow_tpu.search.cost_model import (
    FWD_BWD_FACTOR,
    DeviceModel,
    contracted_input_dims,
    op_cost,
    shard_cost_us,
    sync_cost_us,
)

AXIS_INDEX = {a: i for i, a in enumerate(AXES)}


def build_virtual_plan(num_devices: int) -> MeshPlan:
    """A MeshPlan with axis bookkeeping but no jax Mesh — the offline
    search plans for a device count that need not be attached (the
    reference simulator likewise models 2x4 GPUs from one process,
    ``simulator.cc:32-33``)."""
    sizes = _prime_factors(num_devices) or [1]
    names = tuple(f"x{i}" for i in range(len(sizes)))
    return MeshPlan(mesh=None, axis_names=names, axis_sizes=tuple(sizes))


def shard_devices(plan: MeshPlan, pc: ParallelConfig) -> List[int]:
    """Device id of every shard of ``pc``, row-major over (n,c,h,w,s).

    Mirrors how the runtime's mesh assignment places shards (the
    FFMapper ``slice_task`` analogue, ``mapper.cc:54-112``): each
    semantic coordinate decomposes into its assigned mesh-axis
    coordinates; unassigned mesh axes sit at coordinate 0 (first
    replica)."""
    if pc.device_ids is not None:
        assert len(pc.device_ids) == pc.num_parts
        return list(pc.device_ids)
    asg = plan.assign(pc)
    size_of = dict(zip(plan.axis_names, plan.axis_sizes))
    axis_pos = {nm: i for i, nm in enumerate(plan.axis_names)}
    degs = [pc.degree(a) for a in AXES]
    devs: List[int] = []
    for k in range(pc.num_parts):
        rem = k
        coords: Dict[str, int] = {}
        for a, d in zip(reversed(AXES), reversed(degs)):
            coords[a] = rem % d
            rem //= d
        mesh_coord = [0] * len(plan.axis_names)
        for a in AXES:
            c = coords[a]
            for nm in reversed(asg.get(a, ())):
                mesh_coord[axis_pos[nm]] = c % size_of[nm]
                c //= size_of[nm]
        flat = 0
        for i, sz in enumerate(plan.axis_sizes):
            flat = flat * sz + mesh_coord[i]
        devs.append(flat)
    return devs


def enumerate_candidates(
    op: Op, plan: MeshPlan, max_candidates: int = 64
) -> List[ParallelConfig]:
    """All feasible degree vectors for ``op`` over its semantic axes.

    An axis is usable if it tags a dim of the op's primary output, or
    of a PARAMETER only (e.g. the MoE expert dim, where 'c' shards the
    experts but the token-shaped output carries no 'c' — the analogue
    of the reference pinning whole tables whose outputs are
    sample-sharded, ``dlrm_strategy.cc:11-19``); a degree is usable if
    it divides every tagged extent (keeps shards even, the reference's
    rect partitions round instead) and the mesh can realize the
    combination.  Candidate 0 is the data-parallel fallback (largest
    feasible pure-``n`` split) so the search starts from — and
    ``init_us`` reports — the DP baseline, like the reference's
    ``dpCompTime`` (``simulator.cc:117``).
    """
    ndev = plan.num_devices
    out = op.outputs[0]
    axis_min_extent: Dict[str, int] = {}
    for ext, ax in zip(out.shape, out.dim_axes):
        if ax is not None:
            axis_min_extent[ax] = min(ext, axis_min_extent.get(ax, ext))
    out_axes = frozenset(axis_min_extent)
    for spec in op.param_specs().values():
        for ext, ax in zip(spec.shape, spec.dim_axes):
            if ax is not None and ax not in out_axes:
                axis_min_extent[ax] = min(ext, axis_min_extent.get(ax, ext))
    options: Dict[str, List[int]] = {}
    for ax, ext in axis_min_extent.items():
        options[ax] = [d for d in range(1, ndev + 1) if ext % d == 0 and ndev % d == 0]
    axes = [a for a in AXES if a in options]
    combos: List[ParallelConfig] = []
    for degs in itertools.product(*(options[a] for a in axes)):
        parts = int(np.prod(degs)) if degs else 1
        if parts > ndev:
            continue
        pc = ParallelConfig(**dict(zip(axes, degs)))
        try:
            plan.assign(pc)
        except InfeasibleStrategyError:
            continue
        combos.append(pc)
    # DP fallback first (largest pure-n split), then by ascending parts.
    n_only = [pc for pc in combos if pc.num_parts == pc.n]
    dp = max(n_only, key=lambda pc: pc.n, default=ParallelConfig())
    rest = sorted(
        (pc for pc in combos if pc != dp),
        key=lambda pc: (-pc.num_parts, pc.n, pc.c, pc.h, pc.w, pc.s),
    )
    # Device-shifted sub-mesh placements: a pure-n candidate using
    # k < ndev devices may sit on ANY aligned k-block, not just the
    # mesh origin — the search freedom behind the reference's per-table
    # DLRM pinning (``dlrm_strategy.cc:11-19``: each 1-part embedding
    # on a different GPU) and layer-wise NMT splits.  The runtime
    # executes these via PipelineExecutor device subsets.
    shifted: List[ParallelConfig] = []
    for pc in [dp] + rest:
        k = pc.num_parts
        if k >= ndev or pc.num_parts != pc.n or pc.device_ids is not None:
            continue
        canon = tuple(shard_devices(plan, pc))
        for b in range(0, ndev // k):
            ids = tuple(range(b * k, (b + 1) * k))
            if ids == canon:
                # b=0 exists so CONTIGUOUS origin blocks (the stage
                # partitions layer-wise execution configs use) are
                # first-class candidates even when the canonical mesh
                # placement of pure-n strides the devices; skip only
                # an exact duplicate of the canonical placement.
                continue
            shifted.append(ParallelConfig(n=pc.n, device_ids=ids))
    # Smallest blocks first (single-device pinning is the DLRM case);
    # shifted candidates get a RESERVED quota so hybrid-combo floods on
    # big meshes cannot truncate the placement freedom away.
    shifted.sort(key=lambda pc: (pc.num_parts, pc.device_ids))
    quota = min(
        len(shifted), max(8, (max_candidates - 1) // 4), max_candidates - 1
    )
    budget = max(0, max_candidates - 1 - quota)
    if len(rest) > budget or len(shifted) > quota:
        _log.warning(
            "op %r: %d feasible strategies truncated to %d "
            "(pass max_candidates to widen)",
            op.name, len(rest) + len(shifted) + 1, max_candidates,
        )
    kept = rest[:budget]
    kept += shifted[: max(0, max_candidates - 1 - len(kept))]
    return [dp] + kept


def build_stage_partition(
    model: FFModel, num_devices: int, stages: int,
    microbatches: int = 1,
) -> Optional[StrategyStore]:
    """A layer-wise execution-config candidate: the op graph split into
    ``stages`` maximal CONSECUTIVE runs (graph order, balanced op
    counts) over disjoint contiguous device blocks of ``num_devices //
    stages`` each, data-parallel within every stage — the same
    construction the reference's NMT app hand-writes per layer chunk
    (``nmt.cc:269-308``).  Returns
    ``None`` when the partition is infeasible for this model (stage
    count vs ops/devices, or batch extents that don't divide across
    ``microbatches x intra-stage DP``) — the searcher simply skips the
    candidate, which is how every emitted config stays executor-legal.
    """
    n_ops = len(model.layers)
    if stages < 2 or stages > n_ops or num_devices % stages:
        return None
    per = num_devices // stages
    if per < 1:
        return None
    for t in model.input_tensors:
        if not t.shape:
            continue
        if t.dim_axes and t.dim_axes[0] == "n":
            b = t.shape[0]
            if b % microbatches or (b // microbatches) % per:
                return None  # microbatch rows must shard n-ways evenly
    store = StrategyStore(num_devices)
    for i, op in enumerate(model.layers):
        si = min(i * stages // n_ops, stages - 1)
        ids = tuple(range(si * per, (si + 1) * per))
        store.set(op.name, ParallelConfig(n=per, device_ids=ids))
    return store


@dataclasses.dataclass
class SearchProblem:
    text: str
    ops: List[Op]
    candidates: List[List[ParallelConfig]]


def build_problem(
    model: FFModel,
    plan: MeshPlan,
    dev: Optional[DeviceModel] = None,
    max_candidates: int = 64,
    measured_costs: Optional[Dict[str, Any]] = None,
) -> SearchProblem:
    """``measured_costs`` overrides the roofline compute estimate per
    op — the reference's measured-microbenchmark mode
    (``simulator.cc:1420-1440``).  Three formats per op name:

    - ``{(n,c,h,w,s): (fwd us, bwd us)}`` from
      ``runtime.profiler.measured_degree_table`` — per-(op, degree)
      live measurements of BOTH legs, the reference's
      ``computeTime[config]`` cache filled by fwd+bwd microbenchmarks
      (``scripts/cnn.h:204-277`` returns ``t1+t2+t3``); used directly,
      no fwd×factor assumption.  Candidates with no entry fall back to
      the roofline.
    - ``{(n,c,h,w,s): fwd us}`` (legacy fwd-only per-degree): scaled
      by ``FWD_BWD_FACTOR``.
    - a float (legacy ``measured_cost_table``): whole-op time scaled
      by the linear ``/num_parts`` assumption.

    A summary of which mode each op actually got is logged on
    ``ff.search`` (WARNING when any legacy assumption is in play) so
    callers can tell a fully-measured search from a partly-assumed
    one.  Comm and sync stay model-derived."""
    dev = dev or DeviceModel()
    measured_costs = measured_costs or {}
    ops = list(model.layers)
    op_index = {op.name: i for i, op in enumerate(ops)}
    lines: List[str] = [
        "ffsim 1",
        f"ndevices {plan.num_devices}",
        f"devices_per_node {min(dev.devices_per_node, plan.num_devices)}",
        f"bw_intra {dev.ici_bytes_per_us}",
        f"bw_inter {dev.dcn_bytes_per_us}",
        f"nops {len(ops)}",
    ]
    candidates: List[List[ParallelConfig]] = []
    mode_ops: Dict[str, List[str]] = {}
    for i, op in enumerate(ops):
        cands = enumerate_candidates(op, plan, max_candidates)
        candidates.append(cands)
        cost = op_cost(op)
        name = op.name.replace(" ", "_")
        lines.append(f"op {i} {len(cands)} {name}")
        measured = measured_costs.get(op.name)
        cand_modes: Dict[str, int] = {}
        for pc in cands:
            degrees = {a: pc.degree(a) for a in AXES}
            m_us: Optional[float] = None
            mode = "roofline"
            if isinstance(measured, dict):
                m = measured.get(tuple(pc.degree(a) for a in AXES))
                if isinstance(m, (tuple, list)):
                    m_us = dev.task_overhead_us + float(m[0]) + float(m[1])
                    mode = "measured fwd+bwd"
                elif m is not None:
                    m_us = dev.task_overhead_us + m * FWD_BWD_FACTOR
                    mode = "legacy fwd-only x%.1f" % FWD_BWD_FACTOR
            elif measured is not None:
                m_us = (
                    dev.task_overhead_us
                    + measured * FWD_BWD_FACTOR / pc.num_parts
                )
                mode = "legacy whole-op /parts"
            cand_modes[mode] = cand_modes.get(mode, 0) + 1
            c_us = (
                m_us if m_us is not None
                else shard_cost_us(cost, pc.num_parts, dev)
            )
            s_us = sync_cost_us(cost, degrees, dev)
            devs = shard_devices(plan, pc)
            degs = " ".join(str(pc.degree(a)) for a in AXES)
            devs_s = " ".join(map(str, devs))
            lines.append(f"cfg {degs} {c_us:.4f} {s_us:.4f} {devs_s}")
        if len(cand_modes) == 1:
            op_mode = next(iter(cand_modes))
        else:  # per-candidate fallbacks: report the split, not a winner
            total = sum(cand_modes.values())
            op_mode = "mixed (" + ", ".join(
                f"{m} {c}/{total}" for m, c in sorted(cand_modes.items())
            ) + ")"
        mode_ops.setdefault(op_mode, []).append(op.name)
    if measured_costs:
        import logging

        log = logging.getLogger("ff.search")
        summary = ", ".join(
            f"{mode}: {len(names)} ops" for mode, names in mode_ops.items()
        )
        assumed = [
            m for m in mode_ops
            if m.startswith("legacy") or m.startswith("mixed")
        ]
        if assumed:
            log.warning(
                "measured search cost modes — %s; non-'measured fwd+bwd' "
                "modes keep a fwd-derived backward or roofline assumption "
                "(%s)", summary,
                ", ".join(f"{m}: {mode_ops[m][:4]}" for m in assumed),
            )
        else:
            log.info("measured search cost modes — %s", summary)
    edges: List[str] = []
    for j, op in enumerate(ops):
        contracted = set(contracted_input_dims(op))
        for ti, t in enumerate(op.inputs):
            if t.producer is None:
                continue  # placeholder: fed by the data loader
            i = op_index[t.producer.name]
            assert i < j, f"graph must be topologically ordered: {t.name}"
            bpe = int(np.dtype(t.dtype).itemsize)
            nd = len(t.shape)
            dims = " ".join(str(e) for e in t.shape)
            src_axes = " ".join(
                str(AXIS_INDEX[a]) if a is not None else "-1" for a in t.dim_axes
            )
            # Consumer-side rects: a contracted dim is read in full by
            # every shard (broadcast), so it maps to no axis.
            dst_axes = " ".join(
                "-1" if (ti == 0 and d in contracted) or a is None
                else str(AXIS_INDEX[a])
                for d, a in enumerate(t.dim_axes)
            )
            edges.append(f"edge {i} {j} {bpe} {nd} {dims} {src_axes} {dst_axes}")
    lines.append(f"nedges {len(edges)}")
    lines.extend(edges)
    lines.append("")
    return SearchProblem(text="\n".join(lines), ops=ops, candidates=candidates)
