"""Analytic per-op cost model for the strategy search.

The reference measures per-op, per-degree compute times with live
cuDNN/cuBLAS microbenchmarks (reference: ``scripts/cnn.h:204+``,
``measure_conv2d_time`` et al.) and feeds them to the simulator.  On
TPU the equivalent measured mode is
``flexflow_tpu.runtime.profiler.measured_cost_table`` (pass its result
as ``measured_costs`` to ``search_strategy``), but the
default is a roofline model: an op's time is
``max(flops / MXU_rate, bytes / HBM_rate)`` plus a fixed per-task
overhead — the standard TPU performance mental model (MXU-bound vs
HBM-bandwidth-bound).  Costs only need to *rank* strategies, as in the
reference, where the simulator's absolute times are not validated
against wall clock either.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

_log = logging.getLogger("ff.search")

from flexflow_tpu.ops import (
    LSTM,
    Conv2D,
    Embedding,
    Linear,
    MixtureOfExperts,
    MultiEmbedding,
    MultiHeadAttention,
    Op,
    WordEmbedding,
)
from flexflow_tpu.ops.attention import PositionEmbedding

#: Lookup-table ops: forward is a gather, so the table parameter is
#: neither contracted (no MXU flops) nor streamed in full from HBM —
#: only the selected rows (~= the output) move.  The *gradient* is
#: still table-dense when replicated (the reference's scatter-add into
#: the whole grad region, ``embedding.cu:128-158``), so tables keep
#: their full weight in the sync cost.
LOOKUP_OPS = (Embedding, MultiEmbedding, WordEmbedding, PositionEmbedding)

#: fwd+bwd multiplier: backward is ~2x forward flops (two GEMMs per
#: fwd GEMM — the reference's bwd tasks run data- and filter-grad
#: kernels per fwd kernel, e.g. ``linear.cu:388-488``).
FWD_BWD_FACTOR = 3.0


@dataclasses.dataclass
class DeviceModel:
    """TPU chip + interconnect constants (v5e-flavored defaults).

    Rates are per-microsecond so simulated times are in us.  The 4:1
    shape of intra:inter bandwidth mirrors the reference simulator's
    NVLink:IB ratio (``simulator.cc:37-38``), here ICI:DCN.
    """

    mxu_flops_per_us: float = 1.97e14 / 1e6 * 0.5  # bf16 peak, 50% eff.
    hbm_bytes_per_us: float = 8.19e11 / 1e6
    ici_bytes_per_us: float = 4.5e10 / 1e6
    dcn_bytes_per_us: float = 2.5e9 / 1e6
    task_overhead_us: float = 2.0
    devices_per_node: int = 256  # one v5e pod slice = one ICI domain


@dataclasses.dataclass
class OpCost:
    flops: float          # forward flops
    bytes: float          # forward activation+param traffic, bytes
    param_bytes: Dict[str, Tuple[float, Tuple]]  # name -> (bytes, dim_axes)
    #: bytes of the primary input for ops that contract it against a
    #: ``c``-sharded weight: under TP each shard computes a full-size
    #: partial input-gradient that must be reduced across the c-group
    #: (the reference's replica-grad ``backward2`` saxpy-reduction,
    #: ``linear.cu:494-520``).
    contracted_input_bytes: float = 0.0
    #: bytes that cross the ``c``-group per step for expert-parallel
    #: ops (MoE dispatch + combine all-to-alls: tokens to experts and
    #: back — the activation traffic Legion coherence generated for
    #: the reference's pinned tables).
    ep_alltoall_bytes: float = 0.0


def contracted_input_dims(op: Op) -> Tuple[int, ...]:
    """Dims of ``op.inputs[0]`` that are contracted (read in full by
    every c-shard): the feature dim of Linear/Attention, the channel
    dim of NHWC Conv2D."""
    if isinstance(op, (Linear, MultiHeadAttention)):
        return (op.inputs[0].ndim - 1,)
    if isinstance(op, Conv2D):
        return (3,)
    return ()


def _dtype_size(dtype) -> int:
    return int(np.dtype(dtype).itemsize)


def op_cost(op: Op) -> OpCost:
    """Forward flops/bytes for one op from its declared shapes.

    Dense-compute flops follow from the parameters: every weight of
    size ``prod(W)`` is contracted against each of the output's
    non-feature positions, i.e. ``2 * prod(out dims not tagged 'c') *
    prod(W)`` — exact for conv (``2*N*Ho*Wo*kh*kw*Cin*Cout``), linear,
    LSTM gates, and attention projections.  Attention adds its
    ``O(seq^2)`` score/value term explicitly.
    """
    out = op.outputs[0]
    esize = _dtype_size(out.dtype)
    non_c = 1.0
    for ext, ax in zip(out.shape, out.dim_axes):
        if ax != "c":
            non_c *= ext
    flops = 0.0
    bytes_ = 0.0
    params: Dict[str, Tuple[float, Tuple]] = {}
    lookup = isinstance(op, LOOKUP_OPS)
    moe = isinstance(op, MixtureOfExperts)
    for name, spec in op.param_specs().items():
        psize = float(np.prod(spec.shape)) if spec.shape else 1.0
        pbytes = psize * _dtype_size(spec.dtype)
        params[name] = (pbytes, tuple(spec.dim_axes))
        if lookup:
            # Gather: touches ~output-many rows, already counted below.
            continue
        bytes_ += pbytes
        if moe:
            continue  # only capacity-many tokens contract each expert
        if isinstance(op, (MultiHeadAttention, LSTM)):
            continue  # explicit formulas below: their outputs carry no
            # 'c' tag, so the generic non_c rule would multiply in the
            # feature dim and overcount by ~d (bench round-4 MFU audit)
        if len(spec.shape) >= 2:
            flops += 2.0 * non_c * psize
    moe_ep_bytes = 0.0
    if moe:
        # Switch MoE: router matmul, dispatch/combine one-hot einsums
        # (O(S * E*C * d), the GShard dispatch cost), and the expert
        # FFN over E*C ~= cf*S capacity slots.
        b, t, d = op.inputs[0].shape
        s = float(b * t)
        e = op.attrs["num_experts"]
        fdim = op.attrs["ffn_dim"]
        cap = float(op.capacity(b * t))
        flops += 2.0 * s * d * e                  # router
        flops += 2.0 * 2.0 * s * e * cap * d      # dispatch + combine
        flops += 2.0 * 2.0 * e * cap * d * fdim   # expert up+down matmuls
        # Tokens to experts and back under a c-split (fwd; bwd mirrors
        # it — FWD_BWD_FACTOR is applied by the caller's compute side,
        # so charge fwd+bwd = 2 round trips here explicitly).
        moe_ep_bytes = 2.0 * 2.0 * e * cap * d * esize
    if isinstance(op, MultiHeadAttention):
        b, s, d = op.inputs[0].shape
        flops += 8.0 * b * s * float(d) ** 2  # q/k/v/o projections
        flops += 4.0 * b * float(s) ** 2 * d  # QK^T and PV
    if isinstance(op, LSTM):
        # Gate matmuls over the scan: 2*b*(in+h)*4h per step.
        # Sequential scan: MXU utilization is poor for the per-step
        # small GEMMs; charge 4x.
        b, s, h = op.outputs[0].shape
        flops += 4.0 * (2.0 * b * s * 4.0 * h * (op.in_dim + h))
    for t in op.inputs:
        bytes_ += float(np.prod(t.shape)) * _dtype_size(t.dtype)
    for t in op.outputs:
        bytes_ += float(np.prod(t.shape)) * _dtype_size(t.dtype)
    cib = 0.0
    if contracted_input_dims(op) and op.inputs:
        x = op.inputs[0]
        cib = float(np.prod(x.shape)) * _dtype_size(x.dtype)
    return OpCost(
        flops=flops, bytes=bytes_, param_bytes=params,
        contracted_input_bytes=cib,
        ep_alltoall_bytes=moe_ep_bytes,
    )


def shard_cost_us(cost: OpCost, parts: int, dev: DeviceModel) -> float:
    """Per-shard fwd+bwd compute time under an even ``parts``-way split."""
    f = cost.flops * FWD_BWD_FACTOR / parts
    b = cost.bytes * FWD_BWD_FACTOR / parts
    return dev.task_overhead_us + max(
        f / dev.mxu_flops_per_us, b / dev.hbm_bytes_per_us
    )


def sync_cost_us(cost: OpCost, degrees: Dict[str, int], dev: DeviceModel) -> float:
    """Gradient-reduction time for one op under the given degrees.

    A parameter sharded along semantic axes A is replicated across the
    product of the remaining degrees ``r``; its gradient needs a ring
    all-reduce over the replica group: ``2*(r-1)/r * shard_bytes / bw``
    (the reference's replica-grad gather in the optimizer,
    ``optimizer_kernel.cu:118-123``, generalized to a ring over ICI).
    """
    parts = 1
    for d in degrees.values():
        parts *= d
    total = 0.0
    for _, (pbytes, dim_axes) in cost.param_bytes.items():
        shard_deg = 1
        for ax in dim_axes:
            if ax is not None:
                shard_deg *= degrees.get(ax, 1)
        replicas = max(1, parts // max(shard_deg, 1))
        if replicas <= 1:
            continue
        shard_bytes = pbytes / max(shard_deg, 1)
        total += 2.0 * (replicas - 1) / replicas * shard_bytes / dev.ici_bytes_per_us
    c = degrees.get("c", 1)
    if c > 1 and cost.contracted_input_bytes > 0:
        # TP input-grad reduce-scatter across the c-group.
        total += (
            2.0 * (c - 1) / c * cost.contracted_input_bytes / dev.ici_bytes_per_us
        )
    if c > 1 and cost.ep_alltoall_bytes > 0:
        # Expert-parallel dispatch/combine: each device keeps 1/c of
        # its tokens and exchanges the rest (all-to-all over ICI).
        total += (
            (c - 1) / c * cost.ep_alltoall_bytes / dev.ici_bytes_per_us
        )
    return total


# -- host dispatch / fence calibration ----------------------------------------
#
# PIPELINE_OVERHEAD.md's central finding: at dispatch-bound shapes the
# step time is dominated not by the compute the roofline above models
# but by PER-PROGRAM HOST DISPATCH (~1.4-1.6 ms/program on the CPU
# mesh; not measured on the chip, ROADMAP A2) and host-readback
# fences.  The
# execution-config search (search/execution.py) therefore adds an
# explicit ``programs_per_step x dispatch_ms + fences_per_step x
# fence_ms`` term, whose constants a :class:`Calibration` fits from a
# run's own JSONL telemetry (runtime/telemetry.py records step wall
# times, fence wall times, and the exact programs-per-step accounting).

#: Uncalibrated fallbacks: the measured per-program host dispatch cost
#: on the reference dev host's CPU mesh (PIPELINE_OVERHEAD.md rounds
#: 3/6) and the same-magnitude host-readback round trip.  Neither is
#: measured on the chip — calibrate from a real run's telemetry there.
DEFAULT_DISPATCH_MS = 1.5
DEFAULT_FENCE_MS = 1.5

def _fence_exclude() -> frozenset:
    """Fence labels excluded from fence_ms fitting — the ONE exclusion
    rule shared with the in-memory fitter
    (``Telemetry.calibration_summary``), so a constant fitted from a
    live run and one re-derived from its JSONL agree.  Imported lazily:
    the plain per-op search must stay importable without the runtime
    stack (see search/__init__'s lazy ``__getattr__``)."""
    from flexflow_tpu.runtime.telemetry import CALIBRATION_FENCE_EXCLUDE

    return CALIBRATION_FENCE_EXCLUDE


@dataclasses.dataclass
class Calibration:
    """Dispatch/fence constants for the execution cost model — either
    the uncalibrated defaults above, or fitted from one run's JSONL
    telemetry (:meth:`from_jsonl` / :meth:`from_dir`) or an in-memory
    :class:`~flexflow_tpu.runtime.telemetry.Telemetry`
    (:meth:`from_telemetry`).

    Fitting protocol (OBSERVABILITY.md records every input):

    - ``fence_ms``: the MINIMUM non-warmup/final fence wall time — on
      an async backend every fence also drains queued compute, so the
      cheapest observed fence is the round-trip floor estimate.
    - ``dispatch_ms``: ``step_ms_p50 / programs_per_step`` when the
      run was dispatch-audited at >= 2 programs/step (a host-driven
      pipeline run, where per-program dispatch is what the step time
      IS); runs at 1 program/step keep the default constant and let
      ``compute_scale`` (solved at search time from ``step_ms_p50``,
      see ``search/execution.py``) absorb the residual.
    - ``step_ms_p50`` / ``programs_per_step`` / ``fences_per_step``
      ride along so the search can solve the compute-scale equation
      against the run's OWN accounting.
    """

    dispatch_ms: float = DEFAULT_DISPATCH_MS
    fence_ms: float = DEFAULT_FENCE_MS
    #: Measured per-step wall p50 of the calibration run (ms), when
    #: known — the left-hand side of the compute-scale fit.
    step_ms_p50: Optional[float] = None
    programs_per_step: float = 1.0
    fences_per_step: float = 0.0
    steps: int = 0
    fence_samples: int = 0
    calibrated: bool = False
    source: Optional[str] = None
    #: True when the constants come from a COMPLETE accounting (the
    #: run_end ``calibration`` block, or a live in-memory Telemetry).
    #: A truncated log re-derives fence_ms / step p50 from raw events,
    #: but its programs-per-step may be unrecoverable (plain step
    #: events don't carry it), so the compute-scale fit — which prices
    #: the run's own overhead from that counter — requires ``complete``.
    complete: bool = False
    #: True when the calibration run executed an auto-CHOSEN config
    #: (its log carries a ``search`` event): its step p50 then measures
    #: the winner, not the baseline, and must not anchor the
    #: compute-scale fit (the dispatch/fence constants still apply).
    auto_executed: bool = False

    def describe(self) -> str:
        if not self.calibrated:
            return (f"uncalibrated defaults (dispatch {self.dispatch_ms} "
                    f"ms/program, fence {self.fence_ms} ms)")
        return (f"calibrated from {self.source or 'telemetry'} "
                f"(dispatch {self.dispatch_ms:.3f} ms/program, fence "
                f"{self.fence_ms:.3f} ms, {self.steps} steps / "
                f"{self.fence_samples} fences)")

    # -- constructors -------------------------------------------------------

    @staticmethod
    def from_summary(summary: Dict[str, Any],
                     source: Optional[str] = None,
                     complete: bool = True) -> "Calibration":
        """Build from a telemetry ``calibration`` block (the run_end
        event's, or ``Telemetry.calibration_summary()``).
        ``complete=False`` marks constants re-derived from a truncated
        log (see the ``complete`` field)."""
        cal = Calibration(source=source, complete=complete)
        pps = float(summary.get("programs_per_step") or 1.0)
        p50 = summary.get("step_ms_p50")
        fence = summary.get("fence_ms")
        if fence is not None:
            cal.fence_ms = float(fence)
            cal.calibrated = True
        if p50 is not None:
            cal.step_ms_p50 = float(p50)
            cal.calibrated = True
            if pps >= 2.0:
                # Dispatch-audited regime: per-program dispatch is what
                # the host-driven pipeline's step time is made of.
                cal.dispatch_ms = float(summary.get(
                    "dispatch_ms_per_program", p50 / pps
                ))
        cal.programs_per_step = pps
        cal.fences_per_step = float(summary.get("fences_per_step") or 0.0)
        cal.steps = int(summary.get("steps") or 0)
        cal.fence_samples = int(summary.get("fence_samples") or 0)
        return cal

    @staticmethod
    def from_events(events, source: Optional[str] = None) -> "Calibration":
        """Fit from raw JSONL events (robust to truncated logs with no
        ``run_end``): step wall p50, min non-warmup fence wall, and the
        programs/fences-per-step counters re-derived from ``step`` /
        ``fence`` / ``superstep`` events (a serving round's steps from
        its one event, ``obs.reader.round_steps``)."""
        from flexflow_tpu.obs.reader import round_steps

        run_end_cal: Optional[Dict[str, Any]] = None
        step_walls: List[float] = []
        fence_walls: List[float] = []
        steps = fences = 0
        programs = program_steps = 0.0
        saw_search = False
        exclude = _fence_exclude()
        for ev in events:
            kind = ev.get("ev")
            walls = round_steps(ev)
            steps += len(walls)
            step_walls.extend(walls)
            if kind == "step":
                steps += 1
                if ev.get("wall_s") is not None:
                    step_walls.append(float(ev["wall_s"]))
            elif kind == "fence":
                fences += 1
                if (ev.get("label") not in exclude
                        and ev.get("wall_s") is not None):
                    fence_walls.append(float(ev["wall_s"]))
            elif kind == "superstep":
                pps = ev.get("programs_per_step")
                k = float(ev.get("k") or 1)
                if pps is not None:
                    programs += float(pps) * k
                    program_steps += k
            elif kind == "search":
                # The run trained under an auto-CHOSEN config; its
                # step p50 must not anchor the baseline compute fit.
                saw_search = True
            elif kind == "run_end" and isinstance(ev.get("calibration"), dict):
                run_end_cal = ev["calibration"]
        if run_end_cal is not None:
            cal = Calibration.from_summary(run_end_cal, source=source)
            cal.auto_executed = saw_search
            return cal
        summary: Dict[str, Any] = {}
        if step_walls:
            ts = sorted(step_walls)
            summary["step_ms_p50"] = ts[len(ts) // 2] * 1e3
        if fence_walls:
            summary["fence_ms"] = max(min(fence_walls) * 1e3, 1e-3)
            summary["fence_samples"] = len(fence_walls)
        if program_steps:
            summary["programs_per_step"] = programs / program_steps
        # Steady-state count: same warmup/final exclusion as fence_ms
        # (and as Telemetry.calibration_summary's block).
        summary["fences_per_step"] = len(fence_walls) / max(steps, 1)
        summary["steps"] = steps
        cal = Calibration.from_summary(summary, source=source,
                                       complete=False)
        cal.auto_executed = saw_search
        return cal

    @staticmethod
    def from_jsonl(path: str) -> "Calibration":
        """Load one run's JSONL telemetry (via the ONE log parser,
        ``obs.reader.RunLog`` — truncation-tolerant exactly as before);
        falls back LOUDLY to the uncalibrated defaults on a
        missing/unreadable file."""
        from flexflow_tpu.obs.reader import RunLog

        log = RunLog.load(path)
        if log.read_error is not None:
            _log.warning(
                "calibration: cannot read %s (%s); using uncalibrated "
                "roofline/dispatch defaults", path, log.read_error,
            )
            return Calibration()
        if not log.events:
            _log.warning(
                "calibration: %s holds no events; using uncalibrated "
                "defaults", path,
            )
            return Calibration()
        return Calibration.from_events(log.iter_raw(), source=path)

    @staticmethod
    def from_dir(directory: str,
                 exclude: Optional[str] = None) -> "Calibration":
        """Latest ``run-*.jsonl`` under ``directory`` (excluding e.g.
        the ACTIVE run's own file; selection rule shared with
        ``obs.reader.latest_run``); uncalibrated defaults when none."""
        from flexflow_tpu.obs.reader import latest_run

        path = latest_run(directory, exclude=exclude)
        if path is None:
            return Calibration()
        return Calibration.from_jsonl(path)

    @staticmethod
    def from_path(path: str) -> "Calibration":
        """File -> :meth:`from_jsonl`; directory -> :meth:`from_dir`."""
        if os.path.isdir(path):
            return Calibration.from_dir(path)
        return Calibration.from_jsonl(path)

    @staticmethod
    def from_telemetry(tel) -> "Calibration":
        """Fit from a live in-memory Telemetry."""
        return Calibration.from_summary(
            tel.calibration_summary(), source="in-memory telemetry"
        )
