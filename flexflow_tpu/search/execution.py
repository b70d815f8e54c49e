"""Execution-config autotuner: search over what the runtime ACTUALLY
executes.

The per-op strategy search (``search_strategy``, the paper's MCMC over
``ffsim``) predates the runtime's dispatch-amortization machinery: it
knows nothing about superstep ``k`` (``--steps-per-call``), pipeline
chunking ``c``, compiled-vs-host pipeline dispatch, or accumulation —
yet PIPELINE_OVERHEAD.md shows per-program HOST DISPATCH + fence costs
dominate step time at dispatch-bound shapes (the regime where the
superstep/chunk/compiled work won 1.17-1.9x).  A candidate here is a
full :class:`ExecutionConfig` — (per-op ``ParallelConfig`` table,
stage partition for layer-wise strategies, chunk ``c``, superstep
``k <= 20``, compiled on/off, accum ``a``) — and the cost model is::

    predicted_ms = compute_ms(strategy)            # ffsim makespan
                 + programs_per_step x dispatch_ms # the dispatch term
                 + fences_per_step   x fence_ms    # the fence term

where ``programs_per_step`` reuses the EXACT accounting the run
telemetry already pins (``2*S*ceil(m/c)`` host-driven pipeline, ``1/k``
fused/compiled — OBSERVABILITY.md) and ``dispatch_ms`` / ``fence_ms``
come from a :class:`~flexflow_tpu.search.cost_model.Calibration`
fitted from a run's own JSONL telemetry (uncalibrated fallback: the
measured host constants).  Legality is REUSED from the runtime, never
duplicated: ``StrategyStore.layer_wise`` / ``superstep_mode`` decide
which superstep form a strategy supports, and
``runtime.pipeline.compiled_unsupported_reason`` is the SAME
eligibility ladder ``PipelineExecutor`` enforces — so every config the
search emits executes without a loud fallback (pinned by
tests/test_search.py).

``--strategy auto`` (``-s auto``) on every app runs this search then
trains under the winner (``apps/common.py``); ``python -m
flexflow_tpu.search --auto`` runs it offline.  SEARCH.md documents the
candidate space, the calibration protocol, and measured auto-vs-default
results.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

_log = logging.getLogger("ff.search")

from flexflow_tpu.graph import FFModel
from flexflow_tpu.parallel.strategy import StrategyStore
from flexflow_tpu.search.cost_model import (
    FWD_BWD_FACTOR,
    Calibration,
    DeviceModel,
)
from flexflow_tpu.search.problem import build_stage_partition

def _max_steps_per_call() -> int:
    """Fused-step bound for superstep candidates — the runtime's
    OWN constant (``Trainer.fit`` clamps k at it), imported lazily so this module stays importable
    without the runtime stack.  A duplicated literal here would let
    the search price a k the Trainer then silently clamps."""
    from flexflow_tpu.runtime.trainer import MAX_STEPS_PER_CALL

    return MAX_STEPS_PER_CALL

#: Stage-boundary remat: the pipeline's backward recomputes each
#: stage's forward, so pipeline compute pays one extra fwd on top of
#: fwd+bwd — (FWD_BWD_FACTOR + 1) / FWD_BWD_FACTOR.
REMAT_FACTOR = (FWD_BWD_FACTOR + 1.0) / FWD_BWD_FACTOR


@dataclasses.dataclass
class ExecutionConfig:
    """One point of the execution search space: a strategy table plus
    every dispatch-shaping knob the runtime exposes.  ``apply_to(cfg)``
    writes the knobs into an ``FFConfig`` so ``make_executor`` +
    ``Trainer.fit`` run exactly this config."""

    store: StrategyStore
    microbatches: int = 1
    chunk: int = 1
    steps_per_call: int = 1
    compiled: bool = False
    accum_steps: int = 1
    schedule: str = "1f1b"
    #: Pipeline stage count (1 = full-mesh Executor).
    stages: int = 1
    label: str = ""
    # -- filled by predict_step_ms -----------------------------------------
    predicted_ms: float = float("nan")
    compute_ms: float = 0.0
    dispatch_term_ms: float = 0.0
    fence_term_ms: float = 0.0

    @property
    def layer_wise(self) -> bool:
        return self.stages > 1

    def programs_per_step(self) -> float:
        """Host programs per train step — the EXACT accounting the run
        telemetry pins (OBSERVABILITY.md "Dispatch audit"): the
        host-driven pipeline dispatches ``2*S*ceil(m_eff/c)`` stage
        programs (``m_eff`` includes accum's lowered microbatches);
        full-mesh and compiled-pipeline steps are ONE fused program, or
        ``1/k`` on the fused superstep path."""
        if self.layer_wise and not self.compiled:
            m_eff = self.microbatches * self.accum_steps
            return 2.0 * self.stages * math.ceil(m_eff / max(self.chunk, 1))
        return 1.0 / max(self.steps_per_call, 1)

    def fences_per_step(self, clip_norm: float = 0.0) -> float:
        """Host-readback fences per step: the per-step loops are
        unfenced (k=1 -> ~0; the final fence amortizes over the run);
        superstep execution fences once per k steps; the host-driven
        pipeline keeps its loudly-warned one-fence-per-step floor under
        ``clip_norm > 0`` (the global-norm fetch)."""
        if self.layer_wise and not self.compiled and clip_norm > 0.0:
            return 1.0
        k = max(self.steps_per_call, 1)
        return 0.0 if k == 1 else 1.0 / k

    def describe(self) -> str:
        if self.layer_wise:
            base = (f"layer-wise S={self.stages} m={self.microbatches}"
                    + (f" a={self.accum_steps}" if self.accum_steps > 1
                       else "")
                    + (" compiled" if self.compiled
                       else f" c={self.chunk} host"))
        else:
            base = ("full-mesh " + (self.label or "strategy")
                    + (f" a={self.accum_steps}" if self.accum_steps > 1
                       else ""))
        return f"{base} k={self.steps_per_call}"

    def to_json(self) -> Dict[str, Any]:
        """The config as one JSON-able record — what the ``search``
        telemetry event carries so a run's choice is reconstructable
        from its log alone."""
        return {
            "label": self.label,
            "ops": {k: v.to_json() for k, v in self.store.table.items()},
            "num_devices": self.store.num_devices,
            "stages": self.stages,
            "microbatches": self.microbatches,
            "chunk": self.chunk,
            "steps_per_call": self.steps_per_call,
            "compiled": self.compiled,
            "accum_steps": self.accum_steps,
            "predicted_ms": None if math.isnan(self.predicted_ms)
            else round(self.predicted_ms, 4),
        }

    def apply_to(self, cfg) -> None:
        """Write this config's execution knobs into an ``FFConfig`` (the
        strategy store itself travels separately to ``make_executor``)."""
        cfg.microbatches = self.microbatches
        cfg.pipeline_chunk = self.chunk
        cfg.steps_per_call = self.steps_per_call
        cfg.pipeline_compiled = self.compiled
        cfg.pipeline_schedule = self.schedule


def predict_step_ms(
    model: FFModel,
    ecfg: ExecutionConfig,
    num_devices: int,
    calibration: Optional[Calibration] = None,
    device_model: Optional[DeviceModel] = None,
    measured_costs: Optional[dict] = None,
    clip_norm: float = 0.0,
    compute_us: Optional[float] = None,
    compute_scale: float = 1.0,
) -> float:
    """Predicted wall ms/step of one execution config: the ffsim
    compute makespan (x the remat factor on pipeline paths, x the
    calibrated ``compute_scale``) plus the dispatch and fence terms.
    ``compute_us`` overrides the simulator (recorded-constant tests,
    per-store caching).  Fills the config's component fields and
    returns the total."""
    cal = calibration or Calibration()
    if compute_us is None:
        from flexflow_tpu.search import simulate_strategy

        compute_us = simulate_strategy(
            model, ecfg.store, num_devices, device_model,
            measured_costs=measured_costs,
        )
    compute_ms = compute_us / 1e3 * compute_scale
    if ecfg.layer_wise:
        compute_ms *= REMAT_FACTOR
    ecfg.compute_ms = compute_ms
    ecfg.dispatch_term_ms = ecfg.programs_per_step() * cal.dispatch_ms
    ecfg.fence_term_ms = ecfg.fences_per_step(clip_norm) * cal.fence_ms
    ecfg.predicted_ms = (
        compute_ms + ecfg.dispatch_term_ms + ecfg.fence_term_ms
    )
    return ecfg.predicted_ms


@dataclasses.dataclass
class ExecutionSearchResult:
    best: ExecutionConfig
    baseline: ExecutionConfig
    candidates: List[ExecutionConfig]
    calibration: Calibration
    compute_scale: float
    wall_s: float
    #: Simulated per-op-search stats when the MCMC leg ran (else 0).
    dp_time_us: float = 0.0
    op_search_time_us: float = 0.0

    @property
    def speedup(self) -> float:
        """Predicted best-vs-baseline step-time ratio."""
        return self.baseline.predicted_ms / max(self.best.predicted_ms, 1e-9)


def _superstep_options(store: StrategyStore, compiled: bool,
                       ks: Sequence[int], resilient: bool) -> List[int]:
    """Legal ``steps_per_call`` values for one strategy, routed through
    the runtime's OWN eligibility: ``superstep_mode`` says whether k
    fuses ("fused") or only amortizes the fence ("amortized"); the
    resilient loop additionally refuses k>1 on the amortized path
    (apps/common._run_resilient)."""
    mode = store.superstep_mode(compiled=compiled)
    if mode == "amortized":
        if resilient:
            return [1]
        # Fence-only amortization: k changes one term; the extremes
        # cover the curve.
        return sorted({1, max(ks)})
    return sorted(set(ks))


def search_execution_config(
    model: FFModel,
    num_devices: int,
    iters: int = 20_000,
    seed: int = 0,
    calibration: Optional[Calibration] = None,
    device_model: Optional[DeviceModel] = None,
    measured_costs: Optional[dict] = None,
    clip_norm: float = 0.0,
    accum_steps: int = 1,
    resilient: bool = False,
    allow_layer_wise: bool = True,
    include_op_search: bool = True,
    ks: Sequence[int] = (1, 4, 8, 16, 20),
    stage_options: Sequence[int] = (2, 4),
    microbatch_options: Sequence[int] = (4, 8),
    baseline: Optional[ExecutionConfig] = None,
    max_candidates: int = 64,
) -> ExecutionSearchResult:
    """Search the full execution-config space for ``model`` on
    ``num_devices`` devices (offline — no accelerator needed).

    Strategy tables come from the DP fallback plus the paper's per-op
    MCMC search (``iters`` > 0) plus synthetic layer-wise stage
    partitions; each table then fans out over the dispatch knobs its
    legality admits (see module docstring).  ``baseline`` is the config
    to beat (an app's hand-written default; DP k=1 when omitted) and
    COMPETES as a candidate, so ``best`` is never predicted-slower
    than it — it
    also anchors the compute-scale fit when ``calibration`` carries a
    measured ``step_ms_p50``: the run's measured step time, minus its
    OWN dispatch/fence overhead (its telemetry-pinned programs- and
    fences-per-step x the calibrated constants), is what the simulated
    compute of the config that produced it must scale to.
    """
    t0 = time.perf_counter()
    cal = calibration or Calibration()
    ks = sorted({
        min(max(int(k), 1), _max_steps_per_call()) for k in ks
    }) or [1]

    from flexflow_tpu.search import search_strategy, simulate_strategy

    def compute_us_of(store: StrategyStore) -> float:
        return simulate_strategy(
            model, store, num_devices, device_model,
            measured_costs=measured_costs,
        )

    compute_cache: Dict[int, float] = {}

    def cached_compute(store: StrategyStore) -> float:
        key = id(store)
        if key not in compute_cache:
            compute_cache[key] = compute_us_of(store)
        return compute_cache[key]

    if baseline is None:
        baseline = ExecutionConfig(
            store=StrategyStore.data_parallel(num_devices),
            accum_steps=accum_steps, label="dp-default",
        )

    # Compute-scale fit: measured p50 = scale*compute + overhead, with
    # the overhead priced from the calibration run's OWN accounting.
    # The p50 anchors the BASELINE's simulated compute, so the fit
    # requires a run that executed the baseline config: skipped when
    # the calibration log carries a `search` event (that run trained
    # under an auto-chosen winner — its p50 measures the wrong config)
    # or is truncated (its programs-per-step may be unrecoverable, so
    # its own overhead cannot be priced).  Dispatch/fence constants
    # still apply either way.
    compute_scale = 1.0
    if cal.auto_executed and cal.step_ms_p50:
        _log.info(
            "calibration source %s trained under an auto-chosen config; "
            "using its dispatch/fence constants but skipping the "
            "baseline compute-scale fit", cal.source,
        )
    if (cal.calibrated and cal.step_ms_p50 and cal.complete
            and not cal.auto_executed):
        overhead = (cal.programs_per_step * cal.dispatch_ms
                    + cal.fences_per_step * cal.fence_ms)
        base_ms = cached_compute(baseline.store) / 1e3
        if baseline.layer_wise:
            base_ms *= REMAT_FACTOR
        residual = cal.step_ms_p50 - overhead
        if residual > 0 and base_ms > 0:
            compute_scale = residual / base_ms
        else:
            _log.info(
                "calibration: measured step p50 %.3f ms is within the "
                "dispatch/fence overhead estimate (%.3f ms); compute "
                "term effectively calibrated to zero",
                cal.step_ms_p50, overhead,
            )
            compute_scale = 1e-6

    stores: List[Tuple[str, StrategyStore]] = [
        ("dp", StrategyStore.data_parallel(num_devices))
    ]
    dp_us = op_us = 0.0
    if include_op_search and iters > 0:
        try:
            opres = search_strategy(
                model, num_devices=num_devices, iters=iters, seed=seed,
                device_model=device_model, max_candidates=max_candidates,
                measured_costs=measured_costs,
            )
            dp_us, op_us = opres.dp_time_us, opres.best_time_us
            stores.append(("op-search", opres.store))
        except Exception as e:  # the DP ladder must survive a sim failure
            _log.warning(
                "per-op strategy search failed (%s: %s); execution "
                "search continues on the DP table", type(e).__name__, e,
            )

    candidates: List[ExecutionConfig] = []

    def add(ecfg: ExecutionConfig, compute_us: float) -> None:
        predict_step_ms(
            model, ecfg, num_devices, calibration=cal,
            clip_norm=clip_norm, compute_us=compute_us,
            compute_scale=compute_scale,
        )
        candidates.append(ecfg)

    batch = model.input_tensors[0].shape[0] if model.input_tensors else 0

    for label, store in stores:
        if store.layer_wise:
            if not allow_layer_wise:
                # The caller cannot run pipeline executors at all
                # (e.g. --zc-dataset stages onto the full mesh):
                # a layer-wise MCMC winner must be dropped here, not
                # refused by the app after the search chose it.
                _log.info(
                    "execution search: dropping layer-wise %s table "
                    "(layer-wise execution disabled for this run)",
                    label,
                )
                continue
            # An op-search result that pinned device subsets runs on
            # the PipelineExecutor; fan it out below with the stage
            # structure the runtime itself derives.
            try:
                from flexflow_tpu.runtime.pipeline import derive_stages

                n_stages = len(derive_stages(model, store))
            except Exception as e:
                _log.warning(
                    "layer-wise %s table is not stageable (%s); "
                    "dropping it from the execution search", label, e,
                )
                continue
            _fan_out_pipeline(
                model, store, n_stages, label, candidates_add=add,
                cached_compute=cached_compute, ks=ks,
                resilient=resilient, accum_steps=accum_steps,
                microbatch_options=(1,) + tuple(microbatch_options),
                batch=batch,
            )
            continue
        c_us = cached_compute(store)
        for k in _superstep_options(store, False, ks, resilient):
            add(ExecutionConfig(
                store=store, steps_per_call=k, accum_steps=accum_steps,
                label=label,
            ), c_us)

    if allow_layer_wise and num_devices >= 2:
        for S in sorted(set(stage_options)):
            for m in sorted(set(microbatch_options)):
                m_eff = m * accum_steps
                if batch and batch % m_eff:
                    continue
                store_s = build_stage_partition(
                    model, num_devices, S, microbatches=m_eff
                )
                if store_s is None:
                    continue
                _fan_out_pipeline(
                    model, store_s, S, f"stage-partition S={S}",
                    candidates_add=add, cached_compute=cached_compute,
                    ks=ks, resilient=resilient, accum_steps=accum_steps,
                    microbatch_options=(m,), batch=batch,
                )

    predict_step_ms(
        model, baseline, num_devices, calibration=cal,
        clip_norm=clip_norm, compute_us=cached_compute(baseline.store),
        compute_scale=compute_scale,
    )
    # The baseline COMPETES: search-then-run must never apply a config
    # its own cost model predicts is slower than the app's default.
    candidates.append(baseline)
    # Deterministic winner: ties break toward the simpler config
    # (fewer stages, smaller m, smaller k, host over compiled).
    candidates.sort(key=lambda c: (
        round(c.predicted_ms, 6), c.stages, c.microbatches,
        c.steps_per_call, c.compiled,
    ))
    return ExecutionSearchResult(
        best=candidates[0],
        baseline=baseline,
        candidates=candidates,
        calibration=cal,
        compute_scale=compute_scale,
        wall_s=time.perf_counter() - t0,
        dp_time_us=dp_us,
        op_search_time_us=op_us,
    )


def _fan_out_pipeline(
    model: FFModel,
    store: StrategyStore,
    n_stages: int,
    label: str,
    candidates_add,
    cached_compute,
    ks: Sequence[int],
    resilient: bool,
    accum_steps: int,
    microbatch_options: Sequence[int],
    batch: int,
) -> None:
    """Fan one layer-wise strategy table out over (m, c, compiled, k) —
    compiled eligibility via the runtime's OWN
    ``compiled_unsupported_reason`` ladder (never duplicated), host
    chunk at the dispatch extremes {1, m_eff}."""
    from flexflow_tpu.runtime.pipeline import compiled_unsupported_reason

    c_us = cached_compute(store)
    reason = compiled_unsupported_reason(model, store)
    if reason is not None:
        _log.info("execution search: %s not compiled-eligible (%s); "
                  "host-driven candidates only", label, reason)
    for m in sorted(set(microbatch_options)):
        m_eff = m * accum_steps
        if batch and batch % m_eff:
            continue
        for chunk in sorted({1, m_eff}):
            for k in _superstep_options(store, False, ks, resilient):
                candidates_add(ExecutionConfig(
                    store=store, microbatches=m, chunk=chunk,
                    steps_per_call=k, accum_steps=accum_steps,
                    stages=n_stages, label=label,
                ), c_us)
        if reason is None:
            for k in _superstep_options(store, True, ks, resilient):
                candidates_add(ExecutionConfig(
                    store=store, microbatches=m, chunk=1, compiled=True,
                    steps_per_call=k, accum_steps=accum_steps,
                    stages=n_stages, label=label,
                ), c_us)
