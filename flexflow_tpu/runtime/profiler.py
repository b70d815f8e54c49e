"""Profiling / tracing.

The reference's profiling is (1) per-task wall-clock via cudaEvent
pairs gated by ``--profiling`` (``conv_2d.cu:515-546``,
``linear.cu:296-332``), (2) whole-run timing between execution fences
(``dlrm.cc:159-163``), and (3) Legion trace capture of the step
(``dlrm.cc:151-156``).  TPU equivalents here:

- ``profile_ops``: per-op forward wall-clock — each op jitted and timed
  in isolation with a host-readback fence (cudaEvent analogue; the
  numbers also serve as a *measured* cost table for the strategy
  search, replacing the reference's cuDNN microbenchmarks,
  ``scripts/cnn.h:204+``).
- ``trace``: ``jax.profiler`` TensorBoard trace of the real fused step
  (what XLA actually runs; per-op eager times do not see fusion).
- Whole-run timing lives in ``Trainer.fit`` (reference formulas).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from flexflow_tpu.ops.base import op_params
from flexflow_tpu.runtime.executor import Executor


@dataclasses.dataclass
class OpProfile:
    name: str
    op_type: str
    time_us: float
    output_shapes: List[tuple]

    def __str__(self):
        shapes = ", ".join(str(s) for s in self.output_shapes)
        return f"{self.name:28s} {self.op_type:12s} {self.time_us:10.1f} us  -> {shapes}"


def profile_ops(
    ex: Executor,
    params: Any,
    state: Any,
    batch: Dict[str, Any],
    reps: int = 5,
    warmup: int = 2,
) -> List[OpProfile]:
    """Time every op's forward in isolation (compiled, fenced).

    Mirrors the reference's per-task event timing under ``--profiling``;
    each op runs with its real sharded inputs (produced by the previous
    ops) so the times include the op's own collectives.
    """
    env: Dict[str, jax.Array] = {}
    for t in ex.model.input_tensors:
        env[t.name] = jax.device_put(batch[t.name], ex.input_sharding(t))
    profiles: List[OpProfile] = []
    for op in ex.model.layers:
        op.bind_mesh(ex.plan, ex._pc(op))
        xs = [env[t.name] for t in op.inputs]
        p = op_params(op, params)
        s = state.get(op.name, {})

        def run(p, xs, s, _op=op):
            result, _ = _op.forward(p, xs, s, training=False)
            if _op.is_loss:
                _, _, ys = result
            else:
                ys = result
            return ys

        fn = jax.jit(run)
        ys = fn(p, xs, s)
        for _ in range(warmup):
            ys = fn(p, xs, s)
        jax.device_get(jax.tree.leaves(ys)[0].ravel()[:1])  # fence
        t0 = time.perf_counter()
        for _ in range(reps):
            ys = fn(p, xs, s)
        jax.device_get(jax.tree.leaves(ys)[0].ravel()[:1])
        dt = (time.perf_counter() - t0) / reps * 1e6
        for t, y in zip(op.outputs, ys):
            env[t.name] = y
        profiles.append(
            OpProfile(
                name=op.name,
                op_type=type(op).__name__,
                time_us=dt,
                output_shapes=[tuple(t.shape) for t in op.outputs],
            )
        )
    return profiles


def report(profiles: List[OpProfile]) -> str:
    total = sum(p.time_us for p in profiles)
    lines = [str(p) for p in profiles]
    lines.append(f"{'TOTAL (unfused sum)':28s} {'':12s} {total:10.1f} us")
    return "\n".join(lines)


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a TensorBoard/XProf trace of everything run inside the
    block (the jitted step as XLA executes it — fusions, collectives,
    real device timelines).  View with ``tensorboard --logdir``; with
    telemetry on, ``obs/trace.py`` reads the ``.xplane.pb`` it leaves
    into the ``run_end`` ``trace_summary`` device-time attribution."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _shard_shapes(op, pc):
    """Shard-local shapes for one candidate config: every dim tagged
    with a semantic axis is divided by that axis's degree, except dims
    the op contracts in full on every shard (linear/attention feature
    dim, conv input channels).  This is the reference's microbenchmark
    geometry: ``measure_conv2d_time`` benches the shard's rect on ONE
    device (``scripts/cnn.h:204+``)."""
    from flexflow_tpu.search.cost_model import contracted_input_dims

    contracted = set(contracted_input_dims(op))

    def local(shape, dim_axes, skip_dims=()):
        out = []
        for d, (ext, ax) in enumerate(zip(shape, dim_axes)):
            deg = 1 if (ax is None or d in skip_dims) else pc.degree(ax)
            out.append(max(1, int(ext) // max(deg, 1)))
        return tuple(out)

    xs = [
        local(t.shape, t.dim_axes, contracted if ti == 0 else ())
        for ti, t in enumerate(op.inputs)
    ]
    ps = {k: local(s.shape, s.dim_axes) for k, s in op.param_specs().items()}
    ss = {k: local(s.shape, s.dim_axes) for k, s in op.state_specs().items()}
    return xs, ps, ss


def _synth(shape, dtype, key):
    import jax.numpy as jnp

    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        # Index inputs: 0 is valid for every table/vocab extent.
        return jnp.zeros(shape, dtype)
    return jax.random.normal(key, shape, dtype) * 0.02


def _perturbed(tree, eps):
    """Add a carry-derived epsilon to the first float leaf — defeats
    CSE/LICM across fori_loop iterations without measurable cost."""
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten(tree)
    done = False
    out = []
    for leaf in leaves:
        if not done and jnp.issubdtype(leaf.dtype, jnp.floating):
            out.append(leaf + eps.astype(leaf.dtype))
            done = True
        else:
            out.append(leaf)
    return jax.tree.unflatten(treedef, out), done


def _two_point_time(make, args, loops, reps):
    """Run ``make(n)(*args)`` at two loop counts; the per-iteration
    slope cancels dispatch + fence overhead, which would otherwise sit
    inside every single-shot eager timing (the reference's analogue
    concern: cudaEvent pairs around repeated kernel launches,
    ``scripts/cnn.h:231-246``).  Two dispatches per measurement, each
    fenced by host readback."""
    lo, hi = loops
    times = {}
    for n in (lo, hi):
        fn = make(n)
        jax.device_get(fn(*args))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.device_get(fn(*args))
            best = min(best, time.perf_counter() - t0)
        times[n] = best
    return max((times[hi] - times[lo]) / (hi - lo) * 1e6, 1e-3)


def _time_shard_forward(op, p, xs, s, loops=(4, 20), reps=2):
    """Per-iteration forward time (us) of one op at fixed shapes.

    Dispatch-proof protocol: the op runs ``n`` serially-dependent times
    inside ONE jitted ``fori_loop`` call (a tiny carry-derived
    perturbation defeats CSE), at two loop counts (``_two_point_time``).
    """
    import jax.numpy as jnp
    from jax import lax

    def make(n):
        def run(p, xs, s):
            def body(i, acc):
                eps = acc * jnp.float32(1e-30)
                xs2, ok = _perturbed(list(xs), eps)
                p2 = p
                if not ok:
                    p2, _ = _perturbed(p, eps)
                result, _ = op.forward(p2, xs2, s, False)
                ys = result[2] if op.is_loss else result
                first = jax.tree.leaves(ys)[0]
                return acc + first.ravel()[0].astype(jnp.float32) * 1e-30

            return lax.fori_loop(0, n, body, jnp.float32(0.0))

        return jax.jit(run)

    return _two_point_time(make, (p, xs, s), loops, reps)


def _time_shard_fwd_bwd(op, p, xs, s, loops=(4, 20), reps=2):
    """Measured (fwd_us, bwd_us) of one op at fixed shard-local shapes.

    The reference measures forward AND both backward legs per config —
    ``measure_conv2d_time`` returns ``t1+t2+t3`` (fwd + bwd-filter +
    bwd-data, ``scripts/cnn.h:252-277``) — so backward cost structure
    that differs from forward (spatial conv bwd-data halos, embedding
    scatter, flash bwd's two kernels) is *measured*, not assumed.
    Here: time the forward loop, then a ``jax.vjp`` fwd+bwd loop
    (cotangent of ones ≙ the reference's unit upstream grad, gradients
    w.r.t. params and float inputs ≙ bwd-filter + bwd-data); the
    difference is the backward time.  Loss ops differentiate
    ``(loss, ys)`` jointly — grad of the scalar loss alone would let
    XLA dead-code-eliminate the main-output backward of non-terminal
    loss ops (MoE's aux loss vs its expert FFNs).  Same two-point
    dispatch-proof protocol.
    """
    import jax.numpy as jnp
    from jax import lax

    fwd_us = _time_shard_forward(op, p, xs, s, loops=loops, reps=reps)

    float_ix = [
        i for i, x in enumerate(xs)
        if jnp.issubdtype(jnp.dtype(x.dtype), jnp.floating)
    ]

    def make(n):
        def run(p, xs, s):
            def body(i, acc):
                eps = acc * jnp.float32(1e-30)
                p2, okp = _perturbed(p, eps)
                fxs = [xs[j] for j in float_ix]
                if not okp:
                    fxs, _ = _perturbed(fxs, eps)

                def fwd_fn(p3, fxs3):
                    xs2 = list(xs)
                    for k, j in enumerate(float_ix):
                        xs2[j] = fxs3[k]
                    result, _ = op.forward(p3, xs2, s, False)
                    return (result[0], result[2]) if op.is_loss else result

                y, vjp = jax.vjp(fwd_fn, p2, fxs)
                grads = vjp(jax.tree.map(jnp.ones_like, y))
                leaves = [
                    g for g in jax.tree.leaves(grads)
                    if jnp.issubdtype(g.dtype, jnp.floating)
                ]
                first = leaves[0] if leaves else jnp.float32(0.0)
                return acc + first.ravel()[0].astype(jnp.float32) * 1e-30

            return lax.fori_loop(0, n, body, jnp.float32(0.0))

        return jax.jit(run)

    total_us = _two_point_time(make, (p, xs, s), loops, reps)
    return fwd_us, max(total_us - fwd_us, 0.0)


def measured_degree_table(
    model,
    num_devices: int,
    max_candidates: int = 64,
    loops=(4, 20),
    measure=None,
    seed: int = 0,
) -> Dict[str, Dict[tuple, Tuple[float, float]]]:
    """Measure every (op, parallel-degree) candidate live — the
    reference's ``computeTime[]`` cache filled by per-config cuDNN
    microbenchmarks (``scripts/cnn.h:204-260``, ``simulator.cc:
    142-151``).  Returns ``{op name: {(n,c,h,w,s): (fwd us, bwd us)}}``
    for ``search_strategy(measured_costs=...)`` — both legs measured
    per config like the reference's ``t1+t2+t3`` (fwd + bwd-filter +
    bwd-data, ``scripts/cnn.h:252-277``), so no fwd×factor assumption
    survives in the measured path.  Per-shard times come from running
    the shard's LOCAL shapes on one device, so nonlinear scaling (MXU
    under-utilization at small tiles, fixed overheads, asymmetric
    backward) is captured instead of the old measured/parts linear
    assumption.

    Structurally identical shards (same op type, attrs and local
    shapes — e.g. repeated Inception blocks, or a (n=2,c=1) shard
    equal to a (n=2,c=1,h=1...) one) are measured once via a shape
    cache.  ``measure(op, pc, p, xs, s) -> us | (fwd_us, bwd_us)`` is
    injectable (tests, alternative timers; a bare float is treated as
    fwd-only and scaled by the legacy ×``FWD_BWD_FACTOR`` downstream);
    ops whose forward cannot run at sliced shapes
    (static-shape reshapes) are skipped — the search falls back to the
    roofline for them.
    """
    from flexflow_tpu.parallel.mesh import build_mesh_plan
    from flexflow_tpu.parallel.strategy import AXES, ParallelConfig
    from flexflow_tpu.search.problem import build_virtual_plan, enumerate_candidates

    vplan = build_virtual_plan(num_devices)
    plan1 = build_mesh_plan(1)
    key = jax.random.PRNGKey(seed)
    cache: Dict[tuple, Tuple[float, float]] = {}
    table: Dict[str, Dict[tuple, Tuple[float, float]]] = {}
    for op in model.layers:
        op.bind_mesh(plan1, ParallelConfig())
        entries: Dict[tuple, Tuple[float, float]] = {}
        for pc in enumerate_candidates(op, vplan, max_candidates):
            degs = tuple(pc.degree(a) for a in AXES)
            if degs in entries:
                continue  # device-shifted variant: same shard geometry
            xs_shapes, p_shapes, s_shapes = _shard_shapes(op, pc)
            ck = (
                type(op).__name__,
                str(sorted(getattr(op, "attrs", {}).items())),
                tuple(zip(xs_shapes, (str(t.dtype) for t in op.inputs))),
                tuple(sorted((k, v) for k, v in p_shapes.items())),
            )
            if ck in cache:
                entries[degs] = cache[ck]
                continue
            key, *subs = jax.random.split(key, 4)
            try:
                xs = [
                    _synth(sh, t.dtype, subs[0])
                    for sh, t in zip(xs_shapes, op.inputs)
                ]
                p = {
                    k: _synth(sh, op.param_specs()[k].dtype, subs[1])
                    for k, sh in p_shapes.items()
                }
                s = {
                    k: _synth(sh, op.state_specs()[k].dtype, subs[2])
                    for k, sh in s_shapes.items()
                }
                if measure is not None:
                    us = measure(op, pc, p, xs, s)
                else:
                    us = _time_shard_fwd_bwd(op, p, xs, s, loops=loops)
            except Exception as e:
                _log_measure_skip(op, pc, e)
                continue
            cache[ck] = us
            entries[degs] = us
        if entries:
            table[op.name] = entries
    return table


_seen_measure_skips: set = set()


def _log_measure_skip(op, pc, e):
    import logging

    k = (op.name, type(e).__name__)
    if k not in _seen_measure_skips:
        _seen_measure_skips.add(k)
        logging.getLogger("ff.profiler").warning(
            "measured_degree_table: %s at %s failed (%s: %s); roofline "
            "fallback for this candidate",
            op.name, {a: pc.degree(a) for a in "nchws"}, type(e).__name__, e,
        )


def measured_cost_table(
    ex: Executor,
    params: Any,
    state: Any,
    batch: Dict[str, Any],
    reps: int = 5,
) -> Dict[str, float]:
    """Per-op measured *whole-op* forward time (us) keyed by op name —
    pluggable into the strategy search as a measured cost model (the
    reference feeds ``measure_*_time`` results into its simulator the
    same way, ``simulator.cc:1420-1440``).

    ``profile_ops`` times each op under the executor's own strategy,
    i.e. per-shard; the search divides by each candidate's shard count,
    so the table normalizes back to whole-op time by multiplying with
    the profiled strategy's shard count (exact on a single-device
    executor, a collective-inclusive approximation on a parallel one).
    """
    profiles = profile_ops(ex, params, state, batch, reps=reps)
    return {
        op.name: p.time_us * ex._pc(op).num_parts
        for op, p in zip(ex.model.layers, profiles)
    }
