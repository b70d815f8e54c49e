"""One training cell: the program's compiled step, driven for a window.

Set-up builds ONE object — ``Executor.train_step`` with its state — and
drives it from the seed through its first steps on the window's own
feed; the loss of each, the first gradient as the optimizer got it and
the parameters' change are read there.  The same object then runs the
window.  The plain reference follows the same steps after the window,
once the program's state is freed, and ``correct`` is their agreement.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict

import jax
from jax.profiler import TraceAnnotation

from benchmark import common
from flexflow_tpu.apps.common import make_optimizer
from flexflow_tpu.runtime import telemetry
from flexflow_tpu.runtime.pipeline import make_executor

FOLLOWED_STEPS = 3


def _first_gradient_norms(opt: Dict[str, Any], opt_state, change_norms) -> Dict[str, float]:
    """The first gradient as the optimizer got it, from its state after
    one step: Adam's first moment over ``1 - b1``, or plain SGD's
    parameter change (``change_norms()``) over the learning rate."""
    if opt["name"] == "adam":
        return {k: v / (1.0 - opt["b1"]) for k, v in common.tree_norms(opt_state["m"]).items()}
    return {k: v / opt["lr"] for k, v in change_norms().items()}


def run(ctx) -> Dict[str, Any]:
    cfg, traffic, fam = ctx.config, ctx.traffic, ctx.family
    t_run = time.time()
    if "train" not in ctx.reuse:
        ff, ffcfg, strategy = fam.build_train(cfg, traffic, ctx.chips)
        ffcfg.num_devices = ctx.chips
        ctx.reuse["train"] = make_executor(
            ff, strategy, config=ffcfg, optimizer=make_optimizer(ffcfg))
    ex = ctx.reuse["train"]
    batch = ex.config.batch_size
    opt = traffic["optimizer"]

    abstract, _, state_avals = jax.eval_shape(ex.init)
    if jax.tree.leaves(state_avals):
        raise SystemExit("this runner drives graphs without op state")
    spec = fam.leaf_spec(cfg, traffic)
    shardings = ex.params_shardings()
    params = common.make_params(spec, ctx.seed, abstract, shardings)
    common.stamp(t_run, "seeded parameters on the device")
    opt_state = ex.optimizer.init(params)
    state: Dict[str, Any] = {}
    change_norms = common.leaf_norms_fn(spec, ctx.seed, abstract, shardings)
    for op, leaves in shardings.items():
        for key, sh in leaves.items():
            if any(a is not None for a in sh.spec):
                common.say(f"[train] sharded leaf {op}/{key}: {sh.spec} of {abstract[op][key].shape}")

    host = fam.host_batches(cfg, traffic, ctx.seed, int(traffic["batch_pool"]), batch)
    pool = [ex.shard_batch(b) for b in host]

    def step(i):
        nonlocal params, opt_state, state
        params, opt_state, state, m = ex.train_step(params, opt_state, state, pool[i % len(pool)])
        return m["train_loss"]

    # -- the first steps, through the window's own call and feed ------------
    got: Dict[str, Any] = {"losses": []}
    for i in range(FOLLOWED_STEPS):
        got["losses"].append(float(jax.device_get(step(i))))
        if i == 0:
            got["grad_norms"] = _first_gradient_norms(opt, opt_state, lambda: change_norms(params))
    got["delta_norms"] = change_norms(params)
    common.stamp(t_run, "first steps followed")
    common.say(f"[train] first losses {got['losses']}")
    n = FOLLOWED_STEPS
    for _ in range(int(traffic.get("warm_steps", 3))):  # the window's own rhythm
        pending = step(n)
        n += 1
    jax.device_get(pending)

    common.stamp(t_run, "warm")
    # -- the window ----------------------------------------------------------
    seconds = ctx.window_seconds()
    # Only a traced run's reducers read the stream: a timed window keeps
    # its telemetry in the process and writes no file.
    tel = telemetry.Telemetry(directory=ctx.telemetry_dir if ctx.trace else None,
                              meta={"cell": ctx.cell["name"]})
    steps = failed = 0
    last_loss = float("nan")
    longest = 0.0
    with tel, ctx.profile(), ctx.compiles.window():
        ctx.mark_window_start()
        t0 = time.perf_counter()
        pending, t_prev = None, t0
        while True:
            with TraceAnnotation("bench/dispatch"):
                cur = step(n)
            n += 1
            if pending is not None:
                with TraceAnnotation("bench/fence"):
                    last_loss = float(jax.device_get(pending))
                now = time.perf_counter()
                tel.record_step(steps, loss=last_loss, wall_s=now - t_prev)
                longest = max(longest, now - t_prev)
                t_prev = now
                steps += 1
                failed += not math.isfinite(last_loss)
            pending = cur
            if time.perf_counter() - t0 >= seconds:
                break
        with TraceAnnotation("bench/fence"):
            last_loss = float(jax.device_get(pending))
        elapsed = time.perf_counter() - t0
        longest = max(longest, time.perf_counter() - t_prev)
        steps += 1
        failed += not math.isfinite(last_loss)
    peak = common.peak_memory_bytes(ex.plan.mesh.devices.flat)
    samples_per_s = steps * batch / elapsed
    common.say(f"[train] window {elapsed:.3f} s, {steps} steps of {batch}, "
               f"{elapsed / steps * 1e3:.3f} ms a step, longest step {longest * 1e3:.3f} ms, "
               f"last loss {last_loss:.6g}, "
               f"compiles in window {ctx.compiles.count}, peak {peak / 2**30:.2f} GiB")

    # -- the reference, once the program's state is freed -------------------
    del params, opt_state, pool, pending, cur
    check = common.Check()
    t_ref = time.perf_counter()
    want = fam.reference.train(cfg, traffic, ctx.seed, host[:FOLLOWED_STEPS])
    common.say(f"[train] reference followed {FOLLOWED_STEPS} steps in "
               f"{time.perf_counter() - t_ref:.1f} s, losses {want['losses']}")
    lim = traffic["limits"]
    check.add("loss_gap", max(abs(g - w) / abs(w) for g, w in zip(got["losses"], want["losses"])),
              lim["loss_gap"])
    check.add("grad_norm_gap", common.worst_leaf_gap(got["grad_norms"], want["grad_norms"]),
              lim["grad_norm_gap"])
    noise = common.noise_leaves(want["grad_norms"]) if opt["name"] == "adam" else set()
    if noise:
        common.say(f"[check] {len(noise)} leaves with a round-off gradient left out of the "
                   f"parameters' change, e.g. {sorted(noise)[:3]}")
    check.add("param_change_gap",
              common.worst_leaf_gap(got["delta_norms"], want["delta_norms"], skip=noise),
              lim["param_change_gap"])
    check.add("window_nonfinite_steps", float(failed), 0.0)
    if ctx.control:
        ctl = fam.reference.train(cfg, traffic, ctx.seed, host[:FOLLOWED_STEPS], quant=True)
        common.say(f"[control] losses {ctl['losses']}")
        common.say("[control] loss_gap = %.6g grad_norm_gap = %.6g param_change_gap = %.6g" % (
            max(abs(g - w) / abs(w) for g, w in zip(ctl["losses"], want["losses"])),
            common.worst_leaf_gap(ctl["grad_norms"], want["grad_norms"]),
            common.worst_leaf_gap(ctl["delta_norms"], want["delta_norms"], skip=noise)))

    per = fam.items_per_sample(traffic)
    return {
        "correct": check.correct, "attempted": steps, "failed": failed, "batch": batch,
        "quantities": {"samples_per_s": samples_per_s, "items_per_s": samples_per_s * per,
                       "step_ms": elapsed / steps * 1e3},
        "peak_bytes": peak, "window_s": elapsed, "telemetry_path": tel.path,
    }
