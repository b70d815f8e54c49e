"""Calibrate ffsim against the chip (VERDICT r4 item 3).

The reference's simulator lived and died by measured times
(``scripts/cnn.h:204-260``, ``simulator.cc:142-151``): per-config
microbenchmarks anchored every simulated makespan.  This repo's
per-(op,degree) table is measured the same way, but the END-TO-END
simulated step time had never been compared to a measured fused step —
so the ``*_speedup_sim`` numbers were internally consistent yet
externally unanchored.

This tool closes the loop on the one device we can reach: for
alexnet (b=2048) / vgg16 (search shape, b=64) / dlrm
(run_random.sh shape) it
  1. measures the per-(op, degree=1) fwd+bwd table live,
  2. predicts the single-chip step via ffsim in BOTH pricing modes
     (measured table / analytic roofline),
  3. measures the real fused ``Trainer.fit`` step (host-readback
     fenced, reference formula), and
  4. prints percent error of each prediction vs the fused step.

Interpretation: the measured-mode error isolates what ffsim's
sum-of-parts model misses (XLA cross-op fusion, optimizer, dispatch);
the roofline-mode error additionally includes the device-model
constants — tune those (``search/cost_model.py DeviceModel``) until
the roofline column lands <20%.  Results land in OP_PARALLEL.md.
"""
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _models(on_tpu: bool):
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.alexnet import build_alexnet
    from flexflow_tpu.models.cnn_catalog import build_vgg16
    from flexflow_tpu.models.dlrm import (
        build_dlrm,
        dlrm_random_benchmark_config,
    )

    out = []
    # alexnet at the batch OP_PARALLEL.md's table was read at; vgg16
    # at its search shape (b=64).
    b = 2048 if on_tpu else 16
    cfg = FFConfig(batch_size=b, compute_dtype="bfloat16")
    out.append(("alexnet", build_alexnet(
        batch_size=b, image_size=229 if on_tpu else 64,
        num_classes=1000, config=cfg)))
    bv = 64 if on_tpu else 8
    out.append(("vgg16", build_vgg16(
        batch_size=bv, image_size=224 if on_tpu else 64,
        config=FFConfig(batch_size=bv, compute_dtype="bfloat16"))))
    dcfg = dlrm_random_benchmark_config(num_tables=8)
    if not on_tpu:
        dcfg.embedding_size = [10000] * 8
    bd = 256
    out.append(("dlrm", build_dlrm(
        bd, dcfg, config=FFConfig(batch_size=bd, compute_dtype="bfloat16"))))
    return out


def main():
    import jax

    if (jax.default_backend() == "cpu"
            and os.environ.get("JAX_PLATFORMS") != "cpu"):
        # The CPU only when it was asked for.
        sys.exit("calibrate_ffsim: jax found no accelerator and "
                 "JAX_PLATFORMS=cpu was not asked for")
    if jax.default_backend() == "cpu":
        print("calibrating plumbing on the CPU — numbers are NOT chip "
              "data", file=sys.stderr)

    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.parallel.strategy import StrategyStore
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.profiler import measured_degree_table
    from flexflow_tpu.runtime.trainer import Trainer
    from flexflow_tpu.search import simulate_strategy

    on_tpu = jax.default_backend() != "cpu"
    iters = 20 if on_tpu else 3
    rows = []
    for name, ff in _models(on_tpu):
        t0 = time.time()
        table = measured_degree_table(ff, num_devices=1)
        dp1 = StrategyStore(1)
        sim_meas_us = simulate_strategy(ff, dp1, 1, measured_costs=table)
        sim_roof_us = simulate_strategy(ff, dp1, 1)
        ex = Executor(ff, optimizer=SGDOptimizer(lr=0.01),
                      devices=jax.devices()[:1])
        stats = Trainer(ex).fit(iterations=iters, warmup=3)
        step_us = stats["elapsed_s"] / iters * 1e6
        err = lambda sim: (sim - step_us) / step_us * 100.0
        row = {
            "model": name,
            "measured_step_us": round(step_us, 1),
            "sim_measured_us": round(sim_meas_us, 1),
            "sim_roofline_us": round(sim_roof_us, 1),
            "err_measured_pct": round(err(sim_meas_us), 1),
            "err_roofline_pct": round(err(sim_roof_us), 1),
            "platform": jax.default_backend(),
            "wall_s": round(time.time() - t0, 1),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    print("CALIBRATION " + json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
