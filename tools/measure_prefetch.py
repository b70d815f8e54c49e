"""Input-pipeline overlap A/B: Trainer.fit prefetch=0 vs prefetch=2.

VERDICT r4 item 4: the reference overlaps H2D staging with compute
(zero-copy dataset region + in-step gather, ``dlrm.cu:20-50``,
``dlrm.cc:151-156``); ``Trainer.fit`` now double-buffers the host
gather + ``shard_batch`` H2D behind the device step.  This tool
measures the before/after on the live chip with a HOST-RESIDENT
dataset (the expensive per-step host path: native row gather + H2D of
a b=512 f32 image batch ~ 320 MB/step at 229x229).

Runs AlexNet (the headline app) with host arrays through
``ArrayDataLoader``; prints one summary line per arm plus the delta.
Both arms time 12 fused steps between host-readback fences
(Trainer.fit's protocol).
"""
import sys
import time

import numpy as np


def main():
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.data.loader import ArrayDataLoader, synthetic_arrays
    from flexflow_tpu.models.alexnet import build_alexnet
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.trainer import Trainer

    import jax

    on_tpu = jax.default_backend() != "cpu"
    batch = 512 if on_tpu else 16
    image = 229 if on_tpu else 64
    iters = 12 if on_tpu else 3
    cfg = FFConfig(batch_size=batch, compute_dtype="bfloat16")
    ff = build_alexnet(batch_size=batch, image_size=image,
                       num_classes=1000, config=cfg)
    ex = Executor(ff, optimizer=SGDOptimizer(lr=0.01, momentum=0.9))
    arrays = synthetic_arrays(ff, num_samples=batch * 8, seed=0,
                              int_high={"label": 1000})

    from flexflow_tpu.data.loader import DeviceResidentLoader

    results = {}
    # ABCABC: host-sync / host-prefetch / device-resident (ZC pattern),
    # interleaved to split drift from effect.
    for arm in ("sync", "prefetch", "device") * 2:
        if arm == "device":
            batches = iter(DeviceResidentLoader(
                arrays, batch, ex, shuffle=True, seed=1))
            # Keep the depth-2 overlap here too: the per-step dispatch
            # chain (idx put + eager takes) would otherwise serialize
            # inside the timed loop while the host arm overlaps, biasing
            # the comparison (shard_batch re-place is a no-op).
            depth = 2
        else:
            batches = iter(ArrayDataLoader(arrays, batch, shuffle=True,
                                           seed=1))
            depth = 2 if arm == "prefetch" else 0
        t0 = time.time()
        stats = Trainer(ex).fit(iterations=iters, batches=batches,
                                warmup=3, prefetch=depth)
        results.setdefault(arm, []).append(stats["samples_per_s"])
        print(f"{arm}: {stats['samples_per_s']:.1f} samples/s "
              f"(wall {time.time()-t0:.1f}s)", flush=True)

    best = {k: max(v) for k, v in results.items()}
    print(f"SUMMARY prefetch_off={best['sync']:.1f} "
          f"prefetch_on={best['prefetch']:.1f} "
          f"device_resident={best['device']:.1f} "
          f"speedup={best['prefetch'] / best['sync']:.3f}x "
          f"zc_speedup={best['device'] / best['sync']:.3f}x "
          f"platform={jax.default_backend()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
