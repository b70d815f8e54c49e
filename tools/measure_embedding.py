#!/usr/bin/env python
"""Sharded-embedding acceptance A/B (ISSUE 20, SHARDING.md) on the
8-dev virtual CPU mesh.

Three measurements, each against its acceptance bar:

- ``capacity``: under an ``FF_DEVICE_MEM_BYTES`` budget sized so the
  REPLICATED table refuses (``DeviceMemoryError`` naming
  ``--shard-embeddings``), the c=4 row-sharded layout must admit AND
  train.  The doubling probe then reports max admitted vocab per
  layout; bar: sharded >= 2x replicated (the per-device table shrinks
  by c, so c=4 lands at ~4x up to probe granularity).
- ``sharded_vs_replicated``: paired throughput ratio at a vocab both
  layouts hold — a context bar at >= 0.5x (sharding trades bounded
  gather/psum overhead for unbounded vocab; the combine is
  in-program, not an extra dispatch).
- ``overlap_speedup``: the id-heavy model fed by the streaming reader
  + H2D prefetch vs unprefetched inline reads, both on the SAME
  per-row throttled source (measure_data.py's protocol).  Bar:
  >= 1.3x — id staging must hide behind compute, the property the
  ids-first ``stack_steps`` ordering extends to the fused-superstep
  queue.

The statistic is the paired-median protocol from
``obs.compare.paired_measure`` (alternating order, median of per-pair
ratios, A/A control column) — CPU wall noise at these sizes swings
more than the effects measured.

Usage: env PYTHONPATH=/root/repo python tools/measure_embedding.py
       [--reps N] [--iters N] [--tpu]
(One process.  Without --tpu it pins the 8-device virtual CPU mesh
before jax initializes (``analysis.program_audit.ensure_cpu_mesh``);
with --tpu it leaves the environment alone and runs on the attached
chip.)
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _arg(argv, flag, default):
    if flag in argv:
        return int(argv[argv.index(flag) + 1])
    return default


def run(argv):
    os.environ.pop("FF_TELEMETRY_DIR", None)
    import jax
    import numpy as np

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.data.loader import (
        DeviceMemoryError,
        DeviceResidentLoader,
        PrefetchLoader,
    )
    from flexflow_tpu.data.stream import (
        ArrayStreamSource,
        StreamingLoader,
        ThrottledSource,
    )
    from flexflow_tpu.graph import FFModel
    from flexflow_tpu.obs.compare import paired_measure
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.trainer import Trainer

    reps = _arg(argv, "--reps", 9)
    iters = _arg(argv, "--iters", 48)
    batch, bag, d_emb = 32, 4, 16
    rows = batch * 8
    nd = len(jax.devices())

    rng = np.random.default_rng(13)

    def arrays(vocab):
        return {
            "ids": rng.integers(0, vocab, size=(rows, bag)).astype(np.int32),
            "label": rng.integers(0, 8, size=(rows,)).astype(np.int32),
        }

    def executor(vocab, c):
        ff = FFModel(FFConfig(batch_size=batch, seed=7,
                              shard_embeddings=c > 1))
        ids = ff.create_tensor((batch, bag), dtype=np.int32, name="ids")
        lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
        t = ff.embedding(ids, vocab, d_emb, aggr="sum", name="emb")
        t = ff.dense(t, 8, name="head")
        ff.softmax(t, lbl, name="softmax")
        store = StrategyStore(nd)
        if c > 1:
            store.set("emb", ParallelConfig(n=nd // c, c=c))
        return Executor(ff, strategy=store,
                        optimizer=SGDOptimizer(lr=0.01))

    failures = 0
    print(f"sharded-embedding A/B: median of {reps} paired ratios, "
          f"{iters} iters, batch {batch}, bag {bag}, {nd} devices")

    # -- capacity: the budget where replicated refuses ----------------
    budget = 72 * 1024
    big_vocab = 2048  # table 128 KiB replicated, 32 KiB/device at c=4
    os.environ["FF_DEVICE_MEM_BYTES"] = str(budget)
    try:
        data = arrays(big_vocab)
        try:
            DeviceResidentLoader(data, batch, executor(big_vocab, 1),
                                 shuffle=True, seed=3)
            print(f"{'capacity':<22} replicated vocab={big_vocab} "
                  f"unexpectedly admitted FAIL")
            failures += 1
        except DeviceMemoryError as e:
            assert "--shard-embeddings" in str(e), e
            ex = executor(big_vocab, 4)
            batches = iter(DeviceResidentLoader(data, batch, ex,
                                                shuffle=True, seed=3))
            stats = Trainer(ex).fit(iterations=8, batches=batches,
                                    warmup=1)
            ok = np.isfinite(stats["loss"])
            print(f"{'capacity':<22} vocab={big_vocab}: replicated "
                  f"refused, c=4 trained (loss {stats['loss']:.4f}) "
                  f"{'PASS' if ok else 'FAIL'}")
            if not ok:
                failures += 1

        def admits(vocab, c):
            try:
                DeviceResidentLoader(arrays(vocab), batch,
                                     executor(vocab, c),
                                     shuffle=True, seed=3)
                return True
            except DeviceMemoryError:
                return False

        def max_vocab(c):
            v, probe = 0, 128
            while probe <= (1 << 20) and admits(probe, c):
                v, probe = probe, probe * 2
            return v

        rep, shd = max_vocab(1), max_vocab(4)
        ratio = shd / rep if rep else float("inf")
        ok = ratio >= 2.0
        print(f"{'max_vocab':<22} replicated {rep}, sharded c=4 {shd} "
              f"({ratio:.1f}x, bar >= 2x) {'PASS' if ok else 'FAIL'}")
        if not ok:
            failures += 1
    finally:
        os.environ.pop("FF_DEVICE_MEM_BYTES", None)

    # -- paired throughput + overlap legs -----------------------------
    common_vocab = 512
    data = arrays(common_vocab)

    def fit(ex, batches):
        try:
            return Trainer(ex).fit(iterations=iters, batches=batches,
                                   warmup=1)
        finally:
            if hasattr(batches, "close"):
                batches.close()

    ex_rep = executor(common_vocab, 1)
    ex_shd = executor(common_vocab, 4)
    for ex in (ex_rep, ex_shd):  # warm the jits, shared by all reps
        fit(ex, iter(DeviceResidentLoader(data, batch, ex,
                                          shuffle=True, seed=3)))

    def sps(ex):
        return fit(ex, iter(DeviceResidentLoader(
            data, batch, ex, shuffle=True, seed=3)))["samples_per_s"]

    def paired_ratio(name, a, b, bar):
        res = paired_measure(
            make_a=lambda r: a(),
            make_b=lambda r: b(),
            reps=reps,
            control=lambda r: b(),
        )
        med, ctl = res.median_ratio, res.median_aa_ratio
        ok = "PASS" if med >= bar else "FAIL"
        print(f"{name:<22} {med:>7.3f}x  (bar >= {bar}x, a_a "
              f"{ctl:.3f}x) {ok}")
        return med >= bar

    if not paired_ratio("sharded_vs_replicated",
                        lambda: sps(ex_shd), lambda: sps(ex_rep),
                        bar=0.5):
        failures += 1

    # -- throttled H2D overlap (measure_data protocol, id-heavy) ------
    per_row_s = 1e-4

    def stream_batches():
        src = ThrottledSource(ArrayStreamSource(data),
                              per_row_s=per_row_s)
        return PrefetchLoader(
            iter(StreamingLoader(src, batch, shuffle=True, seed=3,
                                 shuffle_window=batch * 2)),
            ex_rep.shard_batch)

    def inline_batches():
        src = ThrottledSource(ArrayStreamSource(data),
                              per_row_s=per_row_s)
        pos = 0
        while True:
            if pos + batch > rows:
                pos = 0
            yield ex_rep.shard_batch(src.read(pos, pos + batch))
            pos += batch

    if not paired_ratio(
            "overlap_speedup",
            lambda: fit(ex_rep, stream_batches())["samples_per_s"],
            lambda: fit(ex_rep, inline_batches())["samples_per_s"],
            bar=1.3):
        failures += 1

    return 1 if failures else 0


def main():
    argv = sys.argv[1:]
    sys.path.insert(0, REPO)
    if "--tpu" not in argv:
        from flexflow_tpu.analysis.program_audit import ensure_cpu_mesh

        ensure_cpu_mesh()  # before jax initializes: the flags parse once
    return run(argv)


if __name__ == "__main__":
    sys.exit(main())
