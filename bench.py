"""Headline benchmarks on the attached accelerator.

Measurement protocol mirrors the reference exactly — fence
(``block_until_ready``), N timed iterations, ``tp = iters*batch/elapsed``
images/s (``cnn.cc:122-129``) and ``THROUGHPUT = samples/elapsed``
samples/s (``dlrm.cc:165-166``).

Prints ONE JSON line for the driver.  Primary metric: AlexNet
images/s/chip (the reference's canonical app).  The ``extra`` field
carries DLRM samples/s (``run_random.sh`` shape), MFU against the
device's published bf16 peak, platform, device kind and batch size.

A measurement path that finds no chip fails: the run is on the CPU only
when ``JAX_PLATFORMS=cpu`` asked for it (shrunken shapes, no MFU), and
otherwise a missing accelerator is a non-zero exit.  A leg that raises
is reported under ``<leg>_error`` in the JSON line and makes the run
exit non-zero after the line is printed.  One process: it touches jax
itself and starts no child that needs the chip.
"""

import contextlib
import json
import os
import sys
import time
import traceback

#: 4xV100 AlexNet target (BASELINE.md "match 4xV100 on v5e-4"), per chip.
#: The reference publishes no absolute number; 1500 img/s total is the
#: ICML'18-era figure the driver's BASELINE.json names.
BASELINE_IMGS_PER_SEC_PER_CHIP = 1500.0 / 4.0

#: Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s.  A device that is not
#: in the table is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 1.97e14,
        "hbm_bytes_per_s": 8.19e11,
        "hbm_bytes": 16e9,
    },
}


def peak_bf16_flops() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peak for device kind {kind!r}: add it to "
            f"bench.DEVICE_PEAKS with its source"
        )
    return DEVICE_PEAKS[kind]["bf16_flops"]


def _mfu(ff, batch: int, samples_per_s: float, n_chips: int, on_tpu: bool):
    """Model FLOP/s utilisation: the graph's analytic train flops per
    sample times samples/s over chips times the device's bf16 peak.
    None on a CPU run — a utilisation is a device metric."""
    if not on_tpu:
        return None
    return (_train_flops(ff) / batch) * samples_per_s / (
        peak_bf16_flops() * n_chips
    )


def _train_flops(ff) -> float:
    """Analytic train-step flops from the op graph (fwd * 3 for
    fwd+bwd, ``cost_model.FWD_BWD_FACTOR``)."""
    from flexflow_tpu.search.cost_model import FWD_BWD_FACTOR, op_cost

    return FWD_BWD_FACTOR * sum(op_cost(op).flops for op in ff.layers)


def bench_alexnet(n_chips: int, on_tpu: bool):
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.alexnet import build_alexnet
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.trainer import Trainer

    # v5e-1 sweep (b=512/1024/2048/4096 -> 22.8k/24.3k/25.9k/26.1k
    # imgs/s): 2048 sits at the knee — 0.567 MFU, half the step
    # latency of 4096 for 0.7% less throughput.
    batch_size = int(os.environ.get("BENCH_BATCH", "2048" if on_tpu else "32"))
    iters = 20 if on_tpu else 5
    cfg = FFConfig(batch_size=batch_size, compute_dtype="bfloat16")
    ff = build_alexnet(batch_size=batch_size, image_size=229, num_classes=1000,
                       config=cfg)
    ex = Executor(ff, optimizer=SGDOptimizer(lr=0.01, momentum=0.9,
                                             weight_decay=1e-4))
    stats = Trainer(ex).fit(iterations=iters, warmup=3)
    per_chip = stats["samples_per_s"] / n_chips
    mfu = _mfu(ff, batch_size, stats["samples_per_s"], n_chips, on_tpu)
    return per_chip, mfu, batch_size


def bench_dlrm(n_chips: int, on_tpu: bool):
    """``run_random.sh`` shape: 8 x 1M-row x 64-dim tables, 256
    samples/chip/iter (``dlrm.cc:165-166``; tables shrunk on a CPU
    run, where the 2 GB of tables would swamp it).
    Returns (samples/s, mfu)."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.dlrm import (
        build_dlrm,
        dlrm_random_benchmark_config,
        dlrm_strategy,
    )
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.trainer import Trainer

    cfg = dlrm_random_benchmark_config(num_tables=8)
    if not on_tpu:
        cfg.embedding_size = [10000] * 8
    batch = 256 * n_chips

    # Row-sparse updates (the config default): a failure of that path
    # fails the leg — no dense stand-in.
    ffcfg = FFConfig(batch_size=batch, compute_dtype="bfloat16")
    ff = build_dlrm(batch, cfg, config=ffcfg)
    ex = Executor(ff, strategy=dlrm_strategy(n_chips, cfg),
                  optimizer=SGDOptimizer(lr=0.01))
    stats = Trainer(ex).fit(iterations=10 if on_tpu else 3, warmup=2)
    return stats["samples_per_s"], _mfu(
        ff, batch, stats["samples_per_s"], n_chips, on_tpu
    )


def _bench_lm(batch: int, seq: int, layers: int, iters: int):
    """One GPT-style LM measurement (shared by the 2k and 8k legs):
    build, jit, fit, return (tokens/s, mfu)."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.optim import AdamOptimizer
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.trainer import Trainer

    ff = build_transformer_lm(
        batch_size=batch, seq_len=seq, vocab_size=32768, d_model=512,
        num_heads=8, num_layers=layers,
        config=FFConfig(batch_size=batch, compute_dtype="bfloat16"),
    )
    import jax

    ex = Executor(ff, optimizer=AdamOptimizer(lr=1e-4),
                  devices=jax.devices()[:1])  # single-chip by contract
    stats = Trainer(ex).fit(iterations=iters, warmup=2)
    mfu = _mfu(ff, batch, stats["samples_per_s"], 1,
               jax.default_backend() != "cpu")
    return stats["samples_per_s"] * seq, mfu


def bench_transformer(on_tpu: bool):
    """Long-context flagship: GPT-style LM step with the Pallas flash
    attention kernel (dense single-chip path; the ring/CP path is
    exercised by the driver's multi-chip dry run).  Returns
    (tokens/s, mfu)."""
    # v5e-1 sweep: b=8 -> 102k tokens/s, b=16 -> 113k, b=32 OOM.
    if on_tpu:
        return _bench_lm(batch=16, seq=2048, layers=6, iters=10)
    return _bench_lm(batch=2, seq=128, layers=2, iters=3)


def bench_transformer_longctx(on_tpu: bool):
    """Long-context leg: same 6-layer LM at seq 8192 on one chip —
    the flash kernel's O(t) memory (VMEM-capped blocks) is what makes
    this shape trainable at all; dense attention would materialize a
    b*h*8192^2 f32 score tensor (16 GB at b=4).  Returns
    (tokens/s, mfu)."""
    if on_tpu:
        return _bench_lm(batch=4, seq=8192, layers=6, iters=5)
    return _bench_lm(batch=1, seq=256, layers=2, iters=2)


def bench_transformer_32k(on_tpu: bool):
    """t=32768 single-chip (VERDICT r4 item 7): past the single-launch
    VMEM cap AND past the 16k ceiling rounds 2-4 stopped at — the
    chunked decomposition runs 4x8192 kernel chunks per layer
    (``flash_attention_lse_chunked``; gate pinned by
    ``tests/test_pallas.py::test_chunked_gates_32k_and_beyond``).
    b=1 keeps the 32768x32768 bf16 logits block (2 GB) plus its
    cotangent inside HBM.  Returns (tokens/s, mfu)."""
    if on_tpu:
        return _bench_lm(batch=1, seq=32768, layers=6, iters=3)
    return _bench_lm(batch=1, seq=512, layers=2, iters=2)


def bench_nmt(n_chips: int, on_tpu: bool):
    """The fourth BASELINE config: NMT seq2seq LSTM step time
    (``nmt.cc:34-44,71-83`` defaults: bs 64 PER WORKER, 2 layers,
    hidden = embed = 2048, vocab 20K, seq 20; prints ``time = %.4fs``
    over 10 iterations).  Shapes shrink on the CPU fallback.  Returns
    (elapsed_s, pairs_per_s, iterations)."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.nmt import build_nmt
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.trainer import Trainer

    batch = 64 * n_chips if on_tpu else 4
    hidden = 2048 if on_tpu else 64
    vocab = 20480 if on_tpu else 512
    iters = 10 if on_tpu else 2
    ff = build_nmt(
        batch_size=batch, src_len=20, tgt_len=20, vocab_size=vocab,
        embed_dim=hidden, hidden_size=hidden, num_layers=2,
        config=FFConfig(batch_size=batch, compute_dtype="bfloat16"),
    )
    ex = Executor(ff, optimizer=SGDOptimizer(lr=0.01))
    stats = Trainer(ex).fit(iterations=iters, warmup=2)
    return stats["elapsed_s"], stats["samples_per_s"], iters


def bench_candle(on_tpu: bool):
    """The fifth BASELINE config: Candle-Uno multi-tower MLP
    (``examples/candle_uno``; defaults mirror the reference model
    shapes).  Single-chip throughput; the multi-host hybrid strategy
    leg is validated by the driver's multichip dry run and
    ``tests/test_apps.py`` granules tests.  Returns samples/s."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.candle_uno import build_candle_uno
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.trainer import Trainer

    batch = 512 if on_tpu else 32
    ff = build_candle_uno(
        batch_size=batch,
        config=FFConfig(batch_size=batch, compute_dtype="bfloat16"),
    )
    ex = Executor(ff, optimizer=SGDOptimizer(lr=0.01))
    stats = Trainer(ex).fit(iterations=10 if on_tpu else 2, warmup=2)
    return stats["samples_per_s"]


def bench_superstep(n_chips: int, on_tpu: bool):
    """Dispatch-amortization sweep (superstep execution): k train steps
    fused into ONE compiled ``lax.scan`` dispatch with a single
    host-readback fence per call (``Executor.build_superstep``).  Swept
    at k in {1,4,8,16} on a dispatch-bound MLP — per-step compute far
    below the per-dispatch cost.  Reports ms/step per k plus the k=8
    amortization factor (the default ``--steps-per-call`` operating
    point; k=16 probes the approach to the fused-step bound)."""
    import numpy as np

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.graph import FFModel
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.trainer import Trainer

    batch = 64 * n_chips if on_tpu else 32
    width = 256 if on_tpu else 64
    iters = 32 if on_tpu else 16  # divisible by 16: no tail recompile
    ff = FFModel(FFConfig(batch_size=batch, seed=3))
    x = ff.create_tensor((batch, width), name="x")
    lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
    t = ff.dense(x, width, activation="relu", name="fc1")
    t = ff.dense(t, 8, name="fc2")
    ff.softmax(t, lbl, name="softmax")
    ex = Executor(ff, optimizer=SGDOptimizer(lr=0.01, momentum=0.9))
    out = {"batch_size": batch, "iterations": iters}
    for k in (1, 4, 8, 16):
        stats = Trainer(ex).fit(iterations=iters, warmup=1,
                                steps_per_call=k)
        out[f"k{k}_ms_per_step"] = round(stats["elapsed_s"] / iters * 1e3, 3)
    out["amortization_k8_vs_k1"] = round(
        out["k1_ms_per_step"] / out["k8_ms_per_step"], 3
    )
    return out


def bench_pipeline(n_chips: int, on_tpu: bool):
    """Layer-wise pipeline leg: S stages x mb microbatches at chunk
    c in {1, mb} — c=mb folds each stage's per-microbatch fwd/bwd
    programs into ONE scanned program, cutting host programs per step
    from 2*S*mb to 2*S (``programs`` fields record the actual
    ``last_schedule`` event counts) — plus the k=8 fence-amortized
    pipeline superstep A/B at the dispatch-minimal chunk.  Stage count
    is capped by the visible device count (stages need distinct device
    subsets); a 1-chip run reports why it skipped instead of faking a
    pipeline."""
    import numpy as np

    import jax

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.graph import FFModel
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
    from flexflow_tpu.runtime.pipeline import PipelineExecutor
    from flexflow_tpu.runtime.trainer import Trainer

    nd = len(jax.devices())
    batch = 64 * nd if on_tpu else 32
    width = 256 if on_tpu else 64
    iters = 16 if on_tpu else 8
    depth = 4

    def build():
        ff = FFModel(FFConfig(batch_size=batch, seed=5))
        x = ff.create_tensor((batch, width), name="x")
        lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
        t = x
        for i in range(depth):
            t = ff.dense(t, width, activation="relu", name=f"fc{i}")
        t = ff.dense(t, 8, name="head")
        ff.softmax(t, lbl, name="softmax")
        return ff

    def store(S):
        st = StrategyStore(nd)
        per = nd // S
        names = [f"fc{i}" for i in range(depth)] + ["head", "softmax"]
        for i, name in enumerate(names):
            si = min(i * S // len(names), S - 1)
            ids = tuple(range(si * per, (si + 1) * per))
            st.set(name, ParallelConfig(n=per, device_ids=ids))
        return st

    out = {"batch_size": batch, "iterations": iters, "n_devices": nd}
    sweep_S = [S for S in (2, 4) if S <= nd]
    if not sweep_S:
        out["skipped"] = (
            f"{nd} device(s): pipeline stages need distinct device "
            f"subsets (>= 2 devices)"
        )
        return out
    ff = build()
    for S in sweep_S:
        for mb in (4, 8):
            for c in (1, mb):
                pipe = PipelineExecutor(
                    ff, store(S),
                    optimizer=SGDOptimizer(lr=0.01, momentum=0.9),
                    microbatches=mb, chunk=c,
                )
                stats = Trainer(pipe).fit(iterations=iters, warmup=1)
                key = f"s{S}_mb{mb}_c{c}"
                out[f"{key}_ms_per_step"] = round(
                    stats["elapsed_s"] / iters * 1e3, 3
                )
                out[f"{key}_programs"] = len(pipe.last_schedule)
            # Compiled whole-step column: the SAME schedule as ONE
            # jitted program (host programs per step: 2*S*ceil(m/c)
            # -> 1; numerics bit-identical to the host columns,
            # tests/test_pipeline_chunk.py).
            pipe = PipelineExecutor(
                ff, store(S),
                optimizer=SGDOptimizer(lr=0.01, momentum=0.9),
                microbatches=mb, compiled=True,
            )
            stats = Trainer(pipe).fit(iterations=iters, warmup=1)
            out[f"s{S}_mb{mb}_compiled_ms_per_step"] = round(
                stats["elapsed_s"] / iters * 1e3, 3
            )
            out[f"s{S}_mb{mb}_compiled_programs"] = len(pipe.last_schedule)
    # Amortization headlines at the deepest swept config:
    # dispatch-minimal chunk vs per-microbatch, and the compiled
    # whole-step program vs that chunked host floor.
    S, mb = sweep_S[-1], 8
    out["chunk_amortization"] = round(
        out[f"s{S}_mb{mb}_c1_ms_per_step"]
        / out[f"s{S}_mb{mb}_c{mb}_ms_per_step"], 3
    )
    out["compiled_speedup"] = round(
        out[f"s{S}_mb{mb}_c{mb}_ms_per_step"]
        / out[f"s{S}_mb{mb}_compiled_ms_per_step"], 3
    )
    # Pipeline supersteps: k=8 steps under one device_get fence —
    # host-driven (fence-amortized) vs compiled (ONE fused dispatch:
    # 1/k host programs per step).
    pipe = PipelineExecutor(
        ff, store(sweep_S[0]),
        optimizer=SGDOptimizer(lr=0.01, momentum=0.9),
        microbatches=4, chunk=4,
    )
    stats = Trainer(pipe).fit(iterations=iters, warmup=1, steps_per_call=8)
    out["superstep_k8_ms_per_step"] = round(
        stats["elapsed_s"] / iters * 1e3, 3
    )
    pipe = PipelineExecutor(
        ff, store(sweep_S[0]),
        optimizer=SGDOptimizer(lr=0.01, momentum=0.9),
        microbatches=4, compiled=True,
    )
    stats = Trainer(pipe).fit(iterations=iters, warmup=8, steps_per_call=8)
    out["superstep_k8_compiled_ms_per_step"] = round(
        stats["elapsed_s"] / iters * 1e3, 3
    )
    return out


def bench_telemetry(n_chips: int, on_tpu: bool):
    """Run-telemetry summary leg: the dispatch-bound MLP trained with
    run telemetry enabled (in-memory — counters/percentiles, no JSONL)
    so the round artifact carries the observability layer's headline
    numbers: fences/step, host-side step-time p50/p95/max, pipeline
    programs/step, and the measured enabled-vs-off per-step overhead
    (the < 2% acceptance bar, OBSERVABILITY.md)."""
    import numpy as np

    import jax

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.graph import FFModel
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.telemetry import Telemetry
    from flexflow_tpu.runtime.trainer import Trainer

    batch = 64 * n_chips if on_tpu else 32
    width = 256 if on_tpu else 64
    iters = 32 if on_tpu else 16

    def build():
        ff = FFModel(FFConfig(batch_size=batch, seed=7))
        x = ff.create_tensor((batch, width), name="x")
        lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
        t = ff.dense(x, width, activation="relu", name="fc1")
        t = ff.dense(t, 8, name="fc2")
        ff.softmax(t, lbl, name="softmax")
        return Executor(ff, optimizer=SGDOptimizer(lr=0.01, momentum=0.9))

    # Pin the baseline leg genuinely OFF: FF_TELEMETRY_DIR (e.g. from
    # tools/tpu_watcher.sh) would otherwise install file-backed
    # telemetry on the "off" fit and corrupt the overhead A/B.
    env_dir = os.environ.pop("FF_TELEMETRY_DIR", None)
    try:
        off = Trainer(build()).fit(iterations=iters, warmup=1)
        with Telemetry() as tel:
            on = Trainer(build()).fit(iterations=iters, warmup=1)
    finally:
        if env_dir is not None:
            os.environ["FF_TELEMETRY_DIR"] = env_dir
    t = on["telemetry"]
    out = {
        "batch_size": batch,
        "iterations": iters,
        "fences_per_step": t.get("fences_per_step"),
        "step_ms_p50": t.get("step_ms_p50"),
        "step_ms_p95": t.get("step_ms_p95"),
        "step_ms_max": t.get("step_ms_max"),
        "overhead_pct": round(
            (on["elapsed_s"] - off["elapsed_s"]) / off["elapsed_s"] * 100, 2
        ),
    }
    nd = len(jax.devices())
    if nd >= 2:
        # Pipeline programs/step: a 2-stage layer-wise run whose
        # folded last_schedule counters audit 2*S*ceil(m/c).
        from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
        from flexflow_tpu.runtime.pipeline import PipelineExecutor

        ff = FFModel(FFConfig(batch_size=batch, seed=7))
        x = ff.create_tensor((batch, width), name="x")
        lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
        t2 = ff.dense(x, width, activation="relu", name="fc0")
        t2 = ff.dense(t2, 8, name="head")
        ff.softmax(t2, lbl, name="softmax")
        per = nd // 2
        st = StrategyStore(nd)
        st.set("fc0", ParallelConfig(n=per, device_ids=tuple(range(per))))
        for name in ("head", "softmax"):
            st.set(name, ParallelConfig(
                n=per, device_ids=tuple(range(per, 2 * per))))
        pipe = PipelineExecutor(
            ff, st, optimizer=SGDOptimizer(lr=0.01, momentum=0.9),
            microbatches=4, chunk=4,
        )
        with Telemetry() as ptel:
            Trainer(pipe).fit(iterations=4, warmup=1)
        out["programs_per_step"] = ptel.step_summary().get("programs_per_step")
    return out


def bench_data_plane(n_chips: int, on_tpu: bool):
    """Streaming data-plane leg (DATA.md): the dispatch-bound MLP fed
    through each loader tier — host ArrayDataLoader+prefetch, the
    device-resident zero-copy stage, and the out-of-core StreamingLoader
    (reader thread + windowed shuffle + H2D prefetch, dataset = 4x
    window) — plus the throttled-source A/B that shows the overlap
    hiding disk latency (streaming reader vs unprefetched inline
    reads on the SAME per-row throttle).  Input-starvation p50/p95
    come from the ``input_wait`` telemetry accounting."""
    import numpy as np

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.data.loader import (
        ArrayDataLoader,
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
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.telemetry import Telemetry
    from flexflow_tpu.runtime.trainer import Trainer

    batch = 64 * n_chips if on_tpu else 32
    width = 256 if on_tpu else 64
    iters = 32 if on_tpu else 16
    rows = batch * 8  # 8 batches/epoch; streaming window = rows/4

    rng = np.random.default_rng(11)
    arrays = {
        "x": rng.standard_normal((rows, width)).astype(np.float32),
        "label": rng.integers(0, 8, size=(rows,)).astype(np.int32),
    }

    ff = FFModel(FFConfig(batch_size=batch, seed=7))
    x = ff.create_tensor((batch, width), name="x")
    lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
    t = ff.dense(x, width, activation="relu", name="fc1")
    t = ff.dense(t, 8, name="fc2")
    ff.softmax(t, lbl, name="softmax")
    ex = Executor(ff, optimizer=SGDOptimizer(lr=0.01, momentum=0.9))

    def fit(batches, telemetry=False):
        try:
            if telemetry:
                with Telemetry():
                    return Trainer(ex).fit(iterations=iters,
                                           batches=batches, warmup=1)
            return Trainer(ex).fit(iterations=iters, batches=batches,
                                   warmup=1)
        finally:
            if hasattr(batches, "close"):
                batches.close()

    out = {"batch_size": batch, "iterations": iters, "rows": rows}

    host = fit(PrefetchLoader(
        iter(ArrayDataLoader(arrays, batch, shuffle=True, seed=3)),
        ex.shard_batch))
    out["array_samples_per_s"] = round(host["samples_per_s"], 2)

    def stream_loader(source, window=rows // 4):
        return StreamingLoader(source, batch, shuffle=True, seed=3,
                               shuffle_window=window)

    stream = fit(PrefetchLoader(
        iter(stream_loader(ArrayStreamSource(arrays))), ex.shard_batch),
        telemetry=True)
    out["stream_samples_per_s"] = round(stream["samples_per_s"], 2)
    tel = stream.get("telemetry", {})
    out["input_wait_ms_p50"] = tel.get("input_wait_ms_p50")
    out["input_wait_ms_p95"] = tel.get("input_wait_ms_p95")

    try:
        zc = fit(iter(DeviceResidentLoader(arrays, batch, ex,
                                           shuffle=True, seed=3)))
        out["zc_samples_per_s"] = round(zc["samples_per_s"], 2)
        out["stream_vs_zc"] = round(
            stream["samples_per_s"] / zc["samples_per_s"], 3)
    except DeviceMemoryError as e:
        out["zc_error"] = str(e)

    # Overlap A/B on a throttled source (the same per-row disk-latency
    # model both ways): streaming's reader thread + prefetch hide the
    # read behind compute; the inline baseline blocks on it per batch.
    per_row_s = 1e-4
    throttled = fit(PrefetchLoader(
        iter(stream_loader(
            ThrottledSource(ArrayStreamSource(arrays), per_row_s=per_row_s),
            window=batch * 2)),
        ex.shard_batch))
    out["throttled_stream_samples_per_s"] = round(
        throttled["samples_per_s"], 2)

    def inline_batches():
        src = ThrottledSource(ArrayStreamSource(arrays),
                              per_row_s=per_row_s)
        pos = 0
        while True:
            if pos + batch > rows:
                pos = 0
            yield ex.shard_batch(src.read(pos, pos + batch))
            pos += batch

    unpref = fit(inline_batches())
    out["throttled_unprefetched_samples_per_s"] = round(
        unpref["samples_per_s"], 2)
    out["throttled_overlap_speedup"] = round(
        throttled["samples_per_s"] / unpref["samples_per_s"], 3)

    # Sharded-embedding capacity (ISSUE 20, SHARDING.md): under a
    # synthetic FF_DEVICE_MEM_BYTES budget, the max vocab the
    # zero-copy tier admits with the table replicated (c=1) vs
    # row-sharded over c=4 — the per-device table shrinks by c, so
    # the admitted vocab must grow >= 2x (acceptance bar lives in
    # tools/measure_embedding.py; bench just reports the columns).
    out.update(_embedding_capacity_columns(batch))
    return out


def _embedding_capacity_columns(batch: int):
    """Doubling-probe the max vocab ``DeviceResidentLoader`` admits
    under a fixed budget, replicated vs c=4 row-sharded, plus the
    throughput ratio at a vocab both layouts hold."""
    import os

    import jax
    import numpy as np

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.data.loader import (
        DeviceMemoryError,
        DeviceResidentLoader,
    )
    from flexflow_tpu.graph import FFModel
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.trainer import Trainer

    bag, d_emb = 4, 16
    rows = batch * 8
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
        nd = len(jax.devices())
        store = StrategyStore(nd)
        if c > 1:
            store.set("emb", ParallelConfig(n=nd // c, c=c))
        return Executor(ff, strategy=store,
                        optimizer=SGDOptimizer(lr=0.01))

    def admits(vocab, c):
        try:
            DeviceResidentLoader(arrays(vocab), batch, executor(vocab, c),
                                 shuffle=True, seed=3)
            return True
        except DeviceMemoryError:
            return False

    def max_vocab(c, start=128, cap=1 << 20):
        v = 0
        probe = start
        while probe <= cap and admits(probe, c):
            v = probe
            probe *= 2
        return v

    budget = 72 * 1024  # fits ~1k replicated rows over dataset + head
    saved = os.environ.get("FF_DEVICE_MEM_BYTES")
    os.environ["FF_DEVICE_MEM_BYTES"] = str(budget)
    try:
        rep = max_vocab(c=1)
        shd = max_vocab(c=4)
    finally:
        if saved is None:
            os.environ.pop("FF_DEVICE_MEM_BYTES", None)
        else:
            os.environ["FF_DEVICE_MEM_BYTES"] = saved
    out = {
        "emb_budget_bytes": budget,
        "max_vocab_replicated": rep,
        "max_vocab_sharded_c4": shd,
        "vocab_capacity_ratio": round(shd / rep, 2) if rep else None,
    }

    # Throughput at a vocab both layouts hold (no budget in force).
    common = max(rep, 128)
    data = arrays(common)

    def sps(c):
        ex = executor(common, c)
        batches = iter(DeviceResidentLoader(data, batch, ex,
                                            shuffle=True, seed=3))
        return Trainer(ex).fit(iterations=8, batches=batches,
                               warmup=1)["samples_per_s"]

    rep_sps, shd_sps = sps(1), sps(4)
    out["replicated_emb_samples_per_s"] = round(rep_sps, 2)
    out["sharded_emb_samples_per_s"] = round(shd_sps, 2)
    out["sharded_vs_replicated"] = round(shd_sps / rep_sps, 3)
    return out


def bench_serving(n_chips: int, on_tpu: bool):
    """Inference serving leg (SERVING.md): the transformer LM
    continuous-batching loop — pad-to-bucket prefill, KV-cache decode,
    K-token fused decode supersteps (one dispatch + one fence per K
    tokens across the whole slot batch).  Reports request latency
    p50/p95, tokens/s, decode ms/token, programs per decode superstep,
    and the acceptance A/B: fused K=8 supersteps vs per-token (K=1)
    dispatch — the serving analogue of the training superstep
    amortization."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.runtime.serving import (
        Server,
        ServingExecutor,
        synthetic_requests,
    )

    if on_tpu:
        vocab, d_model, heads, layers = 32768, 512, 8, 6
        max_seq, max_batch, n_req, max_new = 128, 8, 16, 32
    else:
        vocab, d_model, heads, layers = 256, 64, 2, 2
        max_seq, max_batch, n_req, max_new = 32, 4, 6, 12
    ff = build_transformer_lm(
        batch_size=max_batch, seq_len=max_seq, vocab_size=vocab,
        d_model=d_model, num_heads=heads, num_layers=layers,
        config=FFConfig(batch_size=max_batch,
                        compute_dtype="bfloat16" if on_tpu else "float32"),
    )
    sex = ServingExecutor(ff, max_batch=max_batch, max_seq=max_seq,
                          buckets=(max_seq // 2, max_seq))
    params, state = sex.init(0)
    out = {"max_batch": max_batch, "max_seq": max_seq, "requests": n_req}

    def run(k):
        reqs = lambda: synthetic_requests(
            n_req, vocab, prompt_len=(4, max_seq // 4),
            max_new_tokens=max_new, seed=13,
        )
        srv = Server(sex, params, state, decode_steps=k)
        srv.run(reqs())  # warm: compiles outside the measured run
        _, stats = srv.run(reqs())
        decode_tokens = max(stats["tokens"] - stats["prefills"], 1)
        return stats, stats["decode_s"] / decode_tokens * 1e3

    k8_stats = None
    for k in (1, 8):
        stats, ms_tok = run(k)
        out[f"k{k}_tokens_per_s"] = round(stats["tokens_per_s"], 1)
        out[f"k{k}_decode_ms_per_token"] = round(ms_tok, 3)
        if k == 8:
            k8_stats = stats
    out["fused_speedup_k8_vs_k1"] = round(
        out["k1_decode_ms_per_token"] / out["k8_decode_ms_per_token"], 3
    )
    # Headline latency/accounting fields come from the fused k=8 run
    # (the production operating point), explicitly — not whichever k
    # the sweep happened to run last.
    out["request_latency_ms_p50"] = k8_stats["request_latency_ms_p50"]
    out["request_latency_ms_p95"] = k8_stats["request_latency_ms_p95"]
    out["programs_per_decode_superstep"] = k8_stats[
        "programs_per_decode_superstep"
    ]

    # Scheduler A/B (SERVING.md "Scheduler policy"): the same bursty
    # open-loop workload under FIFO vs the SLO policy (tier+EDF
    # admission, adaptive K, preemption).  All latency columns are
    # VIRTUAL-clock values (deterministic, box-independent) — the
    # scheduling win, not wall noise.
    from flexflow_tpu.serving import (
        ScheduledServer,
        SchedulerPolicy,
        WorkloadSpec,
        make_workload,
    )

    def workload():
        return make_workload(WorkloadSpec(
            n_requests=2 * n_req, vocab=vocab,
            prompt_len=(4, max_seq // 4), max_new=(2, max_new),
            mean_gap_ms=2.0, burst=n_req, priorities=2, slo_ms=60.0,
            seed=13,
        ))

    def run_sched(policy):
        srv = ScheduledServer(sex, params, state, decode_steps=8,
                              policy=policy)
        _, stats = srv.run(workload())
        return stats

    slo = run_sched(SchedulerPolicy(name="slo"))
    fifo = run_sched(SchedulerPolicy.fifo())
    out["queue_wait_ms_p50"] = slo["queue_wait_ms_p50"]
    out["queue_wait_ms_p95"] = slo["queue_wait_ms_p95"]
    out["queue_wait_ms_p99"] = slo["queue_wait_ms_p99"]
    out["e2e_ms_p99"] = slo["e2e_ms_p99"]
    out["slo_attainment"] = slo["slo_attainment"]
    out["request_sheds"] = slo["request_sheds"]
    out["request_preempts"] = slo["request_preempts"]
    out["fifo_queue_wait_ms_p99"] = fifo["queue_wait_ms_p99"]
    out["fifo_slo_attainment"] = fifo["slo_attainment"]
    out["fifo_vs_slo_queue_wait_p99"] = round(
        fifo["queue_wait_ms_p99"] / max(slo["queue_wait_ms_p99"], 1e-9),
        3,
    )
    # Tail-autopsy columns (OBSERVABILITY.md "Reading a request"):
    # which phase dominated the SLO misses, per tier — the span-layer
    # attribution folded straight from the run's stats block.
    autopsy = slo.get("slo_autopsy") or {}
    out["slo_missed"] = sum(r["missed"] for r in autopsy.values())
    out["slo_dominant_phase"] = {
        tier: row["dominant_phase"] for tier, row in autopsy.items()
    }

    # Failure-model columns (SERVING.md "Failure model"): the same
    # workload with one injected slot fault and one engine-class fault
    # under a retry/restart budget — the counters prove the recovery
    # machinery ran (a healthy run reports zeros).
    from flexflow_tpu.runtime.serving import ServingFaultInjector
    from flexflow_tpu.serving import ServingResilience

    rsrv = ScheduledServer(
        sex, params, state, decode_steps=8,
        policy=SchedulerPolicy(name="slo"),
        resilience=ServingResilience(max_retries=1, max_restarts=1),
        fault_injector=ServingFaultInjector(
            nan_cache_at={1: 0},
            engine_raise_at={3: "injected engine fault"}),
    )
    _, rstats = rsrv.run(workload())
    out["request_retries"] = rstats["request_retries"]
    out["request_expiries"] = rstats["request_expiries"]
    out["engine_restarts"] = rstats["engine_restarts"]

    # Capacity columns (SERVING.md "Cache layout"): per-slot HBM under
    # both layouts at the leg's typical short prompt, the max batch a
    # fixed cache budget admits (the paged-vs-padded capacity win), and
    # paged / sharded tokens/s against the single-mesh padded run.
    kv_block = 16 if on_tpu else 8
    sexp = ServingExecutor(ff, max_batch=max_batch, max_seq=max_seq,
                           buckets=(max_seq // 2, max_seq),
                           kv_block=kv_block)
    plen = 4
    out["hbm_per_slot_bytes"] = sex.hbm_per_slot_bytes()
    out["paged_hbm_per_slot_bytes"] = sexp.hbm_per_slot_bytes(plen, max_new)
    budget = sex.cache_total_bytes()
    out["padded_max_admitted_batch"] = sex.max_admissible_batch(
        budget, plen, max_new)
    out["paged_max_admitted_batch"] = sexp.max_admissible_batch(
        budget, plen, max_new)

    def throughput(engine):
        reqs = lambda: synthetic_requests(
            n_req, vocab, prompt_len=(4, max_seq // 4),
            max_new_tokens=max_new, seed=13,
        )
        # Per-engine init: same seed = identical weights, placed for
        # the engine's own mesh (sharded caches reject single-device
        # params at dispatch).
        p, s = engine.init(0)
        srv = Server(engine, p, s, decode_steps=8)
        srv.run(reqs())  # warm: compiles outside the measured run
        _, stats = srv.run(reqs())
        return stats

    pstats = throughput(sexp)
    out["paged_tokens_per_s"] = round(pstats["tokens_per_s"], 1)
    sexs = ServingExecutor(ff, max_batch=max_batch, max_seq=max_seq,
                           buckets=(max_seq // 2, max_seq), shard=(2, 1))
    sstats = throughput(sexs)
    out["sharded_mesh"] = sstats["shard"]  # None = single-mesh fallback
    out["sharded_tokens_per_s"] = round(sstats["tokens_per_s"], 1)
    out["sharded_vs_single_mesh_tokens_per_s"] = round(
        sstats["tokens_per_s"] / max(out["k8_tokens_per_s"], 1e-9), 3)

    # Speculation columns (SERVING.md "Speculative decoding"): a d=12
    # full self-draft (the degenerate fully-accepting case — the draft
    # SOURCE on a real deployment is a checkpoint or truncation, a
    # deployment fact, but the dispatch accounting is the same) vs the
    # plain fused k=8 run.  Tokens per decode dispatch is the headline
    # (d=12 emits up to 13 tokens per dispatch where plain decode is
    # capped at k=8);
    # the match bit proves acceptance decides dispatch count, never
    # content.
    def reqs13():
        return synthetic_requests(
            n_req, vocab, prompt_len=(4, max_seq // 4),
            max_new_tokens=max_new, seed=13,
        )

    plain_res, _ = Server(sex, params, state, decode_steps=8).run(reqs13())
    spec_srv = Server(sex, params, state, decode_steps=8, speculate=12)
    spec_srv.run(reqs13())  # warm: compiles outside the measured run
    spec_res, spec_stats = spec_srv.run(reqs13())
    out["speculate"] = spec_stats["speculate"]
    out["spec_tokens_per_s"] = round(spec_stats["tokens_per_s"], 1)
    out["spec_acceptance_rate"] = spec_stats["spec_acceptance_rate"]
    out["spec_tokens_per_dispatch"] = spec_stats["spec_tokens_per_dispatch"]
    plain_tpd = (k8_stats["tokens"] - k8_stats["prefills"]) / max(
        k8_stats["decode_supersteps"], 1)
    out["plain_tokens_per_dispatch"] = round(plain_tpd, 3)
    out["spec_vs_plain_tokens_per_dispatch"] = round(
        spec_stats["spec_tokens_per_dispatch"] / max(plain_tpd, 1e-9), 3)
    out["spec_match"] = all(
        spec_res[r].tokens == plain_res[r].tokens for r in plain_res)

    # Fleet columns (SERVING.md "Fleet"): the same bursty workload on
    # a 2-replica fleet behind the least-loaded router vs the
    # single-replica slo run (attainment is the headline — two chip
    # groups absorb the burst), plus a replica-loss sub-leg: an
    # engine-class fault kills replica 0 mid-run and the router
    # redistributes its journaled in-flight requests to the survivor
    # (the counters prove the loss path ran; all virtual-clock values).
    from flexflow_tpu.serving import FleetRouter, MemoryJournal

    sexf = ServingExecutor(ff, max_batch=max_batch, max_seq=max_seq,
                           buckets=(max_seq // 2, max_seq))
    pf, sf = sexf.init(0)

    def make_fleet(injected):
        stacks = ((sex, params, state), (sexf, pf, sf))
        reps = []
        for i, (ex_i, p_i, s_i) in enumerate(stacks):
            reps.append(ScheduledServer(
                ex_i, p_i, s_i, decode_steps=8,
                policy=SchedulerPolicy(name="slo"),
                resilience=ServingResilience(max_restarts=0),
                journal=MemoryJournal(),
                fault_injector=ServingFaultInjector(
                    engine_raise_at={1: "injected replica death"})
                if injected and i == 0 else None,
            ))
        return FleetRouter(reps, router="least-loaded")

    _, fstats = make_fleet(injected=False).run(workload())
    out["fleet_replicas"] = fstats["replicas"]
    out["fleet_router"] = fstats["router"]
    out["fleet_queue_wait_ms_p99"] = fstats["queue_wait_ms_p99"]
    out["fleet_slo_attainment"] = fstats["slo_attainment"]
    out["fleet_vs_single_attainment"] = round(
        fstats["slo_attainment"] / max(slo["slo_attainment"], 1e-9), 3)
    _, lstats = make_fleet(injected=True).run(workload())
    out["fleet_dead_replicas"] = lstats["dead_replicas"]
    out["fleet_redistributed"] = lstats["redistributed"]
    out["fleet_loss_slo_attainment"] = lstats["slo_attainment"]

    # Prefix-cache columns (SERVING.md "Prefix sharing"): the bursty
    # workload with a shared system-prompt span on the paged pool with
    # the content-hash index armed vs the SAME pool without it — hit
    # rate, prefill dispatches saved, and the byte-parity bit (shared
    # decode must match the unshared run token-for-token).
    def pfx_workload():
        return make_workload(WorkloadSpec(
            n_requests=2 * n_req, vocab=vocab,
            prompt_len=(4, max_seq // 4), max_new=(2, max_new),
            mean_gap_ms=2.0, burst=n_req, priorities=2, slo_ms=60.0,
            shared_prefix=kv_block, seed=13,
        ))

    def run_pfx(engine):
        p, s = engine.init(0)  # same seed = identical weights
        srv = ScheduledServer(engine, p, s, decode_steps=8,
                              policy=SchedulerPolicy(name="slo"))
        return srv.run(pfx_workload())

    sexpc = ServingExecutor(ff, max_batch=max_batch, max_seq=max_seq,
                            buckets=(max_seq // 2, max_seq),
                            kv_block=kv_block, prefix_cache=True)
    off_res, off_stats = run_pfx(sexp)
    on_res, on_stats = run_pfx(sexpc)
    out["prefix_hits"] = on_stats["prefix_hits"]
    out["prefix_hit_rate"] = on_stats["prefix_hit_rate"]
    out["prefill_tokens_saved"] = on_stats["prefill_tokens_saved"]
    out["prefix_kv_cows"] = on_stats["kv_cows"]
    out["prefix_prefills"] = on_stats["prefills"]
    out["prefix_off_prefills"] = off_stats["prefills"]
    out["prefix_match"] = all(
        on_res[r].tokens == off_res[r].tokens for r in off_res)
    return out


def bench_search(n_chips: int, on_tpu: bool):
    """Execution-autotuner leg (``-s auto``'s engine,
    search/execution.py): the dispatch-bound MLP trained under the
    default config (DP, per-step dispatch) vs the auto-chosen execution
    config — the search calibrated from the default leg's OWN in-memory
    telemetry (dispatch/fence constants + compute scale), exactly the
    apps' ``--calibration`` flow.  Reports measured default/auto
    ms/step, the chosen config with its PREDICTED ms/step (the
    predicted-vs-measured honesty check), and search wall time."""
    import numpy as np

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.graph import FFModel
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.pipeline import make_executor
    from flexflow_tpu.runtime.telemetry import Telemetry
    from flexflow_tpu.runtime.trainer import Trainer
    from flexflow_tpu.search import Calibration, search_execution_config

    batch = 64 * n_chips if on_tpu else 32
    width = 256 if on_tpu else 64
    iters = 32 if on_tpu else 16

    def build():
        ff = FFModel(FFConfig(batch_size=batch, seed=11))
        x = ff.create_tensor((batch, width), name="x")
        lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
        t = ff.dense(x, width, activation="relu", name="fc1")
        t = ff.dense(t, 8, name="fc2")
        ff.softmax(t, lbl, name="softmax")
        return ff

    opt = lambda: SGDOptimizer(lr=0.01, momentum=0.9)
    with Telemetry() as tel:
        stats = Trainer(Executor(build(), optimizer=opt())).fit(
            iterations=iters, warmup=1
        )
    default_ms = stats["elapsed_s"] / iters * 1e3
    cal = Calibration.from_telemetry(tel)
    ff = build()
    t0 = time.perf_counter()
    # ks capped at 16 so iters stays superstep-divisible (no tail
    # recompile inside the timed region).
    res = search_execution_config(
        ff, n_chips, iters=2000, seed=0, calibration=cal,
        ks=(1, 2, 4, 8, 16),
    )
    wall = time.perf_counter() - t0
    best = res.best
    ex = make_executor(
        ff, best.store if best.store.table else None, optimizer=opt(),
        microbatches=best.microbatches, chunk=best.chunk,
        compiled=best.compiled,
    )
    stats = Trainer(ex).fit(iterations=iters, warmup=1,
                            steps_per_call=best.steps_per_call)
    auto_ms = stats["elapsed_s"] / iters * 1e3
    return {
        "batch_size": batch,
        "iterations": iters,
        "default_ms_per_step": round(default_ms, 3),
        "auto_ms_per_step": round(auto_ms, 3),
        "auto_speedup": round(default_ms / max(auto_ms, 1e-9), 3),
        "auto_config": best.describe(),
        "predicted_ms_per_step": round(best.predicted_ms, 3),
        "search_wall_s": round(wall, 3),
        "calibrated": cal.calibrated,
    }


def bench_op_parallel_speedup(n_devices: int = 4):
    """The third BASELINE metric: operator-parallel vs data-parallel
    speedup (the ICML'18 headline claims it for AlexNet/VGG/Inception;
    reference prints dpCompTime / bestCompTime from the simulator,
    ``simulator.cc:117-118``).  Multi-chip hardware is not reachable
    from the bench harness, so the numbers come from the same place
    the reference's do: the strategy-search simulator (native ffsim)
    with the analytic roofline device model on ``n_devices`` chips."""
    from flexflow_tpu.models.alexnet import build_alexnet
    from flexflow_tpu.models.cnn_catalog import build_inception_v3, build_vgg16
    from flexflow_tpu.search import search_strategy

    ff = build_alexnet(batch_size=256, image_size=229, num_classes=1000)
    result = search_strategy(ff, num_devices=n_devices)
    out = {
        "op_parallel_speedup_sim": round(result.speedup, 3),
        "dp_time_us": round(result.dp_time_us, 1),
        "best_time_us": round(result.best_time_us, 1),
        "devices": n_devices,
    }
    for name, build in (("vgg16", build_vgg16), ("inception", build_inception_v3)):
        try:
            # Best of 3 seeds at 100k iters (the reference runs 250k,
            # simulator.cc:1444): VGG is converged by 20k; Inception's
            # branch-heavy space still wiggles ~1% between seeds.
            ff_m = build(batch_size=64)
            r = max(
                (search_strategy(ff_m, num_devices=n_devices,
                                 iters=100_000, seed=s) for s in (0, 1, 2)),
                key=lambda r: r.speedup,
            )
            out[f"{name}_speedup_sim"] = round(r.speedup, 3)
        except Exception as e:  # a catalog model must not sink the metric
            out[f"{name}_error"] = f"{type(e).__name__}: {e}"
    return out


def _r4(x):
    return None if x is None else round(x, 4)


def main():
    import jax

    from flexflow_tpu.apps.common import enable_compile_cache

    enable_compile_cache()
    platform = jax.default_backend()
    if platform == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(
            "bench.py: jax found no accelerator and JAX_PLATFORMS=cpu was "
            "not asked for; nothing was measured",
            file=sys.stderr,
        )
        return 2
    on_tpu = platform != "cpu"
    n_chips = len(jax.devices())
    extra = {"platform": platform,
             "device_kind": jax.devices()[0].device_kind,
             "n_chips": n_chips}
    failed = []

    def leg(name, fn, *args):
        """Run one leg; the Trainer mirrors the reference's ``tp = ...``
        printouts on stdout and the driver wants exactly one JSON line
        there, so everything a leg prints goes to stderr.  A raise
        becomes ``<name>_error`` in the report and a non-zero exit
        after the report is printed."""
        try:
            with contextlib.redirect_stdout(sys.stderr):
                return fn(*args)
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            extra[f"{name}_error"] = f"{type(e).__name__}: {e}"
            failed.append(name)
            return None

    with contextlib.redirect_stdout(sys.stderr):
        per_chip, mfu, batch_size = bench_alexnet(n_chips, on_tpu)
    extra["batch_size"] = batch_size
    extra["alexnet_mfu"] = _r4(mfu)
    r = leg("dlrm", bench_dlrm, n_chips, on_tpu)
    if r is not None:
        extra["dlrm_samples_per_s"] = round(r[0], 2)
        extra["dlrm_mfu"] = _r4(r[1])
    for name, fn in (("transformer", bench_transformer),
                     ("transformer_8k", bench_transformer_longctx),
                     ("transformer_32k", bench_transformer_32k)):
        r = leg(name, fn, on_tpu)
        if r is not None:
            extra[f"{name}_tokens_per_s"] = round(r[0], 1)
            extra[f"{name}_mfu"] = _r4(r[1])
    r = leg("candle", bench_candle, on_tpu)
    if r is not None:
        extra["candle_samples_per_s"] = round(r, 2)
    r = leg("nmt", bench_nmt, n_chips, on_tpu)
    if r is not None:
        nmt_s, nmt_sps, nmt_iters = r
        extra["nmt_pairs_per_s"] = round(nmt_sps, 2)
        if nmt_iters == 10:  # the reference's exact protocol
            extra["nmt_10iter_time_s"] = round(nmt_s, 4)
        else:  # shrunken CPU run: label honestly
            extra["nmt_time_s"] = round(nmt_s, 4)
            extra["nmt_iters"] = nmt_iters
            extra["nmt_protocol_deviation"] = (
                f"reference protocol is 10 iterations (nmt.cc:72-83); "
                f"this CPU run took {nmt_iters} on shrunken shapes"
            )
    for name, fn in (("superstep", bench_superstep),
                     ("pipeline", bench_pipeline),
                     ("telemetry", bench_telemetry),
                     ("serving", bench_serving),
                     ("search", bench_search),
                     ("data_plane", bench_data_plane)):
        r = leg(name, fn, n_chips, on_tpu)
        if r is not None:
            extra[name] = r
    # ICML'18 reports 4-chip speedups; simulate at least that even
    # when the machine holds one chip.
    r = leg("op_parallel", bench_op_parallel_speedup, max(4, n_chips))
    if r is not None:
        extra["op_parallel"] = r

    # Box-state fingerprint (git sha, jax/jaxlib, platform, devices,
    # host): lets obs.compare pair this artifact against other runs
    # (every field degrades to None).
    from flexflow_tpu.obs.registry import box_fingerprint

    extra["fingerprint"] = box_fingerprint()

    print(json.dumps({
        "metric": "alexnet_imgs_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/s/chip",
        "vs_baseline": round(per_chip / BASELINE_IMGS_PER_SEC_PER_CHIP, 3),
        "extra": extra,
    }))
    if failed:
        print(f"bench.py: legs failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:
        print(
            json.dumps(
                {
                    "metric": "alexnet_imgs_per_sec_per_chip",
                    "value": None,
                    "unit": "images/s/chip",
                    "vs_baseline": None,
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-1500:],
                }
            )
        )
        sys.exit(1)
