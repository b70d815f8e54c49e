#!/usr/bin/env python
"""Streaming data-plane A/B: the DATA.md acceptance run on the 8-dev
virtual CPU mesh.

Three measurements, each against its acceptance bar:

- ``stream_vs_zc``: out-of-core StreamingLoader (reader thread +
  windowed shuffle + PrefetchLoader H2D overlap, dataset = 4x the
  shuffle window) vs the device-resident zero-copy loader on the SAME
  arrays.  Bar: >= 0.9x — streaming trades a bounded slowdown for
  unbounded dataset size.
- ``overlap_speedup``: streaming vs unprefetched inline reads when the
  source is throttled with a per-row disk-latency model (the SAME
  throttle both ways).  Bar: >= 1.3x — the reader thread + prefetch
  must actually hide the read behind compute.
- ``input-wait audit``: one telemetry-enabled streaming run; the
  summary's ``input_wait_s_total`` must equal the sum of the JSONL
  ``input_wait`` events' ``wall_s`` EXACTLY (the accounting is the
  same rounded number on both sides), and ``input_waits`` must equal
  the event count.

CPU wall noise at these sizes swings more between identical runs than
the effects being measured, so the protocol is the paired one from
measure_telemetry.py: each rep runs the two variants back to back
(order alternating between reps) and the statistic is the MEDIAN OF
PER-PAIR RATIOS; an ``a_a`` control column runs the protocol on two
identical legs — read each ratio against it.

Usage: env PYTHONPATH=/root/repo python tools/measure_data.py
       [--reps N] [--iters N] [--tpu]
(One process.  Without --tpu it pins the 8-device virtual CPU mesh
before jax initializes (``analysis.program_audit.ensure_cpu_mesh``);
with --tpu it leaves the environment alone and runs on the attached
chip.)
"""

import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _arg(argv, flag, default):
    if flag in argv:
        return int(argv[argv.index(flag) + 1])
    return default


def run(argv):
    # An inherited FF_TELEMETRY_DIR would install file-backed
    # telemetry on the supposedly-bare legs and skew every pair; the
    # audit leg builds its own Telemetry explicitly.
    os.environ.pop("FF_TELEMETRY_DIR", None)
    import jax
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

    reps = _arg(argv, "--reps", 9)
    iters = _arg(argv, "--iters", 64)
    batch, width = 32, 64
    rows = batch * 8  # dataset = 8 batches; window = rows/4 => 4x bar
    nd = len(jax.devices())

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
    tr = Trainer(ex)
    tr.fit(iterations=2, warmup=1)  # warm the jits once, shared by all legs

    def fit(batches, tel=None):
        try:
            if tel is not None:
                with tel:
                    return tr.fit(iterations=iters, batches=batches,
                                  warmup=1)
            return tr.fit(iterations=iters, batches=batches, warmup=1)
        finally:
            if hasattr(batches, "close"):
                batches.close()

    def stream_batches(source=None, window=rows // 4):
        src = source if source is not None else ArrayStreamSource(arrays)
        return PrefetchLoader(
            iter(StreamingLoader(src, batch, shuffle=True, seed=3,
                                 shuffle_window=window)),
            ex.shard_batch)

    def zc_batches():
        return iter(DeviceResidentLoader(arrays, batch, ex,
                                         shuffle=True, seed=3))

    def host_batches():
        return PrefetchLoader(
            iter(ArrayDataLoader(arrays, batch, shuffle=True, seed=3)),
            ex.shard_batch)

    per_row_s = 1e-4

    def throttled_stream_batches():
        return stream_batches(
            ThrottledSource(ArrayStreamSource(arrays), per_row_s=per_row_s),
            window=batch * 2)

    def inline_throttled_batches():
        src = ThrottledSource(ArrayStreamSource(arrays),
                              per_row_s=per_row_s)
        pos = 0
        while True:
            if pos + batch > rows:
                pos = 0
            yield ex.shard_batch(src.read(pos, pos + batch))
            pos += batch

    # The alternating-order paired protocol lives in
    # obs.compare.paired_measure (shared with measure_telemetry.py);
    # here the statistic is the RATIO form, control = two B legs.
    from flexflow_tpu.obs.compare import paired_measure

    def paired_ratio(name, make_a, make_b, bar):
        """Median over reps of (A samples/s) / (B samples/s), with an
        A/A control run under the same alternating-order pairing."""
        res = paired_measure(
            make_a=lambda r: fit(make_a())["samples_per_s"],
            make_b=lambda r: fit(make_b())["samples_per_s"],
            reps=reps,
            control=lambda r: fit(make_b())["samples_per_s"],
        )
        med, ctl = res.median_ratio, res.median_aa_ratio
        ok = "PASS" if med >= bar else "FAIL"
        print(f"{name:<22} {med:>7.3f}x  (bar >= {bar}x, a_a "
              f"{ctl:.3f}x) {ok}")
        return med >= bar

    print(f"streaming data-plane A/B: median of {reps} paired ratios, "
          f"{iters} iters, batch {batch}, {rows} rows, {nd} devices")
    failures = 0

    # Context row, not an acceptance bar: host ArrayDataLoader tier.
    host = fit(host_batches())["samples_per_s"]
    print(f"{'host+prefetch':<22} {host:>9.1f} samples/s")

    try:
        fit(zc_batches())  # probe the budget before committing to reps
        if not paired_ratio("stream_vs_zc", stream_batches, zc_batches,
                            bar=0.9):
            failures += 1
    except DeviceMemoryError as e:
        print(f"stream_vs_zc skipped: {e}")

    if not paired_ratio("overlap_speedup", throttled_stream_batches,
                        inline_throttled_batches, bar=1.3):
        failures += 1

    # Input-wait audit: JSONL events vs the folded summary, exact
    # (parsed through the ONE log reader, obs.reader.RunLog).
    from flexflow_tpu.obs.reader import RunLog

    with tempfile.TemporaryDirectory(prefix="data_ab_") as d:
        tel = Telemetry(os.path.join(d, "audit"))
        path = tel.path
        stats = fit(throttled_stream_batches(), tel=tel)
        summary = stats.get("telemetry", {})
        events = RunLog.load(path).select("input_wait")
        total = round(sum(e["wall_s"] for e in events), 6)
        n_ok = summary.get("input_waits") == len(events)
        t_ok = summary.get("input_wait_s_total") == total
        ok = "PASS" if (n_ok and t_ok and events) else "FAIL"
        print(f"{'input_wait audit':<22} {len(events)} events, "
              f"sum {total}s == summary "
              f"{summary.get('input_wait_s_total')}s, "
              f"count == {summary.get('input_waits')} {ok}")
        if not (n_ok and t_ok and events):
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
