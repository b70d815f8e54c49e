"""XProf op-level breakdown of the transformer-LM train step.

Captures a trace of a few fused train steps on the live backend, then
parses the XPlane proto with ``jax.profiler.ProfileData`` and prints
the top device ops by total self time — the precise version of the
layer-count decomposition in ``profile_lm_decomp.py`` (per-op eager
timing carries the host's dispatch cost; the trace sees device-side
truth).

Usage: python tools/profile_lm_trace.py [outdir]
"""

import collections
import glob
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def capture(outdir: str) -> None:
    import jax

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.optim import AdamOptimizer
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.trainer import Trainer

    smoke = os.environ.get("FF_TRACE_SMOKE") == "1"
    batch, seq, vocab, d, L = ((4, 128, 512, 64, 2) if smoke
                               else (16, 2048, 32768, 512, 6))
    ff = build_transformer_lm(
        batch_size=batch, seq_len=seq, vocab_size=vocab, d_model=d,
        num_heads=8, num_layers=L,
        config=FFConfig(batch_size=batch, compute_dtype="bfloat16"),
    )
    ex = Executor(ff, optimizer=AdamOptimizer(lr=1e-4),
                  devices=jax.devices()[:1])
    tr = Trainer(ex)
    tr.fit(iterations=3, warmup=1)          # compile outside the trace
    jax.profiler.start_trace(outdir)
    tr.fit(iterations=3, warmup=0)
    jax.profiler.stop_trace()


def report(outdir: str, top: int = 25) -> None:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(outdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        print(f"no .xplane.pb under {outdir}", file=sys.stderr)
        return
    data = ProfileData.from_file(paths[-1])

    def plane_totals(plane):
        totals = collections.Counter()
        for line in plane.lines:
            for ev in line.events:
                totals[ev.name] += ev.duration_ns
        return totals

    # Device planes carry the accelerator truth; the host plane's
    # python events double-count.  Fall back to the busiest plane when
    # the backend exposes no device plane (CPU smoke runs).
    planes = list(data.planes)
    device = [p for p in planes
              if "TPU" in p.name or "GPU" in p.name
              or "/device" in p.name.lower()]
    chosen = device or sorted(
        planes, key=lambda p: sum(plane_totals(p).values()), reverse=True)[:1]
    for plane in chosen:
        totals = plane_totals(plane)
        if not totals:
            continue
        whole = sum(totals.values())
        tag = "" if device else "  [host fallback: no device plane]"
        print(f"== plane: {plane.name}{tag}  (sum {whole / 1e6:.1f} ms over "
              f"{len(totals)} op names)")
        for name, ns in totals.most_common(top):
            print(f"  {ns / 1e6:9.3f} ms  {ns / whole * 100:5.1f}%  "
                  f"{name[:110]}")


def main():
    outdir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/ff_lm_trace"
    capture(outdir)
    report(outdir)


if __name__ == "__main__":
    main()
