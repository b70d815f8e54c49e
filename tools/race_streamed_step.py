"""Fused-step race: production flash dispatch vs FF_FLASH_STREAMED=1.

Per-kernel chain timing (probe_flash_variants) ranks the kernels; this
races them where it counts — the full jitted LM train step through
Trainer.fit.  Each arm runs in a FRESH subprocess because the dispatch
flag is read at module import; the parent stays off jax and the arms
run one after another, so each finds the chip free.  ABAB interleave
splits drift from effect.

Usage: python tools/race_streamed_step.py [iters]
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ARM = r"""
import sys
sys.path.insert(0, {repo!r})
from bench import _bench_lm
import jax
on_tpu = jax.default_backend() != "cpu"
tps, mfu = _bench_lm(batch=16 if on_tpu else 2,
                     seq=2048 if on_tpu else 256,
                     layers=6 if on_tpu else 2,
                     iters={iters} if on_tpu else 2)
print(f"RESULT tokens_per_s={{tps:.1f}} mfu={{mfu}} "
      f"platform={{jax.default_backend()}}", file=sys.stderr)
"""


def run_arm(streamed: bool, iters: int) -> str:
    env = dict(os.environ)
    env["FF_FLASH_STREAMED"] = "1" if streamed else "0"
    out = subprocess.run(
        [sys.executable, "-c", _ARM.format(repo=REPO, iters=iters)],
        capture_output=True, text=True, env=env, cwd=REPO,
    )
    for line in (out.stderr or "").splitlines():
        if line.startswith("RESULT"):
            return line
    return f"FAIL rc={out.returncode}: {(out.stderr or '')[-300:]}"


def main():
    iters = int(sys.argv[1]) if len(sys.argv) > 1 else 10
    ok = 0
    for arm in (False, True, False, True):
        name = "streamed" if arm else "production"
        line = run_arm(arm, iters)
        ok += line.startswith("RESULT")
        print(f"{name:10s} {line}", flush=True)
    # A race where no arm produced data must not log rc=0 in the
    # measurement sequence.
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
