"""Decompose the transformer-LM step time on the live chip.

The bench headline (round 2: 113k tokens/s, MFU 0.166 at b16/t2048/L6)
leaves ~45% of the step unexplained by the analytic flop budget at
plausible kernel efficiencies.  This tool measures, in fresh
subprocesses run one after another (the parent stays off jax, so each
child finds the chip free):

  L=1 vs L=6 at b16   -> per-transformer-block ms (slope) and the
                         embed+head+xent+optimizer intercept
  b32 + --remat at L6 -> whether rematerialization unlocks the larger
                         batch (round-2 sweep: b32 OOM'd) and what it
                         yields in tokens/s

Usage: python tools/profile_lm_decomp.py
"""

import os
import subprocess
import sys

BODY = r"""
import sys, time
import jax
layers, batch, remat, seq_arg = (int(x) for x in sys.argv[1:5])

from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.optim import AdamOptimizer
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.trainer import Trainer

import os
smoke = os.environ.get("FF_DECOMP_SMOKE") == "1"
seq, vocab, d, iters = ((128, 512, 64, 3) if smoke
                        else (2048, 32768, 512, 12))
if seq_arg and not smoke:
    seq = seq_arg
    iters = max(3, iters // max(1, seq // 2048))
cfg = FFConfig(batch_size=batch, compute_dtype="bfloat16", remat=bool(remat))
ff = build_transformer_lm(batch_size=batch, seq_len=seq, vocab_size=vocab,
                          d_model=d, num_heads=8, num_layers=layers,
                          config=cfg)
ex = Executor(ff, optimizer=AdamOptimizer(lr=1e-4),
              devices=jax.devices()[:1])
stats = Trainer(ex).fit(iterations=iters, warmup=1 if smoke else 3)
ms = 1e3 / (stats["samples_per_s"] / batch)
chunk = os.environ.get("FF_FLASH_FORCE_CHUNK", "0")
print(f"RESULT L={layers} b={batch} seq={seq} remat={remat} chunk={chunk}: "
      f"{ms:8.1f} ms/step  {stats['samples_per_s'] * seq:,.0f} tokens/s",
      flush=True)
"""


def main():
    os.chdir(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    # (layers, batch, remat, seq, chunk): seq=0 keeps the default 2048;
    # chunk>0 exports FF_FLASH_FORCE_CHUNK, racing the chunked flash
    # decomposition against the monolithic kernel INSIDE the fused
    # train step, where the kernels run as the step runs them.  The
    # seq=16384 row drives the chunked path at its real scale (past the
    # single-launch VMEM cap).
    for layers, batch, remat, seq, chunk in (
        (1, 16, 0, 0, 0), (6, 16, 0, 0, 0), (6, 16, 0, 0, 512),
        (6, 16, 0, 0, 1024), (6, 32, 1, 0, 0), (6, 1, 0, 16384, 0),
    ):
        env = dict(os.environ)
        if chunk:
            env["FF_FLASH_FORCE_CHUNK"] = str(chunk)
        r = subprocess.run(
            [sys.executable, "-c", BODY,
             str(layers), str(batch), str(remat), str(seq)],
            text=True, capture_output=True, env=env,
        )
        for line in (r.stdout + r.stderr).splitlines():
            if line.startswith("RESULT") or "rror" in line[:60]:
                print(line, flush=True)
        if r.returncode != 0 and "RESULT" not in r.stdout:
            tail = (r.stderr or r.stdout).strip().splitlines()[-3:]
            print(f"FAIL L={layers} b={batch} remat={remat} seq={seq}: "
                  + " | ".join(tail), flush=True)


if __name__ == "__main__":
    main()
