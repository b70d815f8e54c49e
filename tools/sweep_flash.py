"""Flash-attention block-size sweep on the attached TPU.

Larger blocks amortize the per-block streaming-softmax corrections;
this sweeps FF_FLASH_BLOCK (which pallas_kernels reads at import) in
fresh subprocesses, one after another — the parent stays off jax, so
each child finds the chip free — and times fwd and fwd+bwd as jitted
chains (one jax.device_get per measurement).

Usage: python tools/sweep_flash.py [b h t hd]
"""

import os
import subprocess
import sys

BODY = r"""
import os, sys, time
import jax, jax.numpy as jnp

b, h, t, hd = (int(x) for x in sys.argv[1:5])
from flexflow_tpu.ops import pallas_kernels as pk

shape = (b, h, t, hd)
if not pk.flash_supported(shape, jnp.bfloat16):
    print(f"block {os.environ.get('FF_FLASH_BLOCK')}: unsupported at {shape}")
    sys.exit(0)
# The VMEM cap may shrink the requested block (oversized requests now
# clamp instead of OOMing Mosaic); label the row with what actually ran.
actual = pk._flash_block(t, hd, 2)
if str(actual) != os.environ.get("FF_FLASH_BLOCK", ""):
    print(f"(FF_FLASH_BLOCK={os.environ.get('FF_FLASH_BLOCK')} "
          f"clamped to {actual})")
key = jax.random.PRNGKey(0)
q, k, v = (jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16)
           for i in range(3))

def loss(q, k, v):
    return jnp.sum(pk.flash_attention(q, k, v, True).astype(jnp.float32))

# All three cotangents summed into a q-shaped carry, so the chain keeps
# BOTH backward kernels (dq and dkv) alive — grad wrt q alone would let
# XLA dead-code-eliminate the dkv pallas_call.
grad_all = jax.grad(loss, argnums=(0, 1, 2))

def bwd_step(x):
    dq, dk, dv = grad_all(x, k, v)
    return (dq + dk + dv).astype(x.dtype)

# Two-point jitted-chain timing (a single call carries the host's
# dispatch and fence cost): one jit'd dependent chain x = f(x) of
# length N is ONE dispatch, and the (N2 - N1) slope isolates
# per-iteration cost.
def timeit(step, pallas_per_step=1):
    # bwd_step carries ~3 pallas calls (fwd recompute + dq + dkv), so
    # its chain lengths shrink to (2, 8).
    n2 = min(16, max(2, 24 // pallas_per_step))
    n1 = max(1, n2 // 4)
    def chain(n):
        # Min of 3: host delays are additive one-sided noise, so the
        # min estimates the compute time.
        @jax.jit
        def run(x):
            return jax.lax.fori_loop(0, n, lambda _, x: step(x), x)
        y = run(q)
        jax.device_get(y.ravel()[:1])  # compile+warm fence
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            y = run(q)
            jax.device_get(y.ravel()[:1])
            best = min(best, time.perf_counter() - t0)
        return best
    # Non-positive slope = host noise swamped the signal; retry once,
    # then flag so nobody tunes a block size from garbage.
    for _ in range(2):
        slope = (chain(n2) - chain(n1)) / (n2 - n1) * 1e3
        if slope > 0:
            return slope
    # stdout, not stderr: the parent sweep drops child stderr whenever
    # stdout is non-empty, and this flag must reach the user.
    print(f"WARNING: non-positive slope {slope:.2f} ms (host noise); "
          f"treat this row as unreliable", flush=True)
    return float("nan")

fwd_ms = timeit(lambda x: pk.flash_attention(x, k, v, True).astype(x.dtype))
bwd_ms = timeit(bwd_step, pallas_per_step=3)
flops = 4.0 * b * h * t * t * hd / 2  # causal fwd
print(f"block {os.environ.get('FF_FLASH_BLOCK', '128'):>4s}: "
      f"fwd {fwd_ms:7.2f} ms ({flops / (fwd_ms * 1e-3) / 1.97e14 * 100:4.1f}% "
      f"of bf16 peak)  fwd+bwd {bwd_ms:7.2f} ms")
"""


def main():
    shape = sys.argv[1:5] or ["16", "8", "2048", "64"]
    print(f"flash sweep at (b,h,t,hd)={tuple(int(x) for x in shape)}")
    for block in ("128", "256", "512", "1024"):
        env = dict(os.environ, FF_FLASH_BLOCK=block)
        proc = subprocess.run(
            [sys.executable, "-c", BODY, *shape],
            env=env, capture_output=True, text=True, timeout=900,
        )
        out = proc.stdout.strip() or proc.stderr.strip()[-300:]
        print(out)


if __name__ == "__main__":
    main()
