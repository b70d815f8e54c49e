"""``ff_flash_fwd_uneven`` alone on the attached chip, one or two trees
side by side (PERF.md §6 PR 50; .claude/skills/verify/SKILL.md).

Each spec is first checked against a plain einsum oracle ON THE CHIP
(one KV head's query heads, a strip of query rows at a time: the whole
score matrix of a 32k bucket is 200 GB), then timed: ``N_CALLS``
dependent calls in one jitted ``fori_loop``, run once more under
``jax.profiler`` and read with ``benchmark/trace_read.py``: the kernel's
device time a call and TF/s by the ``t (t + 1) / 2`` pairs the cells'
cost files count, whatever the walk computes.  One process, so the chip
is held once.

    python3 tools/time_prefill_kernel.py SIDE:SHAPE:T[:BLOCK] ... | @file

SIDE   ``change`` (this tree), ``parent`` (``_parent/``, a ``git archive``
       of the parent commit) or the name of a directory under
       ``_scratch/`` that holds a ``pallas_kernels.py`` (a snapshot)
SHAPE  ``laguna`` (48 over 8, 128 | 128), ``solar`` (64 over 8), ``keye``
       (32 over 4), ``latent`` (32 heads, 192 | 128: kanana2 and xing4),
       ``axk2`` (64 heads, 192 | 128); ``tiny``/``tinyl`` (a CPU
       rehearsal of the script, never a number)
T      rows of the bucket
BLOCK  the largest block of ``_CAUSAL_BLOCKS`` the walk may take (a sweep;
       the change's side only)
"""
import importlib.util
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import trace_read
from benchmark.costs import peaks

N_CALLS = int(os.environ.get("N_CALLS", "8"))

SHAPES = {
    # query heads, KV heads, qk, dv
    "laguna": (48, 8, 128, 128),
    "solar": (64, 8, 128, 128),
    "keye": (32, 4, 128, 128),
    "latent": (32, 32, 192, 128),
    "axk2": (64, 64, 192, 128),
    "tiny": (4, 2, 128, 128),
    "tinyl": (4, 4, 192, 128),
}


def load_module(side):
    path = {"parent": os.path.join(ROOT, "_parent", "flexflow_tpu", "ops", "pallas_kernels.py"),
            "change": os.path.join(ROOT, "flexflow_tpu", "ops", "pallas_kernels.py")}.get(
                side, os.path.join(ROOT, "_scratch", side, "pallas_kernels.py"))
    name = f"pk_{side}_{len(sys.modules)}"      # one module a spec: a block override is its own
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def oracle_group(q, k, v, scale, strip):
    """Causal attention of one KV head's query heads ``q`` (g, t, qk)
    over ``k`` (t, qk), ``v`` (t, dv), ``strip`` query rows at a time."""
    g, t, _ = q.shape
    qs = q.astype(jnp.float32).reshape(g, t // strip, strip, -1).transpose(1, 0, 2, 3)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)

    def rows(args):
        i, x = args
        s = jnp.einsum("gqd,td->gqt", x, kf, precision="highest") * scale
        at = i * strip + jnp.arange(strip)
        s = jnp.where(jnp.arange(t)[None, :] <= at[:, None], s, -jnp.inf)
        return jnp.einsum("gqt,td->gqd", jax.nn.softmax(s, axis=-1), vf,
                          precision="highest")

    out = lax.map(rows, (jnp.arange(t // strip), qs))
    return out.transpose(1, 0, 2, 3).reshape(g, t, -1)


def run(spec):
    side, shape, t, *rest = spec.split(":")
    t = int(t)
    pk = load_module(side)
    if rest:
        pk._CAUSAL_BLOCKS = tuple(b for b in pk._CAUSAL_BLOCKS if b <= int(rest[0]))
    h, h_kv, qk, dv = SHAPES[shape]
    group, scale, dt = h // h_kv, qk ** -0.5, jnp.bfloat16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(t), 3)
    q = jax.random.normal(kq, (1, h, t, qk), dt)
    k = jax.random.normal(kk, (1, h_kv, t, qk), dt)
    v = jax.random.normal(kv, (1, h_kv, t, dv), dt)
    walk = pk.flash_uneven_walk(q.shape, h_kv, dv, dt) if hasattr(pk, "flash_uneven_walk") \
        else (pk._prefill_block(t), 1, 1)

    call = jax.jit(lambda q, k, v: pk.flash_fwd_uneven(q, k, v, scale))
    got = call(q, k, v)
    errs = []
    for j in sorted({0, h_kv - 1}):             # the first and the last KV head's query heads
        want = jax.jit(oracle_group, static_argnums=(3, 4))(
            q[0, j * group:(j + 1) * group], k[0, j], v[0, j], scale, min(t, 512))
        errs.append(float(jnp.max(jnp.abs(
            got[0, j * group:(j + 1) * group].astype(jnp.float32) - want))))
        del want
    finite = bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    print(f"    check {spec}: walk {walk}; max |out - oracle| {max(errs):.4g} "
          f"(bf16 out: one step near 1 is 0.0078); finite {finite}", flush=True)
    del got

    def body(_, c):
        q, out = c
        # The next call's queries follow the last call's output: nothing
        # in front of the kernel is loop invariant.
        q = q + (out[..., :1] * 1e-3).astype(dt)
        return q, pk.flash_fwd_uneven(q, k, v, scale)

    loop = jax.jit(lambda q: lax.fori_loop(
        0, N_CALLS, body, (q, jnp.zeros((1, h, t, dv), dt))))
    _, out = loop(q)
    jax.device_get(out[0, 0, 0, :2])
    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        _, out = loop(q)
        jax.device_get(out[0, 0, 0, :2])
        walls.append(time.perf_counter() - t0)
    tdir = os.path.join(ROOT, ".bench_scratch", "kernel_trace", spec.replace(":", "_"))
    jax.profiler.start_trace(tdir)
    _, out = loop(q)
    jax.device_get(out[0, 0, 0, :2])
    jax.profiler.stop_trace()
    ops = trace_read.load(trace_read.find_xplane(tdir)).devices[0]
    kern, n = trace_read.op_seconds(ops, [r"ff_flash_fwd_uneven"])
    top = trace_read.top_ops(ops, 4)
    flops = 2.0 * (qk + dv) * h * t * (t + 1) / 2
    per = kern / max(n, 1)
    kind = jax.devices()[0].device_kind
    peak = peaks.PEAKS[kind]["bf16_flops"] if kind in peaks.PEAKS else float("nan")
    print(f"=== {spec}: walk {walk}; wall/call {min(walls) / N_CALLS * 1e3:.4f} ms; "
          f"kernel {per * 1e6:.1f} us x {n}; {flops / per / 1e12 if per else 0:.2f} TF/s "
          f"({flops / per / peak * 100 if per else 0:.2f}% of peak)", flush=True)
    print("    top:", [(name, round(sec / N_CALLS * 1e6, 1)) for name, sec in top], "us a call", flush=True)


if __name__ == "__main__":
    print("device", jax.devices()[0].platform, jax.devices()[0].device_kind, flush=True)
    specs = [w for a in sys.argv[1:]
             for w in (open(os.path.join(ROOT, a[1:])).read().split() if a.startswith("@") else [a])]
    for spec in specs:
        try:
            run(spec)
        except Exception as e:  # one refused variant does not end a sweep
            print(f"=== {spec}: FAILED {type(e).__name__}: {str(e)[:1500]}", flush=True)
