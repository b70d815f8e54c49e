"""``ff_flash_decode`` alone on the attached chip, one or two trees side
by side (PERF.md §6 PR 41; .claude/skills/verify/SKILL.md).

Each spec is first checked against a plain jnp oracle ON THE CHIP (the
output, and the caches equal to one written column a slot, bit for
bit), then timed: forty dependent calls in one jitted ``fori_loop``
(the caches carried and donated, so the aliasing holds), run once more
under ``jax.profiler`` and read with ``benchmark/trace_read.py``: the
kernel's device time a call, and everything the program runs a call
(the glue XLA puts in front of the kernel too).  One process, so the
chip is held once.

    python3 tools/time_decode_kernel.py SIDE:SHAPE:LENGTHS[:CHUNK:RING] ... | @file

SIDE     ``change`` (this tree), ``parent`` (``_parent/``, a ``git archive``
         of the parent commit) or the name of a directory under
         ``_scratch/`` that holds a ``pallas_kernels.py`` (a snapshot: a
         queued chip call copies the tree when it starts, not when it
         was asked for)
SHAPE    ``gpt2`` (48, 1024, 16, 64) bf16; ``solar`` (32, 32768, 8, 128) bf16,
         8 query heads a cached head, positions last; ``lfm2`` (192, 3072,
         8, 64) bf16, 4 query heads a cached head; ``tiny``/``tinyg``/
         ``tinyf`` (a CPU rehearsal of the script, never a number)
LENGTHS  ``ones`` | ``full`` | ``half`` | ``mix`` (the cell's: its occupancy's
         share of the slots hold the backlog's first requests half way
         through their budgets, the others idle) | a number
CHUNK:RING  override ``flash_decode_chunk`` and ``_DECODE_RING`` (a sweep)
"""
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import trace_read, workload_gen

N_CALLS = int(os.environ.get("N_CALLS", "40"))

SHAPES = {
    # slots, max_seq, kv heads, hd, group, positions_last, traffic file
    "gpt2": (48, 1024, 16, 64, 1, False, "closed48"),
    "solar": (32, 32768, 8, 128, 8, True, "closed32.p4k-31k"),
    "lfm2": (192, 3072, 8, 64, 4, False, "closed192.p256-2k"),
    "tiny": (6, 512, 4, 64, 1, False, "closed48"),
    "tinyg": (4, 1024, 2, 128, 4, True, "closed32.p4k-31k"),
    "tinyf": (6, 1024, 4, 64, 4, False, "closed192.p256-2k"),
}


def load_module(side):
    path = {"parent": os.path.join(ROOT, "_parent", "flexflow_tpu", "ops", "pallas_kernels.py"),
            "change": os.path.join(ROOT, "flexflow_tpu", "ops", "pallas_kernels.py")}.get(
                side, os.path.join(ROOT, "_scratch", side, "pallas_kernels.py"))
    spec = importlib.util.spec_from_file_location(f"pk_{side}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def mix_lengths(shape):
    """The cell's mix: its occupancy's share of the slots hold the first
    requests of the backlog half way through their budgets; the others
    are idle (dispatched at position 0..7: length 4)."""
    b, s, *_, traffic = SHAPES[shape]
    mix = json.load(open(os.path.join(ROOT, "benchmark", "traffic", traffic + ".json")))
    n = int(round(mix["requests_per_second"] * 30))
    reqs = workload_gen.closed_backlog(mix, n, 1, 16)
    # Mean occupancy of each cell's window (PERF.md §2).
    occupancy = {"closed48": 0.67, "closed192.p256-2k": 0.566}.get(traffic, 0.377)
    active = int(round(b * occupancy))
    lens = [min(len(r["prompt"]) * s // mix["max_seq"] + r["max_new_tokens"] // 2, s)
            for r in reqs[:active]]
    lens += [4] * (b - active)
    rng = np.random.default_rng(7)
    return rng.permutation(np.asarray(lens, np.int32))


def lengths_of(shape, kind):
    b, s = SHAPES[shape][:2]
    if kind == "ones":
        return np.ones(b, np.int32)
    if kind == "full":
        return np.full(b, s, np.int32)
    if kind == "half":
        return np.full(b, s // 2, np.int32)
    if kind == "mix":
        return mix_lengths(shape)
    return np.full(b, int(kind), np.int32)


def run(spec):
    side, shape, kind, *rest = spec.split(":")
    pk = load_module(side)
    if rest:
        chunk, ring = int(rest[0]), int(rest[1])
        pk.flash_decode_chunk = lambda *a, **k: chunk
        pk._DECODE_RING = ring
    b, s, h, hd, group, last, _ = SHAPES[shape]
    dt = jnp.bfloat16
    lens = lengths_of(shape, kind)
    key = jax.random.PRNGKey(0)
    kq, kk, kv, kc = jax.random.split(key, 4)
    q0 = jax.random.normal(kq, (b, h * group, hd), dt)
    k0 = jax.random.normal(kk, (b, h, hd), dt)
    v0 = jax.random.normal(kv, (b, h, hd), dt)
    cshape = (b, h, hd, s) if last else (b, s, h, hd)
    mk = jax.jit(lambda k: (jax.random.normal(k, cshape, jnp.float32) * 0.5).astype(dt))
    ck, cv = mk(kc), mk(kq)
    lengths = jnp.asarray(lens)

    # One call against a plain oracle first: the output and the written
    # column, and nothing else of the caches moved.
    def oracle(q, k1, v1, ck, cv, lengths):
        kt = ck if last else ck.transpose(0, 2, 3, 1)            # (b, h, hd, s)
        vt = cv if last else cv.transpose(0, 2, 3, 1)
        at = (jnp.arange(s)[None, :] == (lengths - 1)[:, None])[:, None, None, :]
        kt = jnp.where(at, k1[..., None], kt)
        vt = jnp.where(at, v1[..., None], vt)
        qg = q.reshape(b, h, group, hd).astype(jnp.float32)
        sc = jnp.einsum("bhgd,bhds->bhgs", qg, kt.astype(jnp.float32)) / np.sqrt(hd)
        sc = jnp.where((jnp.arange(s)[None, :] < lengths[:, None])[:, None, None, :], sc, -1e30)
        o = jnp.einsum("bhgs,bhds->bhgd", jax.nn.softmax(sc, axis=-1), vt.astype(jnp.float32))
        back = (lambda x: x) if last else (lambda x: x.transpose(0, 3, 1, 2))
        return o.reshape(b, h * group, hd), back(kt), back(vt)

    want = jax.jit(oracle)(q0, k0, v0, ck, cv, lengths)
    got = jax.jit(lambda *a: pk.flash_decode(*a, positions_last=last))(q0, k0, v0, ck, cv, lengths)
    err = float(jnp.max(jnp.abs(got[0].astype(jnp.float32) - want[0])))
    same = bool(jnp.array_equal(got[1], want[1])) and bool(jnp.array_equal(got[2], want[2]))
    print(f"    check {spec}: max |out - oracle| {err:.4g}; caches equal the oracle's: {same}", flush=True)
    del want, got

    def body(_, c):
        ck, cv, out = c
        # Operands that follow the last call's output: nothing in front
        # of the kernel is loop invariant.
        eps = (out * 1e-3).astype(dt)
        o, ck, cv = pk.flash_decode(q0 + eps, k0 + eps[:, :h], v0 + eps[:, :h],
                                    ck, cv, lengths, positions_last=last)
        return ck, cv, o

    loop = jax.jit(
        lambda ck, cv: lax.fori_loop(0, N_CALLS, body, (ck, cv, jnp.zeros_like(q0))),
        donate_argnums=(0, 1))
    ck, cv, out = loop(ck, cv)
    jax.device_get(out[0, 0, :2])
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        ck, cv, out = loop(ck, cv)
        jax.device_get(out[0, 0, :2])
        walls.append(time.perf_counter() - t0)
    tdir = os.path.join(ROOT, ".bench_scratch", "kernel_trace", spec.replace(":", "_"))
    jax.profiler.start_trace(tdir)
    ck, cv, out = loop(ck, cv)
    jax.device_get(out[0, 0, :2])
    jax.profiler.stop_trace()
    tr = trace_read.load(trace_read.find_xplane(tdir))
    ops = tr.devices[0]
    kern, n = trace_read.op_seconds(ops, [r"ff_flash_decode"])
    top = trace_read.top_ops(ops, 8)
    total = sum(sec for _, sec in top)
    print(f"=== {spec}: lens mean {lens.mean():.1f} min {lens.min()} max {lens.max()}; "
          f"wall/call {min(walls) / N_CALLS * 1e3:.4f} ms; kernel {kern / max(n, 1) * 1e3:.4f} ms x {n}; "
          f"all device ops/call {total / N_CALLS * 1e3:.4f} ms", flush=True)
    print("    top:", [(name, round(sec / N_CALLS * 1e6, 2)) for name, sec in top], "us a call", flush=True)
    del ck, cv, out


if __name__ == "__main__":
    print("device", jax.devices()[0].platform, jax.devices()[0].device_kind, flush=True)
    for shape in ("gpt2", "solar", "lfm2"):
        lens = mix_lengths(shape)
        s = SHAPES[shape][1]
        print(f"mix {shape}: mean {lens.mean():.1f} ({lens.mean() / s * 100:.2f}% live); fetched at 128/256/512: "
              + ", ".join(f"{(-(-lens // g) * g).mean() / s * 100:.2f}%" for g in (128, 256, 512))
              + f"; lens {sorted(lens.tolist())}", flush=True)
    specs = [w for a in sys.argv[1:]
             for w in (open(os.path.join(ROOT, a[1:])).read().split() if a.startswith("@") else [a])]
    for spec in specs:
        try:
            run(spec)
        except Exception as e:  # one refused variant does not end a sweep
            print(f"=== {spec}: FAILED {type(e).__name__}: {str(e)[:1500]}", flush=True)
