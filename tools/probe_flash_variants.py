"""Round-4 flash-forward kernel variants, raced on the live chip.

The round-3/4 sweeps put the production flash forward at 2.4-4.8% of
bf16 peak with a strong block-size dependence — evidence the per-block
VPU work (width-1 lane broadcasts of m/l, streaming corrections,
cross-lane reduces), not the raw exp count, is the ceiling.  Each
variant below isolates one remedy; the winner gets folded into
``ops/pallas_kernels.py``:

  v1_base     the production streaming kernel (control)
  v2_lanes    m/l carried at 128-lane width; subtract via jnp.tile
              (the lane-broadcast idiom from the public JAX TPU flash
              kernel, flash_attention.py:439-453)
  v3_twopass  s staged in a VMEM scratch; pass 1 dots+rowmax only,
              pass 2 exp+sum+p@v — no streaming corrections at all
  v4_fullrow  single-step softmax over the whole (masked) row; trades
              2x dot/exp flops above the diagonal for zero streaming
              machinery and one reduce per row

Usage (one process; jitted-chain timing, one fence per measurement):
    python tools/probe_flash_variants.py [b h t hd] [--blocks 256,512]
"""

import functools
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
LANES = 128


# ---------------------------------------------------------------------------
# v2: 128-lane m/l carries
# ---------------------------------------------------------------------------


def _v2_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k, causal, scale):
    qi = pl.program_id(1)
    q = q_ref[0]
    block_q, hd = q.shape
    seq_k = k_ref.shape[1]
    num_kb = seq_k // block_k
    reps = block_k // LANES
    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    m0 = jnp.full((block_q, LANES), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, LANES), jnp.float32)
    acc0 = jnp.zeros((block_q, hd), jnp.float32)

    def make_body(masked):
        def body(kb, carry):
            m, l, acc = carry
            k = k_ref[0, pl.ds(kb * block_k, block_k), :]
            v = v_ref[0, pl.ds(kb * block_k, block_k), :]
            s = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if masked:
                k_pos = kb * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)      # (bq, 1)
            m_new = jnp.maximum(m, m_cur)                   # (bq, LANES)
            p = jnp.exp(s - jnp.tile(m_new, (1, reps))
                        if reps != 1 else s - m_new)
            corr = jnp.exp(m - m_new)                       # (bq, LANES)
            acc = acc * corr[:, :hd] + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            return m_new, l, acc

        return body

    if causal:
        full_upper = lax.div(qi * block_q, block_k)
        upper = jnp.minimum(
            lax.div((qi + 1) * block_q + block_k - 1, block_k), num_kb)
        carry = lax.fori_loop(0, full_upper, make_body(False), (m0, l0, acc0))
        m, l, acc = lax.fori_loop(full_upper, upper, make_body(True), carry)
    else:
        m, l, acc = lax.fori_loop(0, num_kb, make_body(False), (m0, l0, acc0))
    o_ref[0] = (acc / l[:, :hd]).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# v3: two-pass over a VMEM s-scratch (no streaming corrections)
# ---------------------------------------------------------------------------


def _v3_kernel(q_ref, k_ref, v_ref, o_ref, s_scr, *, block_k, causal, scale):
    qi = pl.program_id(1)
    q = q_ref[0]
    block_q, hd = q.shape
    seq_k = k_ref.shape[1]
    num_kb = seq_k // block_k
    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def score(kb, masked):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if masked:
            k_pos = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        return s

    def pass1(masked):
        def body(kb, m):
            s = score(kb, masked)
            s_scr[pl.ds(0, block_q), pl.ds(kb * block_k, block_k)] = s
            return jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        return body

    def pass2(kb, carry):
        l, acc = carry
        s = s_scr[pl.ds(0, block_q), pl.ds(kb * block_k, block_k)]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        p = jnp.exp(s)                                      # s pre-shifted
        l = l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return l, acc

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    if causal:
        full_upper = lax.div(qi * block_q, block_k)
        upper = jnp.minimum(
            lax.div((qi + 1) * block_q + block_k - 1, block_k), num_kb)
    else:
        full_upper = num_kb
        upper = num_kb
    m = lax.fori_loop(0, full_upper, pass1(False), m0)
    m = lax.fori_loop(full_upper, upper, pass1(True), m)

    # Shift s once in scratch so pass 2 is a bare exp (saves the
    # per-block broadcast-subtract of m).
    def shift(kb, _):
        s_scr[pl.ds(0, block_q), pl.ds(kb * block_k, block_k)] = (
            s_scr[pl.ds(0, block_q), pl.ds(kb * block_k, block_k)] - m
        )
        return 0

    lax.fori_loop(0, upper, shift, 0)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, hd), jnp.float32)
    l, acc = lax.fori_loop(0, upper, pass2, (l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# v4: single-step full-row softmax (full rectangle, one reduce)
# ---------------------------------------------------------------------------


def _v4_kernel(q_ref, k_ref, v_ref, o_ref, *, causal, scale):
    qi = pl.program_id(1)
    q = q_ref[0]
    block_q, hd = q.shape
    k = k_ref[0]                                            # (t, hd)
    v = v_ref[0]
    t = k.shape[0]
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                               # (bq, t)
    if causal:
        q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, t), 0)
        k_pos = lax.broadcasted_iota(jnp.int32, (block_q, t), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0] = (acc / l).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------


def _call(kernel_factory, q, k, v, block_q, scratch=None):
    bh, t, hd = q.shape
    full = pl.BlockSpec((1, t, hd), lambda b, i: (b, 0, 0))
    blocked = pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0))
    return pl.pallas_call(
        kernel_factory,
        grid=(bh, t // block_q),
        in_specs=[blocked, full, full],
        out_specs=blocked,
        out_shape=jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
        scratch_shapes=scratch or [],
        interpret=jax.default_backend() != "tpu",
    )(q, k, v)


def variants(t, hd, block_q, block_k, dtype):
    scale = 1.0 / math.sqrt(hd)

    def v1(q, k, v):
        from flexflow_tpu.ops import pallas_kernels as pk
        bh, tt, dd = q.shape
        unfold = lambda x: x.reshape(1, bh, tt, dd)
        return pk.flash_attention(
            unfold(q), unfold(k), unfold(v), True).reshape(bh, tt, dd)

    def v2(q, k, v):
        return _call(
            functools.partial(_v2_kernel, block_k=block_k, causal=True,
                              scale=scale), q, k, v, block_q)

    def v3(q, k, v):
        return _call(
            functools.partial(_v3_kernel, block_k=block_k, causal=True,
                              scale=scale), q, k, v, block_q,
            scratch=[pltpu.VMEM((block_q, t), jnp.float32)])

    def v4(q, k, v):
        return _call(
            functools.partial(_v4_kernel, causal=True, scale=scale),
            q, k, v, block_q)

    def v5_stock(q, k, v):
        # The yardstick (VERDICT r5 item 2): jax's own TPU pallas flash
        # kernel at default block sizes.  TPU-only (no interpret path);
        # the harness's per-variant try/except reports it as FAIL on
        # CPU smoke runs.
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as stock_flash,
        )

        bh, tt, dd = q.shape
        unfold = lambda x: x.reshape(1, bh, tt, dd)
        return stock_flash(
            unfold(q), unfold(k), unfold(v), causal=True, sm_scale=scale
        ).reshape(bh, tt, dd)

    def v6_stream(q, k, v):
        # Streamed 3D-grid formulation (no resident K/V, no VMEM cap
        # on t): K/V blocks arrive via pipelined BlockSpecs; softmax
        # state persists in scratch across the sequential k dimension.
        from flexflow_tpu.ops import pallas_kernels as pk

        bh, tt, dd = q.shape
        unfold = lambda x: x.reshape(1, bh, tt, dd)
        return pk.flash_attention_lse_streamed(
            unfold(q), unfold(k), unfold(v), True,
            block_q=block_q, block_k=block_k,
        )[0].reshape(bh, tt, dd)

    # NOTE: the chunked-decomposition candidate is deliberately NOT in
    # this race: at chunk=256/t=2048 it issues 36 dependent pallas
    # launches per call.  It races at the fused-train-step level
    # instead, via FF_FLASH_FORCE_CHUNK in tools/profile_lm_decomp.py.
    return {"v1_base": v1, "v2_lanes": v2, "v3_twopass": v3,
            "v4_fullrow": v4, "v5_stock": v5_stock, "v6_stream": v6_stream}


def main():
    from probe_common import chain_slope_ms, parse_dims_blocks

    (b, h, t, hd), blocks = parse_dims_blocks(sys.argv[1:])

    import numpy as np
    key = jax.random.PRNGKey(0)
    shape = (b * h, t, hd)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.bfloat16) for i in range(3))
    flops = 2.0 * b * h * t * t * hd  # causal fwd (2 dots, half the square)

    ref = None
    import time
    for block in blocks:
        for name, fn in variants(t, hd, block, block, jnp.bfloat16).items():
            if name in ("v4_fullrow", "v5_stock") and block != blocks[0]:
                continue  # block-size independent (stock picks its own)
            if name == "v2_lanes" and block < LANES:
                continue  # the lane-tile trick needs >= 128-wide blocks
            try:
                jfn = jax.jit(fn)
                out = jfn(q, k, v)
                jax.device_get(out.ravel()[:1])
                got = np.asarray(
                    jax.device_get(out[0, : min(64, t)]), np.float32)
                if ref is None:
                    ref = got
                err = float(np.max(np.abs(got - ref)))

                # Two-point jitted-chain timing: a single call carries
                # the host's dispatch cost.  One jit'd dependent chain
                # x = f(x) of length N is ONE dispatch; the (N2 - N1)
                # slope cancels both dispatch and the fixed in-chain
                # overheads.
                def make_run(n, fn=fn):
                    @jax.jit
                    def run(x):
                        def body(_, x):
                            return fn(x, k, v).astype(x.dtype)
                        return lax.fori_loop(0, n, body, x)
                    return run

                ms = chain_slope_ms(make_run, q, 4, 16)
                print(f"block {block:4d} {name:10s}: {ms:7.2f} ms "
                      f"({flops / (ms * 1e-3) / 1.97e14 * 100:4.1f}% peak) "
                      f"maxerr {err:.3g}", flush=True)
            except Exception as e:
                msg = str(e).split("\n")[0][:200]
                print(f"block {block:4d} {name:10s}: FAIL "
                      f"{type(e).__name__}: {msg}", flush=True)


if __name__ == "__main__":
    main()
