"""Round-4 flash-BACKWARD kernel variants, raced on the live chip.

The training step spends ~2.5x the forward's attention flops in the
dq/dkv kernels, which carry the same per-block width-1 lane-broadcast
pattern (``exp(s - lse)`` with lse at (bq, 1)) the forward race probes.
Variants:

  b1_prod    the production _bwd_call kernels (control)
  b2_lanes   lse/delta staged at 128-lane width; subtract via jnp.tile

Both run the kernels DIRECTLY (no custom-vjp wrapper): the chain step
is (dq, dk, dv) = bwd(q, ...) with dq fed back as the next q — 2
dependent pallas calls per iteration, chains (2, 8) = 16 calls.

Usage: python tools/probe_flash_bwd_variants.py [b h t hd] [--blocks 256,512]
"""

import functools
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from flexflow_tpu.ops import pallas_kernels as pk

LANES = 128


def _dq_kernel_lanes(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                     *, block_k, causal, scale):
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    block_q, hd = q.shape
    reps = block_k // LANES
    # lse/delta carried at LSE_LANES(=8) lanes; widen once to 128 and
    # tile per block instead of broadcasting a width-1 column per pair.
    lse128 = jnp.tile(lse_ref[0, :, 0:1], (1, LANES))
    delta128 = jnp.tile(delta_ref[0, :, 0:1], (1, LANES))
    seq_k = k_ref.shape[1]
    num_kb = seq_k // block_k
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def make_body(masked):
        def body(kb, dq):
            k = k_ref[0, pl.ds(kb * block_k, block_k), :]
            v = v_ref[0, pl.ds(kb * block_k, block_k), :]
            s = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if masked:
                k_pos = kb * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(k_pos <= q_pos, s, -1e30)
            p = jnp.exp(s - (jnp.tile(lse128, (1, reps))
                             if reps > 1 else lse128))
            dp = lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - (jnp.tile(delta128, (1, reps))
                            if reps > 1 else delta128))
            return dq + lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        return body

    dq0 = jnp.zeros((block_q, hd), jnp.float32)
    if causal:
        full_upper = lax.div(qi * block_q, block_k)
        upper = jnp.minimum(
            lax.div((qi + 1) * block_q + block_k - 1, block_k), num_kb)
        dq = lax.fori_loop(0, full_upper, make_body(False), dq0)
        dq = lax.fori_loop(full_upper, upper, make_body(True), dq)
    else:
        dq = lax.fori_loop(0, num_kb, make_body(False), dq0)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel_lanes(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, *, block_q, causal, scale):
    ki = pl.program_id(1)
    k = k_ref[0]
    v = v_ref[0]
    block_k, hd = k.shape
    reps = block_k // LANES
    seq_q = q_ref.shape[1]
    num_qb = seq_q // block_q
    k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def make_body(masked):
        def body(qb, carry):
            dk, dv = carry
            q = q_ref[0, pl.ds(qb * block_q, block_q), :]
            do = do_ref[0, pl.ds(qb * block_q, block_q), :]
            lse128 = jnp.tile(
                lse_ref[0, pl.ds(qb * block_q, block_q), 0:1], (1, LANES))
            delta128 = jnp.tile(
                delta_ref[0, pl.ds(qb * block_q, block_q), 0:1], (1, LANES))
            s = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if masked:
                q_pos = qb * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                s = jnp.where(k_pos <= q_pos, s, -1e30)
            p = jnp.exp(s - (jnp.tile(lse128, (1, reps))
                             if reps > 1 else lse128))
            dv = dv + lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - (jnp.tile(delta128, (1, reps))
                            if reps > 1 else delta128))
            dk = dk + lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return dk, dv

        return body

    zeros = (
        jnp.zeros((block_k, hd), jnp.float32),
        jnp.zeros((block_k, hd), jnp.float32),
    )
    if causal:
        lower = lax.div(ki * block_k, block_q)
        first_full = jnp.clip(
            lax.div((ki + 1) * block_k + block_q - 2, block_q), lower, num_qb)
        carry = lax.fori_loop(lower, first_full, make_body(True), zeros)
        dk, dv = lax.fori_loop(first_full, num_qb, make_body(False), carry)
    else:
        dk, dv = lax.fori_loop(0, num_qb, make_body(False), zeros)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_call_lanes(q, k, v, do, lse, delta, causal, interpret):
    """pk._bwd_call with the lane-width kernels swapped in."""
    bh, t, hd = q.shape
    block_q = pk._require_block(t, hd, q.dtype.itemsize)
    block_k = block_q
    scale = 1.0 / math.sqrt(hd)
    L = pk.LSE_LANES
    full = pl.BlockSpec((1, t, hd), lambda b, i: (b, 0, 0))
    full_r = pl.BlockSpec((1, t, L), lambda b, i: (b, 0, 0))
    q_blocked = pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0))
    q_blocked_r = pl.BlockSpec((1, block_q, L), lambda b, i: (b, i, 0))
    k_blocked = pl.BlockSpec((1, block_k, hd), lambda b, i: (b, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel_lanes, block_k=block_k, causal=causal,
                          scale=scale),
        grid=(bh, t // block_q),
        in_specs=[q_blocked, full, full, q_blocked, q_blocked_r, q_blocked_r],
        out_specs=q_blocked,
        out_shape=jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel_lanes, block_q=block_q, causal=causal,
                          scale=scale),
        grid=(bh, t // block_k),
        in_specs=[full, k_blocked, k_blocked, full, full_r, full_r],
        out_specs=[k_blocked, k_blocked],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, hd), k.dtype),
            jax.ShapeDtypeStruct((bh, t, hd), v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def main():
    from probe_common import chain_slope_ms, parse_dims_blocks

    (b, h, t, hd), blocks = parse_dims_blocks(sys.argv[1:])

    import numpy as np
    interpret = jax.default_backend() != "tpu"
    key = jax.random.PRNGKey(0)
    shape = (b * h, t, hd)
    q, k, v, do = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.bfloat16) for i in range(4))
    # bwd flops (causal): dq (3 dots) + dkv (4 dots) over half the square.
    flops = 7.0 * b * h * t * t * hd

    for block in blocks:
        os.environ["FF_FLASH_BLOCK"] = str(block)
        import importlib
        importlib.reload(pk)  # re-read the block target
        o, lse = pk._fwd_call(q, k, v, True, interpret)
        delta = jnp.broadcast_to(
            jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1,
                    keepdims=True), (b * h, t, pk.LSE_LANES))

        variants = {
            "b1_prod": lambda x: pk._bwd_call(
                x, k, v, do, lse, delta, True, interpret),
            # Streamed 3D-grid dq/dkv (no resident K/V — the backward
            # half of the v6_stream formulation).
            "b3_stream": lambda x: pk._bwd_stream_call(
                x, k, v, do, lse, delta, True, interpret,
                block_q=block, block_k=block),
        }
        if block >= LANES:  # the lane-tile trick needs >= 128-wide blocks
            variants["b2_lanes"] = lambda x: _bwd_call_lanes(
                x, k, v, do, lse, delta, True, interpret)
        ref = None
        for name, fn in variants.items():
            try:
                out = jax.jit(fn)(q)
                jax.device_get(out[0].ravel()[:1])
                # Validate ALL THREE cotangents (dq, dk, dv) — a broken
                # dkv kernel must not win the race on a dq-only check.
                got = np.concatenate([
                    np.asarray(jax.device_get(o[0, :64]), np.float32)
                    for o in out
                ])
                if ref is None:
                    ref = got
                err = float(np.max(np.abs(got - ref)))

                def make_run(n, fn=fn):
                    @jax.jit
                    def run(x):
                        def body(_, x):
                            dq, dk, dv = fn(x)
                            return (dq + dk + dv).astype(x.dtype)
                        return lax.fori_loop(0, n, body, x)
                    return run

                # 2 pallas calls/iter -> 16-call chain max (cap <= 24).
                ms = chain_slope_ms(make_run, q, 2, 8)
                print(f"block {block:4d} {name:8s}: {ms:7.2f} ms "
                      f"({flops / (ms * 1e-3) / 1.97e14 * 100:4.1f}% peak) "
                      f"maxerr {err:.3g}", flush=True)
            except Exception as e:
                msg = str(e).split("\n")[0][:200]
                print(f"block {block:4d} {name:8s}: FAIL "
                      f"{type(e).__name__}: {msg}", flush=True)


if __name__ == "__main__":
    main()
