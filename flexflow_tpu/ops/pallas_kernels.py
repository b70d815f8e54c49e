"""Pallas TPU kernels for the hot ops.

The reference's leaf tasks are hand-written CUDA (cuDNN calls plus
custom kernels, e.g. ``src/ops/*.cu``, ``nmt/*.cu``).  On TPU the MXU
path (matmul/conv) belongs to XLA; what deserves hand kernels is the
memory-bound fused attention inner loop, where a blocked
flash-attention kernel keeps the T×T score matrix out of HBM entirely
(VMEM-resident blocks, streaming log-sum-exp) — the TPU counterpart of
the reference fusing softmax+loss into one kernel
(``src/ops/softmax.cu:91-160``).

``flash_attention`` is a full custom-VJP op: forward and both backward
kernels are Pallas, with f32 accumulation regardless of input dtype.
On non-TPU backends the same kernels run under the Pallas interpreter,
so the unit tests exercise the identical code path the chip runs.

Every ``pallas_call`` carries a ``name=`` from
``obs/events.py::KERNEL_CATALOG`` (fflint FF008): it is the custom
call's instruction name in a TPU trace (``%ff_flash_fwd.24``), which is
how the per-kernel metrics find it whatever scope or jit encloses it.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# Per-query scalars (lse, delta) carry this many broadcast lanes so
# their pallas blocks meet the TPU tiling constraints.
LSE_LANES = 8
#: Lanes of a vector register, the minor edge of the chip's (8, 128) tile.
_LANES = 128


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _mxu_precision(dtype):
    """The ``precision`` of a kernel's matrix product on operands of
    ``dtype``: below float32 the unit's own (named, so that an ambient
    ``jax.default_matmul_precision("highest")`` -- chip_smoke's recount
    of a flipped token -- does not ask Mosaic for a float32 contraction
    of bfloat16 operands, which it refuses); float32 operands take what
    the caller's context asks."""
    return None if jnp.dtype(dtype).itemsize >= 4 else lax.Precision.DEFAULT


def _pick_block(t: int, target: int) -> int:
    """Largest divisor of ``t`` <= target that satisfies the TPU block
    rule (multiple of 8, or the whole dim).  0 if none exists."""
    if t <= target:
        return t
    b = target
    while b >= 8:
        if t % b == 0 and b % 8 == 0:
            return b
        b -= 8
    return 0


def _vmem_block_cap(t: int, hd: int, itemsize: int) -> int:
    """Largest block edge for ``(t, hd, itemsize)``, keyed on ``t``, the
    bytes of an operand's row and the size of one resident (t, hd)
    operand, ``u = t*hd*itemsize``:

        t <= 2048 in whole lane tiles, at most 256 bytes a row of the
          operand (bf16 at hd <= 128, f32 at hd 64): block 1024.  Up to
          t 1024 the whole sequence is one block: no streaming loop, no
          rescaling, one static walk a head.
        u <= 1M (bf16 t <= 8192 / f32 t <= 4096 at hd 64): block 512.
        u = 2M (bf16 t=16384, f32 t=8192): unsupported; such shapes
          belong on the chunked launches or ring attention.

    A bigger block streams more rows past each set of matrix-unit
    weights and pays a grid step's prologue and epilogue less often.
    v5e, bf16 causal, ms a call forward / dq / dkv by block (my chip
    runs, PR 34; PERF.md section 6 has every reading):
    (bh 128, t 1024, hd 64) 1024: 0.352 / 0.374 / 0.494, 512: 0.429 /
    0.440 / 0.507, 256: 1.015 / 0.724 / 0.713;
    (64, 2048, 64) 1024: 0.585 / 0.634 / 0.824, 512: 0.731 / 0.710 /
    0.901; (64, 2048, 128) 1024: 0.586 / 0.626 / 0.819, 512: 0.723 /
    0.702 / 0.892, 256: 1.834 / 1.172 / 1.159;
    (16, 8192, 64) 512: 2.509 / 2.469 / 3.216, 256: 6.731 / 4.148 /
    4.073, 1024 runs out of scoped VMEM (16.5M of 16M);
    (16, 4096, 128) 512: 0.672 / 0.681 / 0.833, 256: 1.736 / 1.114 /
    1.071; f32 (64, 1024, 64) 1024: 0.178 / 0.202 / 0.241, 512: 0.211
    / 0.231 / 0.294; f32 (16, 4096, 64) 512: 0.632 / 0.650 / 0.870,
    256: 1.867 / 1.058 / 1.088.  f32 at t 2048 takes 1024 by the rule
    and compiles for the described chip; it was not timed.

    Read at hd 64 and 128; the caps shrink proportionally for larger
    head dims (unmeasured territory must fail toward smaller blocks,
    not Mosaic compile errors)."""
    u = t * hd * itemsize

    def scaled(cap: int) -> int:
        return max(8, (cap * _LANES // max(hd, _LANES)) // 8 * 8)

    if t <= 2048 and t % _LANES == 0 and hd * itemsize <= 256:
        return scaled(1024)
    if u <= 1024 * 1024:
        return scaled(512)
    return 0


def _flash_block(t: int, hd: int, itemsize: int) -> int:
    """Block edge for the flash kernels at (t, hd): the VMEM cap
    intersected with the divisor/alignment rule.  0 if no legal block
    exists (callers gate on flash_supported)."""
    cap = _vmem_block_cap(t, hd, itemsize)
    return _pick_block(t, cap) if cap >= 8 else 0


def _require_block(t: int, hd: int, itemsize: int) -> int:
    """``_flash_block`` for callers already committed to the kernel:
    raises the clear error instead of launching Mosaic with an
    unsupported block (the ``flash_supported`` gate, enforced)."""
    block = _flash_block(t, hd, itemsize)
    if block < 8 or t < 16:
        raise ValueError(
            f"flash attention needs seq >= 16 with a block divisor that "
            f"is a multiple of 8, <= 1024 and within the VMEM "
            f"budget; got t={t}, hd={hd}. Gate callers on "
            f"flash_supported()."
        )
    return block


def flash_supported(shape: Tuple[int, ...], dtype=jnp.float32) -> bool:
    """Whether the blocked kernel applies to (b, h, t, hd) attention."""
    if len(shape) != 4:
        return False
    _, _, t, hd = shape
    if t < 16 or hd < 8:
        return False
    return _flash_block(t, hd, jnp.dtype(dtype).itemsize) >= 8


# ---------------------------------------------------------------------------
# the walk: which scores a call computes
# ---------------------------------------------------------------------------


def _diag_walk(block: int):
    """How a block that straddles the causal diagonal is visited: the
    edge of its square sub-blocks and the sub-blocks ``(row0, col0,
    masked)`` themselves, in block-local positions.  With ``block_q ==
    block_k`` such a block is always the same lower triangle, so the
    walk is static: sub-blocks of the lane tile (a block that is not
    whole lane tiles is its own sub-block), the ones below the diagonal
    unmasked, the ones on it under the one triangular mask, the ones
    above it not visited at all."""
    sub = _LANES if block % _LANES == 0 else block
    return sub, tuple(
        (r, c, r == c)
        for r in range(0, block, sub)
        for c in range(0, r + sub, sub)
    )


def _row_tile(rows: int) -> int:
    """Rows of scores normalised at a time: 32 rows of a 512-wide block
    are 16 of the 64 vector registers."""
    for r in (32, 16, 8):
        if rows % r == 0:
            return r
    return rows


def _tri_mask(x, first_row, lower):
    """``x``, rows ``first_row...`` of a square sub-block on the
    diagonal, with the scores the causal mask drops at ``_NEG_INF``:
    column <= row kept in a lower triangle, column >= row in ``dkv``'s
    transposed one."""
    row = first_row + lax.broadcasted_iota(jnp.int32, x.shape, 0)
    col = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((col <= row) if lower else (col >= row), x, _NEG_INF)


def _nt(a, b):
    """``a · bᵀ`` into float32 (contract the minor dimension of both)."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _nn(a, b):
    """``a · b`` into float32."""
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _cat(xs, axis):
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=axis)


def _visit(shape, lhs, rhs_refs, r0, tile_math, outs, first):
    """One block of scores, in one basic block of the kernel: the
    products that make them, the rows' arithmetic, the products that
    consume them.

    ``shape``: ``"full"`` (every score live), ``"lower"`` (the block
    straddles the diagonal; row >= column live) or ``"upper"`` (the
    same block seen by ``dkv``, whose rows are K positions: column >=
    row live).  ``lhs``: the resident operands ``(block, hd)``, one a
    score matrix; ``rhs_refs``: where their partners' rows ``r0...``
    lie; the first pair makes the scores the mask applies to.
    ``tile_math(at, c0, c1, *scores)`` turns a tile of rows
    ``at`` of every score matrix, columns ``[c0, c1)`` of the block,
    into the operand tiles of the consuming products; ``outs`` pairs
    each of those with ``(accumulator, rhs_ref)``.

    A straddling block goes by sub-block COLUMNS on the way in (a
    column is one set of matrix-unit weights, streamed by the rows that
    see it: rows above a sub-block's first live row take no part) and
    by sub-block ROWS from there on: a row's live columns are
    normalised at once and consumed in one product.  Values, not
    scratch: emitted a row tile at a time, a tile's chain runs from the
    matrix unit's result registers back into its operand registers, and
    what does not fit the 64 vector registers the compiler spills (an
    array-wide op at a time, the old order, kept 256 registers of
    scores alive through six passes: a store a bundle was the pace,
    PERF.md §6 PR 34)."""
    block = lhs[0].shape[0]
    if shape == "full":
        sub, cells = block, {(0, 0): False}
    else:
        # (row strip, column) -> masked, in sub-blocks; dkv sees the
        # walk transposed
        sub, walk = _diag_walk(block)
        cells = {((r // sub, c // sub) if shape == "lower"
                  else (c // sub, r // sub)): masked
                 for r, c, masked in walk}
    n = block // sub
    lower = shape != "upper"
    # scores by sub-block column: one product of the rows that see it
    first_seen = [min(i for i, jj in cells if jj == j) for j in range(n)]
    last_seen = [max(i for i, jj in cells if jj == j) for j in range(n)]
    cols = [[_nt(x[first_seen[j] * sub:(last_seen[j] + 1) * sub],
                 ref[0, pl.ds(r0 + j * sub, sub), :])
             for j in range(n)]
            for x, ref in zip(lhs, rhs_refs)]
    tile = _row_tile(sub)
    for i in range(n):
        live = [j for j in range(n) if (i, j) in cells]
        c0, c1 = live[0] * sub, (live[-1] + 1) * sub
        operands = []
        for first_row in range(0, sub, tile):
            row = i * sub + first_row
            scores = []
            for which, by_col in enumerate(cols):
                parts = []
                for j in live:
                    at = row - first_seen[j] * sub
                    x = by_col[j][at:at + tile]
                    if which == 0 and cells[i, j]:
                        x = _tri_mask(x, first_row, lower)
                    parts.append(x)
                scores.append(_cat(parts, 1))
            operands.append(
                tile_math(slice(row, row + tile), c0, c1, *scores))
        strip = slice(i * sub, (i + 1) * sub)
        for k, (acc, ref) in enumerate(outs):
            got = _nn(_cat([o[k] for o in operands], 0),
                      ref[0, pl.ds(r0 + c0, c1 - c0), :])
            if first:
                acc[strip, :] = got
            else:
                acc[strip, :] += got


def _block_order(causal, diag, i, n):
    """The blocks grid step ``i`` of ``n`` visits (``i`` a host integer
    or the kernel's program id): ``(shape, first, lo, hi)``, the shape
    and index of the block visited first, then the whole, unmasked
    blocks ``[lo, hi)``.  Causal: the block on the diagonal first, by
    its static walk (``diag``: ``"lower"``, or ``"upper"`` for
    ``dkv``), then the live ones off it: below it for a query block,
    after it for a K block; the dead ones not at all."""
    if not causal:
        return "full", 0, 1, n
    lo, hi = (0, i) if diag == "lower" else (i + 1, n)
    return diag, i, lo, hi


def _each_block(causal, diag, i, n, visit):
    """``_block_order``'s blocks through ``visit(shape, block, first)``.
    The first visit starts the accumulators, so nothing is zeroed; a
    sequence of one block has no loop."""
    shape, b0, lo, hi = _block_order(causal, diag, i, n)
    visit(shape, b0, True)
    if n > 1:
        lax.fori_loop(
            lo, hi, lambda b, c: (visit("full", b, False), c)[1], 0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, causal, scale):
    qi = pl.program_id(1)
    block = q_ref.shape[1]
    num_kb = k_ref.shape[1] // block
    # Products run in the INPUT dtype with f32 accumulation; the scale
    # is applied to the f32 scores, behind the product.
    q = q_ref[0]
    lanes = l_scr.shape[1]

    def row_sum(p):
        if lanes == 1:
            return jnp.sum(p, axis=-1, keepdims=True)
        # The row sum stays a lane-wide partial through the call
        # (elementwise adds); lanes merge once, at the end: one lane
        # reduction a tile (the max), not two.
        return functools.reduce(
            jnp.add, [p[:, c:c + lanes] for c in range(0, p.shape[1], lanes)])

    def softmax(first):
        def tile_math(at, c0, c1, s):
            s = s * scale
            m_new = jnp.max(s, axis=-1, keepdims=True)
            if first:
                # The first block a grid step visits starts the
                # streaming softmax: nothing to rescale.
                p = jnp.exp(s - m_new)
                l_scr[at, :] = row_sum(p)
            else:
                m = m_scr[at, :]
                m_new = jnp.maximum(m, m_new)
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m - m_new)
                l_scr[at, :] = l_scr[at, :] * corr + row_sum(p)
                acc_scr[at, :] = acc_scr[at, :] * corr
            m_scr[at, :] = m_new
            return (p.astype(q.dtype),)

        return tile_math

    def visit(shape, kb, first):
        _visit(shape, (q,), (k_ref,), pl.multiple_of(kb * block, block),
               softmax(first), ((acc_scr, v_ref),), first)

    _each_block(causal, "lower", qi, num_kb, visit)
    l = l_scr[...]
    if lanes > 1:
        l = jnp.sum(l, axis=-1, keepdims=True)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
    # lse is stored with a trailing lane dim of LSE_LANES (broadcast
    # copies) so its blocks satisfy the TPU (8, 128)-or-full tile rule.
    lse_ref[0] = jnp.broadcast_to(m_scr[...] + jnp.log(l), (block, LSE_LANES))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, causal, scale):
    qi = pl.program_id(1)
    block = q_ref.shape[1]
    num_kb = k_ref.shape[1] // block
    q = q_ref[0]
    do = do_ref[0]

    def tile_math(at, c0, c1, s, dp):
        p = jnp.exp(s * scale - lse_ref[0, at, 0:1])
        ds = p * (dp - delta_ref[0, at, 0:1])
        return (ds.astype(q.dtype),)

    def visit(shape, kb, first):
        _visit(shape, (q, do), (k_ref, v_ref),
               pl.multiple_of(kb * block, block),
               tile_math, ((dq_scr, k_ref),), first)

    _each_block(causal, "lower", qi, num_kb, visit)
    dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, causal, scale):
    """The K block's rows against every query block at or below it, on
    the TRANSPOSED scores ``k · qᵀ`` (``lse`` and ``delta`` come
    lane-major, a row of a query block's positions): ``pᵀ · do`` and
    ``dsᵀ · q`` are then plain products.  Contracting dimension 0 of a
    score tile made Mosaic transpose it on the cross-lane unit (128
    ``vxpose`` a block in the lowered kernel, PERF.md §6 PR 34)."""
    ki = pl.program_id(1)
    block = k_ref.shape[1]
    num_qb = q_ref.shape[1] // block
    k = k_ref[0]
    v = v_ref[0]

    def visit(shape, qb, first):
        def tile_math(at, c0, c1, s, dp):
            p = jnp.exp(s * scale - lse_ref[0, qb, :, c0:c1])
            ds = p * (dp - delta_ref[0, qb, :, c0:c1])
            return p.astype(k.dtype), ds.astype(k.dtype)

        _visit(shape, (k, v), (q_ref, do_ref),
               pl.multiple_of(qb * block, block),
               tile_math, ((dv_scr, do_ref), (dk_scr, q_ref)), first)

    _each_block(causal, "upper", ki, num_qb, visit)
    # ds . q still needs the ds/dk = scale . q factor.
    dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers (shapes folded to (bh, t, hd))
# ---------------------------------------------------------------------------


def _score_lanes(block: int) -> int:
    """Lanes the forward's running row sum is carried on."""
    return _LANES if block % _LANES == 0 else 1


def _fwd_call(q, k, v, causal, interpret):
    _, t, hd = q.shape
    return _fwd_launch(q, k, v, causal, interpret,
                       _require_block(t, hd, q.dtype.itemsize))


# The launches are jitted: one trace and one lowering a shape, however
# many layers call it (a kernel's walk is unrolled at trace time, a
# thousand operations at the training cell's shape, and an enclosing
# program re-lowers every un-jitted call site: gpt2-medium's step has
# 72 of them, twice).  The block is a static argument, so the cache's
# key carries what the table chose.
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _fwd_launch(q, k, v, causal, interpret, block):
    bh, t, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale)
    full = pl.BlockSpec((1, t, hd), lambda b, i: (b, 0, 0))
    blocked = pl.BlockSpec((1, block, hd), lambda b, i: (b, i, 0))
    return pl.pallas_call(
        kernel,
        grid=(bh, t // block),
        in_specs=[blocked, full, full],
        out_specs=[
            blocked,
            pl.BlockSpec((1, block, LSE_LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
            jax.ShapeDtypeStruct((bh, t, LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, 1), jnp.float32),            # m
            pltpu.VMEM((block, _score_lanes(block)), jnp.float32),  # l
            pltpu.VMEM((block, hd), jnp.float32),           # acc
        ],
        name="ff_flash_fwd",
        interpret=interpret,
    )(q, k, v)


def _bwd_call(q, k, v, do, lse, delta, causal, interpret):
    _, t, hd = q.shape
    return _bwd_launch(q, k, v, do, lse, delta, causal, interpret,
                       _require_block(t, hd, q.dtype.itemsize))


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _bwd_launch(q, k, v, do, lse, delta, causal, interpret, block):
    bh, t, hd = q.shape
    nb = t // block
    scale = 1.0 / math.sqrt(hd)
    static = dict(causal=causal, scale=scale)
    full = pl.BlockSpec((1, t, hd), lambda b, i: (b, 0, 0))
    blocked = pl.BlockSpec((1, block, hd), lambda b, i: (b, i, 0))
    blocked_r = pl.BlockSpec((1, block, LSE_LANES), lambda b, i: (b, i, 0))
    grad = pltpu.VMEM((block, hd), jnp.float32)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid=(bh, nb),
        in_specs=[blocked, full, full, blocked, blocked_r, blocked_r],
        out_specs=blocked,
        out_shape=jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
        scratch_shapes=[grad],
        name="ff_flash_dq",
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dkv walks the transposed scores: its per-query scalars lie along
    # the lanes, a row a query block (a slice and a reshape here: two
    # small relayouts a call, 1.3 ms of a gpt2-medium step's `copy`).
    def lane_major(x):
        return x[:, :, 0].reshape(bh, nb, 1, block)

    full_r = pl.BlockSpec((1, nb, 1, block), lambda b, i: (b, 0, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        grid=(bh, nb),
        in_specs=[full, blocked, blocked, full, full_r, full_r],
        out_specs=[blocked, blocked],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, hd), k.dtype),
            jax.ShapeDtypeStruct((bh, t, hd), v.dtype),
        ],
        scratch_shapes=[grad, grad],
        name="ff_flash_dkv",
        interpret=interpret,
    )(q, k, v, do, lane_major(lse), lane_major(delta))
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_lse(q, k, v, causal: bool = True,
                        interpret: Optional[bool] = None):
    """Blocked flash attention over (b, h, t, hd).

    Returns ``(out, lse)`` with ``lse = logsumexp(scores)`` per query —
    the pair ring attention merges across sequence chunks.  f32
    streaming-softmax accumulation; O(t) memory per (batch, head).
    ``interpret=None`` compiles on TPU and interprets elsewhere.
    """
    (o, lse), _ = _flash_fwd(q, k, v, causal, interpret)
    return o, lse


def _flash_fwd(q, k, v, causal, interpret):
    if interpret is None:
        interpret = _interpret_default()
    b, h, t, hd = q.shape
    fold = lambda x: x.reshape(b * h, t, hd)
    o, lse_l = _fwd_call(fold(q), fold(k), fold(v), causal, interpret)
    o = o.reshape(b, h, t, hd)
    lse = lse_l[:, :, 0].reshape(b, h, t)
    return (o, lse), (q, k, v, o, lse_l)


def _cotangent_delta_lanes(o, g_o, g_lse, b, h, t):
    """The backward's per-row ``delta = sum(o * do)``, the lse cotangent
    folded in (``d lse / d s = p``: it enters ``ds = p * (dp - delta)``
    as ``delta -= g_lse``), broadcast to the LSE_LANES layout."""
    delta = jnp.sum(o.astype(jnp.float32) * g_o.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32).reshape(b, h, t)
    return jnp.broadcast_to(
        delta.reshape(b * h, t)[:, :, None], (b * h, t, LSE_LANES)
    )


def _flash_bwd(causal, interpret, res, g):
    if interpret is None:
        interpret = _interpret_default()
    q, k, v, o, lse_l = res
    g_o, g_lse = g
    b, h, t, hd = q.shape
    fold = lambda x: x.reshape(b * h, t, hd)
    delta_l = _cotangent_delta_lanes(o, g_o, g_lse, b, h, t)
    dq, dk, dv = _bwd_call(
        fold(q), fold(k), fold(v), fold(g_o.astype(q.dtype)),
        lse_l, delta_l, causal, interpret
    )
    unfold = lambda x: x.reshape(b, h, t, hd)
    return unfold(dq), unfold(dk), unfold(dv)


flash_attention_lse.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    interpret: Optional[bool] = None):
    """Flash attention returning just the output (dense, non-ring use)."""
    return flash_attention_lse(q, k, v, causal, interpret)[0]


# ---------------------------------------------------------------------------
# chunked flash: sequences past the single-kernel VMEM cap
# ---------------------------------------------------------------------------


def merge_lse(o1, lse1, o2, lse2):
    """Combine two flash partials (o_i, lse_i) -> (o, lse).

    The streaming-softmax merge used between ring steps and sequence
    chunks: o_i (..., t, hd) f32, lse_i (..., t) f32 (-inf marks an
    empty contribution).
    """
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse)[..., None]
    w2 = jnp.exp(lse2 - lse)[..., None]
    return o1 * w1 + o2 * w2, lse


def _chunk_len(t: int, hd: int, itemsize: int) -> int:
    """Largest divisor of ``t`` that the single-launch kernel supports
    (VMEM-capped), or 0.  Chunks below 512 are pure overhead — such
    sequences either fit a single launch or are not worth chunking."""
    c = t
    while c >= 512:
        if t % c == 0 and _flash_block(c, hd, itemsize) >= 8:
            return c
        c //= 2
    return 0


def flash_chunked_supported(shape: Tuple[int, ...], dtype=jnp.float32) -> bool:
    """Whether ``flash_attention_lse_chunked`` applies: the shape is
    beyond the single-kernel cap but decomposes into supported
    sequence chunks."""
    if len(shape) != 4:
        return False
    _, _, t, hd = shape
    if hd < 8 or flash_supported(shape, dtype):
        return False
    return _chunk_len(t, hd, jnp.dtype(dtype).itemsize) > 0


#: Sequences past this length whose t the kernel paths cannot
#: decompose (non-power-of-two tails) stream through the jnp blocked
#: formulation instead of materializing a t x t score matrix.
_BLOCKED_MIN_T = 4096


def attention_lse_blocked(q, k, v, causal: bool = True,
                          block_q: int = 512, block_k: int = 512):
    """Pure-jnp streaming (flash-style) attention: (o, lse) like the
    Pallas kernels, O(t·block) memory, ANY sequence length (tails are
    padded and masked).  The long-context safety net for shapes no
    kernel formulation decomposes — q blocks ride ``lax.scan`` (one
    compiled body, not t/block unrolled copies), k/v stream through a
    ``fori_loop`` whose upper bound stops at the causal diagonal.
    Fully differentiable through XLA; the VJP re-streams the same
    blocks.  Reference lineage: the SP chunking this generalizes,
    ``rnn.h:21-23``."""
    b, h, t, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    nq = -(-t // block_q)
    nk = -(-t // block_k)
    tq_pad, tk_pad = nq * block_q, nk * block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, tq_pad - t), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, tk_pad - t), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, tk_pad - t), (0, 0)))
    # (nq, b, h, block_q, hd) for scan.
    qb = jnp.moveaxis(
        qp.reshape(b, h, nq, block_q, hd), 2, 0
    )

    def q_block(_, inp):
        qi, qidx = inp
        q_pos = qidx * block_q + jnp.arange(block_q)
        m0 = jnp.full((b, h, block_q, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, block_q, 1), jnp.float32)
        a0 = jnp.zeros((b, h, block_q, hd), jnp.float32)

        def body(j, mla):
            m, l, acc = mla
            kj = lax.dynamic_slice_in_dim(kp, j * block_k, block_k, 2)
            vj = lax.dynamic_slice_in_dim(vp, j * block_k, block_k, 2)
            s = jnp.einsum(
                "bhqd,bhkd->bhqk", qi, kj,
                preferred_element_type=jnp.float32,
            ) * scale
            k_pos = j * block_k + jnp.arange(block_k)
            valid = (k_pos < t)[None, :]
            if causal:
                valid = valid & (k_pos[None, :] <= q_pos[:, None])
            s = jnp.where(valid[None, None], s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            # _NEG_INF is a finite -1e30, so rows with no valid key yet
            # run the plain update: exp(-1e30 - m_new) underflows to 0
            # (same convention as the Pallas kernels above).
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            acc = acc * corr + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32,
            )
            l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            return m_new, l, acc

        # Static bound: a dynamic (diagonal-capped) stop would break
        # reverse-mode AD through the loop; blocks past the causal
        # diagonal are fully masked and contribute nothing (the
        # formulation trades ~2x flops for differentiability — it is
        # the safety net, not the fast path).
        m, l, acc = lax.fori_loop(0, nk, body, (m0, l0, a0))
        l_safe = jnp.maximum(l, 1e-30)
        o = (acc / l_safe).astype(q.dtype)
        # Fully-masked rows exist only in the padded tail (sliced off
        # below); their lse lands near _NEG_INF via the plain formula.
        lse = (m + jnp.log(l_safe))[..., 0]
        return None, (o, lse)

    _, (o_blocks, lse_blocks) = lax.scan(
        q_block, None, (qb, jnp.arange(nq))
    )
    o = jnp.moveaxis(o_blocks, 0, 2).reshape(b, h, tq_pad, hd)[:, :, :t]
    lse = jnp.moveaxis(lse_blocks, 0, 2).reshape(b, h, tq_pad)[:, :, :t]
    return o, lse


def flash_attention_lse_auto(q, k, v, causal: bool = True,
                             interpret: Optional[bool] = None):
    """``flash_attention_lse`` when the shape fits one launch, the
    chunked decomposition when it only fits per-chunk, ``None`` when no
    flash formulation supports the shape — callers take None as the
    fall-back-to-dense signal instead of catching a trace-time raise
    (keeps the einsum path reachable if the support gates and this
    dispatcher ever diverge)."""
    if flash_supported(q.shape, q.dtype):
        return flash_attention_lse(q, k, v, causal, interpret)
    if flash_chunked_supported(q.shape, q.dtype):
        return flash_attention_lse_chunked(q, k, v, causal, interpret)
    if blocked_attention_applies(q.shape):
        # No kernel decomposition (e.g. a non-power-of-two tail) but
        # far too long for a t x t einsum: stream it in jnp blocks.
        return attention_lse_blocked(q, k, v, causal)
    return None


def blocked_attention_applies(shape: Tuple[int, ...]) -> bool:
    """Long-context shapes the jnp blocked formulation should absorb
    when no Pallas path decomposes them (the einsum fallback would
    materialize a t x t score matrix)."""
    if len(shape) != 4:
        return False
    _, _, t, hd = shape
    return t >= _BLOCKED_MIN_T and hd >= 8


def flash_any_supported(shape: Tuple[int, ...], dtype=jnp.float32) -> bool:
    """Whether ``flash_attention_lse_auto`` returns a streaming
    formulation for this shape (single-launch kernel, chunked kernels,
    or the jnp blocked fallback) — the gate dense/ring dispatchers use;
    False means the einsum path is the right call (small shapes)."""
    return (
        flash_supported(shape, dtype)
        or flash_chunked_supported(shape, dtype)
        or blocked_attention_applies(shape)
    )


def flash_attention_lse_chunked(q, k, v, causal: bool = True,
                                interpret: Optional[bool] = None):
    """Flash attention for sequences past the single-launch VMEM cap
    (``_vmem_block_cap`` marks e.g. bf16 t=16384/hd=64 unsupported —
    the pipeline's resident copies alone exceed scoped VMEM).

    The sequence is split into the largest kernel-supported chunk
    size; each (q-chunk, k-chunk) pair runs one flash launch and the
    partials merge with the streaming-softmax combine — the same
    decomposition ring attention does across devices
    (``ops/attention.py``), applied on-device.  Fully differentiable:
    composition of the custom-VJP kernel and jnp merges.  Memory stays
    O(t·hd): only per-chunk (o, lse) partials materialize, never a
    score matrix.
    """
    b, h, t, hd = q.shape
    c = _chunk_len(t, hd, q.dtype.itemsize)
    if c == 0 or c == t:
        raise ValueError(
            f"flash_attention_lse_chunked: no supported chunking for "
            f"t={t}, hd={hd}; callers gate on flash_chunked_supported()."
        )
    nq = t // c
    sl = lambda x, i: lax.slice_in_dim(x, i * c, (i + 1) * c, axis=2)
    outs, lses = [], []
    for i in range(nq):
        qi = sl(q, i)
        # Diagonal chunk: in-kernel causal mask (or plain for non-causal).
        o, lse = flash_attention_lse(qi, sl(k, i), sl(v, i), causal, interpret)
        o = o.astype(jnp.float32)
        # Off-diagonal chunks: fully visible under causal masking only
        # for j < i; non-causal sees every chunk.
        for j in range(nq) if not causal else range(i):
            if j == i:
                continue
            o_j, lse_j = flash_attention_lse(
                qi, sl(k, j), sl(v, j), False, interpret
            )
            o, lse = merge_lse(o, lse, o_j.astype(jnp.float32), lse_j)
        outs.append(o)
        lses.append(lse)
    out = jnp.concatenate(outs, axis=2).astype(q.dtype)
    return out, jnp.concatenate(lses, axis=2)


# ---------------------------------------------------------------------------
# flash DECODE: q_len=1 against a KV cache (the serving inner loop)
# ---------------------------------------------------------------------------
#
# The training kernels above are fwd/bwd pairs over (b, h, t, hd) with
# t == t_kv; serving's decode step is a different shape class entirely:
# ONE query per (batch, head) against a preallocated (B, max_seq, h, hd)
# cache whose valid prefix length varies PER SLOT (continuous batching).
#
# The kernel works on the cache in the order the chip stores it.  A
# last dim narrower than a lane tile (hd 64) makes the chip hold
# ``(B, max_seq, h, hd)`` as ``{1,3,2,0}``: POSITIONS ALONG THE LANES,
# hd on the sublanes, dense.  ``transpose(cache, (0, 2, 3, 1))`` is a
# bitcast of that, and it is what the kernel takes: a row-major
# ``(B, max_seq, h, hd)`` operand made the compiler copy both caches
# into a lane-padded layout in front of every superstep and back
# behind it (PERF.md §6 PR 32).
#
# Both caches stay in HBM (``pl.ANY``, aliased to the results) and the
# kernel moves what it needs itself: one slot a grid step, a slot's
# live ``(h, hd, chunk)`` pieces of K and of V, and the live chunks of
# ALL slots, in slot order, as one stream of DMAs through a ring of
# VMEM buffers whose cursor is carried over the grid in SMEM
# (``_kv_stream``, the shape of ``_mla_decode_kernel``).  A slot costs
# its live chunks and nothing else: no grid step without a body, and
# the next slots' first chunks are in flight while this one's last is
# scored (a grid of ``slots x max_seq / block`` clamped to the last
# live block spent 113 of 192 steps dead at the serving cell's mix and
# fetched every slot's first block behind one of them, PERF.md §6 PR
# 41).  ``flash_decode_chunk`` picks the chunk from the shape.
#
# A head's score over 128 positions is a sum over the hd sublanes of
# ``q * k`` and the value sum stays a ``(hd, 128)`` partial a lane: VPU
# work.  The softmax statistics are PER LANE too (each lane its own
# running max, merged when the slot ends), so a tile costs no
# cross-lane operation: on the chip those (lane reductions, lane
# gathers) cost about ten vector operations each (PERF.md §6 PR 32).
# For the same reason a slot's query is laid along the lanes once, by
# one product with a one-row matrix on the otherwise idle matrix unit.
# Several heads a loop iteration give the scheduler independent chains
# to interleave; (m, l, acc) stay f32 in scratch.  Grouped queries
# (``_decode_grouped_kernel``) take a whole chunk in two small matrix
# products instead, a cached head a loop turn.  Where a head is narrower
# than a lane tile (``hd`` 64) a turn a head is a dependent chain of two
# products on half-empty 128-deep weight tiles, paid 19 times a slot at
# two or three chunks of eight heads: ``_decode_folded_kernel`` takes
# ``flash_decode_heads_per_step`` cached heads a step through ONE pair
# of products, the queries block diagonal against the heads' rows viewed
# as one ``(heads * hd, chunk)`` operand, and a chunk is one basic block
# (PERF.md §6 PR 52).  At ``hd`` >= 128 a head fills its weight tiles
# and a block-diagonal query would only push more rows through each, so
# those shapes keep the body they had: the fold is a function of the
# shape, never a switch.
#
# The step's own K/V column is WRITTEN here too.  ``k_new``/``v_new``
# enter as one ``(2, h, hd, slots -> lanes)`` operand resident in VMEM
# (in the cache's dtype, as the 32-bit words a vector register packs it
# in); a slot's column is moved to the lane of ``pos`` by one rotate
# (``_move_lane``), selected into the lane tile of the ring buffer
# that holds ``pos``, and that one ``(h, hd, 128)`` tile goes back to
# the cache by a DMA of its own.  An XLA scatter (or
# ``dynamic_update_slice`` of one column a slot) in front of the kernel
# is laid out row-major by the compiler, which brings the cache-sized
# copies back, inside the decode scan.
# Inference-only: no VJP (the decode path is reachable only from the
# ServingExecutor, never from a differentiated train step; the pure-jnp
# ``_einsum_decode`` in ops/attention.py stays the numerics oracle and
# the fallback).

#: Chunks of K and of V in VMEM each: one being scored, the others in
#: flight (v5e, 48 slots of 16 x 64 at the serving cell's mix: 0.151 /
#: 0.140 / 0.138 ms a call at 2 / 3 / 4; PERF.md §6 PR 41).
_DECODE_RING = 4
#: What the two rings may take of VMEM, and the limit the kernel asks
#: for (v5e has 128 MiB; a kernel gets 16 MB unless it asks).
_DECODE_RING_BYTES = 24 << 20
_DECODE_VMEM_LIMIT = 64 << 20


def _packed_rows(dtype) -> int:
    """Rows of one vector register of ``dtype``: 8 sublanes, narrower
    types packed two or four to a 32-bit word."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def flash_decode_chunk(s: int, h: int, hd: int, dtype, group: int = 1) -> int:
    """The granule the decode kernel fetches a slot's cache in, in
    positions: whole 128-lane tiles that divide the cache length and
    whose two rings fit ``_DECODE_RING_BYTES``; 0 if there is none.
    One query head a cached head is vector work a lane tile at a time,
    so one tile is the granule: what a slot fetches past its length is
    wasted (v5e, the serving cell's mix at 48 x 1024 x 16 x 64 bf16:
    0.138 / 0.159 / 0.210 ms a call at 128 / 256 / 512).  Grouped
    queries score a whole chunk in two matrix products, and what a
    chunk costs there is the loop's turn and the statistics, not its
    width (32 x 32768 x 8 x 128, 8 query heads a cached one: 2.37 /
    1.29 / 0.73 ms at 128 / 256 / 512; PERF.md §6 PR 41).  The folded
    body of heads of 64 (``flash_decode_heads_per_step``) pays one step
    a chunk for several heads and keeps the 512: its call is then the
    chunks' bytes (192 x 3072 x 8 x 64 at LFM2's mix: PERF.md §6 PR
    52)."""
    item = jnp.dtype(dtype).itemsize
    fits = _DECODE_RING_BYTES // (2 * _DECODE_RING * h * hd * item)
    for chunk in ((512, 256, 128) if group > 1 else (128,)):
        if s % chunk == 0 and chunk <= fits:
            return chunk
    return 0


def flash_decode_supported(cache_shape: Tuple[int, ...],
                           dtype=jnp.float32, group: int = 1) -> bool:
    """Whether ``flash_decode`` applies to a (B, max_seq, h, hd) cache
    of ``dtype``: whole lane tiles of positions, hd whole sublane tiles
    (tests/test_chip_compile.py holds the gate to what the TPU compiler
    accepts).  ``group`` query heads a cached head (grouped-query
    attention) take the matrix-unit body, which takes ``hd`` in whole
    lane tiles or half of one: a group's rows ``(g, 64)`` against a
    chunk ``(64, chunk)`` are the same two products at half the
    contraction (LFM2's 32 query heads over 8 cached heads of 64; the
    cache then lies positions-major, as GPT-2's heads of 64 do, and the
    chip stores it in the order the kernel reads).  At half a lane tile
    the body folds ``flash_decode_heads_per_step`` cached heads into
    one step, so that a weight tile is full; at whole lane tiles a head
    a step already fills it."""
    if len(cache_shape) != 4:
        return False
    _, s, h, hd = cache_shape
    if hd % _packed_rows(dtype) or h > _LANES:
        return False
    if group > 1 and hd % (_LANES // 2):
        return False
    return flash_decode_chunk(s, h, hd, dtype, group) >= _LANES


def flash_decode_heads_per_step(h: int, hd: int, group: int, dtype) -> int:
    """How many cached heads one step of the grouped decode body takes
    together, from the shape alone.  A head of ``hd`` >= 128 fills the
    matrix unit's 128-deep weight tiles by itself, and one query head a
    cached head never reaches the matrix unit: 1, the bodies as they
    were (``_decode_grouped_kernel``, ``_decode_kernel``).  Narrower
    heads share a weight tile, ``128 // hd`` of them
    (``_decode_folded_kernel``), and more while their groups' query
    rows still fit one packed sublane tile of ``dtype``: rows the matrix
    unit is handed and the statistics cover whether they hold a query
    or pad (LFM2's 8 cached heads of 64 under groups of 4 in bfloat16:
    4 a step, 16 rows and no pad)."""
    unit = _LANES // max(hd, 1)
    if group == 1 or unit < 2 or h % unit:
        return 1
    fold = unit
    while 2 * fold * group <= _packed_rows(dtype) and h % (2 * fold) == 0:
        fold *= 2
    return fold


def _lane_tile(t):
    """The ``t``-th 128-lane tile of a ref's last axis."""
    return pl.ds(pl.multiple_of(t * _LANES, _LANES), _LANES)


def _kv_stream(len_ref, new_ref, k_hbm, v_hbm, ring_k, ring_v,
               sem, wsem, cur, *, whole, last, emit, at_ref=None):
    """One slot (grid step) of the decode kernels' cache traffic.

    ``k_hbm``/``v_hbm`` (B, h, hd, S) stay in HBM.  The live chunks of
    all slots, in slot order, are one stream of (h, hd, chunk) DMA
    pairs through ``ring_k``/``ring_v``: pair i of the stream lands in
    ring slot ``i % depth``, and a ring slot once scored takes the next
    pair of the stream, a later slot's too.  ``cur`` carries the stream
    over the grid: pairs scored so far, and the slot and chunk to fetch
    next.

    ``whole(k, v)`` scores a chunk wholly below ``pos`` from the ring
    refs ``k``, ``v`` (h, hd, chunk).  ``last(k, v, at, place)``
    gets the chunk that holds ``pos`` (at offset ``at``): for every
    head ``i`` it calls ``place(0, k, i)`` and ``place(1, v, i)``, which
    store this step's column into the lane tile that holds ``at`` and
    return that tile (hd, 128) (or once ``place(0, k)`` and ``place(1,
    v)``: every head's column in one select, nothing returned), and
    scores the chunk up to ``at``; the
    tile then goes back to the cache while ``emit()`` writes the slot's
    output.  The columns come in ``new_ref`` (2, h, hd / p, slots ->
    lanes) as 32-bit words of p values each, the packing a vector
    register holds the cache's dtype in, so that a column moves to its
    lane by one rotate a register and is selected into the tile word
    for word.

    ``at_ref`` (a second scalar operand, absent by default): where each
    slot's column is WRITTEN, apart from how many positions are live.
    The cache is then a ring (``ops/attention.py``, ``window``): the
    slot's live chunks stream in the order that ends with the chunk
    that holds the written position (once the ring is full every chunk
    is live and whole, and the order of a softmax's terms is free), and
    ``last`` gets as ``at`` the last live offset of that chunk, which
    may lie past the chunk's end."""
    b = pl.program_id(0)
    slots = pl.num_programs(0)
    depth, _, _, chunk = ring_k.shape
    pairs = ((k_hbm, ring_k), (v_hbm, ring_v))

    def live(i):
        return lax.div(len_ref[i] + (chunk - 1), chunk)

    def fetch(i, j, k):
        if at_ref is not None:
            j = lax.rem(at_ref[i] // chunk + 1 + j, live(i))
        at = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        return [pltpu.make_async_copy(hbm.at[i, :, :, at], ring.at[k],
                                      sem.at[n, k])
                for n, (hbm, ring) in enumerate(pairs)]

    def fetch_next(k, fb, fj):
        @pl.when(fb < slots)
        def _():
            for copy in fetch(fb, fj, k):
                copy.start()

        done = fj + 1 >= live(jnp.minimum(fb, slots - 1))
        return jnp.where(done, fb + 1, fb), jnp.where(done, 0, fj + 1)

    @pl.when(b == 0)
    def _prime():
        fb = fj = jnp.int32(0)
        for k in range(depth):
            fb, fj = fetch_next(k, fb, fj)
        cur[0], cur[1], cur[2] = jnp.int32(0), fb, fj

    n = live(b)
    pos = len_ref[b] - 1 if at_ref is None else at_ref[b]

    def below(j, c):
        i, fb, fj = c
        k = lax.rem(i, depth)
        for copy in fetch(b, j, k):
            copy.wait()
        whole(ring_k.at[k], ring_v.at[k])
        return (i + 1, *fetch_next(k, fb, fj))

    i, fb, fj = lax.fori_loop(0, n - 1, below, (cur[0], cur[1], cur[2]))
    k = lax.rem(i, depth)
    for copy in fetch(b, n - 1, k):
        copy.wait()
    if at_ref is None:
        at = limit = pos - (n - 1) * chunk
    else:
        at = lax.rem(pos, chunk)
        limit = len_ref[b] - 1 - (pos - at)
    tile, lane = _lane_tile(at // _LANES), lax.rem(at, _LANES)
    here = _lane_iota(new_ref.shape[2]) == lane

    def place(which, ref, head=None):
        if head is None:
            # Every head's column in one select: the tile (h, hd, 128)
            # as (h * hd, 128), whole packed sublane tiles a head.
            column = _move_lane(
                new_ref[which, :, :, _lane_tile(b // _LANES)].reshape(
                    -1, _LANES), lax.rem(b, _LANES), lane)
            words = pltpu.bitcast(ref[:, :, tile].reshape(-1, _LANES),
                                  jnp.uint32)
            ref[:, :, tile] = pltpu.bitcast(
                jnp.where(_lane_iota(words.shape[0]) == lane, column, words),
                ref.dtype).reshape(*ref.shape[:2], _LANES)
            return
        column = _move_lane(new_ref[which, head, :, _lane_tile(b // _LANES)],
                            lax.rem(b, _LANES), lane)
        words = pltpu.bitcast(ref[head, :, tile], jnp.uint32)
        ref[head, :, tile] = new = pltpu.bitcast(
            jnp.where(here, column, words), ref.dtype)
        return new

    last(ring_k.at[k], ring_v.at[k], limit, place)
    writes = [pltpu.make_async_copy(ring.at[k, :, :, tile],
                                    hbm.at[b, :, :, _lane_tile(pos // _LANES)],
                                    wsem.at[n])
              for n, (hbm, ring) in enumerate(pairs)]
    for write in writes:
        write.start()
    emit()
    for write in writes:
        write.wait()
    cur[0] = i + 1
    cur[1], cur[2] = fetch_next(k, fb, fj)


def _decode_tile(s, v, m, l, acc, valid=None):
    """One streaming-softmax step over a lane tile: scores ``s``
    (1, 128), values ``v`` (hd, 128); ``m``, ``l`` (1, 128) and ``acc``
    (hd, 128) are partials a lane, merged when the slot ends.  Lanes
    outside ``valid`` add nothing."""
    m_new = jnp.maximum(m, s)
    p = jnp.exp(s - m_new)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m - m_new)
    return m_new, l * corr + p, acc * corr + p * v


def _decode_kernel(len_ref, q_ref, new_ref, k_in, v_in,
                   o_ref, k_hbm, v_hbm, ring_k, ring_v, sem, wsem, cur,
                   m_scr, l_scr, acc_scr, q_scr, *, scale, heads):
    # k_hbm, v_hbm are k_in's and v_in's buffers (aliased), in HBM.
    del k_in, v_in
    _, h, hd, chunk = ring_k.shape
    tiles = chunk // _LANES
    lanes = _lane_iota(hd)
    b = pl.program_id(0)

    def by_heads(body, carry=0):
        """``body(i, carry)`` over the heads, ``heads`` of them unrolled
        a loop iteration: independent chains the scheduler interleaves."""
        def group(g, c):
            for j in range(heads):
                c = body(g * heads + j, c)
            return c

        return lax.fori_loop(0, h // heads, group, carry)

    # A slot restarts its statistics with ``m`` alone: against a fresh
    # ``m`` a lane's first ``exp(m - m_new)`` is 0, so what the slot
    # before left in ``l`` and ``acc`` (finite) counts for nothing.
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)

    @pl.when(b == 0)
    def _clear():
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def load(ref, i, t):
        return ref[i, :, _lane_tile(t)].astype(jnp.float32)

    # This slot's query, a column of the resident operand (the slots
    # along its lanes), laid along the lanes once for all its tiles:
    # one product with a matrix whose row ``b % 128`` is ones puts the
    # column on every lane, for all heads, on the otherwise idle matrix
    # unit and exactly (a lane rotate and broadcast a head cost the
    # vector unit 0.39 us a slot, XLA's broadcast 12.3 us a call:
    # PERF.md §6 PR 41).
    pick = (lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
            == lax.rem(b, _LANES)).astype(q_ref.dtype)
    q_scr[...] = jnp.dot(
        q_ref[:, :, _lane_tile(b // _LANES)].reshape(h * hd, _LANES), pick,
        preferred_element_type=jnp.float32,
        precision=(lax.Precision.HIGHEST if q_ref.dtype == jnp.float32
                   else lax.Precision.DEFAULT)).reshape(h, hd, _LANES)

    def score(i, k):
        return jnp.sum(q_scr[i] * k, axis=0, keepdims=True) * scale

    def whole_tiles(k_ref, v_ref, count):
        """The chunk's first ``count`` lane tiles: nothing to mask."""
        def group(g, c):
            ids = [g * heads + j for j in range(heads)]

            def tile(t, states):
                return tuple(
                    _decode_tile(score(i, load(k_ref, i, t)),
                                 load(v_ref, i, t), *state)
                    for i, state in zip(ids, states))

            states = lax.fori_loop(
                0, count, tile,
                tuple((m_scr[i], l_scr[i], acc_scr[i]) for i in ids))
            for i, state in zip(ids, states):
                m_scr[i], l_scr[i], acc_scr[i] = state
            return c

        lax.fori_loop(0, h // heads, group, 0)

    def last(k_ref, v_ref, at, place):
        t = at // _LANES
        if tiles > 1:
            pl.when(t > 0)(lambda: whole_tiles(k_ref, v_ref, t))

        # The tile that holds ``pos``: this step's column goes in and
        # lanes past ``pos`` are masked.
        def head(i, c):
            k = place(0, k_ref, i).astype(jnp.float32)
            v = place(1, v_ref, i).astype(jnp.float32)
            m_scr[i], l_scr[i], acc_scr[i] = _decode_tile(
                score(i, k), v, m_scr[i], l_scr[i], acc_scr[i],
                valid=lanes[:1] <= lax.rem(at, _LANES))
            return c

        by_heads(head)

    def emit():
        def head(i, out):
            m = m_scr[i]
            w = jnp.exp(m - jnp.max(m, axis=1, keepdims=True))
            o = (jnp.sum(acc_scr[i] * w, axis=1, keepdims=True)
                 / jnp.sum(l_scr[i] * w, axis=1, keepdims=True))   # (hd, 1)
            return jnp.where(lanes == i, o, out)

        out = by_heads(head, jnp.zeros((hd, _LANES), jnp.float32))
        o_ref[0] = out.astype(o_ref.dtype)

    _kv_stream(len_ref, new_ref, k_hbm, v_hbm, ring_k, ring_v,
               sem, wsem, cur, last=last, emit=emit,
               whole=lambda k_ref, v_ref: whole_tiles(k_ref, v_ref, tiles))


def _decode_grouped_kernel(len_ref, q_ref, new_ref, k_in, v_in,
                           o_ref, k_hbm, v_hbm, ring_k, ring_v, sem, wsem,
                           cur, m_scr, l_scr, acc_scr, *, scale, at_ref=None):
    """The grouped-query body: ``q_ref`` (1, h_kv, g, hd) holds the g
    query heads of every cached head as rows, so a live K chunk
    (hd, chunk) is fetched once for its group and scores and values are
    two small matrix products a chunk; softmax statistics a row.  The
    stream, the written tile and the aliasing are ``_decode_kernel``'s
    (one launch, ``_decode_call``)."""
    del k_in, v_in
    _, h, _, chunk = ring_k.shape

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    # A chunk goes through in one step: the statistics' lane reductions
    # and the loop's turns, not the products, are what a lane tile at a
    # time costs (5.6 ms a call at 32 slots of 8192 positions, PERF.md
    # §6 PR 33).
    def step(i, k, v, valid=None):
        """``k``, ``v`` (hd, chunk) of head ``i``."""
        m, l, acc = m_scr[i], l_scr[i], acc_scr[i]   # (g, 128) x2, (g, hd)
        s = jnp.dot(q_ref[0, i], k, precision=_mxu_precision(k.dtype),
                    preferred_element_type=jnp.float32) * scale    # (g, w)
        if valid is not None:
            s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m - m_new)
        pv = lax.dot_general(p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                             precision=_mxu_precision(v.dtype),
                             preferred_element_type=jnp.float32)   # (g, hd)
        m_scr[i] = m_new
        l_scr[i] = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[i] = acc * corr[:, :1] + pv

    def over_heads(body):
        def head(i, c):
            body(i)
            return c

        lax.fori_loop(0, h, head, 0)

    def last(k_ref, v_ref, at, place):
        valid = lax.broadcasted_iota(jnp.int32, (1, chunk), 1) <= at

        def head(i):
            place(0, k_ref, i)
            place(1, v_ref, i)
            step(i, k_ref[i], v_ref[i], valid)

        over_heads(head)

    def emit():
        def head(i):
            o_ref[0, i] = (acc_scr[i] / l_scr[i][:, :1]).astype(o_ref.dtype)

        over_heads(head)

    _kv_stream(len_ref, new_ref, k_hbm, v_hbm, ring_k, ring_v,
               sem, wsem, cur, last=last, emit=emit, at_ref=at_ref,
               whole=lambda k_ref, v_ref: over_heads(
                   lambda i: step(i, k_ref[i], v_ref[i])))


def _decode_folded_kernel(len_ref, q_ref, new_ref, k_in, v_in,
                          o_ref, k_hbm, v_hbm, ring_k, ring_v, sem, wsem,
                          cur, m_scr, l_scr, acc_scr, *, scale, group,
                          at_ref=None):
    """The grouped-query body where a head is narrower than a lane tile:
    ``fold`` cached heads go through the matrix unit together, and a
    chunk is one basic block for all of them, not a loop turn a head.

    ``q_ref`` (1, h_kv / fold, rows, fold * hd) holds the queries block
    diagonal: row ``i * group + j`` of a step is query head ``j`` of the
    step's cached head ``i`` in columns ``i * hd ... (i + 1) * hd`` and
    zeros elsewhere, so against the step's ``(fold * hd, chunk)`` view of
    the ring (whole packed sublane tiles a head: no move) every row
    scores its own head, the zeros adding exact 0.0 to the float32
    accumulator.  ``p @ v^T`` is ``(rows, fold * hd)`` with the wanted
    ``(group, hd)`` of each head on the diagonal; the other blocks ride
    along in ``acc_scr`` and ``emit`` drops them.  The steps of a chunk
    are independent chains the scheduler interleaves.  The stream, the
    written tile (one select for all heads) and the aliasing are
    ``_decode_kernel``'s."""
    del k_in, v_in
    _, _, hd, chunk = ring_k.shape
    _, steps, rows, width = q_ref.shape
    fold = width // hd

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def chunk_step(k_ref, v_ref, valid=None):
        for t in range(steps):
            heads = slice(t * fold, (t + 1) * fold)
            k = k_ref[heads].reshape(width, chunk)
            v = v_ref[heads].reshape(width, chunk)
            m, l, acc = m_scr[t], l_scr[t], acc_scr[t]
            s = jnp.dot(q_ref[0, t], k, precision=_mxu_precision(k.dtype),
                        preferred_element_type=jnp.float32) * scale
            if valid is not None:
                s = jnp.where(valid, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            corr = jnp.exp(m - m_new)
            pv = lax.dot_general(p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                                 precision=_mxu_precision(v.dtype),
                                 preferred_element_type=jnp.float32)
            m_scr[t] = m_new
            l_scr[t] = l * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[t] = acc * corr[:, :1] + pv

    def last(k_ref, v_ref, at, place):
        place(0, k_ref)
        place(1, v_ref)
        chunk_step(k_ref, v_ref,
                   lax.broadcasted_iota(jnp.int32, (1, chunk), 1) <= at)

    def emit():
        head = lax.broadcasted_iota(jnp.int32, (rows, hd), 0) // group
        for t in range(steps):
            acc = acc_scr[t]
            own = acc[:, :hd]
            for i in range(1, fold):
                own = jnp.where(head == i, acc[:, i * hd:(i + 1) * hd], own)
            o_ref[0, t] = (own / l_scr[t][:, :1]).astype(o_ref.dtype)

    _kv_stream(len_ref, new_ref, k_hbm, v_hbm, ring_k, ring_v,
               sem, wsem, cur, last=last, emit=emit, at_ref=at_ref,
               whole=chunk_step)


def _decode_ring_kernel(len_ref, at_ref, *refs, body):
    """A grouped ``body`` over a ring: a second scalar operand says
    where each slot's column is written."""
    body(len_ref, *refs, at_ref=at_ref)


def flash_decode(q, k_new, v_new, cache_k, cache_v, lengths,
                 interpret: Optional[bool] = None,
                 positions_last: bool = False, write_at=None):
    """One decode step of attention against a KV cache, the step's own
    column written on the way.

    ``q``: (B, h_q, hd); ``k_new``, ``v_new``: (B, h, hd) -- the query,
    key and value of the token at position ``lengths - 1``, ``h_q`` a
    multiple of ``h`` (query head j reads cached head ``j // (h_q //
    h)``: grouped-query attention).  ``cache_k``/``cache_v``:
    (B, max_seq, h, hd) preallocated caches, or with ``positions_last``
    (B, h, hd, max_seq): the order the kernel reads, which an op whose
    ``hd`` fills whole lane tiles declares itself, since the chip would
    store the first form row-major there.  ``lengths``: (B,) int32
    in ``1..max_seq``.  ``k_new``/``v_new`` are stored at position
    ``lengths[b] - 1`` (in the cache's dtype) and the query
    attends key positions ``< lengths[b]``, its own among them.  What a
    slot costs follows its length: its live chunks
    (:func:`flash_decode_chunk`) are what is fetched and scored, and
    the lane tile that holds the new column is what is written.
    Returns ``(out (B, h_q, hd) in q.dtype, cache_k, cache_v)``; donate
    the caches and the write is in place.  Callers gate on
    :func:`flash_decode_supported`.

    ``write_at`` (B,) int32, absent by default: the cache is a ring.
    ``k_new``/``v_new`` are stored at position ``write_at[b]`` and the
    query attends the first ``lengths[b]`` positions of the cache, the
    written one among them (``write_at < lengths``: a ring of W rows at
    position p takes ``write_at = p mod W`` and ``lengths = min(p + 1,
    W)``).  The grouped body alone (``h_q > h``).
    """
    if interpret is None:
        interpret = _interpret_default()
    b, h, hd, s = cache_k.shape if positions_last else \
        tuple(cache_k.shape[i] for i in (0, 2, 3, 1))
    group = q.shape[1] // h
    if q.shape[1] != group * h or not flash_decode_supported(
            (b, s, h, hd), cache_k.dtype, group):
        raise ValueError(
            f"flash_decode needs a cache of whole 128-position lane "
            f"tiles and whole sublane tiles of d_head (a multiple of 64 "
            f"under grouped queries); got q {q.shape}, cache shape "
            f"{cache_k.shape} {cache_k.dtype}.  Gate callers on "
            f"flash_decode_supported()."
        )
    if write_at is not None and group == 1:
        raise ValueError("flash_decode: a ring (write_at) takes the grouped "
                         "body alone: several query heads a cached head")
    return _decode_call(q, k_new, v_new, cache_k, cache_v, lengths,
                        interpret=interpret, positions_last=positions_last,
                        write_at=write_at)


# A jit of its own: a model's layers call this at one signature, so the
# kernel is traced once and lowered once a program (as one function the
# layers call) instead of once a layer -- 24 Mosaic lowerings were 1.1 s
# of the GPT-2 superstep's 1.8 s of lowering, which is set-up.
@functools.partial(jax.jit, static_argnames=("interpret", "positions_last"))
def _decode_call(q, k_new, v_new, cache_k, cache_v, lengths, interpret,
                 positions_last=False, write_at=None):
    if not positions_last:
        cache_k = cache_k.transpose(0, 2, 3, 1)
        cache_v = cache_v.transpose(0, 2, 3, 1)
    b, h, hd, s = cache_k.shape
    group = q.shape[1] // h
    chunk = flash_decode_chunk(s, h, hd, cache_k.dtype, group)
    fold = flash_decode_heads_per_step(h, hd, group, q.dtype)
    scale = 1.0 / math.sqrt(hd)

    def slot(bi, *_):
        return (bi, 0, 0, 0)

    def columns(*xs):
        """(B, h, hd) arrays stacked, in the cache's dtype, with the
        slots along the lanes and ``pack`` neighbours along ``hd`` to a
        32-bit word, as a vector register packs them
        (``pltpu.bitcast``'s order): (len(xs), h, hd / pack, B -> 128s)."""
        pack = 4 // jnp.dtype(cache_k.dtype).itemsize
        x = jnp.stack(xs).astype(cache_k.dtype)
        x = lax.bitcast_convert_type(
            x.reshape(len(xs), b, h, hd // pack, pack), jnp.uint32)
        return jnp.pad(x.reshape(len(xs), b, h, hd // pack).transpose(0, 2, 3, 1),
                       ((0, 0),) * 3 + ((0, _round_up(b, _LANES) - b),))

    resident = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    if group == 1:
        kernel = functools.partial(
            _decode_kernel, scale=scale,
            heads=next(n for n in (4, 2, 1) if h % n == 0))
        # The queries too with the slots along the lanes, resident.
        q_in = jnp.pad(q.transpose(1, 2, 0),
                       ((0, 0), (0, 0), (0, _round_up(b, _LANES) - b)))
        q_spec = resident
        # The output has the heads along the lanes, a whole tile of them.
        o_shape = (b, hd, _LANES)
        o_spec = pl.BlockSpec((1, hd, _LANES), lambda bi, *_: (bi, 0, 0))
        state = [(h, 1, _LANES), (h, 1, _LANES), (h, hd, _LANES),
                 (h, hd, _LANES)]
    elif fold == 1:
        # Queries and outputs (B, h, g, hd): a group's heads as rows,
        # padded to a whole packed sublane tile of the query's dtype.
        kernel = functools.partial(_decode_grouped_kernel, scale=scale)
        gp = _round_up(group, _packed_rows(q.dtype))
        q_in = jnp.pad(q.reshape(b, h, group, hd),
                       ((0, 0), (0, 0), (0, gp - group), (0, 0)))
        o_shape = (b, h, gp, hd)
        q_spec = o_spec = pl.BlockSpec((1, h, gp, hd), slot)
        state = [(h, gp, _LANES), (h, gp, _LANES), (h, gp, hd)]
    else:
        # ``fold`` cached heads a step: their groups' heads as rows of
        # one block-diagonal operand (B, h / fold, fold * g, fold * hd),
        # the rows padded as above; outputs (B, h / fold, fold * g, hd).
        kernel = functools.partial(_decode_folded_kernel, scale=scale,
                                   group=group)
        steps, gp = h // fold, _round_up(fold * group, _packed_rows(q.dtype))
        q_in = jnp.where(
            jnp.eye(fold, dtype=bool)[:, None, :, None],
            q.reshape(b, steps, fold, group, 1, hd), jnp.zeros((), q.dtype))
        q_in = jnp.pad(q_in.reshape(b, steps, fold * group, fold * hd),
                       ((0, 0), (0, 0), (0, gp - fold * group), (0, 0)))
        o_shape = (b, steps, gp, hd)
        q_spec = pl.BlockSpec((1, steps, gp, fold * hd), slot)
        o_spec = pl.BlockSpec((1, steps, gp, hd), slot)
        state = [(steps, gp, _LANES), (steps, gp, _LANES),
                 (steps, gp, fold * hd)]
    cache = jax.ShapeDtypeStruct((b, h, hd, s), cache_k.dtype)
    scalars = (jnp.clip(lengths.astype(jnp.int32), 1, s),)
    if write_at is not None:
        kernel = functools.partial(_decode_ring_kernel, body=kernel)
        scalars += (jnp.clip(write_at.astype(jnp.int32), 0, scalars[0] - 1),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b,),
        in_specs=[q_spec, resident, in_hbm, in_hbm],
        out_specs=[o_spec, in_hbm, in_hbm],
        scratch_shapes=[
            pltpu.VMEM((_DECODE_RING, h, hd, chunk), cache_k.dtype),
            pltpu.VMEM((_DECODE_RING, h, hd, chunk), cache_v.dtype),
            pltpu.SemaphoreType.DMA((2, _DECODE_RING)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((3,), jnp.int32),
        ] + [pltpu.VMEM(shape, jnp.float32) for shape in state],
    )
    out, cache_k, cache_v = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(o_shape, q.dtype), cache, cache],
        # Operands count the scalar prefetch: the caches follow it, the
        # queries and the columns.
        input_output_aliases={len(scalars) + 2: 1, len(scalars) + 3: 2},
        # The grid's steps share the rings and follow one another.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_DECODE_VMEM_LIMIT),
        name="ff_flash_decode",
        interpret=interpret,
    )(*scalars, q_in, columns(k_new, v_new), cache_k, cache_v)
    if group == 1:
        out = jnp.swapaxes(out[:, :, :h], 1, 2)
    else:
        out = out[:, :, :fold * group].reshape(b, h * group, hd)
    if positions_last:
        return out, cache_k, cache_v
    return out, cache_k.transpose(0, 3, 1, 2), cache_v.transpose(0, 3, 1, 2)


# ---------------------------------------------------------------------------
# fused softmax + cross-entropy (the reference's fused softmax/loss op,
# src/ops/softmax.cu:91-160, rebuilt as a vocab-blocked streaming kernel)
# ---------------------------------------------------------------------------

_XENT_BLOCK_N = 128
_XENT_BLOCK_V = 512


def xent_supported(n: int, v: int) -> bool:
    """Gate for the fused kernel: the vocab dim must be large enough to
    be worth streaming and both dims must tile."""
    if v < 2 * _XENT_BLOCK_V or v % _XENT_BLOCK_V:
        return False
    return n >= 8 and _pick_block(n, _XENT_BLOCK_N) >= 8


def _xent_fwd_kernel(logits_ref, labels_ref, nll_ref, lse_ref, pred_ref,
                     m_scr, l_scr, t_scr, am_scr, *, block_v):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        t_scr[:] = jnp.zeros_like(t_scr)
        am_scr[:] = jnp.zeros_like(am_scr)

    x = logits_ref[:].astype(jnp.float32)               # (bn, bv)
    bn = x.shape[0]
    bmax = jnp.max(x, axis=1, keepdims=True)
    bidx = jnp.argmax(x, axis=1).astype(jnp.int32)[:, None] + j * block_v
    # Streaming logsumexp + running argmax.
    m_old = m_scr[:]
    m_new = jnp.maximum(m_old, bmax)
    l_scr[:] = l_scr[:] * jnp.exp(m_old - m_new) + jnp.sum(
        jnp.exp(x - m_new), axis=1, keepdims=True
    )
    am_scr[:] = jnp.where(bmax > m_old, bidx, am_scr[:])
    m_scr[:] = m_new
    # Target logit: the label column, if it falls in this vocab block.
    lbl = labels_ref[:, 0:1]
    col = lbl - j * block_v
    cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    tv = jnp.sum(jnp.where(cols == col, x, 0.0), axis=1, keepdims=True)
    in_blk = (col >= 0) & (col < block_v)
    t_scr[:] = t_scr[:] + jnp.where(in_blk, tv, 0.0)

    @pl.when(j == nv - 1)
    def _():
        lse = m_scr[:] + jnp.log(l_scr[:])
        lse_ref[:] = lse
        nll_ref[:] = lse - t_scr[:]
        pred_ref[:] = am_scr[:]


def _xent_bwd_kernel(logits_ref, labels_ref, lse_ref, gn_ref, gl_ref,
                     dlogits_ref, *, block_v):
    j = pl.program_id(1)
    x = logits_ref[:].astype(jnp.float32)
    p = jnp.exp(x - lse_ref[:])                         # softmax block
    lbl = labels_ref[:, 0:1]
    col = lbl - j * block_v
    cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (cols == col).astype(jnp.float32)
    g_nll = gn_ref[:]
    g_lse = gl_ref[:]
    # d nll/d x = p - onehot ; d lse/d x = p.
    dlogits_ref[:] = (
        p * (g_nll + g_lse) - onehot * g_nll
    ).astype(dlogits_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def softmax_xent(logits, labels, interpret: Optional[bool] = None):
    """Fused cross-entropy over (N, V) logits with int (N,) labels.

    One streaming pass over the vocab per row: returns per-row
    ``(nll, lse, pred)`` without materializing the softmax in HBM —
    the TPU form of the reference's fused softmax+loss kernel chain
    (``softmax.cu:91-160``, ``SoftmaxLossBackprop``).
    """
    (out, _) = _xent_fwd(logits, labels, interpret)
    return out


def _xent_calls(n, v, dtype, interpret):
    block_n = _pick_block(n, _XENT_BLOCK_N)
    block_v = _XENT_BLOCK_V
    grid = (n // block_n, v // block_v)
    row = pl.BlockSpec((block_n, 1), lambda i, j: (i, 0))
    blk = pl.BlockSpec((block_n, block_v), lambda i, j: (i, j))
    fwd = pl.pallas_call(
        functools.partial(_xent_fwd_kernel, block_v=block_v),
        grid=grid,
        in_specs=[blk, row],
        out_specs=[row, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.int32),
        ],
        name="ff_softmax_xent_fwd",
        interpret=interpret,
    )
    bwd = pl.pallas_call(
        functools.partial(_xent_bwd_kernel, block_v=block_v),
        grid=grid,
        in_specs=[blk, row, row, row, row],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((n, v), dtype),
        name="ff_softmax_xent_bwd",
        interpret=interpret,
    )
    return fwd, bwd


def _xent_fwd(logits, labels, interpret):
    if interpret is None:
        interpret = _interpret_default()
    n, v = logits.shape
    fwd, _ = _xent_calls(n, v, logits.dtype, interpret)
    nll, lse, pred = fwd(logits, labels.astype(jnp.int32)[:, None])
    out = (nll[:, 0], lse[:, 0], pred[:, 0])
    return out, (logits, labels, lse)


def _xent_bwd(interpret, res, g):
    if interpret is None:
        interpret = _interpret_default()
    logits, labels, lse = res
    g_nll, g_lse, _ = g  # pred is integer-valued: no cotangent
    n, v = logits.shape
    _, bwd = _xent_calls(n, v, logits.dtype, interpret)
    zeros = jnp.zeros((n, 1), jnp.float32)
    gn = zeros if g_nll is None else g_nll.astype(jnp.float32)[:, None]
    gl = zeros if g_lse is None else g_lse.astype(jnp.float32)[:, None]
    dlogits = bwd(logits, labels.astype(jnp.int32)[:, None], lse, gn, gl)
    return (dlogits, None)


softmax_xent.defvjp(_xent_fwd, _xent_bwd)


# ---------------------------------------------------------------------------
# Embedding row gather / scatter-add
#
# XLA's TPU lowering of gather/scatter over a large table is a
# full-table sweep (measured ~12 ms gather / ~250 ms scatter on a
# 2 GB table for 2k rows — the reference's DLRM embedding path,
# ``embedding.cu:128-158``).  These kernels move only the touched
# rows, addressed by scalar-prefetched ids: the gather as DMAs that
# run ahead of their use; the scatter as an in-kernel
# read-modify-write loop over HBM (correct for duplicate ids, like
# the reference's atomicAdd but deterministic), aliasing the table in
# place.
#
# Two addressings, chosen from the table's shape (``rows_addressing``),
# because the chip stores the two kinds of table differently and a
# view that is not the stored order costs a copy of the whole table
# every step (PERF.md §6 PR 28: 76 of 79 ms of DLRM's step):
#
# - ``row_major``: ``D % 128 == 0``.  The chip keeps ``(R, D)`` row
#   major, ``{1,0:T(8,128)}``; a row is ``D/128`` whole lane tiles and
#   the unit moved is one row (or one 128-lane piece of it).
# - ``lane_major``: ``128 % D == 0``.  A minor dimension under 128
#   would waste most of every 128-lane tile, so the TPU compiler keeps
#   ``(V, D)`` as ``{0,1:T(8,128)}`` and ``(T, V, D)`` as ``{1,2,0}``:
#   physically ``(T, D, V)``, rows along the lanes.  ``swapaxes(-1, -2)``
#   of such a table is a bitcast, and on that view a logical row is one
#   lane of ``D/8`` tiles; Mosaic moves whole 128-lane tiles only, so
#   the unit is the ``(D, 128)`` block of 128 neighbouring rows.
# ---------------------------------------------------------------------------

def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


_ROWS_SMEM_BYTES = 512 * 1024        # scalar-prefetched ids
_ROWS_VMEM_BYTES = 8 * 1024 * 1024   # the update matrix held in VMEM


def _lane_major_shape(shape: Tuple[int, ...]) -> bool:
    """A 2-D ``(V, D)`` or stacked 3-D ``(T, V, D)`` table the chip
    stores rows-along-lanes and the block kernels can address: ``D`` a
    divisor of 128 made of whole sublane tiles, at least one full
    128-row block per table."""
    if len(shape) not in (2, 3):
        return False
    v, d = shape[-2], shape[-1]
    return d < _LANES and _LANES % d == 0 and d % 8 == 0 and v >= _LANES


def rows_addressing(
    n_ids: int,
    shape: Tuple[int, ...],
    dtype=jnp.float32,
    kind: str = "scatter",
) -> Optional[str]:
    """How gather_rows/scatter_add_rows address a table of logical
    ``shape`` (``(R, D)`` or stacked ``(T, V, D)``) on hardware:
    ``"lane_major"``, ``"row_major"``, or None when neither kernel
    form serves it (the caller takes XLA's gather/scatter).

    ``lane_major`` (see the section comment) prefetches ``3n`` (gather)
    or ``3n + 1`` (scatter) scalars and holds the transposed row or
    update matrix in VMEM.  ``row_major``: the gather's (1, 1, D) pipelined
    row blocks compile at any width (v5e-measured at 64, 128 and 256);
    the scatter's manual HBM row DMAs require 128-lane slices (Mosaic
    rejects anything else — d=64 and d=256 both fail, d=128 compiles),
    so it runs on a (P, 128) view — free for ``D == 128``, column
    blocks with expanded ids for other multiples of 128, and for
    ``128 % D == 0`` tables the lane-major form cannot take (a table
    under 128 rows, ``D`` under 8) a packed view that costs a relayout
    and needs the table volume 128-aligned."""
    itemsize = jnp.dtype(dtype).itemsize
    if n_ids < 1 or len(shape) < 2 or shape[-1] < 1:
        return None
    if itemsize != 4:
        # Mosaic packs sub-32-bit dtypes 2/4-per-sublane in VMEM and
        # then cannot statically prove dynamic one-row slices aligned
        # ("index in dimension 0 is a multiple of 4", v5e round-4
        # probe on bf16).  The row kernels are f32-only; smaller
        # dtypes take the dense XLA path.
        return None
    dim = shape[-1]
    if _lane_major_shape(shape):
        words = 3 * n_ids if kind == "gather" else 3 * n_ids + 1
        held = _round_up(n_ids, _LANES) * dim  # rows out / updates in
        if (words * 4 <= _ROWS_SMEM_BYTES
                and held * itemsize <= _ROWS_VMEM_BYTES):
            return "lane_major"
    num_rows = 1
    for s in shape[:-1]:
        num_rows *= s
    if kind == "gather" or dim % _LANES == 0:
        upd_lanes = dim
        ids = n_ids * (dim // _LANES if kind != "gather" and dim > _LANES
                       else 1)
    elif _LANES % dim == 0 and (num_rows * dim) % _LANES == 0:
        upd_lanes, ids = _LANES, n_ids
    else:
        return None
    if (ids * 4 <= _ROWS_SMEM_BYTES
            and n_ids * upd_lanes * itemsize <= _ROWS_VMEM_BYTES):
        return "row_major"
    return None


def rows_supported(
    n_ids: int,
    dim: int,
    dtype=jnp.float32,
    num_rows: Optional[int] = None,
    kind: str = "scatter",
) -> bool:
    """Gate for gather_rows/scatter_add_rows on a 2-D ``(num_rows,
    dim)`` table: some addressing of ``rows_addressing`` serves it.
    Without ``num_rows`` the packed and lane-major forms cannot be
    checked and the gate is conservatively False for them."""
    shape = (num_rows if num_rows is not None else 1, dim)
    return rows_addressing(n_ids, shape, dtype, kind) is not None


def _gather_kernel(idx_ref, row_ref, out_ref):
    out_ref[...] = row_ref[...]


def _lane_iota(d: int):
    return lax.broadcasted_iota(jnp.int32, (d, _LANES), 1)


def _lane_major_view(table, interpret):
    """``(V, D)`` or ``(T, V, D)`` -> ``(T, D, V)``: for the layout the
    chip gives a narrow-row table a bitcast, no data moves.

    Mosaic addresses the HBM buffer at its tiled extent (V rounded up
    to whole 128-lane tiles: ``memref<4x64x1000064xf32>`` for a million
    rows), so a table's last, partial block is a whole block there.
    The interpreter sees the logical extent and is handed that padding
    explicitly."""
    t = jnp.swapaxes(table, -1, -2)
    t = t.reshape((-1,) + t.shape[-2:])
    edge_pad = (-t.shape[-1]) % _LANES if interpret else 0
    if edge_pad:
        t = jnp.pad(t, ((0, 0), (0, 0), (0, edge_pad)))
    return t


def _block_ids(flat_idx, v: int):
    """Global row ids over ``(T*V)`` -> table, 128-row block, lane."""
    idx = flat_idx.astype(jnp.int32)
    t, row = idx // v, idx % v
    return t, row // _LANES, row % _LANES


def _move_lane(block, src, dst):
    """``block`` (D, 128) with lane ``src`` brought to lane ``dst``
    (one rotate a vreg; the other lanes are for the caller to mask)."""
    return pltpu.roll(block, lax.rem(dst - src + _LANES, _LANES), axis=1)


def _gather_lane_kernel(t_ref, blk_ref, lane_ref, table_ref, out_ref,
                        ring, sem):
    # table_ref: the (T, D, V) view in HBM.  Id i wants lane
    # ``lane[i]`` of the (D, 128) block ``blk[i]`` of table ``t[i]``
    # and lands in column i of out_ref (D, n_pad), resident in VMEM.
    # Reads only, so the ring of block DMAs runs ``depth`` ahead with
    # no ordering to keep.
    n = t_ref.shape[0]
    depth = ring.shape[0]
    lanes = _lane_iota(ring.shape[1])

    def load(i, slot):
        start = pl.multiple_of(blk_ref[i] * _LANES, _LANES)
        return pltpu.make_async_copy(
            table_ref.at[t_ref[i], :, pl.ds(start, _LANES)],
            ring.at[slot], sem.at[slot],
        )

    for i in range(min(depth, n)):
        load(i, i).start()

    def body(i, carry):
        slot = lax.rem(i, depth)
        load(i, slot).wait()
        dst = lax.rem(i, _LANES)
        cols = pl.ds(pl.multiple_of(i - dst, _LANES), _LANES)
        out_ref[:, cols] = jnp.where(
            lanes == dst, _move_lane(ring[slot], lane_ref[i], dst),
            out_ref[:, cols],
        )

        @pl.when(i + depth < n)
        def _():
            load(i + depth, slot).start()

        return carry

    lax.fori_loop(0, n, body, 0)


#: Slots of (D, 128) blocks in the lane-major kernels' DMA rings: 16 x
#: 32 KB of VMEM at D = 64.  v5e-measured at 8,192 ids over (8, 2M, 64):
#: the scatter 2.89 / 1.79 / 1.37 / 1.35 ms at 4 / 8 / 16 / 32 slots,
#: the gather 1.04 / 0.79 / 0.79 / 0.79 (PERF.md §6, PR 28).
_LANE_RING = 16


def _gather_rows_lane_major(table, flat_idx, interpret):
    n = flat_idx.shape[0]
    v, d = table.shape[-2:]
    n_pad = _round_up(n, _LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],       # table (HBM)
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),    # rows, transposed
        scratch_shapes=[
            pltpu.VMEM((_LANE_RING, d, _LANES), table.dtype),
            pltpu.SemaphoreType.DMA((_LANE_RING,)),
        ],
    )
    out = pl.pallas_call(
        _gather_lane_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((d, n_pad), table.dtype),
        name="ff_gather_rows",
        interpret=interpret,
    )(*_block_ids(flat_idx, v), _lane_major_view(table, interpret))
    return out[:, :n].T


def gather_rows(table, flat_idx, interpret: Optional[bool] = None):
    """``table[(R, D)][flat_idx (N,)] -> (N, D)`` moving only what the
    N rows need.  ``table`` may be a stacked ``(T, V, D)`` with
    ``flat_idx`` over its ``T*V`` rows: a narrow-row table has to
    arrive unflattened for the lane-major addressing (its ``(T*V, D)``
    view is already a copy).

    ``row_major``: the table is viewed as (R, 1, D) so the (1, 1, D)
    row block meets the TPU block rule (last two block dims full-size);
    the row id comes scalar-prefetched into the index_map, and the
    per-step row DMAs are pipelined by the grid machinery.
    ``lane_major``: one kernel step over all ids, a ring of
    ``(D, 128)`` block DMAs from the transposed view running ahead of
    the lane select that picks each row out of its block.
    """
    if interpret is None:
        interpret = _interpret_default()
    n = flat_idx.shape[0]
    d = table.shape[-1]
    if n == 0:  # a static shape; no kernel form has a zero-step grid
        return jnp.zeros((0, d), table.dtype)
    if _lane_major_shape(table.shape):
        return _gather_rows_lane_major(table, flat_idx, interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, idx_ref: (idx_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, idx_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, d), table.dtype),
        name="ff_gather_rows",
        interpret=interpret,
    )(flat_idx.astype(jnp.int32), table.reshape(-1, 1, d))
    return out.reshape(n, d)


def _scatter_add_kernel(meta_ref, table_ref, upd_ref, out_ref, row_vmem,
                        sem_in, sem_out):
    # out_ref aliases table_ref (same HBM buffer): RMW over the touched
    # rows with double-buffered row DMAs.  The caller has collapsed
    # duplicate-id RUNS (``_collapse_runs``): meta_ref holds
    # [num_runs, row_0, row_1, ...] where adjacent rows always differ
    # and upd_ref[k] is the pre-combined update for run k.
    #
    # Pipeline: load(k+1) overlaps store(k).  Safety argument:
    #   - load(k+1) vs store(k): adjacent runs -> different rows.
    #   - load(k+1) vs any store(j<=k-1): store(k-1) is waited in
    #     iteration k before load(k+1) starts, and inductively every
    #     earlier store was waited in its own successor iteration — so
    #     all stores <= k-1 are complete.  Duplicate rows at ANY
    #     distance are therefore ordered.
    # Each semaphore is started/waited exactly once per run: load(k)
    # waits in iteration k; store(k) waits in iteration k+1 (the final
    # store in the epilogue).  The serial form this replaces exposed
    # two full HBM round-trips of latency per row.
    nr = meta_ref[0]

    def load(k, buf):
        return pltpu.make_async_copy(
            out_ref.at[pl.ds(meta_ref[1 + k], 1), :],
            row_vmem.at[buf], sem_in.at[buf],
        )

    def store(k, buf):
        return pltpu.make_async_copy(
            row_vmem.at[buf],
            out_ref.at[pl.ds(meta_ref[1 + k], 1), :], sem_out.at[buf],
        )

    load(0, 0).start()

    def body(k, carry):
        buf = lax.rem(k, 2)
        nxt = 1 - buf
        load(k, buf).wait()
        row_vmem[buf] = row_vmem[buf] + upd_ref[pl.ds(k, 1), :]
        store(k, buf).start()

        @pl.when(k + 1 < nr)
        def _():
            @pl.when(k >= 1)
            def _():
                store(k - 1, nxt).wait()

            load(k + 1, nxt).start()

        return carry

    lax.fori_loop(0, nr, body, 0)
    # Drain: the last iteration skips the store(k-1) wait (no next
    # load), so both trailing stores are waited here.
    @pl.when(nr >= 2)
    def _():
        store(nr - 2, lax.rem(nr, 2)).wait()

    store(nr - 1, lax.rem(nr - 1, 2)).wait()


def _scatter_add_lane_kernel(meta_ref, table_ref, upd_ref, out_ref, ring,
                             sem_in, sem_out, *, n, t_shift):
    # out_ref aliases table_ref, the (T, D, V) view in HBM: RMW of one
    # (D, 128) block of 128 neighbouring rows per run.  The caller has
    # SORTED the ids by block, so every block is one run and no two
    # DMAs of a call touch the same memory: loads run ``ahead`` runs in
    # front and stores drain behind with nothing to order but the
    # ring's own slots.  meta_ref holds [num_runs, start[n], key[n],
    # val[n]], the last two in sorted order: run k owns the positions
    # ``start[k] .. start[k+1]`` (the last run up to n), all of block
    # ``key & mask`` of table ``key >> t_shift``; position j is column
    # ``val >> 7`` of upd_ref (the update matrix transposed, (D, n_pad),
    # in the caller's order) and lane ``val & 127`` of its block.  Each
    # column is lane-placed here, so no (n, D, 128) expansion exists,
    # and a run's columns are added in the caller's order (the sort is
    # stable): the same row twice gets the same adds in the same order
    # as one id at a time would give it.
    nr = meta_ref[0]
    depth = ring.shape[0]
    ahead = depth // 2
    lanes = _lane_iota(ring.shape[1])

    def block(k):
        key = meta_ref[1 + n + meta_ref[1 + k]]
        start = pl.multiple_of((key & ((1 << t_shift) - 1)) * _LANES, _LANES)
        return out_ref.at[key >> t_shift, :, pl.ds(start, _LANES)]

    def load(k):
        slot = lax.rem(k, depth)
        return pltpu.make_async_copy(block(k), ring.at[slot], sem_in.at[slot])

    def store(k):
        slot = lax.rem(k, depth)
        return pltpu.make_async_copy(ring.at[slot], block(k), sem_out.at[slot])

    for k in range(ahead):
        @pl.when(k < nr)
        def _():
            load(k).start()

    def member(j, acc):
        val = meta_ref[1 + 2 * n + j]
        col, dst = val >> 7, val & (_LANES - 1)
        src = col & (_LANES - 1)
        chunk = upd_ref[:, pl.ds(pl.multiple_of(col - src, _LANES), _LANES)]
        return acc + jnp.where(lanes == dst, _move_lane(chunk, src, dst), 0.0)

    def body(k, carry):
        slot = lax.rem(k, depth)
        load(k).wait()
        # meta_ref[2 + k] at the last run is key[0]: in bounds, unused.
        end = jnp.where(k + 1 < nr, meta_ref[2 + k], n)
        ring[slot] = lax.fori_loop(meta_ref[1 + k], end, member, ring[slot])
        store(k).start()

        @pl.when(k + ahead < nr)
        def _():
            # The slot load(k + ahead) fills was run k + ahead - depth's.
            @pl.when(k + ahead >= depth)
            def _():
                store(k + ahead - depth).wait()

            load(k + ahead).start()

        return carry

    def drain(k, carry):
        store(k).wait()
        return carry

    lax.fori_loop(0, nr, body, 0)
    # The stores no later load had to wait for: the last ``depth`` runs'.
    lax.fori_loop(jnp.maximum(nr - depth, 0), nr, drain, 0)


def _scatter_rows_lane_major(table, flat_idx, updates, interpret):
    n = flat_idx.shape[0]
    v, d = table.shape[-2:]
    n_pad = _round_up(n, _LANES)
    # Sorts only: an s32[n] gather or scatter costs the chip 40-60 us
    # at n = 8192, a sort 7 (PERF.md §6, PR 28).
    t, blk, lane = _block_ids(flat_idx, v)
    t_shift = max(pl.cdiv(v, _LANES) - 1, 1).bit_length()
    pos = jnp.arange(n, dtype=jnp.int32)
    key, val = lax.sort(
        ((t << t_shift) | blk, (pos << 7) | lane), num_keys=1, is_stable=True
    )
    new = jnp.concatenate([jnp.ones((1,), bool), key[1:] != key[:-1]])
    starts = lax.sort(jnp.where(new, pos, n + pos))  # the runs' first, in order
    meta = jnp.concatenate(
        [jnp.sum(new, dtype=jnp.int32)[None], starts, key, val]
    )
    upd_t = jnp.pad(updates.astype(table.dtype).T, ((0, 0), (0, n_pad - n)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),      # table (HBM)
            pl.BlockSpec(memory_space=pltpu.VMEM),  # updates, transposed
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((_LANE_RING, d, _LANES), table.dtype),
            pltpu.SemaphoreType.DMA((_LANE_RING,)),
            pltpu.SemaphoreType.DMA((_LANE_RING,)),
        ],
    )
    lane_major = _lane_major_view(table, interpret)
    out = pl.pallas_call(
        functools.partial(_scatter_add_lane_kernel, n=n, t_shift=t_shift),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(lane_major.shape, table.dtype),
        input_output_aliases={1: 0},  # inputs incl. scalar prefetch
        name="ff_scatter_add_rows",
        interpret=interpret,
    )(meta, lane_major, upd_t)
    out = out[:, :, :v].reshape(table.shape[:-2] + (d, v))
    return jnp.swapaxes(out, -1, -2)


def scatter_add_rows(table, flat_idx, updates,
                     interpret: Optional[bool] = None):
    """``table.at[flat_idx].add(updates)`` touching only the N rows;
    the table buffer is aliased (donated) and updated in place.
    ``table`` is ``(R, D)`` or a stacked ``(T, V, D)`` with ``flat_idx``
    over its ``T*V`` rows (see ``gather_rows``); the result has the
    table's shape.

    ``lane_major`` tables (``rows_addressing``) are read-modify-written
    in ``(D, 128)`` blocks of their transposed view.  Otherwise: Mosaic
    only accepts 128-lane HBM row slices (v5e-measured: d=64
    and d=256 both reject, d=128 compiles), so the kernel runs
    on a ``(P, 128)`` physical view: ``d`` a multiple of 128 splits
    each row into column blocks with expanded ids; ``d`` dividing 128
    packs ``128/d`` logical rows per physical row, lane-placing each
    update by one-hot expansion (exact: one-hot multiply adds zeros).
    Duplicate physical targets — duplicate ids OR distinct logical rows
    sharing a block or a packed row — stay correct because
    ``_collapse_runs`` folds adjacent duplicates into single runs (so
    the pipelined kernel's overlapping load/store never touch the same
    target) and the kernel orders non-adjacent runs via its store-wait
    protocol; the lane-major kernel is fed block-sorted ids, so each
    block is one run.  The kernels must ONLY be fed run-collapsed
    indices.  The same reduction
    runs under ``interpret`` so CPU tests cover it; dims fitting
    neither case (e.g. 96) are interpret-only and raise on TPU
    (``rows_addressing`` gates them off)."""
    if interpret is None:
        interpret = _interpret_default()
    n = flat_idx.shape[0]
    d = table.shape[-1]
    if n == 0:
        # Degenerate batch: the pipelined kernel unconditionally starts
        # load(0) and waits the drain store(nr-1), both invalid at
        # nr=0, and _collapse_runs' run_id[-1] traces an IndexError.
        # Static shape, so a Python-level no-op preserves the old
        # sequential kernel's behavior.
        return table
    if _lane_major_shape(table.shape):
        return _scatter_rows_lane_major(table, flat_idx, updates, interpret)
    shape, table = table.shape, table.reshape(-1, d)
    num_rows = table.shape[0]
    if d % _LANES == 0:
        c = d // _LANES
        idx = (flat_idx[:, None] * c + jnp.arange(c)[None, :]).reshape(-1)
        upd = updates.reshape(n * c, _LANES)
    elif _LANES % d == 0 and (num_rows * d) % _LANES == 0:
        k = _LANES // d
        idx = flat_idx // k
        onehot = jax.nn.one_hot(flat_idx % k, k, dtype=table.dtype)
        upd = (onehot[:, :, None] * updates[:, None, :]).reshape(n, _LANES)
    elif interpret:
        return _scatter_rows_128(table, flat_idx, updates, interpret).reshape(shape)
    else:
        raise ValueError(
            f"scatter_add_rows: row dim {d} needs d % 128 == 0 or "
            f"128 % d == 0 (with 128-aligned table volume) on TPU"
        )
    out = _scatter_rows_128(table.reshape(-1, _LANES), idx, upd, interpret)
    return out.reshape(shape)


def _scatter_rows_128(table, flat_idx, updates, interpret):
    """The raw RMW kernel driver; on hardware ``table`` must be
    (P, 128) (interpret mode accepts any width)."""
    n = flat_idx.shape[0]
    d = table.shape[1]
    meta, upd_runs = _collapse_runs(flat_idx, updates.astype(table.dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),      # table (HBM)
            pl.BlockSpec(memory_space=pltpu.VMEM),  # per-run updates
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, 1, d), table.dtype),     # double-buffered row
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        _scatter_add_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={1: 0},  # inputs incl. scalar prefetch
        name="ff_scatter_add_rows",
        interpret=interpret,
    )(meta, table, upd_runs)


def _collapse_runs(flat_idx, updates):
    """Collapse adjacent duplicate ids into runs for the scatter
    kernel: returns ``meta = [num_runs, row_0, row_1, ...]`` (i32,
    n+1) and per-run summed updates (n, d).  Adjacent meta rows always
    differ, which is what makes the kernel's load/store overlap safe;
    non-adjacent duplicates become separate runs whose ordering the
    kernel enforces.  Cost: one cumsum + one segment-sum over the
    update matrix — trivial next to the row DMAs it unblocks."""
    n = flat_idx.shape[0]
    idx = flat_idx.astype(jnp.int32)
    new = jnp.concatenate(
        [jnp.ones((1,), bool), idx[1:] != idx[:-1]]
    )
    run_id = jnp.cumsum(new.astype(jnp.int32)) - 1
    num_runs = run_id[-1] + 1
    run_row = jnp.zeros((n,), jnp.int32).at[run_id].set(idx)
    upd_runs = jax.ops.segment_sum(updates, run_id, num_segments=n)
    meta = jnp.concatenate([num_runs[None], run_row])
    return meta, upd_runs


# ---------------------------------------------------------------------------
# latent attention (MLA) and grouped expert products: forward-only serving
# kernels, reachable from ops/attention.py::LatentAttention and
# ops/moe.py::MixtureOfExperts only when the serving executor drives them
# ---------------------------------------------------------------------------

def _prefill_block(t: int) -> int:
    """Rows of a query (and key) block of the serving prefill forwards:
    the largest of 512, 256, 128 that divides ``t`` (the gates hold
    ``t`` to whole 128-row blocks)."""
    block = 512
    while t % block:
        block //= 2
    return block


def _stream_softmax_step(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, scale,
                         keep=None, drop_dead_rows=False):
    """One ``(block, block)`` tile of scores folded into the running
    softmax in scratch: ``m`` the rows' maxima, ``l`` their sums, ``acc``
    the weighted values.  ``keep()`` gives the tile's mask (``None``:
    every score is live).  ``drop_dead_rows``: a query row may find no
    live key in this tile (its running max is then still the floor),
    and such a row adds nothing."""
    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    _fold_scores(_nt_f32(q, k) * scale, v, m_scr, l_scr, acc_scr, keep,
                 drop_dead_rows)


def _nt_f32(a, b):
    """``a @ b.T`` on the matrix unit into float32."""
    return lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=_mxu_precision(a.dtype),
        preferred_element_type=jnp.float32,
    )


def _fold_scores(s, v, m_scr, l_scr, acc_scr, keep=None,
                 drop_dead_rows=False):
    """``_stream_softmax_step`` from the tile's scaled float32 scores
    ``s`` (bq, bk) on: the mask, the running maximum and sum, and the
    product with ``v`` (bk, dv) into the accumulator."""
    if keep is not None:
        s = jnp.where(keep(), s, _NEG_INF)
    m = m_scr[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if drop_dead_rows:
        p = jnp.where(s > _NEG_INF, p, 0.0)
    corr = jnp.exp(m - m_new)
    acc_scr[...] = acc_scr[...] * corr + lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        precision=_mxu_precision(v.dtype),
        preferred_element_type=jnp.float32,
    )
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new


def flash_uneven_supported(q_shape: Tuple[int, ...], v_width: int) -> bool:
    """Whether ``flash_fwd_uneven`` applies to (b, h, t, qk) queries and
    keys with ``v_width``-wide values: whole 128-row blocks of the
    sequence (the test suite's AOT compile holds the gate to what the
    TPU compiler accepts)."""
    if len(q_shape) != 4:
        return False
    _, _, t, qk = q_shape
    return t >= 128 and t % 128 == 0 and qk >= 8 and v_width >= 8


#: Rows of a query (and key) block of the causal prefill forward, the
#: largest first.
_CAUSAL_BLOCKS = (1024, 512, 256, 128)
#: What a grid step's blocks, scratch and score tile may take of VMEM.
_CAUSAL_VMEM_LIMIT = 64 << 20


def flash_uneven_walk(q_shape: Tuple[int, ...], h_kv: int, v_width: int,
                      dtype) -> Tuple[int, int, int]:
    """``(block, query heads, KV heads)`` of a grid step of
    ``flash_fwd_uneven`` on (b, h, t, qk) queries over ``h_kv`` heads of
    keys and ``v_width``-wide values: the heads as ``_kept_heads`` takes
    them (a group over its one KV head, or four heads with K and V of
    their own), and the largest block of ``_CAUSAL_BLOCKS`` that divides
    ``t`` and holds the step inside half of ``_CAUSAL_VMEM_LIMIT`` (the
    compiler's own temporaries take the rest); fewer heads of a group a
    step where not even the smallest block holds them all.

    v5e, bf16, ms a call by the largest block allowed (my chip runs, PR 50,
    ``tools/time_prefill_kernel.py``; the parent's square grid of 512 in
    brackets; PERF.md section 6 has every reading): 48 heads over 8 at t
    32,768 1024: 92.59 (72.3% of peak by the t (t + 1) / 2 count), 512:
    127.05 (52.7%) [174.10]; at t 16,384 1024: 23.71 [42.59]; 32 heads of
    192 | 128 at t 16,384 1024: 21.37 (65.3%) [35.67], at t 4,608 512:
    2.277 (48.5%) [3.199], at t 512: 0.0428 [0.0686]; 32 over 4 at t
    2,048 1024: 0.339 [0.538].  A bucket of 8,704 or 4,608 rows keeps
    512."""
    _, h, t, qk = q_shape
    size = jnp.dtype(dtype).itemsize
    lanes = lambda n: _round_up(n, _LANES)

    def step(block, heads, kv):
        # The heads' query and output blocks (two buffers each), their
        # float32 accumulator and two statistics (a lane tile a row), a
        # block of K and V a KV head (two buffers), and a block's float32
        # scores beside their weights.
        return 2 * heads * block * size * (lanes(qk) + lanes(v_width)) \
            + 4 * heads * block * (lanes(v_width) + 2 * _LANES) \
            + 2 * kv * block * size * (lanes(qk) + lanes(v_width)) \
            + block * block * (4 + size)

    heads, kv = _kept_heads(h, h_kv)
    while True:
        fits = [b for b in _CAUSAL_BLOCKS if t % b == 0
                and step(b, heads, kv) <= _CAUSAL_VMEM_LIMIT // 2]
        if fits or heads == 1:
            return (fits[0] if fits else _CAUSAL_BLOCKS[-1]), heads, kv
        # A group too wide for the smallest block: a divisor of it a step.
        heads = max(d for d in range(1, heads) if heads % d == 0)
        kv = min(kv, heads)


def flash_uneven_pairs(n: int):
    """The live ``(query block, key block)`` pairs of a causal sequence
    of ``n`` blocks in the order the grid walks them: a query block's own
    diagonal block first (it starts the running softmax), then the blocks
    below it down to block 0, where the output is written."""
    qi = np.repeat(np.arange(n), np.arange(1, n + 1))
    ki = np.concatenate([np.r_[i, np.arange(i - 1, -1, -1)] for i in range(n)])
    return qi.astype(np.int32), ki.astype(np.int32)


def _fwd_uneven_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, o_ref,
                       m_scr, l_scr, acc_scr, *, scale, per_kv):
    """Causal streamed forward with q·k and v of different widths, grid
    (head block, live pair): step ``s`` folds key block ``ki[s]`` into
    the running softmax of query block ``qi[s]`` of each of the step's
    heads, ``per_kv`` of them over one fetched K/V block.  A block above
    the diagonal is no step at all; a query block's diagonal block comes
    first and starts ``m``, ``l`` and ``acc`` (nothing zeroed, nothing
    rescaled) and is the only one masked.  A head's block is ONE basic
    block of a few dozen operations on whole ``(block, block)`` arrays:
    the product that makes the scores, the rows' arithmetic with the row
    sum lane-wide (elementwise adds; lanes merge once, at the emit: one
    lane reduction a row, the max, not two), the product that consumes
    the weights.  The compiler schedules such a block a vector register
    at a time whatever the arrays' height (4,888 bundles a (1,024, 1,024)
    block whole, 5,467 in tiles of 128-512 rows, 5,291 in ``_visit``'s
    32-row tiles), and a serving program traces and lowers the body once
    a bucket: ``_visit``'s thousand operations a block read +16 s of warm
    set-up a cell on the chip's host (PERF.md §6 PR 50)."""
    step = pl.program_id(1)
    qi, ki = qi_ref[step], ki_ref[step]
    heads, block, _ = q_ref.shape
    lanes = l_scr.shape[-1]

    def row_sum(p):
        return functools.reduce(
            jnp.add, [p[:, c:c + lanes] for c in range(0, block, lanes)])

    def fold(first):
        def head(j, _):
            g = j // per_kv
            s = _nt_f32(q_ref[j], k_ref[g]) * scale
            if first:
                row = lax.broadcasted_iota(jnp.int32, s.shape, 0)
                col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(col <= row, s, _NEG_INF)
                m_new = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp(s - m_new)
                l_scr[j] = row_sum(p)
            else:
                m = m_scr[j]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m - m_new)
                l_scr[j] = l_scr[j] * corr + row_sum(p)
            m_scr[j] = m_new
            out = lax.dot_general(
                p.astype(v_ref.dtype), v_ref[g], (((1,), (0,)), ((), ())),
                precision=_mxu_precision(v_ref.dtype),
                preferred_element_type=jnp.float32)
            acc_scr[j] = out if first else acc_scr[j] * corr + out

        lax.fori_loop(0, heads, head, None)

    pl.when(ki == qi)(lambda: fold(True))
    pl.when(ki != qi)(lambda: fold(False))

    @pl.when(ki == 0)
    def _emit():
        l = jnp.sum(l_scr[...], axis=-1, keepdims=True)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_fwd_uneven(q, k, v, scale: float,
                     interpret: Optional[bool] = None):
    """Causal attention ``softmax(q k^T * scale) v`` on (b, h, t, qk)
    queries and keys and (b, h, t, dv) values, ``qk != dv`` allowed
    (latent attention's expanded path: 192 against 128).  Keys and
    values may have fewer heads, ``h_kv`` dividing ``h`` (grouped-query
    attention): a group's query heads ride one grid step over one
    fetched K/V block, so no repeated copy of K or V exists and none is
    fetched twice.  Only the ``n (n + 1) / 2`` blocks a query can see
    are grid steps (:func:`flash_uneven_walk`).  Forward only.  Callers
    gate on :func:`flash_uneven_supported`."""
    if interpret is None:
        interpret = _interpret_default()
    return _fwd_uneven_call(q, k, v, scale=float(scale), interpret=interpret)


# A jit of its own, as ``_fwd_launch``: traced once a shape, and lowered
# once a program however many layers call it.
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _fwd_uneven_call(q, k, v, scale, interpret):
    b, h, t, qk = q.shape
    dv = v.shape[-1]
    h_kv = k.shape[1]
    assert h % h_kv == 0 and v.shape[1] == h_kv, (q.shape, k.shape, v.shape)
    block, heads, kv = flash_uneven_walk(q.shape, h_kv, dv, q.dtype)
    qi, ki = flash_uneven_pairs(t // block)
    # Head block i holds query heads i * heads ...: KV head i * heads //
    # group, in blocks of kv.
    head_map = lambda i, s, qi, ki: (i, qi[s], 0)
    kv_map = lambda i, s, qi, ki: (i * heads // (h // h_kv * kv), ki[s], 0)
    kernel = functools.partial(_fwd_uneven_kernel, scale=scale,
                               per_kv=heads // kv)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * h // heads, len(qi)),
            in_specs=[
                pl.BlockSpec((heads, block, qk), head_map),
                pl.BlockSpec((kv, block, qk), kv_map),
                pl.BlockSpec((kv, block, dv), kv_map),
            ],
            out_specs=pl.BlockSpec((heads, block, dv), head_map),
            scratch_shapes=[
                pltpu.VMEM((heads, block, 1), jnp.float32),
                pltpu.VMEM((heads, block, _LANES), jnp.float32),
                pltpu.VMEM((heads, block, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, t, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_CAUSAL_VMEM_LIMIT),
        name="ff_flash_fwd_uneven",
        interpret=interpret,
    )
    out = call(jnp.asarray(qi), jnp.asarray(ki), q.reshape(b * h, t, qk),
               k.reshape(b * h_kv, t, qk), v.reshape(b * h_kv, t, dv))
    return out.reshape(b, h, t, dv)


def flash_window_supported(q_shape: Tuple[int, ...], window: int) -> bool:
    """Whether ``flash_fwd_window`` applies to (b, h, t, hd) queries:
    ``flash_fwd_uneven``'s shapes (whole 128-row blocks)."""
    return window >= 1 and flash_uneven_supported(q_shape, q_shape[-1])


def flash_window_walk(t: int, window: int) -> Tuple[int, int]:
    """``(block, reach)`` of the banded forward over ``t`` positions:
    the rows of a query (and key) block, and how many key blocks a query
    block's band ``q - window < s <= q`` can intersect (the grid's last
    axis; a query block near the start skips those before position 0)."""
    block = _prefill_block(t)
    return block, -(-(window - 1) // block) + 1


def _fwd_window_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                       *, block, scale, window, reach):
    """``_fwd_uneven_kernel``'s streamed forward under another block
    walk: 3D grid (bh, q-block, step), step ``j`` of query block ``i``
    at key block ``i - (reach - 1) + j``, the ``reach`` blocks the band
    can touch and no other.  A block before the sequence is neither
    fetched (the index map clamps to block 0) nor computed."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    kb = qi - (reach - 1) + j
    q_start = qi * block
    k_start = kb * block

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def band():
        q_pos = q_start + lax.broadcasted_iota(jnp.int32, (block, block), 0)
        k_pos = k_start + lax.broadcasted_iota(jnp.int32, (block, block), 1)
        return (k_pos <= q_pos) & (k_pos > q_pos - window)

    def step(masked):
        _stream_softmax_step(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                             scale, keep=band if masked else None,
                             drop_dead_rows=masked)

    # A block every key of which lies inside every query's band needs
    # no mask (none at block == window: both blocks cross an edge).
    inside = jnp.logical_and(k_start + block - 1 <= q_start,
                             k_start > q_start + block - 1 - window)
    pl.when(jnp.logical_and(kb >= 0, inside))(lambda: step(False))
    pl.when(jnp.logical_and(kb >= 0, jnp.logical_not(inside)))(
        lambda: step(True))

    @pl.when(j == reach - 1)
    def _emit():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def flash_fwd_window(q, k, v, scale: float, window: int,
                     interpret: Optional[bool] = None):
    """Banded causal attention: query ``t`` over keys ``t - window < s
    <= t``, on (b, h, t, hd) queries and (b, h_kv, t, hd) keys and
    values, ``h_kv`` dividing ``h`` (a group's K and V reached through
    the index map, as ``flash_fwd_uneven`` does).  A query block visits
    the key blocks its band intersects (``flash_window_walk``) and no
    other: the work follows ``t * window``, not ``t^2 / 2``.  Forward
    only.  Callers gate on :func:`flash_window_supported`."""
    if interpret is None:
        interpret = _interpret_default()
    b, h, t, hd = q.shape
    h_kv = k.shape[1]
    group = h // h_kv
    assert h == group * h_kv and v.shape == k.shape, (q.shape, k.shape, v.shape)
    block, reach = flash_window_walk(t, window)
    kernel = functools.partial(_fwd_window_kernel, block=block, scale=scale,
                               window=int(window), reach=reach)

    def kv_map(bi, i, j):
        return (bi if group == 1 else bi // group,
                jnp.maximum(i - (reach - 1) + j, 0), 0)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, t // block, reach),
        in_specs=[
            pl.BlockSpec((1, block, hd), lambda bi, i, j: (bi, i, 0)),
            pl.BlockSpec((1, block, hd), kv_map),
            pl.BlockSpec((1, block, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block, hd), lambda bi, i, j: (bi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, hd), jnp.float32),
        ],
        name="ff_flash_fwd_window",
        interpret=interpret,
    )(q.reshape(b * h, t, hd), k.reshape(b * h_kv, t, hd),
      v.reshape(b * h_kv, t, hd))
    return out.reshape(b, h, t, hd)


#: Query heads with K and V of their own that one grid step of
#: ``attend_kept`` folds against one fetch of the mask tile (grouped
#: queries take a group a step instead).
_KEPT_HEADS = 4
#: What a grid step's blocks, scratch and tile may take of VMEM.
_KEPT_VMEM_LIMIT = 48 << 20


def _kept_heads(h: int, h_kv: int) -> Tuple[int, int]:
    """``(query heads, KV heads)`` a grid step of ``attend_kept`` takes:
    a group of query heads over its one KV head, or ``_KEPT_HEADS``
    heads with K and V of their own (as many as divide ``h``)."""
    if h != h_kv:
        return h // h_kv, 1
    n = _KEPT_HEADS
    while h % n:
        n //= 2
    return n, n


def attend_kept_supported(q_shape: Tuple[int, ...], k_shape: Tuple[int, ...],
                          v_width: int, shared: Optional[int]) -> bool:
    """Whether ``attend_kept`` applies to a chunk of (b, h, c, dk)
    queries over (b, h_kv, T, dk_own) keys with ``v_width``-wide values
    and a ``shared``-wide key part held once for all heads (``None``:
    the keys are whole): whole 128-row blocks of the chunk and of the
    keys, ``h_kv`` dividing ``h``, and a grid step's heads inside
    ``_KEPT_VMEM_LIMIT`` (the test suite's AOT compile holds the gate to
    what the TPU compiler accepts)."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    _, h, c, dk = q_shape
    _, h_kv, t, own = k_shape
    if h_kv < 1 or h % h_kv or c < 128 or c % 128 or t % 128 or t < c:
        return False
    if dk != own + (shared or 0) or own < 8 or v_width < 8 or \
            (shared is not None and shared < 8):
        return False
    heads, kv = _kept_heads(h, h_kv)
    lanes = lambda n: _round_up(n, _LANES)
    # Bytes of a grid step at 2 B a value: the heads' query and output
    # blocks (two buffers each), their float32 accumulator and the two
    # statistics (a lane tile a row), a 512-key block of K and V (two
    # buffers), and a float32 tile's scores, mask and weights.  Half the
    # limit: the compiler's own temporaries take the rest.
    step = 2 * heads * c * 2 * (lanes(dk) + lanes(v_width)) \
        + 4 * heads * c * (lanes(v_width) + 2 * _LANES) \
        + 2 * kv * 512 * 2 * (lanes(own) + lanes(v_width)) + 16 * c * 512
    return step <= _KEPT_VMEM_LIMIT // 2


def _attend_kept_kernel(start_ref, q_ref, k_ref, v_ref, *refs, c, block_k,
                        scale, group, own):
    """Grid (batch x head block, key block).  A step folds one key
    block into the running softmax of each of the block's query heads
    under the chunk's mask tile, fetched once for all of them.  Key
    blocks past the chunk's last row are neither fetched (the index
    maps clamp to the last block it can see) nor computed; the output
    is written at that block."""
    ks_ref = refs[0] if own is not None else None
    keep_ref, o_ref, m_scr, l_scr, acc_scr = refs[-5:]
    kb = pl.program_id(1)
    last = lax.div(start_ref[0] + c - 1, block_k)
    heads = q_ref.shape[0]

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(kb <= last)
    def _fold():
        live = keep_ref[0].astype(jnp.int32) != 0               # (c, bk)

        def head(j, _):
            k, v = k_ref[j // group], v_ref[j // group]
            if own is None:
                s = _nt_f32(q_ref[j], k)
            else:
                s = _nt_f32(q_ref[j, :, :own], k) \
                    + _nt_f32(q_ref[j, :, own:], ks_ref[0])
            # A dropped score is -inf over a finite floor of the maxima:
            # its weight is exp(-inf) = 0 whatever the row has seen, so a
            # row that keeps nothing in this tile adds nothing.
            _fold_scores(jnp.where(live, s * scale, -jnp.inf), v,
                         m_scr.at[j], l_scr.at[j], acc_scr.at[j])

        lax.fori_loop(0, heads, head, None)

    @pl.when(kb == last)
    def _emit():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def attend_kept(q, k, v, keep, start, scale: float, shared_k=None,
                interpret: Optional[bool] = None):
    """A selected prefill's masked chunk: ``softmax(q k^T * scale) v``
    of ``c`` query rows at positions ``start .. start + c - 1`` over the
    keys ``keep`` (b, c, width) marks (one mask for every head: the
    causal edge is in it), streamed a key block at a time and no further
    than the chunk's own last row.  ``q`` (b, h, c, dk); ``k`` (b, h_kv,
    T, dk_own) and ``v`` (b, h_kv, T, dv) with ``T >= width``, whole:
    only the blocks the walk names are fetched, and query head ``j``
    reads head ``j // (h // h_kv)``; ``shared_k`` (b, T, r): a key part
    held once for all heads, scored against the queries' trailing ``r``
    values into the same float32 tile.  Every row keeps at least one
    key.  Float32 statistics, the weights cast to ``v``'s dtype for the
    product.  (b, h, c, dv) in ``q``'s dtype.  Forward only.  Callers
    gate on :func:`attend_kept_supported`."""
    if interpret is None:
        interpret = _interpret_default()
    return _attend_kept_call(q, k, v, shared_k, keep.astype(jnp.int8),
                             jnp.asarray(start, jnp.int32).reshape(1),
                             scale=float(scale), interpret=interpret)


# A jit of its own, as ``_decode_call``: traced once a shape, not once a
# chunk loop of every layer.
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _attend_kept_call(q, k, v, shared_k, keep, start, scale, interpret):
    b, h, c, dk = q.shape
    h_kv, own = k.shape[1], k.shape[-1]
    dv, width = v.shape[-1], keep.shape[-1]
    heads, kv = _kept_heads(h, h_kv)
    block_k = _prefill_block(width)
    per_batch = h // heads
    assert dk == own + (0 if shared_k is None else shared_k.shape[-1]) \
        and keep.shape == (b, c, width) and width <= k.shape[2], \
        (q.shape, k.shape, keep.shape)

    def at(j, start_ref):
        return jnp.minimum(j, lax.div(start_ref[0] + c - 1, block_k))

    head_map = lambda i, j, s: (i, 0, 0)
    kv_map = lambda i, j, s: (i, at(j, s), 0)
    in_specs = [
        pl.BlockSpec((heads, c, dk), head_map),
        pl.BlockSpec((kv, block_k, own), kv_map),
        pl.BlockSpec((kv, block_k, dv), kv_map),
    ]
    operands = [q.reshape(b * h, c, dk), k.reshape(b * h_kv, -1, own),
                v.reshape(b * h_kv, -1, dv)]
    if shared_k is not None:
        in_specs.append(pl.BlockSpec(
            (1, block_k, shared_k.shape[-1]),
            lambda i, j, s: (i // per_batch, at(j, s), 0)))
        operands.append(shared_k)
    in_specs.append(pl.BlockSpec(
        (1, c, block_k), lambda i, j, s: (i // per_batch, 0, at(j, s))))
    operands.append(keep)
    kernel = functools.partial(
        _attend_kept_kernel, c=c, block_k=block_k, scale=scale,
        group=h // h_kv, own=None if shared_k is None else own)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * per_batch, width // block_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((heads, c, dv), head_map),
            scratch_shapes=[
                pltpu.VMEM((heads, c, 1), jnp.float32),
                pltpu.VMEM((heads, c, 1), jnp.float32),
                pltpu.VMEM((heads, c, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, c, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_KEPT_VMEM_LIMIT),
        name="ff_attend_kept",
        interpret=interpret,
    )(start, *operands)
    return out.reshape(b, h, c, dv)


#: Positions the latent decode kernel fetches and scores at a time.
#: v5e-measured at 96 slots of 4096 positions, 58 of them 300..1500
#: long and 38 empty, and at 16 slots of 16384 (PERF.md §6 PR 38): 512
#: and 256 read the same on short rows, 512 a quarter less on long ones
#: (one loop turn and one DMA for twice the positions).
_MLA_DECODE_CHUNKS = (512, 256, 128)
#: Chunks of the cache in VMEM: one being scored, the others in flight
#: (0.266 / 0.238 / 0.237 ms a call at 2 / 3 / 4 on the short rows).
_MLA_DECODE_RING = 3


def mla_decode_chunk(s: int) -> int:
    """The granule the latent decode kernel fetches a slot's cache in:
    the largest chunk of whole 128-position lane tiles that divides
    ``s``; 0 if there is none."""
    return next((c for c in _MLA_DECODE_CHUNKS if s % c == 0), 0)


def mla_decode_supported(cache_shape: Tuple[int, ...], v_width: int) -> bool:
    """Whether ``mla_decode`` applies to a (B, row, max_seq) latent
    cache whose first ``v_width`` values of a row are the value."""
    if len(cache_shape) != 3:
        return False
    _, row, s = cache_shape
    return (mla_decode_chunk(s) > 0 and 0 < v_width <= row
            and v_width % 8 == 0 and row % 8 == 0)


def _mla_decode_kernel(len_ref, q_ref, col_ref, cache_in, o_ref, cache_ref,
                       ring, sem, wsem, cur, *, chunk, scale, dv):
    # One slot a grid step.  cache_ref is cache_in's buffer (aliased),
    # in HBM.  The live chunks of all slots, in order, are one stream of
    # (row, chunk) DMAs through ``ring``: chunk i of the stream lands in
    # ring slot ``i % depth``, and a slot once scored takes the next
    # chunk of the stream, a later slot's too.  ``cur`` carries the
    # stream over the grid: chunks scored so far, and the slot and chunk
    # to fetch next.
    del cache_in
    b = pl.program_id(0)
    slots = pl.num_programs(0)
    depth = ring.shape[0]
    q = q_ref[0]                                        # (h, row)
    h = q.shape[0]

    def live(i):
        return lax.div(len_ref[i] + (chunk - 1), chunk)

    def fetch(i, j, k):
        start = pl.multiple_of(j * chunk, chunk)
        return pltpu.make_async_copy(
            cache_ref.at[i, :, pl.ds(start, chunk)], ring.at[k], sem.at[k])

    def fetch_next(k, fb, fj):
        @pl.when(fb < slots)
        def _():
            fetch(fb, fj, k).start()

        done = fj + 1 >= live(jnp.minimum(fb, slots - 1))
        return jnp.where(done, fb + 1, fb), jnp.where(done, 0, fj + 1)

    @pl.when(b == 0)
    def _prime():
        fb = fj = jnp.int32(0)
        for k in range(depth):
            fb, fj = fetch_next(k, fb, fj)
        cur[0], cur[1], cur[2] = jnp.int32(0), fb, fj

    def step(rows, state, valid=None):
        m, l, acc = state
        s = jnp.dot(q, rows, precision=_mxu_precision(rows.dtype),
                    preferred_element_type=jnp.float32) * scale
        if valid is not None:
            s = jnp.where(valid, s, _NEG_INF)           # (h, chunk) f32
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        acc = acc * corr + lax.dot_general(
            p.astype(rows.dtype), rows[:dv], (((1,), (1,)), ((), ())),
            precision=_mxu_precision(rows.dtype),
            preferred_element_type=jnp.float32,
        )                                               # (h, dv)
        return m_new, l * corr + jnp.sum(p, axis=-1, keepdims=True), acc

    n = live(b)
    pos = len_ref[b] - 1

    # Whole chunks below the one that holds ``pos``: nothing to mask.
    def whole(j, c):
        *state, i, fb, fj = c
        k = lax.rem(i, depth)
        fetch(b, j, k).wait()
        state = step(ring[k], state)
        return (*state, i + 1, *fetch_next(k, fb, fj))

    *state, i, fb, fj = lax.fori_loop(0, n - 1, whole, (
        jnp.full((h, 1), _NEG_INF, jnp.float32),
        jnp.zeros((h, 1), jnp.float32),
        jnp.zeros((h, dv), jnp.float32), cur[0], cur[1], cur[2]))

    # The chunk that holds ``pos``: this step's column goes into its
    # lane tile, the tile goes back to the cache, and positions past
    # ``pos`` are masked.
    k = lax.rem(i, depth)
    fetch(b, n - 1, k).wait()

    at = pos - (n - 1) * chunk
    tile, lane = _lane_tile(at // _LANES), lax.rem(at, _LANES)
    col = _move_lane(col_ref[:, _lane_tile(b // _LANES)], lax.rem(b, _LANES),
                     lane)
    ring[k, :, tile] = jnp.where(
        _lane_iota(col.shape[0]) == lane, col,
        ring[k, :, tile].astype(col.dtype)).astype(ring.dtype)
    write = pltpu.make_async_copy(
        ring.at[k, :, tile], cache_ref.at[b, :, _lane_tile(pos // _LANES)],
        wsem)
    write.start()
    _, l, acc = step(ring[k], state, valid=lax.broadcasted_iota(
        jnp.int32, (h, chunk), 1) <= at)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    write.wait()
    cur[0] = i + 1
    cur[1], cur[2] = fetch_next(k, fb, fj)


def mla_decode(q, col, cache, lengths, v_width: int, scale: float,
               interpret: Optional[bool] = None):
    """One absorbed latent-attention decode step, the step's own column
    written on the way: one query ``q`` (B, h, row) a head against the
    latent cache ``cache`` (B, row, max_seq): one ``row``-value column a
    token, shared by every head, positions along the lanes (the order
    the chip stores a row narrower than a whole number of lane tiles in:
    a (B, max_seq, 576) array would be copied into this order in front
    of every call).  ``col`` (B, row), the column of the token at
    position ``lengths - 1``, is stored there (in the cache's dtype);
    the query attends positions ``< lengths[b]``, its own among them.
    The score of position j is ``q . cache[:, j] * scale`` over the whole
    column (latent part and rotary key), the value its first ``v_width``
    entries.  ``lengths`` (B,) int32 in ``1..max_seq``.  What a slot
    costs follows its length: its live chunks
    (:func:`mla_decode_chunk`) are what is fetched and scored, and the
    lane tile that holds the new column is what is written.  Returns
    ``(out (B, h, v_width) in q.dtype, cache)``; donate the cache and
    the write is in place.  Callers gate on
    :func:`mla_decode_supported`."""
    if interpret is None:
        interpret = _interpret_default()
    return _mla_decode_call(q, col, cache, lengths, v_width=v_width,
                            scale=scale, interpret=interpret)


# A jit of its own, as ``_decode_call``: traced and lowered once a
# program, not once a layer.
@functools.partial(jax.jit,
                   static_argnames=("v_width", "scale", "interpret"))
def _mla_decode_call(q, col, cache, lengths, v_width, scale, interpret):
    b, row, s = cache.shape
    h = q.shape[1]
    chunk = mla_decode_chunk(s)
    kernel = functools.partial(_mla_decode_kernel, chunk=chunk, scale=scale,
                               dv=v_width)
    # The columns with the slots along the lanes: a slot's is moved to
    # its position's lane by one rotate.
    cols = jnp.pad(col.astype(jnp.float32).T,
                   ((0, 0), (0, _round_up(b, _LANES) - b)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, row), lambda i, lens: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.VMEM),          # cols
            pl.BlockSpec(memory_space=pl.ANY),              # cache (HBM)
        ],
        out_specs=[
            pl.BlockSpec((1, h, v_width), lambda i, lens: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((_MLA_DECODE_RING, row, chunk), cache.dtype),
            pltpu.SemaphoreType.DMA((_MLA_DECODE_RING,)),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SMEM((3,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, v_width), q.dtype),
                   jax.ShapeDtypeStruct(cache.shape, cache.dtype)],
        # Operands count the scalar prefetch: 3 is the cache.
        input_output_aliases={3: 1},
        # The grid's steps share the ring and follow one another.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="ff_mla_decode",
        interpret=interpret,
    )(jnp.clip(lengths.astype(jnp.int32), 1, s), q, cols, cache)


#: VMEM the grouped product may use: two (K, N) expert blocks, double
#: buffered, are 12.6 MB at K=2048, N=768 in bf16 — past the 16 MB a
#: kernel gets by default once the row tiles are added (v5e has 128 MiB).
_GMM_VMEM_BYTES = 64 << 20
#: Of it, what the double-buffered expert blocks may take (41.9 MB at
#: K=4096, N=1280 gated): wider experts are walked in blocks of columns
#: (117 MB whole at K=7168, N=2048 gated).
_GMM_WEIGHT_BYTES = 48 << 20


def grouped_block_cols(k: int, n: int, weights: int, itemsize: int) -> int:
    """Columns of the expert blocks one grid step of ``grouped_matmul``
    holds: all ``n`` where ``weights`` double-buffered (k, n) blocks fit
    ``_GMM_WEIGHT_BYTES``, else the widest whole-lane-tile divisor of
    ``n`` that does."""
    fits = [c for c in range(_LANES, n + 1, _LANES) if n % c == 0
            and 2 * weights * k * c * itemsize <= _GMM_WEIGHT_BYTES]
    return max(fits) if fits else _LANES


def grouped_matmul_supported(k: int, n: int, dtype) -> bool:
    """Whether ``grouped_matmul`` applies to (rows, k) x (experts, k, n):
    whole 128-lane tiles on both widths."""
    return k % 128 == 0 and n % 128 == 0 and jnp.dtype(dtype).itemsize <= 4


def grouped_tile_rows(assignments: int, experts: int) -> int:
    """Rows of one tile of the grouped product: 128 where an expert
    sees a tile's worth of rows on average (prefill), else the 16 rows
    of one packed bf16 sublane tile (decode: a handful of rows an
    expert, where the expert's weights are the traffic)."""
    return 128 if assignments >= 128 * experts else 16


def _gmm_kernel(te_ref, nu_ref, x_ref, *refs, gated, tile_axis):
    del te_ref
    o_ref = refs[-1]

    @pl.when(pl.program_id(tile_axis) < nu_ref[0])
    def _tile():
        x = x_ref[...]
        exact = _mxu_precision(x.dtype)
        y = jnp.dot(x, refs[0][0], precision=exact,
                    preferred_element_type=jnp.float32)
        if gated:
            up = jnp.dot(x, refs[1][0], precision=exact,
                         preferred_element_type=jnp.float32)
            y = y * jax.nn.sigmoid(y) * up              # silu(gate) * up
        o_ref[...] = y.astype(o_ref.dtype)


def grouped_matmul(x, w, tile_expert, tiles_used, tile_rows: int,
                   w_up=None, interpret: Optional[bool] = None):
    """Grouped matrix product over rows sorted by expert and padded so
    that every tile of ``tile_rows`` rows belongs to one expert:
    ``out[i] = x[i] @ w[tile_expert[i // tile_rows]]`` for the first
    ``tiles_used`` tiles (rows of later tiles are left unwritten).
    With ``w_up`` the gated form ``silu(x @ w[e]) * (x @ w_up[e])``.

    ``x`` (rows, K), rows a multiple of ``tile_rows``; ``w`` (E, K, N);
    ``tile_expert`` (rows // tile_rows,) int32, with the tiles past
    ``tiles_used`` repeating the last used tile's expert, so that an
    unused tile moves nothing: an expert's block is fetched once for
    each run of its tiles, i.e. once a call for every touched expert.
    Experts too wide for VMEM (``grouped_block_cols``) are walked a block
    of columns at a time, the tiles inside: every touched expert's
    columns still move once a call, the row tiles once a block.
    Forward only.  Callers gate on :func:`grouped_matmul_supported`."""
    if interpret is None:
        interpret = _interpret_default()
    rows, k = x.shape
    n = w.shape[-1]
    n_tiles = rows // tile_rows
    gated = w_up is not None
    weights = (w, w_up) if gated else (w,)
    cols = grouped_block_cols(k, n, len(weights), w.dtype.itemsize)

    def last_used(i, nu):
        return jnp.minimum(i, jnp.maximum(nu[0] - 1, 0))

    if cols == n:
        grid = (n_tiles,)
        row_map = lambda i, te, nu: (last_used(i, nu), 0)
        w_map = lambda i, te, nu: (te[i], 0, 0)
        out_map = row_map
    else:
        grid = (n // cols, n_tiles)
        row_map = lambda j, i, te, nu: (last_used(i, nu), 0)
        w_map = lambda j, i, te, nu: (te[i], 0, j)
        out_map = lambda j, i, te, nu: (last_used(i, nu), j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[pl.BlockSpec((tile_rows, k), row_map)]
        + [pl.BlockSpec((1, k, cols), w_map) for _ in weights],
        out_specs=pl.BlockSpec((tile_rows, cols), out_map),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, gated=gated, tile_axis=len(grid) - 1),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_GMM_VMEM_BYTES),
        name="ff_grouped_matmul",
        interpret=interpret,
    )(tile_expert.astype(jnp.int32),
      jnp.reshape(tiles_used, (1,)).astype(jnp.int32), x, *weights)


# ---------------------------------------------------------------------------
# gated delta-rule linear attention (Kimi Delta Attention, arXiv:2510.26692):
# a recurrent state a head, decayed a key channel.  Forward only, reachable
# from ops/delta_attention.py::KimiDeltaAttention when the serving executor
# drives it; that module's plain recurrence is the oracle.
# ---------------------------------------------------------------------------

#: Tokens a chunk of the prefill scan, and rows of the sub-blocks its
#: intra-chunk decays are taken inside.
KDA_CHUNK = 64
_KDA_SUB = 16
_HIGHEST = lax.Precision.HIGHEST


def kda_supported(d_k: int, d_v: int) -> bool:
    """Whether ``kda_chunk`` and ``kda_decode`` take heads of these
    widths: whole lane tiles."""
    return d_k % _LANES == 0 and d_v % _LANES == 0


#: Heads a grid step of the prefill scan walks, and how many of them
#: are written side by side in the loop's body (PERF.md §6 PR 46).
_KDA_HEADS = 8
_KDA_TOGETHER = 2


# A jit of its own inside the kernel: the sixteen columns are traced once
# a call of the kernel, not once a block row of every head written side
# by side (what a serving program's set-up pays is the tracing).
@jax.jit
def _kda_columns(Gi, ki, qi, bi, at, x, b):
    """The diagonal block of one block row of ``_KDA_SUB`` rows, a column
    at a time: ``Gi``, ``ki``, ``qi`` (s, d) the rows' running decays,
    keys and queries, ``bi`` (s, 1) their betas, ``at`` (s, C) the lane's
    column counted from the block's first, ``x`` (s, d_v + d_k) the
    block's right-hand sides, corrected by the rows above the block, and
    ``b`` (s, C) its row of ``B``, filled left of the diagonal block.
    Returns the solved ``x`` and the filled ``b``."""
    s = Gi.shape[0]
    sub = lax.broadcasted_iota(jnp.int32, at.shape, 0)
    below = lax.broadcasted_iota(jnp.int32, (s, 1), 0)
    kb = bi * ki
    for i in range(s):
        ki_dec = ki[i:i + 1] * jnp.exp(jnp.minimum(Gi - Gi[i:i + 1], 0.0))
        b = jnp.where((at == i) & (sub >= i),
                      jnp.sum(qi * ki_dec, axis=1, keepdims=True), b)
        if i < s - 1:
            ba = jnp.sum(kb * ki_dec, axis=1, keepdims=True)
            x = x - jnp.where(below > i, ba, 0.0) * x[i:i + 1]
    return x, b


def _kda_chunk_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref,
                      o_ref, s_ref, st_scr, sol_scr, bm_scr,
                      *, heads, num_chunks):
    """One chunk of ``heads`` neighbouring heads, ``sol_scr.shape[0]`` of
    them at a time; the states ``st`` (d_v, d_k) arrive from the chunk
    before in ``st_scr``.  For a head, all in f32 in VMEM:

    - ``G``, the chunk's running sum of log decays (non-increasing), by
      shifted adds;
    - the decayed Gram matrices ``A[i, j] = sum_c k_i k_j exp(G_i - G_j)``
      (``j < i``) and ``B[i, j] = sum_c q_i k_j exp(G_i - G_j)`` (``j <=
      i``), a block row of ``_KDA_SUB`` rows at a time: against the
      earlier rows through the block's first row ``r`` (``exp(G_i - r)
      exp(r - G_j)``, both factors <= 1: one product on the matrix unit),
      inside the block a column at a time on the vector unit.  ``exp(G_i)
      exp(-G_j)`` overflows under a strong decay, so every exponent taken
      is a difference that is ``<= 0``;
    - ``[u0 | w] = (I + beta A)^-1 beta [v | k exp(G)]`` by forward
      substitution (never a Neumann product: its powers cancel
      catastrophically where keys repeat and beta nears 2): a block row
      is corrected by the solved rows above it in one product, then a
      solved row leaves the rows below it as soon as its column of ``A``
      exists;
    - the walk: ``u = u0 - w st^T`` the chunk's corrected values, ``o =
      q exp(G) st^T + B u`` its outputs, ``st' = st exp(G_last) + u^T (k
      exp(G_last - G))`` the state it hands on."""
    grp, step = pl.program_id(0), pl.program_id(1)
    c, s = KDA_CHUNK, _KDA_SUB
    dk, dv = q_ref.shape[1] // heads, v_ref.shape[1] // heads

    @pl.when(step == 0)
    def _first():
        st_scr[...] = s0_ref[...]

    def mm(x, y, dims):
        return lax.dot_general(x, y, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)

    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)

    def running_sum(x):
        """Inclusive sum down the rows, by shifted adds."""
        shift = 1
        while shift < c:
            if shift % 8:
                moved = jnp.where(row >= shift, pltpu.roll(x, shift, 0), 0.0)
            else:
                moved = jnp.concatenate(
                    [jnp.zeros((shift, x.shape[1]), x.dtype), x[:c - shift]], axis=0)
            x = x + moved
            shift *= 2
        return x

    lane = lax.broadcasted_iota(jnp.int32, (s, c), 1)
    head_of = lax.broadcasted_iota(jnp.int32, beta_ref.shape, 1)

    together = sol_scr.shape[0]
    each = range(together)

    def some(p, carry):
        # ``together`` heads side by side, statement by statement: their
        # chains (product, sixteen columns, product, ...) do not depend
        # on one another, and the schedule is filled from both.
        js = [p * together + r for r in each]
        at_k = [pl.ds(pl.multiple_of(j * dk, _LANES), dk) for j in js]
        at_v = [pl.ds(pl.multiple_of(j * dv, _LANES), dv) for j in js]
        q = [q_ref[:, at] for at in at_k]                          # (C, d) f32
        k = [k_ref[:, at] for at in at_k]
        beta = [jnp.sum(jnp.where(head_of == grp * heads + j, beta_ref[...], 0.0),
                        axis=1, keepdims=True) for j in js]        # (C, 1)
        G = [running_sum(g_ref[:, at]) for at in at_k]
        eg = [jnp.exp(G[r]) for r in each]
        rhs = [jnp.concatenate([beta[r] * v_ref[:, at_v[r]],
                                beta[r] * (k[r] * eg[r])], axis=1) for r in each]
        # The Gram matrices' block rows left of the diagonal wait for
        # nothing of the solve.
        ab = [{} for r in each]
        for lo in range(s, c, s):
            rows = slice(lo, lo + s)
            for r in each:
                ref = G[r][lo:lo + 1]
                left = jnp.exp(G[r][rows] - ref)
                right = k[r][:lo] * jnp.exp(ref - G[r][:lo])
                ab[r][lo] = mm(
                    jnp.concatenate([k[r][rows] * left, q[r][rows] * left], axis=0),
                    right, ((1,), (1,)))                           # (2 s, lo)
        for lo in range(0, c, s):
            rows = slice(lo, lo + s)
            x = [rhs[r][rows] for r in each]
            b = [jnp.zeros((s, c), jnp.float32) for r in each]
            if lo:
                for r in each:
                    x[r] = x[r] - mm(beta[r][rows] * ab[r][lo][:s],
                                     sol_scr[r, :lo], ((1,), (0,)))
            cols = [_kda_columns(G[r][rows], k[r][rows], q[r][rows],
                                 beta[r][rows], lane - lo, x[r], b[r])
                    for r in each]
            x, b = [xb[0] for xb in cols], [xb[1] for xb in cols]
            for r in each:
                sol_scr[r, rows] = x[r]
                bm_scr[r, rows] = b[r]
                if lo:
                    bm_scr[r, rows, :lo] = ab[r][lo][s:]
        for r in each:
            st, gl = st_scr[js[r]], G[r][c - 1:c]
            both = mm(jnp.concatenate([sol_scr[r, :, dv:], q[r] * eg[r]], axis=0),
                      st, ((1,), (1,)))                            # (2 C, d_v)
            u = sol_scr[r, :, :dv] - both[:c]
            o_ref[:, at_v[r]] = both[c:] + mm(bm_scr[r], u, ((1,), (0,)))
            st_scr[js[r]] = st * jnp.exp(gl) + mm(
                u, k[r] * jnp.exp(gl - G[r]), ((0,), (0,)))        # (d_v, d_k)
        return carry

    lax.fori_loop(0, heads // together, some, 0)

    @pl.when(step == num_chunks - 1)
    def _last():
        s_ref[...] = st_scr[...]


def kda_chunk(q, k, v, g, beta, state, interpret: Optional[bool] = None):
    """The gated delta rule over ``T`` tokens of ``N`` heads, in chunks
    of ``KDA_CHUNK``: for ``t = 0 .. T-1``

        S' = diag(exp(g_t)) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T;
        o_t = S^T q_t

    ``q``, ``k`` (T, N, d_k) (normalised and scaled by the caller), ``v``
    (T, N, d_v), ``g`` (T, N, d_k) log decays ``<= 0``, ``beta`` (T, N);
    ``state`` (N, d_v, d_k) f32, ``S`` transposed.  A token with ``g = 0``
    and ``beta = 0`` leaves the state as it is (how a caller ends a
    prompt inside a padded bucket).  Returns ``(o (T, N, d_v) f32,
    state)``.

    One kernel, ``ff_kda_chunk``, reads the operands where they lie
    (viewed ``(T, N d)``: a chunk of ``_KDA_HEADS`` neighbouring heads
    is a block) and walks the chunks of those heads in order with their
    states in VMEM; what a chunk needs besides the incoming state (the
    running decay, the decayed Gram matrices, the unit-triangular
    solve) it makes there too (``_kda_chunk_kernel``).  ``T`` a multiple
    of ``KDA_CHUNK``; callers gate on :func:`kda_supported`."""
    if interpret is None:
        interpret = _interpret_default()
    assert q.shape[0] % KDA_CHUNK == 0 and \
        kda_supported(q.shape[-1], v.shape[-1]), (q.shape, v.shape)
    return _kda_chunk_call(q, k, v, g, beta, state, interpret=interpret)


# A jit of its own, as ``_decode_call``: traced and lowered once a
# program, not once a delta layer.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_chunk_call(q, k, v, g, beta, state, interpret):
    t, n, dk = q.shape
    dv = v.shape[-1]
    c = KDA_CHUNK
    nc = t // c
    f32 = jnp.float32
    heads = next(h for h in range(min(_KDA_HEADS, n), 0, -1) if n % h == 0)
    together = _KDA_TOGETHER if heads % _KDA_TOGETHER == 0 else 1

    def rows(width):
        return pl.BlockSpec((c, heads * width), lambda i, j: (j, i))

    def flat(x):
        return x.astype(f32).reshape(t, n * x.shape[-1])

    states = pl.BlockSpec((heads, dv, dk), lambda i, j: (i, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kda_chunk_kernel, heads=heads, num_chunks=nc),
        grid=(n // heads, nc),
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk),
                  pl.BlockSpec((c, n), lambda i, j: (j, 0)), states],
        out_specs=[rows(dv), states],
        out_shape=[jax.ShapeDtypeStruct((t, n * dv), f32),
                   jax.ShapeDtypeStruct((n, dv, dk), f32)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), f32),
                        pltpu.VMEM((together, c, dv + dk), f32),
                        pltpu.VMEM((together, c, c), f32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ff_kda_chunk",
        interpret=interpret,
    )(flat(q), flat(k), flat(v), flat(g), beta.astype(f32), state.astype(f32))
    return o.reshape(t, n, dv), state


_KDA_DECODE_HEADS = 16


def _kda_decode_kernel(q_ref, k_ref, kb_ref, a_ref, vb_ref, s_ref,
                       o_ref, so_ref, *, heads):
    """``heads`` heads of one slot: each state tile (d_v, d_k) is
    decayed along its lanes, corrected by one outer product and read
    once by the query, all on the vector unit in f32.  Values and
    outputs ride with the heads along the lanes (a column a head)."""
    grp = pl.program_id(1)
    dv, hl = vb_ref.shape[1:]
    lane = lax.broadcasted_iota(jnp.int32, (dv, hl), 1)

    @pl.when(grp == 0)
    def _first():
        o_ref[...] = jnp.zeros_like(o_ref)

    def head(j, out):
        i = grp * heads + j

        def row(ref):
            return ref[0, pl.ds(i, 1), :]                          # (1, d_k)

        m = s_ref[0, j] * row(a_ref)
        v = jnp.sum(jnp.where(lane == i, vb_ref[0], 0.0), axis=1, keepdims=True)
        err = v - jnp.sum(m * row(kb_ref), axis=1, keepdims=True)  # (d_v, 1)
        m = m + err * row(k_ref)
        so_ref[0, j] = m
        o = jnp.sum(m * row(q_ref), axis=1, keepdims=True)
        return jnp.where(lane == i, o, out)

    o_ref[0] = lax.fori_loop(0, heads, head, o_ref[0])


def kda_decode(q, k, v, g, beta, state, interpret: Optional[bool] = None):
    """One token of the gated delta rule for every slot (``kda_chunk``'s
    recurrence at ``T = 1``): ``q``, ``k``, ``g`` (B, H, d_k), ``v``
    (B, H, d_v), ``beta`` (B, H), ``state`` (B, H, d_v, d_k) f32.
    Returns ``(o (B, H, d_v) f32, state)``; donate the state and it is
    read and written in place.  Callers gate on :func:`kda_supported`."""
    if interpret is None:
        interpret = _interpret_default()
    b, h, dk = q.shape
    dv = v.shape[-1]
    assert kda_supported(dk, dv), (q.shape, v.shape)
    f32 = jnp.float32
    heads = next(n for n in (_KDA_DECODE_HEADS, 8, 4, 2, 1) if h % n == 0)
    hl = _round_up(h, _LANES)
    beta = beta.astype(f32)[..., None]
    k = k.astype(f32)
    vb = jnp.pad(jnp.swapaxes(beta * v.astype(f32), 1, 2),
                 ((0, 0), (0, 0), (0, hl - h)))                    # (B, d_v, hl)
    rows = pl.BlockSpec((1, h, dk), lambda i, j: (i, 0, 0))
    cols = pl.BlockSpec((1, dv, hl), lambda i, j: (i, 0, 0))
    tiles = pl.BlockSpec((1, heads, dv, dk), lambda i, j: (i, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kda_decode_kernel, heads=heads),
        grid=(b, h // heads),
        in_specs=[rows, rows, rows, rows, cols, tiles],
        out_specs=[cols, tiles],
        out_shape=[jax.ShapeDtypeStruct((b, dv, hl), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ff_kda_decode",
        interpret=interpret,
    )(q.astype(f32), k, beta * k, jnp.exp(g.astype(f32)), vb, state)
    return jnp.swapaxes(o[:, :, :h], 1, 2), state
