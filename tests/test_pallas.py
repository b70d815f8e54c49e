"""Pallas flash-attention kernel vs. the naive softmax oracle.

Runs the identical kernel code the TPU compiles, under the Pallas
interpreter on the CPU test mesh (SURVEY.md §4: jax autodiff/naive
math as the numeric oracle for every hand kernel).
"""

import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops import pallas_kernels as pk


def naive_attention(q, k, v, causal):
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        t = scores.shape[-1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    attn = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", attn, v)


def make_qkv(rng, b=2, h=2, t=64, hd=16):
    shape = (b, h, t, hd)
    return tuple(
        jnp.asarray(rng.standard_normal(shape), jnp.float32) for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_naive(rng, causal):
    q, k, v = make_qkv(rng)
    out = pk.flash_attention(q, k, v, causal)
    ref = naive_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_lse_matches_logsumexp(rng):
    q, k, v = make_qkv(rng, t=32)
    _, lse = pk.flash_attention_lse(q, k, v, False)
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    ref = jax.scipy.special.logsumexp(scores, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_naive(rng, causal):
    q, k, v = make_qkv(rng, t=32, hd=8)
    cot = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, causal) * cot)

    def loss_naive(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal) * cot)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_naive = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for gf, gn in zip(g_flash, g_naive):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gn), atol=5e-5)


def test_flash_lse_cotangent(rng):
    """The lse output's gradient path (used by the ring merge) is exact."""
    q, k, v = make_qkv(rng, t=16, hd=8)
    cot = jnp.asarray(rng.standard_normal(q.shape[:3]), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(pk.flash_attention_lse(q, k, v, False)[1] * cot)

    def loss_naive(q, k, v):
        scale = 1.0 / np.sqrt(q.shape[-1])
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        return jnp.sum(jax.scipy.special.logsumexp(scores, axis=-1) * cot)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_naive = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for gf, gn in zip(g_flash, g_naive):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gn), atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_bfloat16(rng, causal):
    """bf16 backward: the kernels dot in the input dtype (ds/p cast to
    bf16 pre-dot) with the scale compensation applied post-dot in
    _dq_kernel/_dkv_kernel — gradients must track the f32 oracle
    within bf16 rounding."""
    qf, kf, vf = make_qkv(rng, t=32, hd=8)
    cot = jnp.asarray(rng.standard_normal(qf.shape), jnp.float32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (qf, kf, vf))

    def loss_flash(q, k, v):
        return jnp.sum(
            pk.flash_attention(q, k, v, causal).astype(jnp.float32) * cot
        )

    def loss_naive(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal) * cot)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_naive = jax.grad(loss_naive, argnums=(0, 1, 2))(qf, kf, vf)
    for gf, gn in zip(g_flash, g_naive):
        assert gf.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gn), atol=0.04, rtol=0.05
        )


def test_flash_uneven_block_sizes(rng):
    # t=48 forces a non-128 block divisor.
    q, k, v = make_qkv(rng, t=48)
    out = pk.flash_attention(q, k, v, True)
    ref = naive_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_bfloat16(rng):
    q, k, v = (x.astype(jnp.bfloat16) for x in make_qkv(rng, t=32))
    out = pk.flash_attention(q, k, v, False)
    assert out.dtype == jnp.bfloat16
    ref = naive_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), False
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2
    )


def test_flash_supported_gating():
    assert pk.flash_supported((2, 2, 128, 64))
    assert not pk.flash_supported((2, 2, 8, 64))      # too short
    assert not pk.flash_supported((2, 128, 64))       # wrong rank
    assert not pk.flash_supported((1, 1, 1 << 17, 128))  # K/V exceed VMEM


def test_flash_block_vmem_cap():
    # Long-context bf16 stays supported at the streaming block (512:
    # PR 34's kernels keep a row tile of a block alive, not the block,
    # and fit scoped VMEM there; the kernels before it needed 256 at
    # t=8192); f32 at the u=2M operand size stays gated off entirely
    # (ring attention covers it).
    assert pk.flash_supported((1, 1, 8192, 64), jnp.bfloat16)
    assert pk._flash_block(8192, 64, 2) == 512
    # Block 1024 up to 2048 positions in whole lane tiles (one block a
    # sequence up to 1024), at a head width of 128 as at 64; wider
    # heads, unread on the chip, shrink the block.
    assert pk._flash_block(1024, 64, 2) == 1024
    assert pk._flash_block(1024, 128, 2) == 1024
    assert pk._flash_block(2048, 128, 2) == 1024
    assert pk._flash_block(4096, 128, 2) == 512
    assert pk._flash_block(2048, 256, 2) == 256
    assert not pk.flash_supported((1, 1, 16384, 64), jnp.bfloat16)
    assert not pk.flash_supported((1, 1, 8192, 64), jnp.float32)
    # Unaligned short sequences keep their whole-dim single block.
    assert pk._flash_block(100, 64, 4) == 100


# -- the walk, and the cell's geometry ----------------------------------------


def _walk(t, block, causal, diag):
    """Every sub-block ``(q0, k0, rows, cols, masked)`` one head's call
    computes scores for, from the two host functions the kernels walk
    by: ``_block_order`` (the blocks a grid step visits) and
    ``_diag_walk`` (a straddling block's sub-blocks).  ``diag``
    ``"lower"``: the forward's and ``dq``'s grid steps, a query block
    each; ``"upper"``: ``dkv``'s, a K block each."""
    n = t // block
    sub, cells = pk._diag_walk(block)
    out = []
    for i in range(n):
        shape, first, lo, hi = pk._block_order(causal, diag, i, n)
        whole = list(range(lo, hi))
        if shape == "full":
            whole.insert(0, first)
        else:
            assert (shape, first) == (diag, i)
            out += [(i * block + r, i * block + c, sub, sub, m)
                    for r, c, m in cells]
        for b in whole:
            qb, kb = (i, b) if diag == "lower" else (b, i)
            out.append((qb * block, kb * block, block, block, False))
    return out


@pytest.mark.parametrize("diag", ["lower", "upper"])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t", [16, 96, 256, 512, 1024, 2048])
def test_flash_walk_covers_the_mask_once(t, causal, diag):
    """The walk as host integers, at the block the gate picks for bf16
    heads of 64, by query blocks (forward, dq) and by K blocks (dkv):
    the visited sub-blocks cover every live score exactly once, none
    lies wholly above the diagonal, only the ones the diagonal crosses
    are masked, and at t 1024 at least 85% of the computed scores are
    live (two 512-blocks a side, visited whole, gave 66.7%)."""
    block = pk._flash_block(t, 64, 2)
    assert block >= 8 and t % block == 0
    walk = _walk(t, block, causal, diag)
    seen = np.zeros((t, t), np.int32)
    for q0, k0, rows, cols, masked in walk:
        assert rows > 0 and cols > 0
        assert q0 + rows <= t and k0 + cols <= t
        crosses = causal and k0 + cols - 1 > q0      # holds a dead score
        assert masked == crosses, (q0, k0, rows, cols, masked)
        if causal:
            assert k0 <= q0 + rows - 1, "a sub-block wholly above the diagonal"
        seen[q0:q0 + rows, k0:k0 + cols] += 1
    live = np.tril(np.ones((t, t), bool)) if causal else np.ones((t, t), bool)
    assert (seen[live] == 1).all()
    assert seen.max() == 1
    if causal and t == 1024:
        assert live.sum() / seen.sum() >= 0.85
    # A straddling block's own walk: lane-tile sub-blocks where the
    # block is whole lane tiles, else the block itself.
    sub, cells = pk._diag_walk(block)
    assert sub == (128 if block % 128 == 0 else block)
    assert len(cells) == (block // sub) * (block // sub + 1) // 2


def _grads_and_lse(q, k, v, cot, cot_lse, causal, flash):
    def loss(q, k, v):
        if flash:
            o, lse = pk.flash_attention_lse(q, k, v, causal)
        else:
            qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
            scale = 1.0 / np.sqrt(q.shape[-1])
            s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf,
                           precision="highest") * scale
            if causal:
                t = s.shape[-1]
                s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
            lse = jax.scipy.special.logsumexp(s, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vf,
                           precision="highest")
        return (jnp.sum(o.astype(jnp.float32) * cot)
                + jnp.sum(lse * cot_lse)), (o, lse)

    (_, (o, lse)), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return o, lse, grads


@pytest.mark.parametrize("t", [256, 1024])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_cell_geometry_matches_naive(rng, t, dtype):
    """Forward, lse and the three gradients at the ``gpt2m.train``
    cell's geometry in small (2 heads of 64 at t 1024: one block of
    eight lane-tile sub-blocks a side) and at the 256 serving bucket,
    against the naive reference, at this file's tolerances."""
    qf, kf, vf = make_qkv(rng, b=1, h=2, t=t, hd=64)
    cot = jnp.asarray(rng.standard_normal(qf.shape), jnp.float32)
    cot_lse = jnp.asarray(rng.standard_normal(qf.shape[:3]), jnp.float32)
    q, k, v = (x.astype(dtype) for x in (qf, kf, vf))
    o, lse, grads = _grads_and_lse(q, k, v, cot, cot_lse, True, True)
    # the reference sees the operands the kernel saw (bf16-rounded)
    ro, rlse, rgrads = _grads_and_lse(q, k, v, cot, cot_lse, True, False)
    assert o.dtype == q.dtype and all(g.dtype == q.dtype for g in grads)
    f32 = dtype == "float32"
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(ro),
                               atol=2e-5 if f32 else 3e-2)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse),
                               atol=2e-5 if f32 else 3e-2)
    for g, rg in zip(grads, rgrads):
        if f32:
            np.testing.assert_allclose(np.asarray(g), np.asarray(rg),
                                       atol=5e-5)
        else:
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(rg, np.float32),
                                       atol=0.04, rtol=0.05)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block", [64, 128, 256])
def test_flash_launch_at_every_block_matches_the_tables(rng, block, causal):
    """The jitted launches take the block as a static argument (the
    cache's key carries what the table chose): at t 512 a 64 block (its
    own sub-block, eight a side), a 128 block (one lane tile) and a 256
    block (two sub-blocks a side, one interior block) all give what the
    table's single 512 block gives, forward and backward."""
    bh, t, hd = 2, 512, 64
    q, k, v, do = (jnp.asarray(rng.standard_normal((bh, t, hd)),
                               jnp.float32) for _ in range(4))
    assert pk._flash_block(t, hd, 4) == t
    o, lse_l = pk._fwd_call(q, k, v, causal, True)
    delta_l = jnp.broadcast_to(jnp.sum(o * do, axis=-1)[:, :, None],
                               (bh, t, pk.LSE_LANES))
    ref = (o, lse_l) + tuple(
        pk._bwd_call(q, k, v, do, lse_l, delta_l, causal, True))
    got = tuple(pk._fwd_launch(q, k, v, causal, True, block)) + tuple(
        pk._bwd_launch(q, k, v, do, lse_l, delta_l, causal, True, block))
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


# -- fused softmax cross-entropy -------------------------------------------


def _xent_oracle(logits, labels):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    nll = lse - jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    return nll, lse, jnp.argmax(logits, axis=-1)


def test_xent_forward_matches_oracle(rng):
    n, v = 32, 2048
    logits = jnp.asarray(rng.standard_normal((n, v)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, size=n), jnp.int32)
    nll, lse, pred = pk.softmax_xent(logits, labels)
    rn, rl, rp = _xent_oracle(logits, labels)
    np.testing.assert_allclose(np.asarray(nll), np.asarray(rn), atol=1e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rl), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(pred), np.asarray(rp))


def test_xent_grads_match_oracle(rng):
    n, v = 16, 1024
    logits = jnp.asarray(rng.standard_normal((n, v)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, size=n), jnp.int32)

    def loss_k(lg):
        nll, lse, _ = pk.softmax_xent(lg, labels)
        return jnp.mean(nll) + 0.1 * jnp.sum(lse)

    def loss_o(lg):
        rn, rl, _ = _xent_oracle(lg, labels)
        return jnp.mean(rn) + 0.1 * jnp.sum(rl)

    gk = jax.grad(loss_k)(logits)
    go = jax.grad(loss_o)(logits)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(go), atol=1e-5)


def test_xent_bfloat16(rng):
    n, v = 16, 1024
    logits = jnp.asarray(rng.standard_normal((n, v)), jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, v, size=n), jnp.int32)
    nll, _, _ = pk.softmax_xent(logits, labels)
    rn, _, _ = _xent_oracle(logits.astype(jnp.float32), labels)
    np.testing.assert_allclose(np.asarray(nll), np.asarray(rn), atol=5e-2)


def test_xent_supported_gating():
    assert pk.xent_supported(128, 2048)
    assert not pk.xent_supported(128, 512)    # vocab too small to stream
    assert not pk.xent_supported(128, 1000)   # not tiled by block_v
    assert not pk.xent_supported(4, 2048)     # too few rows


# -- chunked flash (sequences past the single-launch VMEM cap) --------------


@pytest.mark.parametrize(
    "causal",
    # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
    [pytest.param(False, marks=pytest.mark.slow), True],
)
def test_flash_chunked_matches_naive(rng, causal, monkeypatch):
    # Force chunking at a small shape by shrinking the chunk picker
    # (real chunking triggers at bf16 t=16384, too big for CPU tests).
    monkeypatch.setattr(pk, "_chunk_len", lambda t, hd, it: 16)
    q, k, v = make_qkv(rng, t=64, hd=16)
    out, lse = pk.flash_attention_lse_chunked(q, k, v, causal)
    ref = naive_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((64, 64), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    ref_lse = jax.scipy.special.logsumexp(scores, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5)


@pytest.mark.slow  # ~15s pair (targeted suite: test_pallas)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_chunked_grads(rng, causal, monkeypatch):
    monkeypatch.setattr(pk, "_chunk_len", lambda t, hd, it: 16)
    q, k, v = make_qkv(rng, t=48, hd=16)
    cot = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def loss_chunked(q, k, v):
        return jnp.sum(pk.flash_attention_lse_chunked(q, k, v, causal)[0] * cot)

    def loss_naive(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal) * cot)

    gc = jax.grad(loss_chunked, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gc, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_flash_chunked_supported_gating():
    # bf16 t=16384/hd=64 is past the single-launch VMEM cap but
    # decomposes into supported 8192-chunks.
    shape = (1, 2, 16384, 64)
    assert not pk.flash_supported(shape, jnp.bfloat16)
    assert pk.flash_chunked_supported(shape, jnp.bfloat16)
    # Single-launch shapes do NOT take the chunked path.
    assert not pk.flash_chunked_supported((1, 2, 2048, 64), jnp.bfloat16)
    # Tiny sequences never chunk.
    assert not pk.flash_chunked_supported((1, 2, 64, 4), jnp.float32)


def test_scatter_add_rows_duplicate_distances(rng):
    """The double-buffered scatter must order duplicate rows at every
    pipeline distance (adjacent, distance-2, far), including runs."""
    table = jnp.zeros((64, 128), jnp.float32)
    idx = jnp.asarray([3, 3, 3, 7, 3, 9, 3, 11, 12, 3], jnp.int32)
    upd = jnp.asarray(rng.standard_normal((10, 128)), jnp.float32)
    out = pk.scatter_add_rows(table, idx, upd)
    ref = np.zeros((64, 128), np.float32)
    np.add.at(ref, np.asarray(idx), np.asarray(upd))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6, atol=1e-6)


def test_scatter_add_rows_empty_batch():
    """n=0 must no-op (ADVICE r4: the pipelined kernel's load(0)/
    drain-wait are invalid at zero runs; a Python-level guard returns
    the table unchanged)."""
    table = jnp.asarray(np.arange(64 * 128, dtype=np.float32).reshape(64, 128))
    idx = jnp.zeros((0,), jnp.int32)
    upd = jnp.zeros((0, 128), jnp.float32)
    out = pk.scatter_add_rows(table, idx, upd)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(table))
    # And under jit, where the trace-time IndexError used to surface.
    out_j = jax.jit(pk.scatter_add_rows)(table, idx, upd)
    np.testing.assert_array_equal(np.asarray(out_j), np.asarray(table))


def test_flash_auto_unsupported_returns_none():
    """The dispatcher signals fallback with None instead of raising
    from inside a jitted forward (ADVICE r4)."""
    shape = (1, 2, 8, 4)  # too short for any flash formulation
    assert not pk.flash_supported(shape, jnp.float32)
    assert not pk.flash_chunked_supported(shape, jnp.float32)
    q = jnp.zeros(shape, jnp.float32)
    assert pk.flash_attention_lse_auto(q, q, q) is None


def _ref_attention_lse(q, k, v, causal):
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / np.sqrt(q.shape[-1])
    if causal:
        t = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vf) / l[..., None]
    return o.astype(q.dtype), m + jnp.log(l)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t", [96, 100])  # divisible and ragged tails
def test_blocked_attention_matches_reference(rng, causal, t):
    """The jnp blocked streaming formulation (the any-t long-context
    safety net, VERDICT r4 item 7) matches dense attention, including
    ragged tails that no kernel chunking decomposes."""
    q = jnp.asarray(rng.standard_normal((2, 2, t, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, t, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, t, 16)), jnp.float32)
    o, lse = pk.attention_lse_blocked(q, k, v, causal,
                                      block_q=32, block_k=32)
    o_ref, lse_ref = _ref_attention_lse(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=2e-5, atol=2e-5)


def test_blocked_attention_grads_match(rng):
    q = jnp.asarray(rng.standard_normal((1, 2, 100, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 100, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 100, 16)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((1, 2, 100, 16)), jnp.float32)

    def loss_blocked(q, k, v):
        return jnp.sum(pk.attention_lse_blocked(
            q, k, v, True, block_q=32, block_k=32)[0] * cot)

    def loss_ref(q, k, v):
        return jnp.sum(_ref_attention_lse(q, k, v, True)[0] * cot)

    gb = jax.grad(loss_blocked, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gb, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_auto_dispatch_long_ragged_uses_blocked():
    """A long non-decomposable t must stream, not return None (the
    einsum fallback would materialize t^2 scores)."""
    # 8200 = 2^3 * 5^2 * 41: past the bf16/hd64 single-launch VMEM
    # cap, and no halving >= 512 is 8-block-divisible.
    t = 8200
    shape = (1, 1, t, 64)
    assert not pk.flash_supported(shape, jnp.bfloat16)
    assert not pk.flash_chunked_supported(shape, jnp.bfloat16)
    assert pk.flash_any_supported(shape, jnp.bfloat16)
    q = jnp.zeros(shape, jnp.bfloat16)
    res = pk.flash_attention_lse_auto(q, q, q)
    assert res is not None and res[0].shape == shape


def test_chunked_gates_32k_and_beyond():
    """bf16 t=32768+ decomposes into kernel chunks."""
    for t in (32768, 65536):
        shape = (1, 8, t, 64)
        assert pk.flash_chunked_supported(shape, jnp.bfloat16), t
        assert pk._chunk_len(t, 64, 2) == 8192


def test_the_kernel_library_reads_no_environment():
    """What a cell's kernels are is decided by the shape, in code: the
    module reads no environment variable, and a shell that exports the
    block knob of old gets the table's block all the same."""
    src = pathlib.Path(pk.__file__).read_text()
    assert "os.environ" not in src and "getenv" not in src
    code = ("from flexflow_tpu.ops import pallas_kernels as pk; "
            "print(pk._flash_block(1024, 64, 2))")
    env = dict(os.environ, FF_FLASH_BLOCK="256", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=120, check=True,
        cwd=pathlib.Path(pk.__file__).parents[2])
    assert out.stdout.split()[-1] == "1024", out.stdout


# -- row kernels: the two addressings ----------------------------------------
#
# ``lane_major`` for 128 % D == 0 (the chip stores such a table rows along
# the lanes; the kernels move (D, 128) blocks of its transposed view),
# ``row_major`` for D % 128 == 0.  Interpret mode runs the same kernel
# bodies; what Mosaic accepts is tests/test_chip_compile.py's.

_ROW_SHAPES = [
    shape
    for d in (16, 32, 64, 128, 256)
    # 300 rows: two whole 128-row blocks and an edge block of 44.
    for shape in ((300, d), (3, 300, d))
] + [(256, 64), (2, 128, 32)]


def _np_rows(table):
    return np.asarray(table).reshape(-1, table.shape[-1])


def _row_ids(rng, num_rows, n=41):
    """Random ids behind the ones that matter: first and last row (the
    edge block), duplicates at distance 1 and 2, neighbours in a block."""
    head = [0, 1, 1, 5, num_rows - 1, num_rows - 1, 2, 5, 130, num_rows - 2]
    return np.concatenate(
        [head, rng.integers(0, num_rows, size=n - len(head))]
    ).astype(np.int32)


@pytest.mark.parametrize("shape", _ROW_SHAPES, ids=str)
def test_gather_rows_matches_numpy(rng, shape):
    table = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    idx = _row_ids(rng, int(np.prod(shape[:-1])))
    got = pk.gather_rows(table, jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(got), _np_rows(table)[idx])


@pytest.mark.parametrize("shape", _ROW_SHAPES, ids=str)
def test_scatter_add_rows_matches_numpy(rng, shape):
    table = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    idx = _row_ids(rng, int(np.prod(shape[:-1])))
    upd = rng.standard_normal((len(idx), shape[-1])).astype(np.float32)
    got = pk.scatter_add_rows(table, jnp.asarray(idx), jnp.asarray(upd))
    assert got.shape == shape
    ref = _np_rows(table).copy()
    np.add.at(ref, idx, upd)
    np.testing.assert_allclose(_np_rows(got), ref, rtol=1e-5, atol=1e-6)


#: ids over a (2, 384, 32) table: 3 blocks of 128 rows a table.
_BLOCK_HAZARDS = {
    "same_id_distance_1": [7, 7, 7, 200, 7],
    "same_id_distance_2": [7, 200, 7, 300, 7, 9, 7],
    "same_id_far": [7, 200, 300, 400, 500, 600, 700, 7],
    # Different rows of one 128-row block are ONE physical target.
    "one_block_adjacent": [5, 6, 100, 127, 200, 201],
    "one_block_distance_2": [5, 200, 6, 300, 127, 400, 0],
    "one_block_far": [5, 200, 300, 400, 500, 600, 700, 100],
    "same_lane_other_blocks": [5, 133, 261, 389, 5, 133],
    "one_run_only": [64, 65, 66, 64],
}


@pytest.mark.parametrize("case", sorted(_BLOCK_HAZARDS))
def test_scatter_lane_major_orders_every_block_hazard(rng, case):
    """The two-deep pipeline must order read-modify-writes of one
    BLOCK at every distance — the same id again, or a neighbour in the
    block — and fold adjacent ones into a run; a lost update shows as
    a missing addend."""
    shape = (2, 384, 32)
    assert pk.rows_addressing(8, shape) == "lane_major"
    table = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    idx = np.asarray(_BLOCK_HAZARDS[case], np.int32)
    upd = rng.standard_normal((len(idx), 32)).astype(np.float32)
    got = pk.scatter_add_rows(table, jnp.asarray(idx), jnp.asarray(upd))
    ref = _np_rows(table).copy()
    np.add.at(ref, idx, upd)
    np.testing.assert_allclose(_np_rows(got), ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape", [(256, 64), (3, 300, 16), (64, 128)], ids=str)
def test_row_kernels_empty_batch(shape):
    """n = 0 under either addressing: nothing gathered, the table
    returned as it came (also under jit, where it is a static shape)."""
    table = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
    idx = jnp.zeros((0,), jnp.int32)
    upd = jnp.zeros((0, shape[-1]), jnp.float32)
    assert pk.gather_rows(table, idx).shape == (0, shape[-1])
    for fn in (pk.scatter_add_rows, jax.jit(pk.scatter_add_rows)):
        np.testing.assert_array_equal(np.asarray(fn(table, idx, upd)),
                                      np.asarray(table))


def _pallas_eqns(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_eqns(inner, out)
    return out


@pytest.mark.parametrize("shape", [(256, 64), (4, 256, 64), (256, 128),
                                   (256, 256)], ids=str)
def test_scatter_add_rows_aliases_the_table_under_jit(rng, shape):
    """The table operand is the kernel's output buffer (operand 1
    after the prefetched scalars), and every view between the jitted
    function's argument and that operand keeps the table's element
    count: with the argument donated the update is in place."""
    table = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    idx = jnp.asarray(_row_ids(rng, int(np.prod(shape[:-1]))))
    upd = jnp.asarray(rng.standard_normal((idx.shape[0], shape[-1])),
                      jnp.float32)
    ref = _np_rows(table).copy()
    np.add.at(ref, np.asarray(idx), np.asarray(upd))
    (eqn,) = _pallas_eqns(
        jax.make_jaxpr(pk.scatter_add_rows)(table, idx, upd).jaxpr, [])
    assert tuple(eqn.params["input_output_aliases"]) == ((1, 0),)
    assert eqn.invars[1].aval.size == eqn.outvars[0].aval.size == table.size
    got = jax.jit(pk.scatter_add_rows, donate_argnums=(0,))(table, idx, upd)
    assert table.is_deleted()
    np.testing.assert_allclose(_np_rows(got), ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n_ids, shape, kind, want", [
    (8192, (8, 2000000, 64), "scatter", "lane_major"),   # dlrm-random
    (8192, (8, 2000000, 64), "gather", "lane_major"),
    (4096, (4, 1000000, 64), "scatter", "lane_major"),   # README's: an edge block
    (1024, (1 << 20, 16), "scatter", "lane_major"),
    (1024, (1 << 20, 128), "scatter", "row_major"),
    (1024, (50257, 1024), "scatter", "row_major"),       # GPT-2's token table
    (1024, (50257, 1024), "gather", "row_major"),
    (4096, (50257, 1024), "scatter", None),              # 16 MB of updates
    (6, (40, 64), "scatter", "row_major"),               # under one block: packed
    (6, (40, 4), "scatter", None),                       # volume not 128-aligned
    (6, (4096, 4), "scatter", "row_major"),              # D under a sublane tile
    (6, (41, 96), "scatter", None),
    (6, (41, 96), "gather", "row_major"),
    (20000, (8, 2000000, 64), "scatter", "lane_major"),
    (40000, (8, 2000000, 64), "scatter", None),          # 5n+1 scalars past SMEM
    (30000, (8, 2000000, 64), "gather", "lane_major"),
    (40000, (8, 2000000, 64), "gather", None),           # 10 MB of rows
    (0, (256, 64), "scatter", None),
], ids=str)
def test_rows_addressing_follows_the_shape(n_ids, shape, kind, want):
    assert pk.rows_addressing(n_ids, shape, jnp.float32, kind) == want
    assert pk.rows_addressing(n_ids, shape, jnp.bfloat16, kind) is None
    if len(shape) == 2:
        assert pk.rows_supported(n_ids, shape[1], jnp.float32,
                                 num_rows=shape[0], kind=kind) == (want is not None)
