"""``pallas_kernels.attend_kept`` (a selected prefill's masked chunk,
streamed) in interpret mode against ``ops/attention.py::
_attend_kept_heads``, the plain path it replaces where its gate takes
the shapes, and ``_attend_selected`` through both."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops import attention, pallas_kernels as pk
from flexflow_tpu.ops.token_select import TokenSelector

# 640 keys: five key blocks of 128 (512 or 256 do not divide them).
C, T, TOPK = 128, 640, 130


def _selector(topk=TOPK, chunk=C):
    return TokenSelector(dict(indexer_num_heads=2, indexer_head_dim=16,
                              topk=topk, q_chunk_size=chunk), theta=1e4)


def _operands(seed, h, h_kv, own, dv, shared, dtype=jnp.float32, b=1, t=T):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, h, C, own + (shared or 0)), dtype)
    k = jax.random.normal(ks[1], (b, h_kv, t, own), dtype)
    v = jax.random.normal(ks[2], (b, h_kv, t, dv), dtype)
    sk = jax.random.normal(ks[3], (b, t, shared), dtype) if shared else None
    scores = jax.random.normal(ks[4], (b, C, t), jnp.float32)
    return q, k, v, sk, scores


def _poison_past(x, end, axis):
    """``x`` with NaN at every position ``>= end`` of ``axis``."""
    at = jnp.arange(x.shape[axis]).reshape(
        [-1 if i == axis % x.ndim else 1 for i in range(x.ndim)])
    return jnp.where(at >= end, jnp.nan, x)


@pytest.mark.parametrize("h,h_kv,own,dv,shared", [
    (4, 4, 128, 16, 8),      # heads with K and V of their own, a shared part
    (4, 4, 24, 16, None),    # the same, keys whole
    (8, 1, 24, 16, None),    # a group of 8 on one KV head
    (16, 2, 128, 32, 16),    # groups and a shared part together
    (2, 2, 24, 16, None),    # fewer heads than a step's block
])
@pytest.mark.parametrize("start", [128, 512])
def test_attend_kept_matches_the_plain_path(h, h_kv, own, dv, shared, start):
    """First and last chunk of the walk, with and without the shared
    key part, group 1 and group 8.  Key blocks past the chunk's own end
    are NaN in every operand the kernel reads by key block: it never
    fetches them (a NaN times a zero weight would still poison a row)."""
    q, k, v, sk, scores = _operands(start + h, h, h_kv, own, dv, shared)
    sel = _selector()
    keep = sel.keep(scores, start + jnp.arange(C))
    scale = 1.0 / math.sqrt(q.shape[-1])
    want = attention._attend_kept_heads(q, k, v, keep, scale, sk)
    assert pk.attend_kept_supported(q.shape, k.shape, dv, shared)
    end = -(-(start + C) // 128) * 128
    got = pk.attend_kept(
        q, _poison_past(k, end, 2), _poison_past(v, end, 2),
        keep, jnp.int32(start), scale,
        shared_k=None if sk is None else _poison_past(sk, end, 1),
        interpret=True)
    assert got.shape == want.shape and bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_attend_kept_takes_a_row_that_keeps_nothing_in_a_tile():
    """Rows whose kept keys all lie in one key block: every other tile
    is dead for them and adds nothing, wherever the live one comes."""
    q, k, v, _, _ = _operands(7, 4, 4, 24, 16, None)
    start = 512
    cols = jnp.arange(T)[None, :]
    rows = jnp.arange(C)[:, None]
    # Row i keeps 3 keys of block (i mod 5): the first, a middle, the last tile.
    keep = ((cols // 128 == rows % 5) & (cols % 128 < 3))[None]
    scale = 0.2
    want = attention._attend_kept_heads(q, k, v, keep, scale)
    got = pk.attend_kept(q, k, v, keep, start, scale, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_attend_kept_under_a_mask_with_exact_zero_ties():
    """The selector's own mask where most scores tie at exact zero
    (every head's ReLU shut): ``keep`` takes the lowest positions among
    the ties, ``topk`` a row, and the kernel attends exactly those."""
    q, k, v, sk, scores = _operands(11, 4, 4, 128, 16, 8, dtype=jnp.bfloat16)
    scores = jnp.where(scores > 1.0, scores, 0.0) * jnp.where(
        jnp.arange(T) % 7 == 0, -1.0, 1.0)          # zeros of both signs
    sel = _selector()
    start = 512
    keep = sel.keep(scores, start + jnp.arange(C))
    kept = np.asarray(keep.sum(-1))
    assert (kept == TOPK).all(), kept
    scale = 1.0 / math.sqrt(q.shape[-1])
    want = attention._attend_kept_heads(q, k, v, keep, scale, sk)
    got = pk.attend_kept(q, k, v, keep, start, scale, shared_k=sk,
                         interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=2e-2,
                               atol=2e-2)


@pytest.mark.parametrize("q_shape,k_shape,dv,shared,ok", [
    ((1, 64, 512, 192), (1, 64, 32768, 128), 128, 64, True),    # axk2
    ((1, 32, 512, 128), (1, 4, 32768, 128), 128, None, True),   # keye2
    ((1, 4, 96, 24), (1, 4, 96, 24), 16, None, False),          # no whole block
    ((1, 4, 128, 32), (1, 4, 200, 24), 16, 8, False),           # keys not blocks
    ((1, 4, 128, 32), (1, 4, 256, 24), 16, 8, True),            # own part cut mid-tile
    ((1, 4, 128, 28), (1, 4, 256, 24), 16, 4, False),           # a sliver of a shared part
    ((1, 4, 128, 32), (1, 3, 256, 32), 16, None, False),        # heads do not group
    ((1, 4, 128, 40), (1, 4, 256, 32), 16, None, False),        # widths disagree
    ((1, 64, 512, 128), (1, 1, 32768, 128), 128, None, False),  # a group past VMEM
])
def test_attend_kept_gate(q_shape, k_shape, dv, shared, ok):
    assert pk.attend_kept_supported(q_shape, k_shape, dv, shared) is ok


@pytest.mark.parametrize("shared", [None, 8])
@pytest.mark.parametrize("h,h_kv", [(4, 4), (8, 1)])
def test_attend_selected_through_the_kernel_equals_the_plain_path(
        h, h_kv, shared, monkeypatch):
    """``_attend_selected`` over ``T`` rows with ``serving`` on and off:
    the same walk, the leading rows through ``dense`` either way, the
    masked chunks through the kernel only when serving and the gate
    takes the shapes."""
    own, dv = 128, 16
    dk = own + (shared or 0)
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    q = jax.random.normal(ks[0], (1, h, T, dk))
    k = jax.random.normal(ks[1], (1, h_kv, T, own))
    v = jax.random.normal(ks[2], (1, h_kv, T, dv))
    sk = jax.random.normal(ks[3], (1, T, shared)) if shared else None
    sel = _selector()
    index = (jax.random.normal(ks[4], (1, T, sel.heads, sel.head_dim)),
             jax.random.normal(ks[5], (1, T, sel.head_dim)),
             jax.random.uniform(ks[6], (1, T, sel.heads)))
    scale = 1.0 / math.sqrt(dk)

    def dense(qh, kh, vh, dtype):
        if sk is not None:
            n = kh.shape[2]
            kh = jnp.concatenate([kh, jnp.broadcast_to(
                sk[:, None, :n], kh.shape[:3] + (shared,))], axis=-1)
        g = qh.shape[1] // kh.shape[1]
        kh, vh = (jnp.repeat(x, g, axis=1) for x in (kh, vh))
        o = attention._einsum_attention(qh, kh, vh, True, scale)
        return o.transpose(0, 2, 1, 3).reshape(1, qh.shape[2], -1).astype(dtype)

    calls = []
    real = pk.attend_kept
    monkeypatch.setattr(pk, "attend_kept", lambda *a, **kw: (
        calls.append(a[0].shape), real(*a, **kw))[1])
    run = lambda serving: attention._attend_selected(
        sel, T, lambda s, n: jax.lax.dynamic_slice_in_dim(q, s, n, axis=2),
        k, v, index, scale, dense, jnp.float32, shared_k=sk, serving=serving)
    plain = run(False)
    assert not calls
    kernel = run(True)
    assert calls and all(s == (1, h, C, dk) for s in calls)
    np.testing.assert_allclose(kernel, plain, rtol=2e-5, atol=2e-5)


def test_selected_walk_and_block_counts():
    """The walk's runs at the cells' 32k bucket and what the event
    counts from them."""
    sel = _selector(topk=2048, chunk=512)
    c, head, groups = attention.selected_walk(sel, 32768)
    assert (c, head) == (512, 2048)
    assert groups == [(2048, 4096), (4096, 8192), (8192, 16384), (16384, 32768)]
    assert attention.selected_walk(sel, 8704)[2] == [
        (2048, 4096), (4096, 8192), (8192, 8704)]
    got = attention.kept_blocks(sel, 32768, 64, (1, 64, 32768, 128), 192, 128, 64)
    assert got == dict(kept_kernel=True, kept_key_blocks=sum(range(5, 65)),
                       kept_key_blocks_square=4 * 8 + 8 * 16 + 16 * 32 + 32 * 64)
    assert attention.kept_blocks(sel, 2048, 64, (1, 64, 2048, 128), 192, 128, 64) \
        == dict(kept_kernel=False, kept_key_blocks=0, kept_key_blocks_square=0)
    # Rows that do not divide into chunks are one chunk, which the gate refuses.
    assert not attention.kept_blocks(
        sel, 3000, 4, (1, 4, 3000, 128), 128, 128, None)["kept_kernel"]
