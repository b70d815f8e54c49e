"""``ff_flash_fwd_uneven``, the serving prefill's causal forward
(``ops/pallas_kernels.py::flash_fwd_uneven``), in interpret mode against
the einsum oracle: every group and pair of widths the cells bring, the
walk over live blocks only, and the operands' precision.  (The two tests
``tests/test_latent_moe.py`` and ``tests/test_solar_open2.py`` held it to
are cases here.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops import pallas_kernels
from flexflow_tpu.ops.attention import _einsum_attention


def _uneven_operands(b, h, h_kv, t, qk, dv, dtype=jnp.float32, seed=6):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    return draw(b, h, t, qk), draw(b, h_kv, t, qk), draw(b, h_kv, t, dv)


def _uneven_oracle(q, k, v):
    rep = lambda x: jnp.repeat(x, q.shape[1] // k.shape[1], axis=1)
    return _einsum_attention(q, rep(k), rep(v), True)


#: (batch, query heads, KV heads, t, qk, dv): every group of the cells (1:
#: latent attention, heads with K and V of their own; 6 and 8: a group
#: over its one KV head) at every pair of widths over one block, three
#: blocks of 128, three of 512 and seventeen of 128; then the shapes the
#: two older tests of the kernel held it to, a block of 1024 and a
#: sequence of two of them.
_UNEVEN_CASES = [
    (2, group, 1, t, qk, dv)
    for group in (1, 6, 8)
    for qk, dv in ((24, 24), (192, 128), (128, 128))
    for t in (128, 384, 1536, 2176)
] + [(1, 2, 2, 256, 24, 16), (1, 8, 2, 256, 128, 128),
     (1, 2, 1, 1024, 24, 24), (1, 4, 4, 2048, 24, 16)]


@pytest.mark.parametrize("b,h,h_kv,t,qk,dv", _UNEVEN_CASES)
def test_flash_fwd_uneven_kernel_against_the_einsum_oracle(b, h, h_kv, t, qk, dv):
    q, k, v = _uneven_operands(b, h, h_kv, t, qk, dv)
    assert pallas_kernels.flash_uneven_supported(q.shape, dv)
    assert not pallas_kernels.flash_uneven_supported((b, h, t + 72, qk), dv)
    block, heads, kv = pallas_kernels.flash_uneven_walk(q.shape, h_kv, dv, q.dtype)
    assert t % block == 0 and block == max(
        c for c in (1024, 512, 256, 128) if t % c == 0)
    assert (heads, kv) == ((h // h_kv, 1) if h != h_kv else (min(h, 4),) * 2)
    got = pallas_kernels.flash_fwd_uneven(q, k, v, qk ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_uneven_oracle(q, k, v)),
                               rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("h,h_kv,qk,dv", [(4, 4, 192, 128), (6, 1, 128, 128)])
def test_flash_fwd_uneven_reads_no_block_above_the_diagonal(h, h_kv, qk, dv):
    """Every key and value past a query block's last row NaN: the rows
    up to there come out as they did, so no dead block is fetched into a
    product (a masked score's zero weight times NaN is NaN) and none of
    the diagonal block's dead sub-blocks is computed."""
    t, edge = 1536, 1024                        # blocks of 512: two clean, one poisoned
    q, k, v = _uneven_operands(2, h, h_kv, t, qk, dv)
    clean = pallas_kernels.flash_fwd_uneven(q, k, v, qk ** -0.5)
    poison = lambda x: x.at[:, :, edge:].set(jnp.nan)
    got = pallas_kernels.flash_fwd_uneven(q, poison(k), poison(v), qk ** -0.5)
    assert np.isfinite(np.asarray(got[:, :, :edge])).all()
    np.testing.assert_array_equal(np.asarray(got[:, :, :edge]),
                                  np.asarray(clean[:, :, :edge]))
    assert np.isnan(np.asarray(got[:, :, edge:])).all()


def test_a_group_too_wide_for_the_smallest_block_rides_in_parts(monkeypatch):
    """Where not even a 128-row block holds a whole group inside the
    VMEM rule, a grid step takes a divisor of it over the same K/V head."""
    q, k, v = _uneven_operands(2, 8, 2, 256, 128, 128)
    assert pallas_kernels.flash_uneven_walk(q.shape, 2, 128, q.dtype) == (256, 4, 1)
    monkeypatch.setattr(pallas_kernels, "_CAUSAL_VMEM_LIMIT", 3 << 20)
    assert pallas_kernels.flash_uneven_walk(q.shape, 2, 128, q.dtype) == (128, 2, 1)
    got = pallas_kernels.flash_fwd_uneven(q, k, v, 128 ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_uneven_oracle(q, k, v)),
                               rtol=1e-4, atol=2e-5)


def test_flash_fwd_uneven_in_bfloat16_and_under_an_ambient_precision():
    """bf16 operands: bf16 products into float32 and bf16 weights into
    the second product, whatever matmul precision the caller's context
    names (chip_smoke recounts a flipped token under ``highest``, which
    Mosaic refuses for a bf16 contraction)."""
    q, k, v = _uneven_operands(1, 8, 2, 512, 128, 128, jnp.bfloat16)
    want = np.asarray(_uneven_oracle(*(x.astype(jnp.float32) for x in (q, k, v))))
    call = lambda: pallas_kernels.flash_fwd_uneven(q, k, v, 128 ** -0.5,
                                                   interpret=False)
    text = str(jax.make_jaxpr(call)())
    with jax.default_matmul_precision("highest"):
        got = pallas_kernels.flash_fwd_uneven(q, k, v, 128 ** -0.5)
        ambient = str(jax.make_jaxpr(call)())
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=2e-2)
    assert "HIGHEST" not in ambient and ambient == text


def test_the_walks_counters_on_the_serving_program_event(tmp_path):
    """``causal_blocks`` and ``causal_steps`` of a prefill's
    ``serving_program`` event: at a 32,768 bucket the grid's steps a
    head are the ``n (n + 1) / 2`` blocks a query can see; under a
    selector the call covers the leading ``topk`` rows; a graph's window
    layers and a program whose prefill takes another causal path carry
    neither."""
    from benchmark import common
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import (
        LAGUNA_TINY, build_lm, build_transformer_lm)
    from flexflow_tpu.ops import attention
    from flexflow_tpu.ops.token_select import TokenSelector
    from flexflow_tpu.runtime import telemetry
    from flexflow_tpu.runtime.serving import ServingExecutor

    bf16 = jnp.bfloat16
    for heads, h_kv, qk in ((48, 8, 128), (64, 8, 128), (32, 32, 192)):
        got = attention.causal_blocks(None, 32768, heads, h_kv, qk, 128, bf16)
        block = pallas_kernels.flash_uneven_walk((1, heads, 32768, qk), h_kv, 128, bf16)[0]
        n = 32768 // block
        assert got == dict(causal_blocks=n * (n + 1) // 2,
                           causal_steps=n * (n + 1) // 2), (heads, got)
    qi, ki = pallas_kernels.flash_uneven_pairs(3)
    assert (list(qi), list(ki)) == ([0, 1, 1, 2, 2, 2], [0, 1, 0, 2, 1, 0])
    sel = TokenSelector(dict(indexer_num_heads=2, indexer_head_dim=16, topk=2048,
                             q_chunk_size=512), 1e4)
    assert attention.causal_blocks(sel, 32768, 64, 64, 192, 128, bf16) \
        == attention.causal_blocks(None, 2048, 64, 64, 192, 128, bf16)
    assert attention.causal_blocks(None, 200, 4, 4, 128, 128, bf16) == {}

    ff = build_lm(LAGUNA_TINY, 1, 384, FFConfig(batch_size=1))
    sex = ServingExecutor(ff, ff.config, max_batch=1, max_seq=384, buckets=[128, 384])
    with telemetry.Telemetry(directory=str(tmp_path / "laguna")) as tel:
        sex.build_prefill(384)
        sex.build_decode_superstep(2)
    events = {e["kind"]: e for e in common.read_events(tel.path)
              if e["ev"] == "serving_program"}
    # Two full layers make the call (three blocks of 128: six live pairs);
    # the three window layers run the banded forward.
    assert (events["prefill"]["causal_blocks"], events["prefill"]["causal_steps"]) == (6, 6)
    assert sex.causal_blocks(128) == dict(causal_blocks=1, causal_steps=1)
    assert "causal_blocks" not in events["decode"]

    plain = build_transformer_lm(batch_size=1, seq_len=128, vocab_size=64, d_model=16,
                                 num_heads=2, num_layers=1, config=FFConfig(batch_size=1))
    sex = ServingExecutor(plain, plain.config, max_batch=1, max_seq=128)
    with telemetry.Telemetry(directory=str(tmp_path / "plain")) as tel:
        sex.build_prefill(128)
    (event,) = [e for e in common.read_events(tel.path) if e["ev"] == "serving_program"]
    assert "causal_blocks" not in event and "causal_steps" not in event
    assert sex.causal_blocks(128) == {}
