"""The Laguna family (window and full attention layers with different
head counts in one model, a ring cache beside a full cache, a gate a
head, rotary positions on a sub-width under YaRN, a share of the experts
held) at a small size on the CPU, seeded weights, against the plain
reference (``benchmark/references/laguna.py``, the benchmark's own,
which imports nothing of the program)."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark.references.laguna as ref
from benchmark import common
from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import (
    KEYE_VL2_TINY,
    LAGUNA_SMOKE,
    LAGUNA_TINY,
    SOLAR_OPEN2_TINY,
    build_lm,
    build_transformer_lm,
)
from flexflow_tpu.ops import attention, pallas_kernels as pk
from flexflow_tpu.ops.attention import (
    MultiHeadAttention,
    _ring_rows,
    _yarn_mscale,
    rope_frequencies,
)
from flexflow_tpu.ops.base import TensorSpec
from flexflow_tpu.ops.moe import MixtureOfExperts
from flexflow_tpu.ops.token_select import rope_half
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.serving import ServingExecutor

SEED = 4400000077
S = 64
W = LAGUNA_TINY["sliding_window"]                    # 16: sequences reach 4 W

_ASSUMED = {"init_std": 0.05, "norm_scale_half_width": 0.05,
            "router_dtype": "float32"}


def _cfg(dtype="float32", base=LAGUNA_TINY, **over):
    return dict(base, **over, assumed=dict(_ASSUMED, param_dtype=dtype))


def _model(cfg, batch, seq, dtype="float32"):
    ff = build_lm(cfg, batch, seq, FFConfig(batch_size=batch,
                                            compute_dtype=dtype))
    abstract, _, _ = jax.eval_shape(Executor(ff, config=ff.config).init)
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    params = common.make_params(ref.leaf_spec(cfg), SEED, abstract,
                                jax.tree.map(lambda _: one, abstract))
    return ff, params


def _tokens(n, t, vocab=512):
    return np.random.default_rng(5).integers(0, vocab, size=(n, t),
                                             dtype=np.int32)


def _attn_op(b, t, heads=6, kv=2, hd=16, d=64, **kw):
    """An attention op alone with seeded parameters and an input."""
    x = TensorSpec("x", (b, t, d), jnp.float32, ("n", "s", None))
    op = MultiHeadAttention("attn", x, heads, use_bias=False, num_kv_heads=kv,
                            head_dim=hd, **kw)
    rng = np.random.default_rng(11)
    params = {k: jnp.asarray(rng.normal(size=s.shape).astype(np.float32) * 0.2)
              for k, s in op.param_specs().items()}
    a = jnp.asarray(rng.standard_normal((b, t, d)).astype(np.float32))
    return op, params, a


def test_the_graph_and_what_the_builder_refuses():
    ff = build_lm(LAGUNA_TINY, 1, 32)
    ops = {op.name: op for op in ff.layers}
    attn = [ops[f"blk{i}_attn"] for i in range(5)]
    assert [a.attrs["window"] for a in attn] == [None, W, W, W, None]
    assert [a.attrs["num_heads"] for a in attn] == [4, 6, 6, 6, 4]
    assert all(a.attrs["gate"] == "per_head" and a.attrs["num_kv_heads"] == 2
               for a in attn)
    full, ring = attn[0].attrs["rope"], attn[1].attrs["rope"]
    assert full["rotary_dim"] == 8 and full["scaling"]["rope_type"] == "yarn"
    assert full["theta"] == 5e5 and ring == {"theta": 1e4}
    assert attn[1].serving_path(True) == "gqa_window_decode"
    assert attn[1].serving_path(False) == "gqa_window_dense"
    assert attn[0].serving_path(True) == "gqa_decode"
    assert not attn[1].cache_paged and attn[0].cache_paged
    assert "blk0_mlp_gate" in ops and "blk0_moe" not in ops
    moe = ops["blk3_moe"]
    assert moe.attrs["router"] == "sigmoid" and moe.attrs["dispatch"] == "sorted"
    assert moe.attrs["shared_experts"] == 1 and not moe.attrs["selection_bias"]
    assert moe.attrs["routed_scale"] == 2.5 and moe.attrs["top_k"] == 3
    for key, value in (("moe_apply_router_weight_on_input", True),
                       ("moe_router_logit_softcapping", 30.0),
                       ("gating_types", ["per_head"] * 4 + ["elementwise"]),
                       ("tie_word_embeddings", True), ("attention_bias", True),
                       ("mlp_only_layers", []),
                       ("layer_types", ["full_attention"] * 4 + ["chunked"])):
        with pytest.raises(ValueError, match=key):
            build_lm(dict(LAGUNA_TINY, **{key: value}), 1, 32)
    with pytest.raises(ValueError, match="held_experts"):
        build_lm(dict(LAGUNA_TINY, held_experts=[0, 1]), 1, 32)
    held = build_lm(dict(LAGUNA_TINY, num_experts=4, held_experts=[4, 5, 6, 7],
                         published={"num_experts": 16}), 1, 32)
    assert held.find_op("blk1_moe").attrs["num_experts"] == 16
    assert held.find_op("blk1_moe").held == (4, 5, 6, 7)


# -- the op's three arguments -------------------------------------------------

def test_band_mask_in_the_plain_forward_and_a_window_past_the_length():
    """The plain forward under ``window`` against a masked softmax
    written out here; a window at least the length is the op without."""
    t = 40
    op, params, a = _attn_op(2, t, window=7)
    (y,), _ = op.forward(params, [a], {}, False)
    q, k, v = (np.asarray(a @ params[n]) for n in ("wq", "wk", "wv"))
    q = q.reshape(2, t, 6, 16)
    k, v = (np.repeat(x.reshape(2, t, 2, 16), 3, axis=2) for x in (k, v))
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    s = np.where((cols <= rows) & (cols > rows - 7), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", p, v).reshape(2, t, 96) @ np.asarray(params["wo"])
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    plain, _, _ = _attn_op(2, t)
    (y0,), _ = plain.forward(params, [a], {}, False)
    for wide in (t, t + 5):
        op, _, _ = _attn_op(2, t, window=wide)
        (y1,), _ = op.forward(params, [a], {}, False)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-5)
    assert np.abs(np.asarray(y) - np.asarray(y0)).max() > 1e-2


def test_per_head_gate_is_the_elementwise_gate_with_a_row_constant_weight():
    op_h, params, a = _attn_op(2, 12, gate="per_head")
    op_e, _, _ = _attn_op(2, 12, gate=True)
    assert op_h.param_specs()["wg"].shape == (64, 6)
    assert op_e.param_specs()["wg"].shape == (64, 96)
    wide = dict(params, wg=jnp.repeat(params["wg"], 16, axis=1))
    (yh,), _ = op_h.forward(params, [a], {}, False)
    (ye,), _ = op_e.forward(wide, [a], {}, False)
    np.testing.assert_allclose(np.asarray(yh), np.asarray(ye), atol=1e-6)
    none, _, _ = _attn_op(2, 12)
    (y0,), _ = none.forward(params, [a], {}, False)
    assert np.abs(np.asarray(yh) - np.asarray(y0)).max() > 1e-3


def test_partial_rotary_and_yarn_against_the_references_own_frequencies():
    published = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                 "original_max_position_embeddings": 8192, "beta_slow": 1,
                 "beta_fast": 32, "attention_factor": 1.4852030263919618,
                 "partial_rotary_factor": 0.5}
    assert _yarn_mscale(128, 1) == pytest.approx(published["attention_factor"], rel=1e-12)
    for rope, hd in ((published, 128),
                     (LAGUNA_TINY["rope_parameters"]["full_attention"], 16),
                     (LAGUNA_TINY["rope_parameters"]["sliding_attention"], 16)):
        r = int(hd * rope["partial_rotary_factor"])
        f, wave = ref.rotary_frequencies(r, rope)
        inv, mine, soft = rope_frequencies(
            r, float(rope["rope_theta"]),
            rope if rope["rope_type"] != "default" else None)
        np.testing.assert_allclose(np.asarray(inv), f, rtol=1e-6)
        assert soft == 1.0 and mine == pytest.approx(wave, rel=1e-9)
        x = jnp.asarray(np.random.default_rng(3).standard_normal(
            (2, 3, 10, hd)).astype(np.float32))
        # f32 angles: a frequency's last bit is 1e-4 rad by position 900.
        pos = jnp.asarray([[3, 900, 17, 0, 5, 6, 7, 8, 250, 31]] * 2)
        got = rope_half(x, pos[:, None], float(rope["rope_theta"]),
                        rotary_dim=r, inv=inv, wave=mine)
        want = np.stack([np.asarray(ref.rotary(
            jnp.transpose(x[b], (1, 0, 2)), pos[b], r, f, wave)).transpose(1, 0, 2)
            for b in range(2)])
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-3)
        if r < hd:
            np.testing.assert_array_equal(np.asarray(got[..., r:]),
                                          np.asarray(x[..., r:]))
    # YaRN blends: some pairs keep their frequency, some are slowed whole.
    f, _ = ref.rotary_frequencies(64, published)
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64.0)
    assert f[0] == pytest.approx(plain[0]) and f[-1] == pytest.approx(plain[-1] / 128)
    with pytest.raises(ValueError, match="attention_factor"):
        op, params, a = _attn_op(1, 8, rope={
            "theta": 5e5, "rotary_dim": 8,
            "scaling": dict(published, attention_factor=1.2)})
        op.forward(params, [a], {}, False)


def test_ring_rows_are_the_newest_position_of_each_residue():
    for length, t, w in ((5, 8, 4), (4, 8, 4), (3, 8, 4), (37, 48, 16),
                         (16, 16, 16), (1, 8, 4), (8, 8, 16)):
        got = np.asarray(_ring_rows(jnp.int32(length), t, w))
        for r in range(w):
            live = [s for s in range(length) if s % w == r]
            if live:
                assert got[r] == live[-1], (length, t, w, r)
            assert 0 <= got[r] < t


# -- the served path -------------------------------------------------------------

def _serve_logits(params, ff, toks, plen, bucket, k=1, kernel=None):
    """Logits at positions ``plen-1 ..`` of each row of ``toks``: decode
    supersteps of ``k`` tokens through the caches a prefill left (the
    tokens forced: the superstep's own picks are overwritten)."""
    b, t = toks.shape
    sex = ServingExecutor(ff, ff.config, max_batch=b, max_seq=S,
                          buckets=[bucket], decode_kernel=kernel)
    pf = sex.build_prefill(bucket)
    caches = sex.init_cache()
    padded = np.full((b, bucket), 9, np.int32)
    padded[:, :plen] = toks[:, :plen]
    for i in range(b):
        rows, _tok, ok, *_ = pf(params, {}, padded[i:i + 1], np.int32(plen))
        assert bool(ok)
        caches = sex.install(caches, rows, i)
    dec = sex.build_decode_superstep(1, return_logits=True)
    pos = np.full((b,), plen, np.int32)
    got = []
    for j in range(plen, t):
        caches, _, _, out = dec(params, {}, caches, pos.copy(),
                                toks[:, j].copy())
        got.append(np.asarray(out[2])[0])
        pos += 1
    return np.stack(got, axis=1), sex


@pytest.mark.parametrize("plen,bucket", [(9, 16), (W, 16), (W, 48),
                                         (2 * W + 5, 48), (3 * W + 1, 56)])
def test_prefill_then_decode_through_both_kinds_of_cache(plen, bucket):
    """A prompt below the window, of exactly the window and past twice
    the window (so that the prefill's ring has wrapped twice before the
    first decode step, which then wraps it again), in a bucket of its
    own size and in a longer one, then one-token steps through the full
    caches and the rings, against the reference's one full forward:
    logits, not tokens."""
    cfg = _cfg()
    ff, params = _model(cfg, 2, S)
    toks = _tokens(2, plen + 14)
    got, sex = _serve_logits(params, ff, toks, plen, bucket)
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, np.pad(t, (0, S - len(t)))))
                     [plen:len(t)] for t in toks])
    np.testing.assert_allclose(got, want, atol=2e-5)
    shapes = {k: {e: c.shape for e, c in v.items()}
              for k, v in sex.init_cache().items()}
    assert shapes["blk0_attn"] == {"k": (2, S, 2, 16), "v": (2, S, 2, 16)}
    assert shapes["blk2_attn"] == {"k": (2, W, 2, 16), "v": (2, W, 2, 16)}
    assert sex._attention_paths(True) == "gqa_decode+gqa_window_decode"
    assert sex._attention_paths(False) == "gqa_dense+gqa_window_dense"
    assert [op.name for op in sex.stateful_ops] == [f"blk{i}_attn" for i in (1, 2, 3)]


def test_bfloat16_served_path_stays_near_the_reference():
    cfg = _cfg("bfloat16")
    ff, params = _model(cfg, 2, S, "bfloat16")
    toks = _tokens(2, 56)
    got, _ = _serve_logits(params, ff, toks, 37, 48)
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, np.pad(t, (0, S - len(t)))))
                     [37:len(t)] for t in toks])
    gap = np.abs(got.astype(np.float32) - want)
    assert np.median(gap) < 0.03 and np.mean(gap > 0.15) < 0.15


def test_the_window_is_seen_by_the_logits():
    """With the window switched off in the reference the logits past the
    window move: the comparison above can tell a ring from a full cache."""
    cfg = _cfg()
    toks = _tokens(1, S)[0]
    on = np.asarray(ref.logits_fn(cfg, SEED, toks))
    off = np.asarray(ref.logits_fn(cfg, SEED, toks, window=False))
    np.testing.assert_allclose(on[:W], off[:W], atol=1e-5)
    assert np.abs(on[W + 4:] - off[W + 4:]).max() > 0.02


def test_a_superstep_of_eight_crosses_a_multiple_of_the_window():
    """K = 8 from position 2 W - 3: the ring's write index wraps inside
    one compiled superstep; the tokens it picks are those of one-token
    steps, and the logits the reference's."""
    cfg = _cfg()
    ff, params = _model(cfg, 1, S)
    plen = 2 * W - 3
    toks = _tokens(1, plen)
    sex = ServingExecutor(ff, ff.config, max_batch=1, max_seq=S, buckets=[32])
    pf = sex.build_prefill(32)
    padded = np.full((1, 32), 9, np.int32)
    padded[:, :plen] = toks
    rows, first, ok, *_ = pf(params, {}, padded, np.int32(plen))
    assert bool(ok)

    def run(k, n):
        caches = sex.install(sex.init_cache(), rows, 0)
        dec = sex.build_decode_superstep(k, return_logits=True)
        pos, tok = np.full((1,), plen, np.int32), np.asarray([int(first)], np.int32)
        picked, logits = [], []
        for _ in range(n):
            caches, pos, tok, out = dec(params, {}, caches, pos, tok)
            picked.extend(np.asarray(out[0])[:, 0].tolist())
            logits.extend(np.asarray(out[2])[:, 0])
        return picked, np.stack(logits)

    eight, lg8 = run(8, 1)
    ones, lg1 = run(1, 8)
    assert eight == ones
    np.testing.assert_allclose(lg8, lg1, atol=1e-5)
    full = np.concatenate([toks[0], [int(first)], eight])[:-1]
    want = np.asarray(ref.logits_fn(cfg, SEED, np.pad(full, (0, S - len(full)))))
    np.testing.assert_allclose(lg8, want[plen:plen + 8], atol=2e-5)


def test_one_prompt_in_two_buckets_leaves_the_same_ring():
    cfg = _cfg()
    ff, params = _model(cfg, 1, S)
    toks = _tokens(1, 21)
    rings = []
    for bucket in (24, 56):
        sex = ServingExecutor(ff, ff.config, max_batch=1, max_seq=S, buckets=[bucket])
        padded = np.full((1, bucket), 9, np.int32)
        padded[:, :21] = toks
        rows, tok, _ok, *_ = sex.build_prefill(bucket)(params, {}, padded, np.int32(21))
        rings.append((np.asarray(rows["blk2_attn"]["k"]), int(tok)))
    np.testing.assert_allclose(rings[0][0], rings[1][0], atol=1e-6)
    assert rings[0][1] == rings[1][1]


def test_kv_rows_of_a_graph_that_mixes_full_and_window_layers():
    ff = build_lm(LAGUNA_TINY, 4, S)
    sex = ServingExecutor(ff, ff.config, max_batch=4, max_seq=S, buckets=[S])
    assert [op.decode_fetch_block(4, S, None) for op in sex.attn_ops] == [S, W, W, W, S]
    pos = np.asarray([40, 3, 20, 0])
    live = np.minimum(pos[:, None] + np.arange(8), S - 1) + 1
    full = live.size * S                            # no kernel: every row
    ring = int((-(-np.minimum(live, W) // W) * W).sum())
    assert ring == live.size * W
    rows = sex.kv_rows(pos, 8)
    assert rows == {"kv_rows_fetched": round((2 * full + 3 * ring) / 5),
                    "kv_rows_cache": live.size * S}
    # The smoke preset's widths take the kernel: live chunks of each kind.
    ff = build_lm(LAGUNA_SMOKE, 4, 2048, FFConfig(batch_size=4,
                                                  compute_dtype="bfloat16"))
    sex = ServingExecutor(ff, ff.config, max_batch=4, max_seq=2048,
                          buckets=[2048], decode_kernel=True)
    blocks = [op.decode_fetch_block(4, 2048, True) for op in sex.attn_ops]
    assert blocks == [512] * 5
    pos = np.asarray([2000, 100, 700, 0])
    live = np.minimum(pos[:, None], 2047) + 1
    rows = sex.kv_rows(pos, 1)
    full = int((-(-live // 512) * 512).sum())
    assert rows["kv_rows_fetched"] == round((2 * full + 3 * 4 * 512) / 5)
    assert rows["kv_rows_cache"] == 4 * 2048 and "state_bytes" not in rows


def test_regimes_that_refuse_a_window_name_the_roadmap():
    ff = build_lm(LAGUNA_TINY, 2, 32, FFConfig(batch_size=2))
    with pytest.raises(NotImplementedError, match="B-M4"):
        ServingExecutor(ff, ff.config, max_batch=2, max_seq=32, kv_block=8)
    if len(jax.devices()) >= 2:
        with pytest.raises(NotImplementedError, match="B-M4"):
            ServingExecutor(ff, ff.config, max_batch=2, max_seq=32, shard=(2, 1))
    sex = ServingExecutor(ff, ff.config, max_batch=2, max_seq=32)
    with pytest.raises(ValueError, match="window's ring"):
        sex.build_spec_step(2)
    op, params, a = _attn_op(1, 8, window=4)
    ring = jnp.zeros((1, 4, 2, 16))
    for extra in ({"block_table": jnp.zeros((1, 2), jnp.int32)}, {"chunk": 4}):
        with pytest.raises(NotImplementedError, match="B-M4"):
            op.forward(params, [a], {"cache_k": ring, "cache_v": ring,
                                     "pos": jnp.zeros((1,), jnp.int32), **extra},
                       False)
    with pytest.raises(ValueError, match="window"):
        _attn_op(1, 8, window=4, causal=False)


# -- the kernels, in interpret mode ------------------------------------------------

def _masked_softmax_attention(q, k, v, window):
    """(b, h, t, hd) x (b, h_kv, t, hd): plain numpy under the band."""
    g = q.shape[1] // k.shape[1]
    k, v = (np.repeat(np.asarray(x, np.float32), g, axis=1) for x in (k, v))
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q, np.float32), k) / math.sqrt(q.shape[-1])
    rows, cols = np.arange(q.shape[2])[:, None], np.arange(k.shape[2])[None, :]
    s = np.where((cols <= rows) & (cols > rows - window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("t,window,group", [(1024, 512, 9), (1536, 512, 6),
                                            (384, 200, 3), (256, 1, 1),
                                            (1024, 700, 2)])
def test_flash_fwd_window_against_a_masked_softmax(t, window, group):
    rng = np.random.default_rng(t + window)
    hd = 128 if t > 512 else 32
    q = jnp.asarray(rng.standard_normal((1, group, t, hd)).astype(np.float32))
    k, v = (jnp.asarray(rng.standard_normal((1, 1, t, hd)).astype(np.float32))
            for _ in range(2))
    assert pk.flash_window_supported(q.shape, window)
    got = pk.flash_fwd_window(q, k, v, 1.0 / math.sqrt(hd), window, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               _masked_softmax_attention(q, k, v, window), atol=2e-4)


def test_flash_fwd_window_visits_no_block_outside_the_band():
    """The walk's count against the causal kernel's, and a poisoned key
    and value block outside every band that the output never sees."""
    t, window = 4096, 512
    block, reach = pk.flash_window_walk(t, window)
    assert (block, reach) == (512, 2)
    nq = t // block
    visited = sum(1 for i in range(nq) for j in range(reach) if i - (reach - 1) + j >= 0)
    assert visited == 2 * nq - 1 and nq * (nq + 1) // 2 == 36
    assert pk.flash_window_walk(32768, 512) == (512, 2)
    assert pk.flash_window_walk(8704, 512) == (512, 2)
    assert pk.flash_window_walk(1280, 512) == (256, 3)
    # Queries of the last two blocks alone matter below: poison block 0.
    t = 2048
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 2, t, 128)).astype(np.float32))
    k, v = (rng.standard_normal((1, 1, t, 128)).astype(np.float32) for _ in range(2))
    clean = pk.flash_fwd_window(q, jnp.asarray(k), jnp.asarray(v), 0.1, 512,
                                interpret=True)
    k[:, :, :512], v[:, :, :512] = np.nan, np.nan
    dirty = pk.flash_fwd_window(q, jnp.asarray(k), jnp.asarray(v), 0.1, 512,
                                interpret=True)
    # Query blocks from the third on never visit key block 0.
    np.testing.assert_array_equal(np.asarray(dirty[:, :, 1024:]),
                                  np.asarray(clean[:, :, 1024:]))
    assert np.isnan(np.asarray(dirty[:, :, :1024])).any()


def _ring_oracle(q, k1, v1, ck, cv, live, at):
    """Write at ``at``, attend the first ``live`` rows: plain numpy on
    positions-last caches (B, h, hd, S)."""
    ck, cv = np.array(ck, np.float32), np.array(cv, np.float32)
    b, h, hd, s = ck.shape
    g = q.shape[1] // h
    out = np.zeros(q.shape, np.float32)
    for i in range(b):
        ck[i, :, :, at[i]], cv[i, :, :, at[i]] = k1[i], v1[i]
        for j in range(q.shape[1]):
            sc = np.asarray(q[i, j], np.float32) @ ck[i, j // g, :, :live[i]] / math.sqrt(hd)
            p = np.exp(sc - sc.max())
            out[i, j] = cv[i, j // g, :, :live[i]] @ (p / p.sum())
    return out, ck, cv


@pytest.mark.parametrize("group,s", [(9, 512), (6, 512), (9, 384), (6, 1024)])
def test_groups_of_nine_and_six_through_flash_decode(group, s):
    """The grouped body at the model's two group sizes, neither a power
    of two (the rows are padded to a sublane tile), over a full cache
    (the write at ``lengths - 1``) and over a ring (``write_at`` apart
    from the live count: a ring not yet full, one just full, one that
    has wrapped; of one chunk, and at 384 and 1024 of several, so that
    the stream's order is rotated to end at the written chunk)."""
    rng = np.random.default_rng(group * s)
    b, h, hd = 4, 2, 128
    q = rng.standard_normal((b, h * group, hd)).astype(np.float32)
    k1, v1 = (rng.standard_normal((b, h, hd)).astype(np.float32) for _ in range(2))
    ck, cv = (rng.standard_normal((b, h, hd, s)).astype(np.float32) for _ in range(2))
    assert pk.flash_decode_supported((b, s, h, hd), jnp.float32, group)
    args = [jnp.asarray(x) for x in (q, k1, v1, ck, cv)]
    # A full cache: lengths alone.
    lens = np.asarray([1, s, 130, s // 2 + 3], np.int32)
    got, gk, gv = pk.flash_decode(*args, jnp.asarray(lens), interpret=True,
                                  positions_last=True)
    want, wk, wv = _ring_oracle(q, k1, v1, ck, cv, lens, lens - 1)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(gk), wk)
    np.testing.assert_array_equal(np.asarray(gv), wv)
    # A ring: positions 5 (not yet full), s - 1 (just full), s + 7 and
    # 3 s + 200 (wrapped).
    pos = np.asarray([5, s - 1, s + 7, 3 * s + 200])
    live, at = np.minimum(pos + 1, s).astype(np.int32), (pos % s).astype(np.int32)
    got, gk, gv = pk.flash_decode(*args, jnp.asarray(live), interpret=True,
                                  positions_last=True, write_at=jnp.asarray(at))
    want, wk, wv = _ring_oracle(q, k1, v1, ck, cv, live, at)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(gk), wk)
    np.testing.assert_array_equal(np.asarray(gv), wv)
    with pytest.raises(ValueError, match="grouped"):
        pk.flash_decode(jnp.asarray(q[:, :h]), *args[1:], jnp.asarray(live),
                        interpret=True, positions_last=True, write_at=jnp.asarray(at))


def test_the_op_decodes_a_ring_through_the_kernel_as_through_the_oracle():
    """Heads of a lane tile: the ring lies positions-last and a decode
    step takes ``flash_decode`` (interpret mode here); the same steps
    through the einsum oracle give the same outputs and the same ring."""
    outs = {}
    for kernel in (True, False):
        op, params, a = _attn_op(2, 1, heads=6, kv=2, hd=128, d=64, window=256,
                                 rope={"theta": 1e4})
        op.decode_kernel = kernel
        assert op.positions_last and bool(op._ring_block(2, kernel)) == kernel
        ring = jnp.asarray(np.random.default_rng(4).standard_normal(
            (2, 2, 128, 256)).astype(np.float32))
        state = {"cache_k": ring, "cache_v": ring,
                 "pos": jnp.asarray([300, 17], jnp.int32)}
        (y,), new = op.forward(params, [a], state, False)
        outs[kernel] = (np.asarray(y), np.asarray(new["cache_k"]))
    np.testing.assert_allclose(outs[True][0], outs[False][0], atol=2e-4)
    np.testing.assert_allclose(outs[True][1], outs[False][1], atol=1e-6)
    # Position 300 went to row 300 - 256, position 17 to row 17.
    changed = np.abs(outs[True][1] - np.asarray(ring)).max(axis=(1, 2))
    assert np.flatnonzero(changed[0]).tolist() == [44]
    assert np.flatnonzero(changed[1]).tolist() == [17]


# -- the experts' share ------------------------------------------------------------

def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """Each chip adds its own experts' terms; the shared expert is every
    chip's alike and must count once."""
    cfg = _cfg()
    whole = dict(cfg, held_experts=None)
    get = ref.Leaves(whole, SEED, "blk1_")
    u = jnp.asarray(np.random.default_rng(7).normal(size=(24, 64)), jnp.float32)
    want = np.asarray(ref.experts(whole, get, u))
    alone = np.asarray(ref.experts(whole, get, u, shared=True)
                       - ref.experts(whole, get, u, shared=False))
    x = TensorSpec("x", (1, 24, 64), jnp.float32, ("n", "s", None))
    total = np.zeros_like(want)
    for share in range(4):
        held = list(range(4 * share, 4 * share + 4))
        op = MixtureOfExperts(
            "moe", x, 16, 32, top_k=3, dispatch="sorted", router="sigmoid",
            gated=True, activation="silu", shared_experts=1, routed_scale=2.5,
            held_experts=held)
        params = {k: get(f"moe/{k}") for k in ("gate", "s_gate", "s_up", "s_down")}
        params.update({k: get(f"moe/{k}")[jnp.asarray(held)]
                       for k in ("w_gate", "w_up", "w_down")})
        (y,), _ = op.forward(params, [u[None]], {}, False)
        part = dict(cfg, num_experts=4, held_experts=held,
                    published={"num_experts": 16})
        if share == 0:  # experts 0..3: the leading rows of the leaf
            np.testing.assert_allclose(
                np.asarray(y[0]),
                np.asarray(ref.experts(part, ref.Leaves(part, SEED, "blk1_"), u)),
                atol=1e-5)
        total += np.asarray(y[0]) - alone
    np.testing.assert_allclose(total + alone, want, atol=2e-5)
    idx, w = ref.route(whole, get, u)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, atol=1e-5)


def test_full_forward_logits_match_the_reference():
    """The plain (uncached) forward of the whole graph, the band as a
    dense mask; with a share of the experts held and a gain on a window
    layer's logits the same."""
    for over in ({}, {"num_experts": 4, "held_experts": [4, 5, 6, 7],
                      "published": {"num_experts": 16}}):
        cfg = _cfg(**over)
        if over:
            cfg["assumed"]["attn_logit_gain"] = {"1": 4.0}
            spec, plain = ref.leaf_spec(cfg), ref.leaf_spec(_cfg(**over))
            assert spec["blk1_attn/wq"][1] == pytest.approx(2 * plain["blk1_attn/wq"][1])
            assert spec["blk1_attn/wk"][1] == pytest.approx(2 * plain["blk1_attn/wk"][1])
            assert spec["blk2_attn/wq"] == plain["blk2_attn/wq"]
        ff, params = _model(cfg, 2, S)
        toks = _tokens(2, S)
        ex = Executor(ff, config=ff.config, devices=jax.devices()[:1])
        _loss, outs = ex.forward_step(params, {}, {"tokens": toks, "label": toks})
        want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t)) for t in toks])
        np.testing.assert_allclose(np.asarray(outs["lm_head:out"]), want, atol=2e-5)


def test_published_parameter_counts_from_the_leaf_recipe():
    """The catalog row's widths through ``leaf_spec``: 117.56 B in all,
    8.14 B active a token (ten experts, the shared one, attention, the
    router, layer 0, the head; not the table), 3.002 G held in the cut."""
    import json

    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    for line in open(path):
        if '"Laguna-S-2.1"' in line:
            row = json.loads(line)["config"]
    whole = dict(row, assumed=dict(_ASSUMED, param_dtype="bfloat16"))
    counts = ref.parameter_counts(whole)
    assert round(counts["total"] / 1e9, 2) == 117.56
    assert round(counts["active"] / 1e9, 2) == 8.14
    spec = ref.leaf_spec(whole)
    size = lambda p: sum(int(np.prod(s[0])) for n, s in spec.items() if n.startswith(p))
    assert round(size("blk0_attn") / 1e6, 2) == 44.19
    assert round(size("blk1_attn") / 1e6, 2) == 63.14
    assert round((size("blk0_mlp") + size("blk0_attn") + 2 * 3072) / 1e6, 1) == 157.4
    cut = dict(whole, num_hidden_layers=5, num_experts=64, vocab_size=25088,
               held_experts=list(range(64)), published={"num_experts": 256})
    assert round(ref.parameter_counts(cut)["total"] / 1e9, 3) == 3.002


def test_smoke_preset_takes_the_kernels_widths():
    m = LAGUNA_SMOKE
    assert m["head_dim"] % 128 == 0 and m["sliding_window"] == 512
    groups = {h // m["num_key_value_heads"] for h in m["num_attention_heads_per_layer"]}
    assert groups == {6, 9}
    assert pk.grouped_matmul_supported(m["hidden_size"],
                                       m["moe_intermediate_size"], jnp.bfloat16)
    for g in groups:
        assert pk.flash_decode_supported((4, 512, 2, 128), jnp.bfloat16, g)
        assert pk.flash_decode_chunk(512, 2, 128, jnp.bfloat16, g) == 512
    assert pk.flash_decode_chunk(512, 8, 128, jnp.bfloat16, 9) == 512


@pytest.mark.parametrize("name", ["solar", "keye", "gpt2"])
def test_programs_without_the_new_arguments_reach_none_of_the_new_helpers(name, monkeypatch):
    """With ``window``, ``gate="per_head"`` and the rotary sub-width
    absent, nothing this family added runs while Solar-Open2's, Keye's
    and GPT-2's serving programs trace: the ring, the band, the banded
    kernel, the gate a head and the rotary turn's extras are patched to
    raise, and ``flash_decode`` refuses a ``write_at``."""
    def never(*args, **kw):
        raise AssertionError("a path of window / per_head / rotary_dim ran")

    for helper in ("_forward_window", "_attend_band", "_decode_ring",
                   "_ring_index", "_ring_block", "_rope_turn"):
        monkeypatch.setattr(MultiHeadAttention, helper, never)
    for helper in ("_band_attention", "_ring_rows", "_gate_heads"):
        monkeypatch.setattr(attention, helper, never)
    monkeypatch.setattr(pk, "flash_fwd_window", never)
    monkeypatch.setattr(pk, "_decode_ring_kernel", never)
    real = pk._kv_stream

    def stream(*args, at_ref=None, **kw):
        assert at_ref is None
        return real(*args, **kw)

    monkeypatch.setattr(pk, "_kv_stream", stream)
    cfg = FFConfig(batch_size=2)
    if name == "solar":
        lm = build_lm(SOLAR_OPEN2_TINY, 2, 32, cfg)
    elif name == "keye":
        lm = build_lm(KEYE_VL2_TINY, 2, 32, cfg)
    else:
        lm = build_transformer_lm(batch_size=2, seq_len=32, vocab_size=128,
                                  d_model=32, num_heads=2, num_layers=2,
                                  config=cfg)
    sex = ServingExecutor(lm, lm.config, max_batch=2, max_seq=32, buckets=(32,))
    mha = [op for op in sex.attn_ops if isinstance(op, MultiHeadAttention)]
    assert mha and all(op.attrs["window"] is None and op.decode_window is None
                       and op.attrs["gate"] in (False, True) for op in mha)
    params, _opt, state = jax.eval_shape(Executor(lm, config=lm.config).init)
    caches = sex._cache_tree(
        sex._cache_specs,
        lambda ce: jax.ShapeDtypeStruct((2,) + tuple(ce.shape), ce.dtype))
    vec = jax.ShapeDtypeStruct((2,), jnp.int32)
    jax.make_jaxpr(sex.build_decode_superstep(2))(params, state, caches, vec, vec)
    jax.make_jaxpr(sex.build_prefill(32))(
        params, state, jax.ShapeDtypeStruct((1, 32), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
