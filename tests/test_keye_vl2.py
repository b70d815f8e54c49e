"""The Keye-VL-2.0 language model (grouped-query attention with a head
norm, rotary positions and a learned token selector; a softmax router
through the sorted expert layer) at a small size on the CPU, seeded
weights, against the plain reference
(``benchmark/references/keye_vl2.py``, the benchmark's own, which imports
nothing of the program)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark.references.keye_vl2 as ref
from benchmark import common, weights
from flexflow_tpu.analysis.program_audit import iter_eqns
from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import (
    KEYE_VL2_SMOKE,
    KEYE_VL2_TINY,
    SOLAR_OPEN2_TINY,
    build_lm,
    build_transformer_lm,
)
from flexflow_tpu.ops.attention import MultiHeadAttention
from flexflow_tpu.ops.base import TensorSpec
from flexflow_tpu.ops.moe import MixtureOfExperts
from flexflow_tpu.ops import token_select
from flexflow_tpu.ops.token_select import TokenSelector, rope_half
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.serving import ServingExecutor

SEED = 3300000029
S = 128
TOPK = KEYE_VL2_TINY["sa_config"]["topk"]           # 16: sequences are 4-8x

_ASSUMED = {"init_std": 0.05, "norm_scale_half_width": 0.05,
            "router_dtype": "float32"}


def _cfg(dtype="float32", base=KEYE_VL2_TINY, **over):
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


def _attn_op(cfg, b, t, **over):
    """The block's attention op alone, its reference leaves (layer 0's)
    and a normed-looking input."""
    kw = dict(num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
              use_bias=False, qk_norm=cfg["rms_norm_eps"],
              rope={"theta": float(cfg["rope_theta"]),
                    "sections": cfg["rope_scaling"]["mrope_section"]},
              select=cfg["sa_config"])
    kw.update(over)
    x = TensorSpec("x", (b, t, cfg["hidden_size"]), jnp.float32, ("n", "s", None))
    op = MultiHeadAttention("blk0_attn", x, cfg["num_attention_heads"], **kw)
    get = ref.Leaves(cfg, SEED).at("blk0_")
    params = {k: jnp.asarray(get(f"attn/{k}")) for k in op.param_specs()}
    a = jnp.asarray(np.random.default_rng(2).standard_normal(
        (b, t, cfg["hidden_size"])).astype(np.float32))
    return op, get, params, a


def test_the_graph_and_what_the_builder_refuses():
    ff = build_lm(KEYE_VL2_TINY, 1, 16)
    names = [op.name for op in ff.layers]
    assert [n for n in names if n.endswith("_attn")] == ["blk0_attn", "blk1_attn"]
    assert sum(n.endswith("_moe") for n in names) == 2
    assert not any("pos" in n for n in names)        # positions live in the op
    attn = ff.find_op("blk0_attn")
    assert isinstance(attn.select, TokenSelector) and attn.select.topk == TOPK
    assert not attn.cache_paged and not attn.lane_tile_heads
    assert attn.serving_path(True) == "gqa_select_decode"
    assert attn.serving_path(False) == "gqa_select_dense"
    moe = ff.find_op("blk0_moe")
    assert moe.attrs["router"] == "softmax" and moe.attrs["dispatch"] == "sorted"
    assert not moe.attrs["shared_experts"] and not moe.attrs["selection_bias"]
    for key, value in (("attention_bias", True), ("use_sliding_window", True),
                       ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
                       ("tie_word_embeddings", True), ("num_local_experts", 4)):
        with pytest.raises(ValueError, match=key):
            build_lm(dict(KEYE_VL2_TINY, **{key: value}), 1, 16)
    yarn = dict(KEYE_VL2_TINY["rope_scaling"], rope_type="yarn")
    with pytest.raises(ValueError, match="rope_type"):
        build_lm(dict(KEYE_VL2_TINY, rope_scaling=yarn), 1, 16)
    with pytest.raises(ValueError, match="no block family"):
        build_lm(dict(KEYE_VL2_TINY, model_type="KeyeVL3"), 1, 16)


# -- the selector -----------------------------------------------------------

def _selector_both(t=96):
    cfg = _cfg()
    op, get, params, a = _attn_op(cfg, 1, t)
    index = jnp.arange(t)[None]
    q, k, w = op.select.project(params, a, index)
    rq, rk, rw = ref.indexer(cfg, get, a[0], index[0])
    return cfg, op.select, (q, k, w), (rq, rk, rw)


def test_selector_scores_match_the_reference():
    _cfg_, sel, (q, k, w), (rq, rk, rw) = _selector_both()
    np.testing.assert_allclose(np.asarray(q[0]), np.asarray(rq), atol=2e-6)
    np.testing.assert_allclose(np.asarray(k[0]), np.asarray(rk), atol=2e-6)
    np.testing.assert_allclose(np.asarray(w[0]), np.asarray(rw), atol=2e-6)
    got = sel.scores(q, w, k)[0]
    want = ref.index_scores(rq, rk, rw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # Both signs among a token's head weights, so that I is no plain sum.
    assert float(jnp.min(w)) < 0 < float(jnp.max(w))


def test_selected_set_is_the_references_exactly():
    """The prefill's mask row by row, and the decode step's indices at
    the same positions, against the mask scattered from the reference's
    own ``lax.top_k``: the same set, position for position."""
    t = 96
    _c, sel, (q, k, w), (rq, rk, rw) = _selector_both(t)
    want = np.asarray(ref.selected(ref.index_scores(rq, rk, rw), 0, TOPK))
    scores = sel.scores(q, w, k)
    keep = np.asarray(sel.keep(scores, jnp.arange(t)))[0]
    assert np.array_equal(keep, want)
    # Every causal position while there are no more than topk; topk after.
    counts = keep.sum(axis=1)
    assert np.array_equal(counts, np.minimum(np.arange(t) + 1, TOPK))
    assert np.array_equal(keep[:TOPK], np.tril(np.ones((TOPK, t), bool)))
    # A decode step at position p scores the cache (the keys through p,
    # garbage beyond) and picks the same set.
    cache = jnp.concatenate([k, 9.0 * jnp.ones((1, 32, k.shape[-1]))], axis=1)
    for p in (3, TOPK - 1, TOPK, 40, t - 1):
        row = sel.scores(q[:, p:p + 1], w[:, p:p + 1], cache)[:, 0]
        idx, valid = sel.pick(row, jnp.asarray([p]))
        chosen = np.zeros((t,), bool)
        chosen[np.asarray(idx[0])[np.asarray(valid[0])]] = True
        assert np.array_equal(chosen, want[p]), p
        assert int(valid.sum()) == min(p + 1, TOPK)


def test_equal_scores_go_to_the_lower_position_in_both_paths():
    """Scores that tie with the topk-th (exact zeros, where every head's
    ReLU is shut): ``lax.top_k``'s order, in the mask as in the indices."""
    sel = TokenSelector(dict(KEYE_VL2_TINY["sa_config"], topk=4), 1e4)
    scores = jnp.asarray([[[0.0, 2.0, 0.0, 0.0, 1.0, 0.0, 0.0, 3.0]]])
    keep = np.asarray(sel.keep(scores, jnp.asarray([7])))[0, 0]
    assert keep.tolist() == [True, True, False, False, True, False, False, True]
    idx, valid = sel.pick(scores[0], jnp.asarray([7]))
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1, 4, 7] and bool(valid.all())


def _adversarial_rows(case):
    """``(scores (rows, width) float32, k)`` of one case: row 0 is the
    case's own row, the rest seeded noise with exact zeros of both
    signs, which ride along at every width and ``k``."""
    width, k = 256, 24
    if case == "width_17x128":
        width, k = 17 * 128, 300
    elif case == "width_no_multiple_of_128":
        width, k = 300, 41
    elif case == "k_1":
        k = 1
    elif case == "k_width_less_1":
        k = width - 1
    r = np.random.default_rng(len(case))
    x = r.standard_normal((12, width)).astype(np.float32)
    x = np.where(r.random(x.shape) < 0.25,
                 np.where(r.random(x.shape) < 0.5, 0.0, -0.0), x).astype(np.float32)
    row = x[0]
    if case == "all_equal":
        row[:] = 0.75
    elif case == "zeros_of_both_signs_at_the_threshold":
        row[:] = -1.0
        row[:8] = 1.0                     # k - 8 = 16 of the 40 zeros are kept
        row[100:140] = np.where(np.arange(40) % 3 == 0, 0.0, -0.0)
    elif case == "run_of_neg_inf":
        row[40:200] = -np.inf
    elif case == "fewer_than_k_finite":
        row[k // 2:] = -np.inf
    elif case == "negatives_only":
        row[:] = -np.abs(row) - 1e-3
    elif case == "denormals_beside_1e30":
        row[0::3], row[1::3], row[2::3] = 1e-45, 1e30, -1e-45
    return x, k


@pytest.mark.parametrize("case", [
    "all_equal", "zeros_of_both_signs_at_the_threshold", "run_of_neg_inf",
    "fewer_than_k_finite", "negatives_only", "denormals_beside_1e30",
    "width_17x128", "width_no_multiple_of_128", "k_1", "k_width_less_1"])
def test_prefill_threshold_and_mask_are_top_ks_bit_for_bit(case):
    """``keep`` sorts nothing, and still: its threshold is
    ``lax.top_k``'s ``k``-th value bit for bit (``-0.0`` is not
    ``+0.0``; an order key is its float's bits, folded), and its mask
    the one scattered from ``lax.top_k``'s indices."""
    x, k = _adversarial_rows(case)
    rows, width = x.shape
    # Rows that see from fewer than k positions up to the whole width.
    q_pos = np.linspace(k - 3, width - 1, rows).astype(np.int32)
    q_pos[0] = width - 1
    causal = np.arange(width)[None, :] <= q_pos[:, None]
    masked = jnp.where(causal, x, -jnp.inf)
    top, idx = jax.lax.top_k(masked, k)
    kth = token_select.kth_largest_key(token_select.order_key(masked), k)
    assert kth.shape == (rows, 1)
    assert np.array_equal(np.asarray(kth),
                          np.asarray(token_select.order_key(top[:, -1:])))
    sel = TokenSelector(dict(KEYE_VL2_TINY["sa_config"], topk=k), 1e4)
    want = np.zeros((rows, width), bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=1)
    keep = np.asarray(sel.keep(jnp.asarray(x)[None], jnp.asarray(q_pos)))[0]
    assert np.array_equal(keep, want & causal)
    assert np.array_equal(keep.sum(axis=1), np.minimum(q_pos + 1, k))


def test_cached_prefill_sorts_nothing_and_the_decode_step_once():
    """What PR 43 took and what it left, on the attention op alone (the
    routers' top-8 of 128 is another matter): a prefill past ``topk``
    holds no ``top_k`` and no ``sort``; a decode step still takes one
    ``lax.top_k`` for its ``topk`` indices (ROADMAP B-M1 (f): a PR that
    takes it changes this count)."""
    cfg = _cfg()
    t = 96
    assert t > TOPK
    op, _get, params, a = _attn_op(cfg, 1, t)
    caches = {f"cache_{e}": jnp.zeros((1,) + ce.shape, ce.dtype)
              for e, ce in op.cache_entries(S).items()}

    def forward(x, pos):
        return op.forward(params, [x], dict(caches, pos=pos), training=False)

    def primitives(x, pos):              # the sub-programs' too
        return [e.primitive.name for e in iter_eqns(
            jax.make_jaxpr(forward)(x, pos).jaxpr, descend_custom_ad=True)]

    prefill = primitives(a, jnp.zeros((1,), jnp.int32))
    assert "cond" in prefill                 # keep's tie break: it is reached
    assert not {"top_k", "sort"} & set(prefill)
    step = primitives(a[:, :1], jnp.full((1,), t - 1, jnp.int32))
    assert step.count("top_k") == 1 and "sort" not in step


# -- the attention op -------------------------------------------------------

def test_rotary_with_equal_components_is_plain_half_split_rotary():
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 3, 10, 16)),
                    jnp.float32)
    pos = jnp.asarray(np.random.default_rng(2).integers(0, 5000, (2, 10)))
    plain = rope_half(x, pos[:, None], 1e7)
    three = jnp.broadcast_to(pos[:, None, :, None], (2, 1, 10, 3))
    assert np.array_equal(np.asarray(rope_half(x, three, 1e7, [2, 3, 3])),
                          np.asarray(plain))
    # By hand: pair (i, i + 8) turned by pos * theta^(-2i/16).
    inv = 1e7 ** (-np.arange(8) / 8.0)
    ang = np.asarray(pos)[:, None, :, None] * inv
    a, b = np.asarray(x[..., :8]), np.asarray(x[..., 8:])
    want = np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                           b * np.cos(ang) + a * np.sin(ang)], axis=-1)
    # f32 angles of up to 5000 radians: 5000 * 2^-24 of a turn each.
    np.testing.assert_allclose(np.asarray(plain), want, atol=2e-3)


def test_attention_op_with_unequal_position_components_matches_the_reference():
    """Multimodal rotary positions at the op: pairs 0-1 turn by the
    first component, 2-4 by the second, 5-7 by the third."""
    cfg = _cfg()
    t = 48
    op, get, params, a = _attn_op(cfg, 1, t)
    rng = np.random.default_rng(3)
    positions = np.stack([np.arange(t), rng.integers(0, 30, t),
                          rng.integers(0, 30, t)], axis=-1)
    (got,), _ = op.forward(params, [a], {"positions": jnp.asarray(positions)[None]},
                           training=False)
    want, _ = ref.attention(cfg, get, a[0], positions=positions)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=2e-5)
    plain, _ = ref.attention(cfg, get, a[0])
    assert float(jnp.max(jnp.abs(plain - want))) > 1e-3


def _through_the_caches(op, params, a, plen):
    """Prefill ``a[:, :plen]`` into zeroed caches, then a step a token."""
    b, t, _ = a.shape
    caches = {f"cache_{e}": jnp.zeros((b,) + ce.shape, ce.dtype)
              for e, ce in op.cache_entries(S).items()}
    (y,), state = op.forward(params, [a[:, :plen]],
                             dict(caches, pos=jnp.zeros((b,), jnp.int32)),
                             training=False)
    outs = [y]
    for p in range(plen, t):
        state = dict(state, pos=jnp.full((b,), p, jnp.int32))
        (y,), state = op.forward(params, [a[:, p:p + 1]], state, training=False)
        outs.append(y)
    return jnp.concatenate(outs, axis=1), state


def test_attention_op_prefill_and_decode_through_the_three_cache_entries():
    cfg = _cfg()
    t, plen = 96, 72
    op, get, params, a = _attn_op(cfg, 2, t)
    entries = op.cache_entries(S)
    hkv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    assert {e: ce.shape for e, ce in entries.items()} == {
        "k": (S, hkv * hd), "v": (S, hkv * hd),
        "idx": (S, cfg["sa_config"]["indexer_head_dim"])}
    got, state = _through_the_caches(op, params, a, plen)
    for i in range(2):
        want, _ = ref.attention(cfg, get, a[i])
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want), atol=2e-5)
    # The third entry holds the reference's indexer keys, row for row.
    _, rk, _ = ref.indexer(cfg, get, a[0], jnp.arange(t))
    np.testing.assert_allclose(np.asarray(state["cache_idx"][0, :t]),
                               np.asarray(rk), atol=2e-6)
    assert not np.asarray(state["cache_idx"][0, t:]).any()
    # Selection selects here: the dense answer is another one.
    dense, _ = ref.attention(cfg, get, a[0], select=False)
    want, _ = ref.attention(cfg, get, a[0])
    assert float(jnp.max(jnp.abs(dense - want)[TOPK:])) > 1e-3


@pytest.mark.parametrize("topk", [96, 4096])
def test_topk_at_least_the_sequence_is_the_op_without_select(topk):
    """The tie to dense attention: nothing is left out, so the op with a
    selector equals the same op (head norm, rotary positions) without
    one, on the plain forward and through the caches."""
    cfg = _cfg(sa_config=dict(KEYE_VL2_TINY["sa_config"], topk=topk))
    t, plen = 96, 64
    op, get, params, a = _attn_op(cfg, 1, t)
    bare, _, _, _ = _attn_op(cfg, 1, t, select=None)
    bare_params = {k: v for k, v in params.items() if not k.startswith("idx_")}
    assert set(bare_params) == set(bare.param_specs())
    (want,), _ = bare.forward(bare_params, [a], {}, training=False)
    (got,), _ = op.forward(params, [a], {}, training=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    cached, _ = _through_the_caches(op, params, a, plen)
    np.testing.assert_allclose(np.asarray(cached), np.asarray(want), atol=2e-5)
    bare_cached, state = _through_the_caches(bare, bare_params, a, plen)
    np.testing.assert_allclose(np.asarray(bare_cached), np.asarray(want), atol=2e-5)
    assert state["cache_k"].shape == (1, S, 2, 16)   # the ordinary entry
    ref_dense, _ = ref.attention(cfg, get, a[0], select=False)
    np.testing.assert_allclose(np.asarray(want[0]), np.asarray(ref_dense), atol=2e-5)


def test_regimes_that_refuse_a_selector_name_the_roadmap():
    cfg = _cfg()
    op, _get, params, a = _attn_op(cfg, 1, 32)
    caches = {f"cache_{e}": jnp.zeros((1,) + ce.shape, ce.dtype)
              for e, ce in op.cache_entries(S).items()}
    base = dict(caches, pos=jnp.zeros((1,), jnp.int32))
    for extra in ({"block_table": jnp.zeros((1, 4), jnp.int32)}, {"chunk": 16}):
        with pytest.raises(NotImplementedError, match="ROADMAP B-M1"):
            op.forward(params, [a], dict(base, **extra), training=False)
    ff = build_lm(KEYE_VL2_TINY, 2, S)
    with pytest.raises(ValueError, match="no paged pool"):
        ServingExecutor(ff, ff.config, max_batch=2, max_seq=S, kv_block=16)
    with pytest.raises(NotImplementedError, match="ROADMAP B-M1"):
        ServingExecutor(ff, ff.config, max_batch=2, max_seq=S, shard=(1, 2))


# -- the expert layer -------------------------------------------------------

def test_softmax_router_through_sorted_dispatch_matches_the_dense_sum():
    """``router="softmax"`` through ``dispatch="sorted"`` (no shared
    expert, no bias, no scale) against the reference's loop over every
    expert on every token."""
    cfg = _cfg()
    d = cfg["hidden_size"]
    x = TensorSpec("x", (2, 24, d), jnp.float32, ("n", "s", None))
    op = MixtureOfExperts(
        "blk0_moe", x, cfg["num_experts"], cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"], dispatch="sorted", router="softmax",
        gated=True, activation="silu", norm_topk_prob=True)
    assert set(op.param_specs()) == {"gate", "w_gate", "w_up", "w_down"}
    get = ref.Leaves(cfg, SEED).at("blk0_")
    params = {k: jnp.asarray(get(f"moe/{k}")) for k in op.param_specs()}
    u = jnp.asarray(np.random.default_rng(4).standard_normal((2, 24, d)),
                    jnp.float32)
    for serving in (False, True):
        (got,), state = op.forward(params, [u], {"serving": serving},
                                   training=False)
        want = ref.experts(cfg, get, u.reshape(-1, d)).reshape(u.shape)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    assert 0 < float(state["stats"]["experts_touched"]) <= cfg["num_experts"]
    idx, w = op.route(params, u.reshape(-1, d))
    ridx, rw = ref.route(cfg, get, u.reshape(-1, d))
    assert np.array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), atol=1e-6)


# -- the whole model --------------------------------------------------------

@pytest.mark.parametrize("gain", [None, {"1": 3.0}])
def test_full_forward_logits_match_the_reference(gain):
    """``gain``: the benchmark configuration's ``assumed.q_norm_gain``,
    the layers whose query norm's scale is drawn around another value
    than 1 (the reference's scanned layers carry each layer's own
    offset)."""
    cfg = _cfg()
    if gain:
        cfg["assumed"]["q_norm_gain"] = gain
        spec = ref.leaf_spec(cfg)
        assert [spec[f"blk{i}_attn/q_norm"][2] for i in range(2)] == [1.0, 3.0]
        assert spec["blk1_attn/k_norm"][2] == 1.0
    ff, params = _model(cfg, 2, 64)
    toks = _tokens(2, 64)
    ex = Executor(ff, config=ff.config, devices=jax.devices()[:1])
    _loss, outs = ex.forward_step(params, {}, {"tokens": toks, "label": toks})
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t)) for t in toks])
    np.testing.assert_allclose(np.asarray(outs["lm_head:out"]), want, atol=1e-5)
    dense = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t, select=False))
                      for t in toks])
    assert np.abs(dense - want).max() > 0.05      # the selector selects


def _serve_logits(params, ff, toks, plen, bucket):
    """Logits at positions ``plen-1 ..`` of each row of ``toks``: one
    decode step a token through the caches a prefill left."""
    b, t = toks.shape
    sex = ServingExecutor(ff, ff.config, max_batch=b, max_seq=S,
                          buckets=[bucket], decode_kernel=None)
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


@pytest.mark.parametrize("dtype,atol", [("float32", 1e-5), ("bfloat16", None)])
def test_prefill_then_decode_through_the_caches(dtype, atol):
    """The chunked prefill (its dense head, two key widths, several
    chunks; the bucket's pad rows beyond the prompt) then one-token
    steps over the three caches it left, at sequences 4.5-6x ``topk``,
    against the reference's one full forward: logits, not tokens."""
    cfg = _cfg(dtype)
    ff, params = _model(cfg, 2, S, dtype)
    toks = _tokens(2, 96)
    got, sex = _serve_logits(params, ff, toks, 72, 80)
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t))[72:] for t in toks])
    if atol is None:
        # bf16 against the f32 walk on the same (bf16-rounded) weights:
        # round-off of 8 bits through the blocks, and where a position or
        # an expert near a threshold flips, that token's logits move
        # more: the median and the share far off, not the widest.
        gap = np.abs(got.astype(np.float32) - want)
        assert np.median(gap) < 0.03 and np.mean(gap > 0.15) < 0.15
    else:
        np.testing.assert_allclose(got.astype(np.float32), want, atol=atol)
    shapes = {k: {e: c.shape for e, c in v.items()}
              for k, v in sex.init_cache().items()}
    assert shapes["blk1_attn"] == {"k": (2, S, 32), "v": (2, S, 32),
                                   "idx": (2, S, 8)}
    assert sex._attention_paths(True) == "gqa_select_decode"


def test_kv_rows_count_the_gathered_rows_and_the_selectors_keys():
    """K/V rows a decode step fetches are the ``topk`` its gather takes
    a slot (a fixed shape: rows past a shorter live length are fetched
    and masked), not the live length; the selector's keys are scored
    over the whole padded cache (a plain product)."""
    ff = build_lm(KEYE_VL2_TINY, 4, S)
    sex = ServingExecutor(ff, ff.config, max_batch=4, max_seq=S, buckets=[S])
    assert [op.decode_fetch_block(4, S, None) for op in sex.attn_ops] == [1, 1]
    rows = sex.kv_rows(np.asarray([100, 5, 64, 0]), 8)
    assert rows == {"kv_rows_fetched": 4 * 8 * TOPK, "kv_rows_cache": 4 * 8 * S,
                    "idx_rows_fetched": 4 * 8 * S}
    big = dict(KEYE_VL2_TINY, sa_config=dict(KEYE_VL2_TINY["sa_config"], topk=4096))
    ff = build_lm(big, 4, S)
    sex = ServingExecutor(ff, ff.config, max_batch=4, max_seq=S, buckets=[S])
    assert sex.kv_rows(np.zeros((4,), np.int32), 2)["kv_rows_fetched"] == 4 * 2 * S
    # An op without a selector counts what it always did.
    solar = build_lm(SOLAR_OPEN2_TINY, 4, S)
    sex = ServingExecutor(solar, solar.config, max_batch=4, max_seq=S, buckets=[S])
    assert "idx_rows_fetched" not in sex.kv_rows(np.zeros((4,), np.int32), 2)


def test_kv_rows_of_a_graph_that_mixes_selecting_and_dense_layers(monkeypatch):
    """Each op is counted by what it reads and the counter is one
    layer's rows, the mean: a graph whose second layer selects nothing
    fetches that layer's live rows (to its own block), and scores the
    selector's keys in the first alone."""
    ff = build_lm(KEYE_VL2_TINY, 4, S)
    sex = ServingExecutor(ff, ff.config, max_batch=4, max_seq=S, buckets=[S])
    first, second = sex.attn_ops
    monkeypatch.setattr(second, "select", None)
    monkeypatch.setattr(second, "decode_fetch_block", lambda *a, **k: 32)
    rows = sex.kv_rows(np.asarray([100, 5, 64, 0]), 2)
    # live lengths 101, 102; 6, 7; 65, 66; 1, 2 -> to blocks of 32.
    dense = 2 * 128 + 2 * 32 + 2 * 96 + 2 * 32
    assert rows == {"kv_rows_fetched": (4 * 2 * TOPK + dense) // 2,
                    "kv_rows_cache": 4 * 2 * S,
                    "idx_rows_fetched": 4 * 2 * S // 2}
    assert first.select is not None


# -- what the numbers say ---------------------------------------------------

#: The catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: ``Keye-VL-2.0-30B-A3B``), as published.
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2", "moe_intermediate_size": 768,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


def test_published_parameter_counts_from_the_leaf_recipe():
    """30.64 B in all and about 3.2-3.5 B active (the "30B-A3B" of the
    name), 625.38 M a layer; 4.375 G = 8.75 GB for the six layers held."""
    cfg = dict(CATALOG, assumed=dict(_ASSUMED, param_dtype="bfloat16"))
    n = ref.parameter_counts(cfg)
    assert round(n["total"] / 1e9, 2) == 30.64
    assert 3.1e9 < n["active"] < 3.2e9                    # with its table row
    assert n["active"] + 151935 * 2048 < 3.5e9           # with the whole table
    spec = ref.leaf_spec(cfg)
    layer = sum(int(np.prod(s)) for k, (s, _, _) in spec.items()
                if k.startswith("blk0_"))
    assert round(layer / 1e6, 2) == 625.38
    indexer = sum(int(np.prod(s)) for k, (s, _, _) in spec.items()
                  if k.startswith("blk0_attn/idx_"))
    assert round(indexer / 1e6, 3) == 2.261
    cut = ref.parameter_counts(dict(cfg, num_hidden_layers=6))["total"]
    assert round(cut / 1e9, 3) == 4.375 and round(cut * 2 / 1e9, 2) == 8.75
    # The program declares the same leaves in the same shapes.
    ff = build_lm(dict(cfg, num_hidden_layers=1), 1, 16,
                  FFConfig(batch_size=1, compute_dtype="bfloat16"))
    abstract, _, _ = jax.eval_shape(Executor(ff, config=ff.config).init)
    one = dict(cfg, num_hidden_layers=1)
    assert {f"{o}/{k}": tuple(v.shape) for o, ls in abstract.items()
            for k, v in ls.items()} == \
        {k: tuple(s) for k, (s, _, _) in ref.leaf_spec(one).items()}
    assert abstract["blk0_attn"]["idx_ww"].dtype == jnp.float32
    assert abstract["blk0_moe"]["gate"].dtype == jnp.float32
    assert abstract["blk0_attn"]["idx_wq"].dtype == jnp.bfloat16
    assert ref.stored_dtype(cfg, "blk0_attn/idx_ww") == "float32"
    assert ref.stored_dtype(cfg, "blk0_attn/idx_wk") == "bfloat16"
    assert weights.leaf_values(1, "blk0_attn/idx_ww", (4, 3), 1.0).shape == (4, 3)


def test_smoke_preset_takes_the_kernels_widths():
    from flexflow_tpu.ops import pallas_kernels as pk

    m = KEYE_VL2_SMOKE
    assert m["head_dim"] % 128 == 0
    assert pk.grouped_matmul_supported(m["hidden_size"],
                                       m["moe_intermediate_size"], jnp.bfloat16)
    assert sum(m["rope_scaling"]["mrope_section"]) == m["head_dim"] // 2
    assert m["sa_config"]["topk"] % m["sa_config"]["q_chunk_size"] == 0


@pytest.mark.parametrize("name", ["solar", "gpt2"])
def test_programs_without_the_three_arguments_take_none_of_the_new_paths(name, monkeypatch):
    """With ``qk_norm``, ``rope`` and ``select`` absent no attention op
    is positional, and nothing this family added runs while
    Solar-Open2's grouped-query layer and GPT-2's serving programs
    trace: the head placement, the selected forward and the rotary
    helper are patched to raise."""
    from flexflow_tpu.ops import attention, token_select

    def never(*args, **kw):
        raise AssertionError("a path of qk_norm / rope / select ran")

    monkeypatch.setattr(MultiHeadAttention, "_place_heads", never)
    monkeypatch.setattr(MultiHeadAttention, "_forward_selected", never)
    monkeypatch.setattr(MultiHeadAttention, "_attend_selected", never)
    monkeypatch.setattr(attention, "rope_half", never)
    monkeypatch.setattr(token_select, "rope_half", never)
    if name == "solar":
        lm = build_lm(SOLAR_OPEN2_TINY, 2, 32, FFConfig(batch_size=2))
    else:
        lm = build_transformer_lm(batch_size=2, seq_len=32, vocab_size=128,
                                  d_model=32, num_heads=2, num_layers=2,
                                  config=FFConfig(batch_size=2))
    sex = ServingExecutor(lm, lm.config, max_batch=2, max_seq=32, buckets=(32,))
    mha = [op for op in sex.attn_ops if isinstance(op, MultiHeadAttention)]
    assert mha and not any(op.positional or op.select is not None for op in mha)
    params, _opt, state = jax.eval_shape(Executor(lm, config=lm.config).init)
    caches = sex._cache_tree(
        sex._cache_specs,
        lambda ce: jax.ShapeDtypeStruct((2,) + tuple(ce.shape), ce.dtype))
    vec = jax.ShapeDtypeStruct((2,), jnp.int32)
    jax.make_jaxpr(sex.build_decode_superstep(2))(params, state, caches, vec, vec)
    jax.make_jaxpr(sex.build_prefill(32))(
        params, state, jax.ShapeDtypeStruct((1, 32), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))


def test_prefill_through_the_kept_kernel_equals_the_plain_path(monkeypatch):
    """The smoke preset's prefill (512 rows under a ``topk`` of 256 in
    chunks of 128: two masked chunks, groups of 2 query heads on a KV
    head) through ``pallas_kernels.attend_kept`` and, with its gate
    turned off, through ``_attend_kept_heads``: the same first token and
    the same rows for the three cache entries of every layer."""
    from flexflow_tpu.ops import pallas_kernels as pk

    seq, plen = 512, 450
    ff, params = _model(_cfg(base=KEYE_VL2_SMOKE), 1, seq)
    toks = _tokens(1, seq)
    calls, real = [], pk.attend_kept
    monkeypatch.setattr(pk, "attend_kept", lambda *a, **k: (
        calls.append(a[0].shape), real(*a, **k))[1])

    def prefill():
        sex = ServingExecutor(ff, ff.config, max_batch=1, max_seq=seq,
                              buckets=[seq])
        rows, tok, ok, *_ = sex.build_prefill(seq)(params, {}, toks,
                                                   np.int32(plen))
        assert bool(ok)
        return sex.kept_blocks(seq), rows, int(tok[0] if np.ndim(tok) else tok)

    kept, rows, tok = prefill()
    layers = KEYE_VL2_SMOKE["num_hidden_layers"]
    heads = KEYE_VL2_SMOKE["num_attention_heads"]
    assert kept == dict(kept_kernel=True, kept_key_blocks=2,
                        kept_key_blocks_square=2)
    # One run (256..512), traced once a layer: its two chunks are a loop.
    assert calls == [(1, heads, 128, 128)] * layers
    monkeypatch.setattr(pk, "attend_kept_supported", lambda *a: False)
    plain_kept, plain_rows, plain_tok = prefill()
    assert len(calls) == layers and plain_kept["kept_kernel"] is False
    assert tok == plain_tok
    assert set(rows["blk1_attn"]) == {"k", "v", "idx"}
    # Round-off of the streamed softmax apart, a later layer's rows move
    # only where a near-tie of a router or a selector falls the other way.
    for got, want in zip(jax.tree.leaves(rows), jax.tree.leaves(plain_rows)):
        gap = np.abs(np.asarray(got[:plen]) - np.asarray(want[:plen]))
        assert np.median(gap) < 1e-6 and np.mean(gap > 1e-4) < 0.005
