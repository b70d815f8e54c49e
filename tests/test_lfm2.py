"""The LFM2-MoE block family (a gated short convolution with a two-row
window, grouped-query attention at heads of 64 with a head norm and
rotary positions, a sigmoid router with a selection bias, a head tied to
the token table) at a small size on the CPU, seeded weights, against the
plain reference (``benchmark/references/lfm2.py``, the benchmark's own,
which imports nothing of the program)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark.references.lfm2 as ref
from benchmark import common
from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import LFM2_SMOKE, LFM2_TINY, build_lm
from flexflow_tpu.ops import delta_attention, short_conv
from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.ops.attention import _einsum_decode
from flexflow_tpu.ops.base import TensorSpec, op_params
from flexflow_tpu.ops.linear import Linear
from flexflow_tpu.ops.moe import MixtureOfExperts
from flexflow_tpu.ops.short_conv import GatedShortConv
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.runtime import telemetry
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.serving import Request, Server, ServingExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3300000051
S = 128  # the kernels want whole 128-position tiles

_ASSUMED = {"init_std": 0.05, "norm_scale_half_width": 0.05,
            "conv_tap_half_width": 0.5, "e_bias_half_width": 0.05,
            "router_dtype": "float32", "q_norm_gain": {"2": 2.5}}

#: Three layers (conv, conv, attention; dense, expert, expert) at the
#: narrowest widths every kernel takes: heads of 64 in groups of four.
_KERNEL_WIDTHS = dict(
    LFM2_SMOKE, num_hidden_layers=3, num_dense_layers=1, hidden_size=256,
    vocab_size=512, num_attention_heads=4, num_key_value_heads=1,
    intermediate_size=256)

#: ... with two cached heads under eight: the decode kernel folds them.
_TWO_CACHED_HEADS = dict(hidden_size=512, num_attention_heads=8,
                         num_key_value_heads=2)


def _cfg(dtype="float32", base=LFM2_TINY, **over):
    return dict(base, **over, assumed=dict(_ASSUMED, param_dtype=dtype))


def _model(cfg, batch, seq, dtype="float32"):
    ff = build_lm(cfg, batch, seq, FFConfig(batch_size=batch,
                                            compute_dtype=dtype))
    abstract, _, _ = jax.eval_shape(Executor(ff, config=ff.config).init)
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    params = common.make_params(ref.leaf_spec(cfg), SEED, abstract,
                                jax.tree.map(lambda _: one, abstract))
    return ff, params


def _activations(b, t, d):
    return TensorSpec("x", (b, t, d), jnp.float32, ("n", "s", None))


def _tokens(n, t, vocab=512):
    return np.random.default_rng(5).integers(0, vocab, size=(n, t),
                                             dtype=np.int32)


def test_the_graph_names_its_mixers_and_refuses_what_it_does_not_build():
    ff = build_lm(LFM2_TINY, 1, 16)
    names = [op.name for op in ff.layers]
    assert [n for n in names if n.endswith(("_attn", "_conv"))] == \
        ["blk0_conv", "blk1_conv", "blk2_attn", "blk3_conv", "blk4_conv",
         "blk5_attn"]
    assert [n for n in names if n.endswith(("_moe", "_mlp_down"))] == \
        ["blk0_mlp_down", "blk1_mlp_down", "blk2_moe", "blk3_moe", "blk4_moe",
         "blk5_moe"]
    moe = ff.find_op("blk2_moe")
    assert moe.attrs["norm_topk_eps"] == 1e-6 and moe.attrs["selection_bias"] \
        and moe.attrs["shared_experts"] == 0 and moe.attrs["router"] == "sigmoid"
    attn = ff.find_op("blk2_attn")
    assert (attn.attrs["head_dim"], attn.group, attn.attrs["qk_norm"]) == (16, 2, 1e-5)
    # layer_types may name more layers than are held: the first ones count.
    more = dict(LFM2_TINY, layer_types=LFM2_TINY["layer_types"] + ["conv"] * 4)
    assert [op.name for op in build_lm(more, 1, 16).layers] == names
    for key, value in (("conv_bias", True), ("tie_embedding", False),
                       ("tie_word_embeddings", False),
                       ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
                       ("layer_types", ["conv", "sliding_attention"] * 3)):
        with pytest.raises(ValueError, match=f"lfm2_moe builder: {key}"):
            build_lm(dict(LFM2_TINY, **{key: value}), 1, 16)
    with pytest.raises(ValueError, match="must name all 6 layers"):
        build_lm(dict(LFM2_TINY, layer_types=["conv"] * 4), 1, 16)


def test_the_tied_head_is_one_leaf_in_every_tree():
    cfg = _cfg()
    ff, params = _model(cfg, 2, 16)
    head, embed = ff.find_op("lm_head"), ff.find_op("embed")
    assert isinstance(head, Linear) and head.tied == {"kernel": ("embed", "table")}
    assert head.param_specs() == {} and "lm_head" not in params
    assert not [k for k in ref.leaf_spec(cfg) if k.startswith("lm_head")]
    assert op_params(head, params)["kernel"] is params["embed"]["table"]
    assert op_params(embed, params) is params["embed"]
    with pytest.raises(KeyError, match="lm_head: tied to"):
        op_params(head, {})
    # The product is x E^T, whatever dtype the table is held in.
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, 64)), jnp.float32)
    (y,), _ = head.forward(op_params(head, params), [x], {}, False)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(x) @ np.asarray(params["embed"]["table"]).T,
        atol=1e-5)
    # A table another op reads stays off the row-sparse update path.
    f32 = build_lm(LFM2_TINY, 2, 16, FFConfig(batch_size=2, compute_dtype="float32"))
    assert f32.find_op("embed").table_dtype == jnp.float32   # else dense anyway
    ex = Executor(f32, config=FFConfig(batch_size=2, sparse_embedding_updates=True),
                  optimizer=SGDOptimizer(lr=0.01))
    assert "embed" not in [op.name for op in ex._sparse_ops]
    # The tie is to an embedding's table of the head's own shape.
    with pytest.raises(ValueError, match="tied_to='embed'"):
        ff.dense(ff.find_op("ln_f").outputs[0], 100, use_bias=False,
                 name="other_head", tied_to="embed")


def test_an_untied_graph_keeps_the_sparse_embedding_path():
    from flexflow_tpu.models.transformer import build_transformer_lm

    ff = build_transformer_lm(2, 16, vocab_size=64, d_model=16, num_heads=2,
                              num_layers=1)
    ex = Executor(ff, config=FFConfig(batch_size=2, sparse_embedding_updates=True),
                  optimizer=SGDOptimizer(lr=0.01))
    assert all(not op.tied for op in ff.layers)
    assert [op.name for op in ex._sparse_ops] == ["embed"]


def test_full_forward_logits_match_the_reference():
    """The training graph: the whole sequence through the convolution,
    einsum attention over repeated heads, ``ragged_dot`` experts, the
    table as the head."""
    cfg = _cfg()
    ff, params = _model(cfg, 2, 32)
    toks = _tokens(2, 32)
    ex = Executor(ff, config=ff.config, devices=jax.devices()[:1])
    _loss, outs = ex.forward_step(params, {}, {"tokens": toks, "label": toks})
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t)) for t in toks])
    np.testing.assert_allclose(np.asarray(outs["lm_head:out"]), want, atol=1e-5)
    # The reference's stateless picture (the newest tap alone) is far
    # from it: what a lost window costs.
    lost = np.asarray(ref.logits_fn(cfg, SEED, toks[0], stateless=True))
    assert np.abs(lost - want[0]).max() > 0.05


def _serve_logits(params, ff, toks, plen, kernel, steps, bucket=S):
    """Logits at positions ``plen-1 ..`` of each row of ``toks``: decode
    supersteps of ``steps`` tokens through the caches a prefill left."""
    b, t = toks.shape
    sex = ServingExecutor(ff, ff.config, max_batch=b, max_seq=S,
                          buckets=[bucket], decode_kernel=kernel)
    pf = sex.build_prefill(bucket)
    caches = sex.init_cache()
    padded = np.full((b, bucket), 9, np.int32)       # pad tokens that matter
    padded[:, :plen] = toks[:, :plen]
    for i in range(b):
        rows, _tok, ok, *_ = pf(params, {}, padded[i:i + 1], np.int32(plen))
        assert bool(ok)
        caches = sex.install(caches, rows, i)
    dec = sex.build_decode_superstep(1, return_logits=True)
    pos = np.full((b,), plen, np.int32)
    got = []
    for j in range(plen, plen + steps):
        caches, _, _, out = dec(params, {}, caches, pos.copy(),
                                toks[:, j].copy())
        got.append(np.asarray(out[2])[0])
        pos += 1
    return np.stack(got, axis=1), sex


@pytest.mark.parametrize("base,dtype,kernel,atol", [
    (LFM2_TINY, "float32", None, 1e-5),
    (_KERNEL_WIDTHS, "float32", True, 2e-5),
    (_KERNEL_WIDTHS, "float32", False, 2e-5),
    # Two cached heads: the decode kernel's folded body.
    (dict(_KERNEL_WIDTHS, **_TWO_CACHED_HEADS), "float32", True, 2e-5),
    # bf16 weights, activations, KV cache and window against the f32
    # reference on the same (bf16-rounded) weights: judged by the median
    # and the share of logits far off (a flipped near-tie between two
    # experts moves a token's logits by a whole expert's output).
    (_KERNEL_WIDTHS, "bfloat16", True, None),
])
def test_prefill_then_decode_through_the_caches(base, dtype, kernel, atol):
    """A prefill of ``length < bucket`` (the window taken at the prompt's
    length: pad tokens that matter fill the rest of the bucket), then
    2 x K one-token steps over the KV cache and the windows it left,
    against the reference's one full forward."""
    cfg = _cfg(dtype, base)
    ff, params = _model(cfg, 2, S, dtype)
    toks = _tokens(2, 48)
    got, sex = _serve_logits(params, ff, toks, 40, kernel, steps=8)
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t))[40:] for t in toks])
    if atol is None:
        gap = np.abs(got.astype(np.float32) - want)
        assert np.median(gap) < 0.03 and np.mean(gap > 0.15) < 0.15
    else:
        np.testing.assert_allclose(got.astype(np.float32), want, atol=atol)
    shapes = {k: {e: c.shape for e, c in v.items()}
              for k, v in sex.init_cache().items()}
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv = (2, S, cfg["num_key_value_heads"], d // h)   # positions-major: heads of 64
    assert shapes["blk2_attn"] == {"k": kv, "v": kv}
    assert shapes["blk0_conv"] == {"conv": (2, 2, d)}
    assert [op.name for op in sex.stateful_ops] == \
        [n for n in shapes if n.endswith("_conv")]


def test_one_prompt_in_two_buckets_leaves_the_same_window():
    """The window a prefill hands on is that of the last two real rows,
    whatever the bucket and whatever lies in its padding."""
    cfg = _cfg()
    ff, params = _model(cfg, 1, 2 * S)
    plen = 77
    prompt = _tokens(1, plen)
    sex = ServingExecutor(ff, ff.config, max_batch=1, max_seq=2 * S,
                          buckets=[S, 2 * S])
    rows = []
    for bucket, pad in ((S, 9), (2 * S, 300)):
        padded = np.full((1, bucket), pad, np.int32)
        padded[0, :plen] = prompt[0]
        rows.append(sex.build_prefill(bucket)(params, {}, padded, np.int32(plen)))
    (small, tok_a, *_), (large, tok_b, *_) = rows
    assert int(tok_a) == int(tok_b)
    for name in ("blk0_conv", "blk4_conv"):
        np.testing.assert_allclose(np.asarray(small[name]["conv"]),
                                   np.asarray(large[name]["conv"]), atol=2e-6)
        assert float(jnp.max(jnp.abs(small[name]["conv"]))) > 1e-3
    # ... and a prompt shorter than the window keeps zeros before it.
    one = np.full((1, S), 9, np.int32)
    short, *_ = sex.build_prefill(S)(params, {}, one, np.int32(1))
    win = np.asarray(short["blk0_conv"]["conv"])
    assert np.all(win[0] == 0) and np.abs(win[1]).max() > 1e-4


@pytest.mark.parametrize("taps", [3, 4])
def test_short_conv_decode_rows_equal_the_prefill_rows(taps):
    """The op alone: a sequence at once, and the same tokens one at a
    time through the window, give the same rows; the window after ``n``
    steps is the last ``taps - 1`` rows of ``u``."""
    b, t, d = 2, 12, 32
    op = GatedShortConv("conv", _activations(b, t, d), kernel_size=taps)
    r = np.random.default_rng(1)
    params = {k: jnp.asarray(r.normal(size=s.shape) * 0.3, jnp.float32)
              for k, s in op.param_specs().items()}
    x = jnp.asarray(r.normal(size=(b, t, d)), jnp.float32)
    (whole,), _ = op.forward(params, [x], {}, False)
    state = {"cache_conv": jnp.zeros((b, taps - 1, d), jnp.float32),
             "pos": jnp.zeros((b,), jnp.int32)}
    rows = []
    for j in range(t):
        (y,), state = op.forward(params, [x[:, j:j + 1]], state, False)
        rows.append(y[:, 0])
    np.testing.assert_allclose(np.stack(rows, axis=1), np.asarray(whole), atol=1e-5)
    gate_in, _, z = jnp.split(x @ params["w_in"], 3, axis=-1)
    np.testing.assert_allclose(np.asarray(state["cache_conv"]),
                               np.asarray((gate_in * z)[:, -(taps - 1):]), atol=1e-6)
    # A prefill told its length hands on the window that ends there.
    at = 7
    _, left = op.forward(params, [x], {"cache_conv": jnp.zeros((b, taps - 1, d)),
                                       "length": jnp.int32(at)}, False)
    np.testing.assert_allclose(np.asarray(left["cache_conv"]),
                               np.asarray((gate_in * z)[:, at - (taps - 1):at]),
                               atol=1e-6)
    with pytest.raises(NotImplementedError, match="convolution window"):
        op.forward(params, [x], {"cache_conv": jnp.zeros((b, taps - 1, d)),
                                 "chunk": 4}, False)


def test_delta_attention_walks_its_window_through_the_shared_helpers():
    assert delta_attention.causal_taps is short_conv.causal_taps
    assert delta_attention.window_at is short_conv.window_at
    r = np.random.default_rng(3)
    ext = jnp.asarray(r.normal(size=(2, 9, 5)), jnp.float32)    # 6 rows behind 3
    taps = jnp.asarray(r.normal(size=(4, 5)), jnp.float32)
    want = sum(np.asarray(ext)[:, j:j + 6] * np.asarray(taps)[j] for j in range(4))
    np.testing.assert_allclose(np.asarray(short_conv.causal_taps(ext, taps, 6)),
                               want, atol=1e-6)
    old = jnp.full((2, 3, 5), 7.0)
    for at, want in ((4, np.asarray(ext)[:, 4:7]), (6, np.asarray(ext)[:, 6:9]),
                     (0, np.asarray(old)), (9, np.asarray(old))):
        got = short_conv.window_at(ext, old, jnp.int32(at), 6)
        np.testing.assert_array_equal(np.asarray(got), want)


def test_router_choices_and_weights_are_the_references_with_a_bias_that_flips():
    """Float32, exact: the four of largest ``score + bias``, weighed by
    their scores over the scores' sum + 1e-6; a bias that flips a
    choice changes who is chosen and no chosen expert's score."""
    cfg = _cfg(num_experts=16, num_experts_per_tok=4)
    t, d, e = 64, cfg["hidden_size"], 16
    op = MixtureOfExperts(
        "blk2_moe", _activations(1, t, d), e, 32, top_k=4, dispatch="sorted",
        router="sigmoid", gated=True, activation="silu", selection_bias=True,
        norm_topk_prob=True, norm_topk_eps=1e-6)
    get = ref.Leaves(cfg, SEED, "blk2_")
    params = {"gate": get("moe/gate"), "e_bias": get("moe/e_bias")}
    u = jnp.asarray(np.random.default_rng(2).normal(size=(t, d)), jnp.float32)
    idx, w = op.route(params, u)
    ridx, rw = ref.route(cfg, get, u)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(rw))
    s = np.asarray(jax.nn.sigmoid(jnp.dot(u, params["gate"], precision="highest")))
    chosen = np.take_along_axis(s, np.asarray(idx), axis=1)
    np.testing.assert_allclose(np.asarray(w), chosen / (chosen.sum(1, keepdims=True) + 1e-6),
                               rtol=1e-6)
    assert np.all(np.asarray(w).sum(1) < 1.0)        # the epsilon is there
    # Without the bias some token chooses otherwise ...
    plain, _ = op.route({**params, "e_bias": jnp.zeros((e,))}, u)
    assert np.any(np.sort(np.asarray(plain), 1) != np.sort(np.asarray(idx), 1))
    # ... and a bias that lifts the fifth expert of token 0 over its
    # fourth flips that choice alone, weighing by the score, not the sum.
    order = np.argsort(-s[0])
    bias = np.zeros((e,), np.float32)
    bias[order[4]] = (s[0, order[3]] - s[0, order[4]]) + 1e-3
    flipped, fw = op.route({**params, "e_bias": jnp.asarray(bias)}, u[:1])
    assert set(np.asarray(flipped)[0]) == set(order[:3]) | {order[4]}
    np.testing.assert_allclose(
        np.asarray(fw)[0].sum() * (s[0, list(np.asarray(flipped)[0])].sum() + 1e-6),
        s[0, list(np.asarray(flipped)[0])].sum(), rtol=1e-6)


def test_routes_epsilon_defaults_to_what_it_was():
    op = MixtureOfExperts("moe", _activations(1, 8, 16), 4, 8, top_k=2,
                          dispatch="sorted", router="sigmoid")
    assert op.attrs["norm_topk_eps"] == 1e-20


@pytest.mark.parametrize("s,lens", [
    (256, [1, 130, 256]),            # one chunk of two lane tiles
    (3072, [700, 3072, 513, 2049]),  # the cell's cache: chunks of 512
])
def test_grouped_decode_kernel_at_heads_of_64_equals_the_einsum_oracle(s, lens):
    """``flash_decode`` at ``hd`` 64, four query heads a cached head, on
    the positions-major cache the op declares at that width (interpret
    mode; the chip's compiler holds the shape in
    ``tests/test_chip_compile.py``)."""
    r = np.random.default_rng(2)
    b, h, hkv, hd = len(lens), 8, 2, 64
    q = jnp.asarray(r.normal(size=(b, h, hd)), jnp.float32)
    k1, v1 = (jnp.asarray(r.normal(size=(b, hkv, hd)), jnp.float32) for _ in range(2))
    ck, cv = (jnp.asarray(r.normal(size=(b, s, hkv, hd)), jnp.float32) for _ in range(2))
    lengths = jnp.asarray(lens, jnp.int32)
    assert pk.flash_decode_chunk(s, hkv, hd, jnp.float32, 4) == min(512, s)
    assert pk.flash_decode_supported((b, s, hkv, hd), jnp.float32, group=4)
    assert pk.flash_decode_supported((b, s, hkv, hd), jnp.bfloat16, group=4)
    assert not pk.flash_decode_supported((b, s, hkv, 32), jnp.float32, group=4)
    out, nk, nv = pk.flash_decode(q, k1, v1, ck, cv, lengths)
    rows = jnp.arange(b)
    wk = ck.at[rows, lengths - 1].set(k1)
    wv = cv.at[rows, lengths - 1].set(v1)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(wv))
    want = _einsum_decode(q, wk, wv, lengths - 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def _decode_oracle(q, k1, v1, ck, cv, live, at):
    """One decode step in numpy over (B, S, h, hd) caches: the column
    written at ``at``, the first ``live`` positions attended."""
    q, k1, v1, ck, cv = (np.asarray(x.astype(jnp.float32)) for x in
                         (q, k1, v1, ck, cv))
    ck, cv = ck.copy(), cv.copy()
    g = q.shape[1] // ck.shape[2]
    out = np.zeros(q.shape, np.float32)
    for i in range(q.shape[0]):
        ck[i, at[i]], cv[i, at[i]] = k1[i], v1[i]
        for j in range(q.shape[1]):
            sc = ck[i, :live[i], j // g] @ q[i, j] / np.sqrt(q.shape[2])
            w = np.exp(sc - sc.max())
            out[i, j] = (w / w.sum()) @ cv[i, :live[i], j // g]
    return out, ck, cv


@pytest.mark.parametrize("ring", [False, True], ids=["full", "ring"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hkv,g", [(8, 4), (2, 4), (4, 2), (1, 8)])
def test_decode_body_folded_over_heads_of_64_equals_the_oracle(hkv, g, dtype, ring):
    """The grouped body at ``hd`` 64 with several cached heads a step
    (``flash_decode_heads_per_step``; one cached head alone keeps the
    body a head a turn) against a plain oracle: lengths on both sides of
    a lane tile's and of a 512-position chunk's edge, over a full cache
    and over a ring (``write_at``: not yet full, just full, wrapped).
    The caches come back equal to the caches that went in, bit for bit,
    but for each slot's one written column (so nothing outside the lane
    tile that holds it moved, and inside it the column alone)."""
    s, hd = 1024, 64
    dt = jnp.dtype(dtype)
    r = np.random.default_rng(hkv * 16 + g)
    pos = np.asarray([0, 126, 127, 128, 511, 512, s - 1] if not ring else
                     [5, 127, 128, 512, s - 1, s + 7, 3 * s + 200])
    b = len(pos)
    q = jnp.asarray(r.normal(size=(b, hkv * g, hd)), dt)
    k1, v1 = (jnp.asarray(r.normal(size=(b, hkv, hd)), dt) for _ in range(2))
    ck, cv = (jnp.asarray(r.normal(size=(b, s, hkv, hd)), dt) for _ in range(2))
    live, at = np.minimum(pos + 1, s).astype(np.int32), (pos % s).astype(np.int32)
    assert pk.flash_decode_supported((b, s, hkv, hd), dt, g)
    fold = pk.flash_decode_heads_per_step(hkv, hd, g, dt)
    assert (fold > 1) == (hkv > 1) and hkv % fold == 0
    out, nk, nv = pk.flash_decode(
        q, k1, v1, ck, cv, jnp.asarray(live),
        write_at=jnp.asarray(at) if ring else None)
    want, wk, wv = _decode_oracle(q, k1, v1, ck, cv, live, at)
    np.testing.assert_array_equal(np.asarray(nk.astype(jnp.float32)), wk)
    np.testing.assert_array_equal(np.asarray(nv.astype(jnp.float32)), wv)
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)), want,
                               atol=2e-5 if dtype == "float32" else 2e-2)


@pytest.mark.parametrize("h,hd,g,dtype,heads", [
    (8, 64, 4, "bfloat16", 4),    # lfm2.serve: 16 query rows a step
    (8, 64, 4, "float32", 2),     # a float32 tile holds 8 rows
    (2, 64, 4, "bfloat16", 2),    # never more than the heads there are
    (4, 64, 2, "bfloat16", 4),
    (3, 64, 4, "bfloat16", 1),    # an odd head count does not pair
    (8, 128, 8, "bfloat16", 1),   # solar2.serve
    (8, 128, 6, "bfloat16", 1),   # laguna.serve's full caches
    (8, 128, 9, "bfloat16", 1),   # ... and its rings
    (16, 64, 1, "bfloat16", 1),   # gpt2m.serve: the vector body
])
def test_heads_a_decode_step_follow_the_shape_alone(h, hd, g, dtype, heads):
    """Folded only where a head is narrower than a lane tile and
    queries are grouped: the long-context cells keep a head a turn."""
    assert pk.flash_decode_heads_per_step(h, hd, g, jnp.dtype(dtype)) == heads


def test_the_decode_program_at_heads_of_64_calls_the_kernel_not_the_einsum():
    """The attention op at the published head width and group decodes
    through ``flash_decode`` (its gate takes the shape), over a cache
    it declares positions-major."""
    from flexflow_tpu.ops.attention import MultiHeadAttention

    x = TensorSpec("x", (4, 256, 2048), jnp.bfloat16, ("n", "s", None))
    op = MultiHeadAttention("attn", x, 32, use_bias=False, num_kv_heads=8,
                            head_dim=64, qk_norm=1e-5, rope={"theta": 1e6})
    assert not op.lane_tile_heads and not op.positions_last and op.group == 4
    assert op.cache_entries(3072)["k"].shape == (3072, 8, 64)
    assert op.decode_fetch_block(192, 3072, None) == 512
    assert op.decode_fetch_block(192, 3072, False) == 3072
    assert op.serving_path(True) == "gqa_decode"
    # Four cached heads a step of the kernel's grouped body; none of it
    # under the einsum oracle.
    assert op.decode_heads_per_step(192, 3072, None) == 4
    assert op.decode_heads_per_step(192, 3072, False) == 0


@pytest.mark.parametrize("over,kernel,want", [
    (_TWO_CACHED_HEADS, None, {"decode_heads_per_step": 2}),
    (_TWO_CACHED_HEADS, False, {}),              # the einsum oracle
    ({}, None, {"decode_heads_per_step": 1}),    # one cached head: a head a turn
])
def test_the_decode_program_says_how_many_heads_a_step_folds(over, kernel, want,
                                                            tmp_path):
    """``serving_program`` of the decode superstep carries
    ``decode_heads_per_step`` where an op decodes through the kernel's
    grouped body, so a run's stream says which body was compiled; a
    prefill program never does."""
    ff = build_lm(dict(_KERNEL_WIDTHS, **over), 2, S, FFConfig(batch_size=2))
    sex = ServingExecutor(ff, max_batch=2, max_seq=S, buckets=(S,),
                          decode_kernel=kernel)
    assert sex.decode_heads_per_step() == want
    with telemetry.Telemetry(directory=str(tmp_path)) as tel:
        sex.build_decode_superstep(2)
        sex.build_prefill(S)
    programs = {e["kind"]: e for e in common.read_events(tel.path)
                if e["ev"] == "serving_program"}
    assert {k: v for k, v in programs["decode"].items()
            if k == "decode_heads_per_step"} == want
    assert "decode_heads_per_step" not in programs["prefill"]


def _req(rid, plen, max_new):
    prompt = np.random.default_rng([SEED, rid]).integers(0, 512, size=plen,
                                                         dtype=np.int32)
    return Request(id=rid, prompt=prompt, max_new_tokens=max_new)


def test_the_executor_counts_the_windows_once_and_announces_the_paths(tmp_path):
    ff = build_lm(LFM2_TINY, 2, 64, FFConfig(batch_size=2))
    sex = ServingExecutor(ff, max_batch=2, max_seq=64, buckets=(16, 64))
    # K and V of two attention layers: 2 x 2 heads x 16 x 4 B x 2.
    assert sex._bytes_per_token == 2 * 2 * 16 * 4 * 2
    fixed = 4 * (2 * 64 * 4)                 # four windows of two rows
    assert sex._bytes_fixed == fixed
    assert sex.cache_total_bytes() == 2 * sex.hbm_per_slot_bytes() == sum(
        c.nbytes for c in jax.tree.leaves(sex.init_cache()))
    assert [op.name for op in sex.stateful_ops] == \
        ["blk0_conv", "blk1_conv", "blk3_conv", "blk4_conv"]
    rows = sex.kv_rows(np.array([5, 0], np.int32), 2)
    assert rows["kv_rows_cache"] == 2 * 2 * 64 and rows["state_bytes"] == 2 * 2 * 2 * fixed
    params, state = sex.init(0)
    with telemetry.Telemetry(directory=str(tmp_path)) as tel:
        results, _ = Server(sex, params, state, decode_steps=4).run(
            [_req(0, 5, 6), _req(1, 20, 6)])
    assert all(r.error is None and len(r.tokens) == 6 for r in results.values())
    events = common.read_events(tel.path)
    programs = {e["kind"]: e["attention"] for e in events
                if e["ev"] == "serving_program"}
    assert programs == {"prefill": "gqa_dense+short_conv",
                        "decode": "gqa_decode+short_conv"}
    steps = [e for e in events if e["ev"] == "decode_superstep"]
    assert steps and all(e["state_bytes"] == 2 * 4 * 2 * fixed
                         and 0 < e["experts_touched"] <= 8
                         and e["expert_load_max"] >= 1.0 for e in steps)


def test_pool_prefix_shard_and_speculation_refuse_the_op_by_name():
    ff = build_lm(LFM2_TINY, 2, 64, FFConfig(batch_size=2))
    with pytest.raises(ValueError, match="blk0_conv.*GatedShortConv"):
        ServingExecutor(ff, max_batch=2, max_seq=64, kv_block=16)
    with pytest.raises(ValueError, match="paged"):
        ServingExecutor(ff, max_batch=2, max_seq=64, prefix_cache=True)
    with pytest.raises(ValueError, match="blk0_conv"):
        ServingExecutor(ff, max_batch=2, max_seq=64, shard=(1, 2))
    sex = ServingExecutor(ff, max_batch=2, max_seq=64)
    for build in (lambda: sex.build_spec_step(2),
                  lambda: sex.build_draft_prefill(64)):
        with pytest.raises(ValueError, match="blk0_conv.*convolution window"):
            build()
    sex.paged = sex.prefix_cache = True
    sex.kv_block = 16
    with pytest.raises(ValueError, match="blk0_conv.*convolution window"):
        sex.build_prefill_from(64, 16)


def test_a_tied_head_refuses_a_c_split():
    from flexflow_tpu.models.transformer import transformer_strategy

    ff = build_lm(LFM2_TINY, 2, 16, FFConfig(batch_size=2))
    ex = Executor(ff, config=ff.config, strategy=transformer_strategy(
        2, 0, dp=1, tp=2), devices=jax.devices()[:2])
    params, _, state = ex.init(0)
    toks = _tokens(2, 16)
    with pytest.raises(NotImplementedError, match="lm_head.*tied"):
        ex.forward_step(params, state, {"tokens": toks, "label": toks})


def test_compiled_serving_programs_carry_the_convolutions_scopes():
    ff = build_lm(LFM2_TINY, 1, 32, FFConfig(batch_size=1))
    sex = ServingExecutor(ff, max_batch=1, max_seq=32, buckets=(32,))
    params, state = sex.init(0)
    text = sex.build_prefill(32).lower(
        params, state, np.zeros((1, 32), np.int32), np.int32(20)).as_text(
            debug_info=True)
    assert "blk0_conv/ff_conv_state" in text and "blk2_attn" in text


def test_published_and_held_parameter_counts_from_the_leaf_recipe():
    """23,843,661,440 in all and 2,326,881,920 active a token at the
    published keys (the row's "24B-A2B": no block missing or doubled,
    one table), 5,267,090,176 held on this stage."""
    cfg = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "lfm2-24b-a2b-l10.json")))
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["num_hidden_layers"] == 10
    assert cfg["layer_types"][:10] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv"]
    whole = ref.parameter_counts({**cfg, **cfg["published"]})
    assert whole == {"total": 23_843_661_440, "active": 2_326_881_920}
    held = ref.parameter_counts(cfg)["total"]
    assert held == 5_267_090_176 and round(held * 2 / 1e9, 2) == 10.53
    for text in ("23,843,661,440", "2,326,881,920", "5,267,090,176"):
        assert text in cfg["assumed"]["parameter_count"]
    assert "5,267,090,176" in cfg["deployment"]
    spec = ref.leaf_spec(cfg)
    size = lambda p: sum(int(np.prod(s[0])) for n, s in spec.items() if n.startswith(p))
    assert size("blk0_conv") == 16_783_360 and size("blk2_attn") == 10_485_888
    assert size("blk0_mlp") == 72_351_744
    assert size("blk2_moe") == 603_979_776 + 131_136
    assert size("embed") == 134_217_728 and not size("lm_head")
    # The program's own tree at the published widths is the recipe's.
    ff = build_lm(cfg, 1, 128, FFConfig(batch_size=1, compute_dtype="bfloat16"))
    abstract, _, _ = jax.eval_shape(Executor(ff, config=ff.config).init)
    got = {f"{op}/{k}": tuple(v.shape) for op, ls in abstract.items()
           for k, v in ls.items()}
    assert got == {n: tuple(s[0]) for n, s in spec.items()}
    assert abstract["embed"]["table"].dtype == jnp.bfloat16
    assert abstract["blk2_moe"]["gate"].dtype == jnp.float32


def test_smoke_preset_takes_the_kernels_widths():
    m = LFM2_SMOKE
    hd = m["hidden_size"] // m["num_attention_heads"]
    assert hd == 64 and m["num_attention_heads"] // m["num_key_value_heads"] == 4
    assert pk.flash_decode_supported((4, 512, m["num_key_value_heads"], hd),
                                     jnp.bfloat16, 4)
    assert pk.flash_uneven_supported((1, m["num_attention_heads"], 512, hd), hd)
    assert pk.grouped_matmul_supported(m["hidden_size"],
                                       m["moe_intermediate_size"], jnp.bfloat16)
