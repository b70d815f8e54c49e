"""The Xing4.0 block family (the hyper-connection's four-stream residual
round latent attention with a compressed query under YaRN and the sorted
expert layer) at a small size on the CPU, seeded weights, against the
plain reference (``benchmark/references/xing4.py``, the benchmark's
own, which imports nothing of the program)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark.references.xing4 as ref
from benchmark import common
from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.models.transformer import (
    DEEPSEEK_V3_TINY,
    SOLAR_OPEN2_TINY,
    XING4_TINY,
    build_lm,
)
from flexflow_tpu.ops import Add, HyperConnectionPost, HyperConnectionPre
from flexflow_tpu.ops.attention import rope_frequencies, rope_interleaved
from flexflow_tpu.ops.hyper_connection import sinkhorn, stochastic_defect
from flexflow_tpu.runtime import telemetry
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.serving import Request, Server, ServingExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2900000035
S = 128  # the kernels want whole 128-position tiles

_ASSUMED = {"init_std": 0.05, "norm_scale_half_width": 0.05,
            "e_bias_half_width": 0.05, "router_dtype": "float32",
            "hc_dtype": "float32", "hc_alpha": [0.5, 1.5],
            "hc_bias_half_width": 0.5, "hc_res_diagonal": 2.0}


def _cfg(dtype="float32", **over):
    return dict(XING4_TINY, assumed=dict(_ASSUMED, param_dtype=dtype), **over)


def _model(cfg, batch, seq, dtype="float32"):
    ff = build_lm(cfg, batch, seq, FFConfig(batch_size=batch,
                                            compute_dtype=dtype))
    abstract, _, _ = jax.eval_shape(Executor(ff, config=ff.config).init)
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    params = common.make_params(ref.leaf_spec(cfg), SEED, abstract,
                                jax.tree.map(lambda _: one, abstract))
    return ff, params


def _tokens(n, t):
    return np.random.default_rng(5).integers(0, 512, size=(n, t),
                                             dtype=np.int32)


# -- the hyper-connection -------------------------------------------------------


def _hc_pair(close=False, streams_in=True):
    """One pre/post pair over a (2, 8, 4, 32) stream with seeded
    parameters in both the program's tree and the reference's names."""
    n, c = 4, 32
    ff = FFModel(FFConfig(batch_size=2))
    shape = (2, 8, n, c) if streams_in else (2, 8, c)
    x = ff.create_tensor(shape, dtype=jnp.float32, name="x",
                         dim_axes=("n", "s") + (None,) * (len(shape) - 2))
    y = ff.create_tensor((2, 8, c), dtype=jnp.float32, name="y",
                         dim_axes=("n", "s", None))
    ff.hyper_connection_pre(x, n, name="blk0_hc1_pre")
    ff.hyper_connection_post(x, y, n, close=close, name="blk0_hc1_post")
    cfg = _cfg(hidden_size=c)
    spec = {k: v for k, v in ref.leaf_spec(cfg).items() if k.startswith("blk0_hc1_")}
    from benchmark import weights

    params = {}
    for name, (shp, hw, off) in spec.items():
        op, key = name.split("/")
        params.setdefault(op, {})[key] = jnp.asarray(
            weights.leaf_values(SEED, name, shp, hw, off))
    return ff, cfg, params


def test_ops_follow_the_references_hyper_connection():
    ff, cfg, params = _hc_pair()
    pre, post = ff.layers
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 8, 4, 32)).astype(np.float32)
    y = rng.normal(size=(2, 8, 32)).astype(np.float32)
    (u,), _ = pre.forward(params[pre.name], [x], {}, False)
    (new,), state = post.forward(params[post.name], [x, y], {"serving": True}, False)
    get = ref.Leaves(cfg, SEED).at("blk0_")
    for b in range(2):
        h_pre, h_post, h_res = ref.hc_coefficients(cfg, get, "hc1", jnp.asarray(x[b]))
        np.testing.assert_allclose(u[b], np.einsum("tn,tnc->tc", h_pre, x[b]), atol=1e-5)
        want, _ = ref.hyper_connection(cfg, get, "hc1", jnp.asarray(x[b]), lambda u_: y[b])
        np.testing.assert_allclose(new[b], want, atol=1e-5)
    worst = max(float(ref.defect(ref.hc_coefficients(cfg, get, "hc1", jnp.asarray(x[b]))[2]))
                for b in range(2))
    assert abs(float(state["stats"]["hc_defect"]) - worst) < 1e-5
    # Training and eval report nothing.
    assert "stats" not in post.forward(params[post.name], [x, y], {}, False)[1]


def test_sinkhorn_invariants():
    """After the twentieth round H_res is positive and its columns sum to
    1 within float32 round-off; the rows' defect grows in no round; op
    and reference agree on the matrix and on the defect.  Twenty rounds
    do not converge for every matrix the clamp admits, so no fixed bound
    on the rows is asserted."""
    rng = np.random.default_rng(2)
    logits = np.clip(rng.normal(size=(64, 4, 4)) * 6.0, -30, 30).astype(np.float32)
    m = jnp.exp(jnp.asarray(logits))
    rounds = ref.sinkhorn_rounds(m, 20, 1e-6)
    mine = [jnp.moveaxis(sinkhorn(jnp.moveaxis(m, 0, -1), k, 1e-6), -1, 0)
            for k in range(1, 21)]
    rows = [float(jnp.max(jnp.abs(jnp.sum(r, axis=-1) - 1.0))) for r in mine]
    assert all(b <= a + 1e-6 for a, b in zip(rows, rows[1:])), rows
    last = np.asarray(mine[-1])
    assert (last > 0).all()
    assert np.abs(last.sum(axis=-2) - 1.0).max() < 1e-5
    np.testing.assert_allclose(last, np.asarray(rounds[-1]), atol=1e-5)
    assert abs(float(stochastic_defect(jnp.moveaxis(mine[-1], 0, -1)))
               - float(ref.defect(rounds[-1]))) < 1e-5
    assert rows[-1] > 1e-4  # this draw has not converged: why no bound is set


def test_identity_coefficients_are_the_plain_residual():
    """With alpha 0 and the biases where H_res = I, H_pre = 1/n and
    H_post = 1, the MEAN of the streams follows m' = m + F(m)."""
    ff, _cfg_, params = _hc_pair()
    pre, post = ff.layers
    p_pre = dict(params[pre.name], alpha=jnp.zeros((1,)),
                 bias=jnp.full((4,), -np.log(3.0), jnp.float32))
    p_post = dict(params[post.name], alpha=jnp.zeros((2,)),
                  b_post=jnp.zeros((4,)),
                  b_res=jnp.asarray(1e4 * (np.eye(4) - 1.0), jnp.float32))
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 8, 4, 32)).astype(np.float32)
    f = lambda u_: jnp.tanh(u_) * 3.0  # noqa: E731
    (u,), _ = pre.forward(p_pre, [x], {}, False)
    np.testing.assert_allclose(u, x.mean(axis=2), atol=1e-6)
    (new,), _ = post.forward(p_post, [x, f(u)], {}, False)
    np.testing.assert_allclose(np.asarray(new).mean(axis=2),
                               x.mean(axis=2) + f(x.mean(axis=2)), atol=1e-5)
    # ... and the op's own initial values are that point.
    fresh = {k: s.initializer(jax.random.key(0), s.shape, s.dtype)
             for k, s in post.param_specs().items()}
    (near,), _ = post.forward(dict(fresh, alpha=jnp.zeros((2,))), [x, f(u)], {}, False)
    np.testing.assert_allclose(np.asarray(near).mean(axis=2),
                               x.mean(axis=2) + f(x.mean(axis=2)), atol=1e-2)


def test_a_table_row_opens_the_stream_and_close_sums_it():
    ff, _c, params = _hc_pair(close=True, streams_in=False)
    pre, post = ff.layers
    assert pre.outputs[0].shape == post.outputs[0].shape == (2, 8, 32)
    rng = np.random.default_rng(4)
    x = rng.normal(size=(2, 8, 32)).astype(np.float32)
    y = rng.normal(size=(2, 8, 32)).astype(np.float32)
    x4 = np.broadcast_to(x[:, :, None, :], (2, 8, 4, 32))
    ff4, _c, _p = _hc_pair()
    (u,), _ = pre.forward(params[pre.name], [x], {}, False)
    (u4,), _ = ff4.layers[0].forward(params[pre.name], [x4], {}, False)
    np.testing.assert_array_equal(u, u4)
    (out,), _ = post.forward(params[post.name], [x, y], {}, False)
    (new,), _ = ff4.layers[1].forward(params[post.name], [x4, y], {}, False)
    np.testing.assert_allclose(out, np.asarray(new).sum(axis=2), atol=1e-5)
    assert (pre.cache_entries(64), post.cache_entries(64)) == ({}, {})


# -- latent attention: the compressed query, YaRN ---------------------------------


def test_yarn_with_factor_one_is_the_plain_rotary_op_bit_for_bit():
    """Kanana's path is unchanged: the plain frequencies are what the op
    computed before it took them as an argument, and a factor of 1 gives
    them back whatever the ramp."""
    plain, wave, soft = rope_frequencies(8, 1e6)
    old = 1e6 ** (-jnp.arange(0, 8, 2, dtype=jnp.float32) / 8)
    assert np.array_equal(np.asarray(plain), np.asarray(old)) and (wave, soft) == (1.0, 1.0)
    one = {"type": "yarn", "factor": 1, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
           "mscale_all_dim": 1, "original_max_position_embeddings": 32}
    inv, wave, soft = rope_frequencies(8, 1e6, one)
    assert np.array_equal(np.asarray(inv), np.asarray(plain)) and (wave, soft) == (1.0, 1.0)
    x = np.random.default_rng(0).normal(size=(2, 16, 8)).astype(np.float32)
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    assert np.array_equal(np.asarray(rope_interleaved(x, pos, inv, wave)),
                          np.asarray(rope_interleaved(x, pos, plain)))
    with pytest.raises(ValueError, match="rope_scaling type 'linear'"):
        rope_frequencies(8, 1e6, {"type": "linear", "factor": 2})


def test_yarn_frequencies_and_scale_are_the_references():
    cfg = _cfg()
    inv, wave, soft = rope_frequencies(cfg["qk_rope_head_dim"], cfg["rope_theta"],
                                       cfg["rope_scaling"])
    want_inv, want_scale = ref.yarn(cfg)
    np.testing.assert_allclose(np.asarray(inv), np.asarray(want_inv), rtol=1e-6)
    assert wave == 1.0
    base = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    assert abs(base * soft - want_scale) < 1e-12
    # The published keys: a softmax scale 2.005x the plain one, the
    # fastest pair untouched and the slowest slowed 64 times.
    pub = json.load(open(os.path.join(REPO, "benchmark", "configs",
                                      "xing4.0-29b-a4b-l7.json")))
    inv, _, soft = rope_frequencies(64, pub["rope_theta"], pub["rope_scaling"])
    assert abs(soft - 2.0047) < 1e-3
    plain = rope_frequencies(64, pub["rope_theta"])[0]
    assert float(inv[0]) == float(plain[0])
    assert abs(float(inv[-1]) * 64 / float(plain[-1]) - 1) < 1e-6


def test_full_forward_logits_match_the_reference():
    """The training graph (einsum attention, ``ragged_dot`` experts)
    round the four streams."""
    cfg = _cfg()
    ff, params = _model(cfg, 2, 32)
    toks = _tokens(2, 32)
    ex = Executor(ff, config=ff.config, devices=jax.devices()[:1])
    _loss, outs = ex.forward_step(params, {}, {"tokens": toks, "label": toks})
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t)) for t in toks])
    np.testing.assert_allclose(np.asarray(outs["lm_head:out"]), want, atol=2e-5)
    assert outs["blk0_hc1_post:out"].shape == (2, 32, 4, 64)
    assert outs["blk2_hc2_post:out"].shape == (2, 32, 64)


def _serve_logits(params, ff, toks, plen, kernel):
    """Logits at positions ``plen-1 ..`` of each row of ``toks``: the
    prefill's last row, then one decode step a token through the cache."""
    b, t = toks.shape
    sex = ServingExecutor(ff, ff.config, max_batch=b, max_seq=S,
                          buckets=[S], decode_kernel=kernel)
    pf = sex.build_prefill(S)
    caches = sex.init_cache()
    padded = np.zeros((b, S), np.int32)
    padded[:, :plen] = toks[:, :plen]
    for i in range(b):
        rows, _tok, ok, *_ = pf(params, {}, padded[i:i + 1], np.int32(plen))
        assert bool(ok)
        caches = sex.install(caches, rows, i)
    dec = sex.build_decode_superstep(1, return_logits=True)
    pos = np.full((b,), plen, np.int32)
    got, stats = [], []
    for j in range(plen, t):
        caches, _, _, out = dec(params, {}, caches, pos.copy(),
                                toks[:, j].copy())
        got.append(np.asarray(out[2])[0])
        stats.append(out[3])
        pos += 1
    return np.stack(got, axis=1), sex, stats


@pytest.mark.parametrize("dtype,kernel,atol,flips", [
    ("float32", True, 2e-5, 0),
    ("float32", False, 2e-5, 0),
    # bf16 weights, activations, streams and cache against the f32
    # reference on the same (bf16-rounded) weights: logits of spread 0.4
    # carry 8 bits through three blocks of four streams (0.013 at most,
    # measured), and at one of the sixteen positions a near-tie between
    # two experts of a token falls the other way, which moves its logits
    # by a whole expert's output (0.39).
    ("bfloat16", True, 0.03, 1),
])
def test_prefill_then_decode_through_the_latent_cache(dtype, kernel, atol, flips):
    """Expanded prefill, then absorbed decode over the cache it wrote
    (compressed query and YaRN in both), against the reference's one
    full forward; logits, not tokens."""
    cfg = _cfg(dtype)
    ff, params = _model(cfg, 2, S, dtype)
    toks = _tokens(2, 48)
    got, sex, stats = _serve_logits(params, ff, toks, 40, kernel)
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t))[40:] for t in toks])
    worst = np.sort(np.abs(got.astype(np.float32) - want).max(axis=-1).ravel())
    assert worst[len(worst) - 1 - flips] <= atol and worst[-1] <= (0.5 if flips else atol), worst
    # Only the attention ops keep anything for a slot: the streams do not.
    assert sorted(sex.init_cache()) == ["blk0_attn", "blk1_attn", "blk2_attn"]
    assert sex.init_cache()["blk0_attn"]["ckr"].shape == (2, 40, S)
    # Both kinds of counter ride the step's outputs, each folded its way.
    assert sorted(stats[0]) == ["expert_load_max", "experts_touched", "hc_defect"]
    assert 0 <= float(stats[0]["hc_defect"][0]) < 0.5
    assert 1 <= float(stats[0]["experts_touched"][0]) <= 8


def test_server_run_reports_the_defect_beside_the_routing_counters(tmp_path):
    cfg = _cfg()
    ff, params = _model(cfg, 2, S)
    sex = ServingExecutor(ff, ff.config, max_batch=2, max_seq=S, buckets=[S])
    srv = Server(sex, params, {}, decode_steps=4)
    reqs = [Request(id=i, prompt=_tokens(1, 12 + i)[0], max_new_tokens=6) for i in range(3)]
    with telemetry.Telemetry(directory=str(tmp_path)) as tel:
        results, stats = srv.run(reqs)
    assert stats["failed"] == 0
    events = common.read_events(tel.path)
    steps = [e for e in events if e["ev"] == "decode_superstep"]
    fills = [e for e in events if e["ev"] == "prefill"]
    assert steps and fills
    for e in steps + fills:
        assert 0 < e["hc_defect"] < 0.5 and 1 <= e["experts_touched"] <= 8
    # The largest over the steps and layers, kept to four significant
    # digits (a mean's four decimals would round 1e-6 away).
    assert any(e["hc_defect"] != round(e["hc_defect"], 4) for e in steps + fills)
    # ... and the served tokens are the reference's own greedy choice.
    r = results[0]
    full = np.concatenate([reqs[0].prompt, np.asarray(r.tokens[:-1], np.int32)])
    lg = np.asarray(ref.logits_fn(cfg, SEED, full))[len(reqs[0].prompt) - 1:]
    assert list(np.argmax(lg, axis=-1)) == list(r.tokens)


# -- the builder -----------------------------------------------------------------


def test_published_parameter_counts():
    """29.51 B parameters, 4.40 B active at the catalog row's keys; the
    cut 4.921 G = 9.84 GB of bf16."""
    cfg = json.load(open(os.path.join(REPO, "benchmark", "configs",
                                      "xing4.0-29b-a4b-l7.json")))
    pub = ref.parameter_counts({**cfg, **cfg["published"]})
    assert round(pub["total"] / 1e9, 2) == 29.51 and round(pub["active"] / 1e9, 2) == 4.40
    cut = ref.parameter_counts(cfg)["total"]
    assert round(cut / 1e9, 3) == 4.921 and round(cut * 2 / 1e9, 2) == 9.84
    # The program declares the same leaves at the same shapes.
    ff = build_lm(cfg, 1, 128, FFConfig(batch_size=1, compute_dtype="bfloat16"))
    abstract, _, _ = jax.eval_shape(Executor(ff, config=ff.config).init)
    got = {f"{op}/{k}": tuple(a.shape) for op, ls in abstract.items() for k, a in ls.items()}
    assert got == {k: tuple(v[0]) for k, v in ref.leaf_spec(cfg).items()}
    hc = abstract["blk3_hc2_post"]
    assert {a.dtype for a in hc.values()} == {jnp.dtype("float32")}
    assert abstract["blk3_attn"]["wq_b"].dtype == jnp.dtype("bfloat16")


def test_one_block_function_builds_both_families():
    """``xing4_0`` and ``deepseek_v3`` are one builder: the residual is an
    add without ``hc_mult`` and the hyper-connection pair with it, and
    kanana's graph is the one it was."""
    names = lambda ff: [(type(op).__name__, op.name) for op in ff.layers]  # noqa: E731
    plain = build_lm(DEEPSEEK_V3_TINY, 2, 16)
    assert not any(isinstance(op, (HyperConnectionPre, HyperConnectionPost))
                   for op in plain.layers)
    assert names(plain)[:5] == [("WordEmbedding", "embed"), ("RMSNorm", "blk0_ln1"),
                                ("LatentAttention", "blk0_attn"), ("Add", "blk0_res1"),
                                ("RMSNorm", "blk0_ln2")]
    assert sorted(plain.find_op("blk0_attn").param_specs()) == \
        ["kv_norm", "wkv_a", "wkv_b", "wo", "wq"]
    assert plain.find_op("blk0_attn").scale == 1.0 / np.sqrt(16 + 8)
    same = build_lm(dict(DEEPSEEK_V3_TINY, model_type="xing4_0"), 2, 16)
    assert names(same) == names(plain)
    streams = build_lm(XING4_TINY, 2, 16)
    assert not any(isinstance(op, Add) for op in streams.layers)
    assert names(streams)[:6] == [
        ("WordEmbedding", "embed"), ("HyperConnectionPre", "blk0_hc1_pre"),
        ("RMSNorm", "blk0_ln1"), ("LatentAttention", "blk0_attn"),
        ("HyperConnectionPost", "blk0_hc1_post"), ("HyperConnectionPre", "blk0_hc2_pre")]
    assert sorted(streams.find_op("blk0_attn").param_specs()) == \
        ["kv_norm", "q_norm", "wkv_a", "wkv_b", "wo", "wq_a", "wq_b"]
    assert streams.find_op("blk2_hc2_post").close and not streams.find_op("blk2_hc1_post").close
    assert streams.find_op("ln_f").inputs[0].shape == (2, 16, 64)
    # Solar's builder was not touched: adds, no stream.
    solar = build_lm(SOLAR_OPEN2_TINY, 2, 16)
    assert sum(isinstance(op, Add) for op in solar.layers) == 10


@pytest.mark.parametrize("key,value", [
    ("n_group", 3), ("topk_group", 2), ("moe_layer_freq", 2),
    ("attention_bias", True), ("hidden_act", "gelu"),
    ("tie_word_embeddings", True), ("rope_interleave", False),
])
@pytest.mark.parametrize("base", ["deepseek_v3", "xing4_0"])
def test_what_the_builder_does_not_build_still_raises_by_key(base, key, value):
    """``q_lora_rank``, ``rope_scaling`` and (PR 48) expert groups are
    built now; the guards that remain did not go with them, and groups
    the experts cannot be cut into (three of eight; two kept of one) are
    refused by the expert layer."""
    preset = DEEPSEEK_V3_TINY if base == "deepseek_v3" else XING4_TINY
    with pytest.raises(ValueError, match=f"{key}={value!r}"):
        build_lm({**preset, key: value}, 2, 16)


def test_scoring_func_and_rope_type_raise_too():
    with pytest.raises(ValueError, match="scoring_func"):
        build_lm({**XING4_TINY, "scoring_func": "tanh"}, 2, 16)
    with pytest.raises(ValueError, match="rope_scaling type"):
        build_lm({**XING4_TINY, "rope_scaling": {"type": "ntk", "factor": 2}}, 2, 16)
    with pytest.raises(ValueError, match="carries 3 streams"):
        ff = FFModel(FFConfig(batch_size=2))
        x = ff.create_tensor((2, 8, 3, 16), dtype=jnp.float32, name="x",
                             dim_axes=("n", "s", None, None))
        ff.hyper_connection_pre(x, 4)
