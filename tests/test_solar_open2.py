"""The Solar-Open2 block family (grouped-query attention with an output
gate, Kimi Delta Attention with its recurrent state, the expert share)
at a small size on the CPU, seeded weights, against the plain reference
(``benchmark/references/solar_open2.py``, the benchmark's own, which
imports nothing of the program)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark.references.solar_open2 as ref
from benchmark import common
from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import (
    SOLAR_OPEN2_SMOKE,
    SOLAR_OPEN2_TINY,
    build_lm,
)
from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.ops.attention import (
    MultiHeadAttention,
    _einsum_decode,
)
from flexflow_tpu.ops.base import TensorSpec
from flexflow_tpu.ops.delta_attention import KimiDeltaAttention, kda_recurrence
from flexflow_tpu.runtime import telemetry
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.serving import Request, Server, ServingExecutor

SEED = 3300000019
S = 128  # the kernels want whole 128-position tiles

_ASSUMED = {"init_std": 0.05, "norm_scale_half_width": 0.05,
            "e_bias_half_width": 0.05, "router_dtype": "float32",
            "conv_half_width": 0.5}

#: Two layers (grouped-query, delta) at the narrowest widths every
#: kernel takes: heads of one lane tile, a model of one.
_KERNEL_WIDTHS = dict(
    SOLAR_OPEN2_SMOKE, num_hidden_layers=2, hidden_size=128, vocab_size=512,
    num_attention_heads=4, num_key_value_heads=2, n_routed_experts=8)


def _cfg(dtype="float32", base=SOLAR_OPEN2_TINY, **over):
    cfg = dict(base, **over)
    return dict(cfg, assumed=dict(
        _ASSUMED, param_dtype=dtype,
        gate_rank=cfg["linear_attn_config"]["head_dim"]))


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


def test_the_graph_keeps_the_three_op_names_and_refuses_what_it_does_not_build():
    ff = build_lm(SOLAR_OPEN2_TINY, 1, 16)
    names = [op.name for op in ff.layers]
    assert [n for n in names if n.endswith(("_attn", "_kda"))] == \
        ["blk0_attn", "blk1_kda", "blk2_kda", "blk3_kda", "blk4_attn"]
    assert sum(n.endswith("_moe") for n in names) == 5
    assert not any("pos" in n for n in names)       # no positional op at all
    for key, value in (("use_rope", True), ("first_k_dense_replace", 1),
                       ("kda_use_full_proj", True),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            build_lm(dict(SOLAR_OPEN2_TINY, **{key: value}), 1, 16)
    with pytest.raises(ValueError, match="held_experts"):
        build_lm(dict(SOLAR_OPEN2_TINY, held_experts=[0, 1]), 1, 16)


def test_full_forward_logits_match_the_reference():
    """The training graph: einsum attention over repeated heads, the
    token-at-a-time recurrence, ``ragged_dot`` experts."""
    cfg = _cfg()
    ff, params = _model(cfg, 2, 32)
    toks = _tokens(2, 32)
    ex = Executor(ff, config=ff.config, devices=jax.devices()[:1])
    _loss, outs = ex.forward_step(params, {}, {"tokens": toks, "label": toks})
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t)) for t in toks])
    np.testing.assert_allclose(np.asarray(outs["lm_head:out"]), want, atol=1e-5)


def _serve_logits(params, ff, toks, plen, kernel, bucket=S):
    """Logits at positions ``plen-1 ..`` of each row of ``toks``: one
    decode step a token through the caches a prefill left."""
    b, t = toks.shape
    sex = ServingExecutor(ff, ff.config, max_batch=b, max_seq=S,
                          buckets=[bucket], decode_kernel=kernel)
    pf = sex.build_prefill(bucket)
    caches = sex.init_cache()
    padded = np.zeros((b, bucket), np.int32)
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


@pytest.mark.parametrize("base,dtype,kernel,atol", [
    (SOLAR_OPEN2_TINY, "float32", None, 1e-5),
    (_KERNEL_WIDTHS, "float32", True, 2e-5),
    (_KERNEL_WIDTHS, "float32", False, 2e-5),
    # bf16 weights, activations, KV cache and window against the f32
    # reference on the same (bf16-rounded) weights: logits of magnitude
    # ~2 carry 8 bits through the blocks (0.03 at most where no choice
    # flips), and one flipped near-tie between two of eight experts
    # moves that token's logits by 0.6 and, through the recurrent state
    # of the layer above, the next few tokens' by less each: judged by
    # the median and the share of logits that far off, not the widest.
    (_KERNEL_WIDTHS, "bfloat16", True, None),
])
def test_prefill_then_decode_through_both_caches(base, dtype, kernel, atol):
    """The chunked (or token-at-a-time) prefill ending at the prompt's
    length inside a padded bucket, then one-token steps over the KV
    cache and the recurrent state it left, against the reference's one
    full forward."""
    cfg = _cfg(dtype, base)
    ff, params = _model(cfg, 2, S, dtype)
    toks = _tokens(2, 48)
    got, sex = _serve_logits(params, ff, toks, 40, kernel)
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t))[40:] for t in toks])
    if atol is None:
        gap = np.abs(got.astype(np.float32) - want)
        assert np.median(gap) < 0.03 and np.mean(gap > 0.15) < 0.15
    else:
        np.testing.assert_allclose(got.astype(np.float32), want, atol=atol)
    shapes = {k: {e: c.shape for e, c in v.items()}
              for k, v in sex.init_cache().items()}
    hd = cfg["head_dim"]
    kv = (2, 2, hd, S) if hd % 128 == 0 else (2, S, 2, hd)
    assert shapes["blk0_attn"] == {"k": kv, "v": kv}
    lin = cfg["linear_attn_config"]
    assert shapes["blk1_kda"] == {
        "state": (2, lin["num_heads"], lin["head_dim"], lin["head_dim"]),
        "conv": (2, 3, 3 * lin["num_heads"] * lin["head_dim"])}


@pytest.mark.parametrize("base,kernel", [(SOLAR_OPEN2_TINY, None),
                                         (_KERNEL_WIDTHS, True)])
def test_one_prompt_in_two_buckets_leaves_the_same_state(base, kernel):
    """A recurrent layer must stop at the prompt's length: the pad rows
    of a larger bucket may not advance its state, and the window is
    that of the last three real rows."""
    cfg = _cfg("float32", base)
    ff, params = _model(cfg, 1, 2 * S)
    plen = 77                       # ends inside a chunk of 64
    prompt = _tokens(1, plen)
    sex = ServingExecutor(ff, ff.config, max_batch=1, max_seq=2 * S,
                          buckets=[S, 2 * S], decode_kernel=kernel)
    rows = []
    for bucket in (S, 2 * S):
        padded = np.full((1, bucket), 9, np.int32)   # pad tokens that matter
        padded[0, :plen] = prompt[0]
        rows.append(sex.build_prefill(bucket)(params, {}, padded,
                                              np.int32(plen)))
    (small, tok_a, *_), (large, tok_b, *_) = rows
    assert int(tok_a) == int(tok_b)
    for name in ("blk1_kda",):
        for entry in ("state", "conv"):
            np.testing.assert_allclose(
                np.asarray(small[name][entry]), np.asarray(large[name][entry]),
                atol=2e-6, err_msg=f"{name}/{entry}")
    assert float(jnp.max(jnp.abs(small["blk1_kda"]["state"]))) > 1e-3
    dec = sex.build_decode_superstep(1, return_logits=True)
    nxt = []
    for r in (small, large):
        caches = sex.install(sex.init_cache(), r, 0)
        _, _, _, out = dec(params, {}, caches, np.array([plen], np.int32),
                           np.array([int(tok_a)], np.int32))
        nxt.append(np.asarray(out[2])[0, 0])
    np.testing.assert_allclose(nxt[0], nxt[1], atol=2e-5)


def _delta_inputs(t, n, d, seed, hard):
    r = np.random.default_rng(seed)
    q, k = (r.normal(size=(t, n, d)).astype(np.float32) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(t, n, d)).astype(np.float32)
    if hard:
        # Decays from all but 1 down to exp(-33) a step, and every other
        # write at beta all but 2 (a reflection of the state along k).
        g = -np.exp(r.uniform(-8, 3.5, size=(t, n, d))).astype(np.float32)
        beta = np.where(r.random((t, n)) < 0.5, 1.999,
                        r.uniform(0, 2, (t, n))).astype(np.float32)
    else:
        g = -r.uniform(0.001, 1.0, size=(t, n, d)).astype(np.float32)
        beta = r.uniform(0, 2, size=(t, n)).astype(np.float32)
    st = (0.1 * r.normal(size=(n, d, d))).astype(np.float32)
    return q, k, v, g, beta, st


#: (T, N, hard, length): chunks 1, 3 and 6; heads 1, 3 (one step), one
#: more than ``_KDA_HEADS`` (steps of 3 heads) and 4 (an even step: its
#: heads go through the body two side by side); a pad tail from a row
#: inside a chunk and from a chunk's first row.
_SCAN_CASES = [
    (64, 1, False, None), (64, 1, True, None),
    (192, 3, False, None), (192, 3, True, None),
    (384, pk._KDA_HEADS + 1, False, None), (384, pk._KDA_HEADS + 1, True, None),
    (128, 4, True, None), (128, 4, False, 70),
    (192, 3, True, 100), (192, pk._KDA_HEADS + 1, False, 128),
    (384, 1, True, 200), (64, 3, False, 1),
]


@pytest.mark.parametrize("t,n,hard,length", _SCAN_CASES)
def test_chunked_scan_equals_the_recurrence(t, n, hard, length):
    """``kda_chunk`` from a non-zero state against the recurrence in
    float32.  With a ``length`` the rows from it on are pad (``g = 0,
    beta = 0``): the state is the one the real rows leave."""
    q, k, v, g, beta, st = _delta_inputs(t, n, 128, 0, hard)
    if length is not None:
        g[length:], beta[length:] = 0.0, 0.0
    live = slice(0, length)
    o0, s0 = kda_recurrence(q[live], k[live], v[live], g[live], beta[live], st)
    o1, s1 = jax.jit(pk.kda_chunk)(q, k, v, g, beta, st)
    assert o1.shape == (t, n, 128) and s1.shape == (n, 128, 128)
    np.testing.assert_allclose(np.asarray(o1[live]), np.asarray(o0), atol=5e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), atol=1e-4)


def test_one_token_kernel_equals_the_recurrence():
    q, k, v, g, beta, st = _delta_inputs(1, 4 * 32, 128, 1, True)
    o0, s0 = kda_recurrence(q, k, v, g, beta, st)
    slots = lambda x: x[0].reshape((4, 32) + x.shape[2:])
    o1, s1 = jax.jit(pk.kda_decode)(slots(q), slots(k), slots(v), slots(g),
                                    slots(beta), st.reshape(4, 32, 128, 128))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o0[0]).reshape(4, 32, 128),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1).reshape(s0.shape), np.asarray(s0),
                               atol=1e-6)


@pytest.mark.parametrize("length", [64, 100, 128, 7])
def test_the_op_ends_its_scan_at_the_length_on_both_paths(length):
    """Lengths that are and are not multiples of the chunk, through the
    kernel and the recurrence, against the sequence cut at the length."""
    x = _activations(1, 128, 64)
    op = KimiDeltaAttention("kda", x, num_heads=2, head_dim=128)
    keys = jax.random.split(jax.random.key(3), len(op.param_specs()))
    params = {n: s.initializer(k, s.shape, s.dtype)
              for k, (n, s) in zip(keys, op.param_specs().items())}
    u = jax.random.normal(jax.random.key(4), (1, 128, 64), jnp.float32)
    st = jnp.zeros((1, 2, 128, 128), jnp.float32)
    tail = jnp.zeros((1, 3, 3 * 256), jnp.float32)
    want_y, want_st, _ = op._sequence(params, u[:, :length], st, tail, None,
                                      kernel=False)
    for kernel in (True, False):
        y, got_st, window = op._sequence(params, u, st, tail,
                                         jnp.int32(length), kernel=kernel)
        np.testing.assert_allclose(np.asarray(y[:, :length]), np.asarray(want_y),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(got_st), np.asarray(want_st),
                                   atol=2e-5)
        rows = np.asarray(op._streams(params, u))[0]
        want_w = np.concatenate([np.zeros((3, 768), np.float32), rows])[length:length + 3]
        np.testing.assert_allclose(np.asarray(window)[0], want_w, atol=1e-6)


@pytest.mark.parametrize("s,lens", [
    (256, [1, 130, 256]),           # one chunk of two lane tiles
    (2048, [700, 2048, 513]),       # chunks of four: whole, partial, last
])
def test_grouped_query_kernels_equal_the_einsum_oracle(s, lens):
    """Decode on a positions-last cache (the step's column written on
    the way) against attention over repeated heads.  (The streamed
    prefill over a group's one K and V:
    ``tests/test_flash_uneven.py``.)"""
    r = np.random.default_rng(2)
    b, h, hkv, hd = 3, 8, 2, 128
    q = jnp.asarray(r.normal(size=(b, h, hd)), jnp.float32)
    k1, v1 = (jnp.asarray(r.normal(size=(b, hkv, hd)), jnp.float32) for _ in range(2))
    ck, cv = (jnp.asarray(r.normal(size=(b, hkv, hd, s)), jnp.float32) for _ in range(2))
    lengths = jnp.asarray(lens, jnp.int32)
    assert pk.flash_decode_chunk(s, hkv, hd, jnp.float32, 4) == min(512, s)
    assert pk.flash_decode_supported((b, s, hkv, hd), jnp.float32, group=4)
    assert not pk.flash_decode_supported((b, s, hkv, 32), jnp.float32, group=4)
    out, nk, nv = pk.flash_decode(q, k1, v1, ck, cv, lengths, positions_last=True)
    rows = jnp.arange(b)
    wk = ck.at[rows, :, :, lengths - 1].set(k1)
    wv = cv.at[rows, :, :, lengths - 1].set(v1)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(wv))
    want = _einsum_decode(q, wk.transpose(0, 3, 1, 2), wv.transpose(0, 3, 1, 2),
                          lengths - 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_plain_attention_is_the_program_it_was():
    """Full heads, no gate: the parameters, the cache and the fused
    projection GPT-2's cells compile are untouched by the new keys."""
    x = _activations(2, 16, 64)
    op = MultiHeadAttention("attn", x, 4)
    assert sorted(op.param_specs()) == ["bk", "bo", "bq", "bv", "wk", "wo", "wq", "wv"]
    assert {e: c.shape for e, c in op.cache_entries(128).items()} == \
        {"k": (128, 4, 16), "v": (128, 4, 16)}
    assert op.cache_paged and not op.positions_last and op.group == 1
    assert op.serving_path(True) == "kv_decode"
    wide = MultiHeadAttention("attn", _activations(2, 16, 512), 4)
    assert wide.positions_last and wide.cache_paged
    assert wide.cache_entries(256)["k"].shape == (4, 128, 256)
    # ... in the padded single-mesh layout: the pool keeps its order.
    from flexflow_tpu.graph import FFModel

    ff = FFModel(FFConfig(batch_size=2))
    tok = ff.create_tensor((2, 32), dtype=jnp.int32, name="tokens")
    h = ff.word_embedding(tok, 64, 256, name="embed")
    h = ff.multihead_attention(h, 2, num_kv_heads=1, name="blk0_attn")
    ff.dense(h, 64, name="lm_head")
    paged = ServingExecutor(ff, max_batch=2, max_seq=32, kv_block=16)
    assert paged._cache_specs["blk0_attn"]["k"].shape == (32, 1, 128)
    padded = ServingExecutor(ff, max_batch=2, max_seq=32)
    assert padded._cache_specs["blk0_attn"]["k"].shape == (1, 128, 32)


def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """Each chip adds its own experts' terms; the shared expert is every
    chip's alike and must count once."""
    from flexflow_tpu.ops.moe import MixtureOfExperts

    cfg = _cfg()
    whole = dict(cfg, held_experts=None)
    get = ref.Leaves(whole, SEED, "blk1_")
    u = jnp.asarray(np.random.default_rng(7).normal(size=(24, 64)), jnp.float32)
    want = np.asarray(ref.experts(whole, get, u))
    alone = np.asarray(ref.experts(whole, get, u, shared=True)
                       - ref.experts(whole, get, u, shared=False))
    x = TensorSpec("x", (1, 24, 64), jnp.float32, ("n", "s", None))
    total = np.zeros_like(want)
    for share in range(8):
        held = [2 * share, 2 * share + 1]
        op = MixtureOfExperts(
            "moe", x, 16, 32, top_k=2, dispatch="sorted", router="sigmoid",
            gated=True, activation="silu", shared_experts=1,
            selection_bias=True, held_experts=held)
        params = {k: get(f"moe/{k}") for k in
                  ("gate", "e_bias", "s_gate", "s_up", "s_down")}
        params.update({k: get(f"moe/{k}")[jnp.asarray(held)]
                       for k in ("w_gate", "w_up", "w_down")})
        (y,), _ = op.forward(params, [u[None]], {}, False)
        # The reference on the same share: its leaves are this chip's rows.
        part = dict(cfg, n_routed_experts=2, held_experts=held,
                    published={"n_routed_experts": 16})
        if share == 0:  # experts 0, 1: the leading rows of the leaf
            np.testing.assert_allclose(
                np.asarray(y[0]),
                np.asarray(ref.experts(part, ref.Leaves(part, SEED, "blk1_"), u)),
                atol=1e-5)
        total += np.asarray(y[0]) - alone
    np.testing.assert_allclose(total + alone, want, atol=2e-5)


def test_logits_over_the_vocabulary_slice_are_the_whole_heads_rows():
    cfg = _cfg()
    toks = _tokens(1, 24, vocab=128)[0]
    whole = np.asarray(ref.logits_fn(cfg, SEED, toks))
    part = dict(cfg, vocab_size=128)
    ff, params = _model(part, 1, 24)
    ex = Executor(ff, config=ff.config, devices=jax.devices()[:1])
    _, outs = ex.forward_step(params, {}, {"tokens": toks[None], "label": toks[None]})
    np.testing.assert_allclose(np.asarray(outs["lm_head:out"])[0], whole[:, :128],
                               atol=1e-5)


def _req(rid, plen, max_new, arrival_ms=0.0, **kw):
    prompt = np.random.default_rng([rid, 11]).integers(0, 512, size=plen,
                                                       dtype=np.int32)
    return Request(id=rid, prompt=prompt, max_new_tokens=max_new,
                   arrival_ms=arrival_ms, **kw)


def test_a_preempted_and_resumed_request_yields_the_unpreempted_tokens():
    """Resume is a re-prefill over prompt ‖ carried tokens: it must
    rebuild the recurrent state and the window, not only K and V."""
    from flexflow_tpu.serving import ScheduledServer, SchedulerPolicy

    ff = build_lm(SOLAR_OPEN2_TINY, 1, 64, FFConfig(batch_size=1))
    sex = ServingExecutor(ff, max_batch=1, max_seq=64, buckets=(8, 64))
    params, state = sex.init(0)
    pol = SchedulerPolicy(name="slo")
    pair = [_req(0, 4, 40, 0.0, priority=1),
            _req(1, 4, 4, 5.0, priority=0, slo_ms=20.0)]
    res, st = ScheduledServer(sex, params, state, decode_steps=8,
                              policy=pol).run(pair)
    assert st["request_preempts"] == 1
    assert res[0].error is None and res[1].error is None
    solo, _ = ScheduledServer(sex, params, state, decode_steps=8,
                              policy=pol).run(pair[:1])
    assert res[0].tokens == solo[0].tokens


def test_the_executor_counts_fixed_entries_once_and_announces_the_paths(tmp_path):
    ff = build_lm(SOLAR_OPEN2_TINY, 2, 64, FFConfig(batch_size=2))
    sex = ServingExecutor(ff, max_batch=2, max_seq=64, buckets=(16, 64))
    # K and V of two grouped-query layers: 2 x 2 heads x 16 x 4 B x 2.
    assert sex._bytes_per_token == 2 * 2 * 16 * 4 * 2
    fixed = 3 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    assert sex._bytes_fixed == fixed
    assert sex.hbm_per_slot_bytes() == 64 * sex._bytes_per_token + fixed
    assert sex.cache_total_bytes() == 2 * sex.hbm_per_slot_bytes() == sum(
        c.nbytes for c in jax.tree.leaves(sex.init_cache()))
    assert sex.max_admissible_batch(5 * sex.hbm_per_slot_bytes(), 8, 8) == 5
    assert [op.name for op in sex.stateful_ops] == ["blk1_kda", "blk2_kda", "blk3_kda"]
    rows = sex.kv_rows(np.array([5, 0], np.int32), 2)
    assert rows["kv_rows_cache"] == 2 * 2 * 64          # whole rows: the einsum oracle
    assert rows["state_bytes"] == 2 * 2 * 2 * fixed
    params, state = sex.init(0)
    with telemetry.Telemetry(directory=str(tmp_path)) as tel:
        Server(sex, params, state, decode_steps=4).run(
            [_req(0, 5, 6), _req(1, 20, 6)])
    events = common.read_events(tel.path)
    programs = {e["kind"]: e["attention"] for e in events
                if e["ev"] == "serving_program"}
    assert programs == {"prefill": "delta_chunked+gqa_dense",
                        "decode": "delta_recurrent+gqa_decode"}
    pre = sorted((e["bucket"], e["length"]) for e in events if e["ev"] == "prefill")
    assert pre == [(16, 5), (64, 20)]
    steps = [e for e in events if e["ev"] == "decode_superstep"]
    assert steps and all(e["state_bytes"] == 2 * 4 * 2 * fixed and
                         0 <= e["experts_touched"] <= 16 for e in steps)


def test_pool_prefix_shard_and_speculation_refuse_the_op_by_name():
    ff = build_lm(SOLAR_OPEN2_TINY, 2, 64, FFConfig(batch_size=2))
    with pytest.raises(ValueError, match="blk1_kda.*KimiDeltaAttention"):
        ServingExecutor(ff, max_batch=2, max_seq=64, kv_block=16)
    with pytest.raises(ValueError, match="paged"):
        ServingExecutor(ff, max_batch=2, max_seq=64, prefix_cache=True)
    with pytest.raises(ValueError, match="blk1_kda"):
        ServingExecutor(ff, max_batch=2, max_seq=64, shard=(1, 2))
    sex = ServingExecutor(ff, max_batch=2, max_seq=64)
    for build in (lambda: sex.build_spec_step(2),
                  lambda: sex.build_draft_prefill(64)):
        with pytest.raises(ValueError, match="blk1_kda.*recurrent state"):
            build()
    sex.paged = sex.prefix_cache = True
    sex.kv_block = 16
    with pytest.raises(ValueError, match="blk1_kda.*recurrent state"):
        sex.build_prefill_from(64, 16)
