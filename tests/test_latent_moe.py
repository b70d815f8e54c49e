"""The DeepSeek-V3 block family (latent attention, the sorted expert
layer, the cache protocol) at a small size on the CPU, seeded weights,
against the plain reference (``benchmark/references/deepseek_v3.py``,
the benchmark's own, which imports nothing of the program)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark.references.deepseek_v3 as ref
from benchmark import common
from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import (
    DEEPSEEK_V3_TINY,
    build_lm,
    build_transformer_lm,
)
from flexflow_tpu.ops import pallas_kernels
from flexflow_tpu.ops.attention import (
    LatentAttention,
    _latent_decode,
)
from flexflow_tpu.runtime import telemetry
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.serving import Request, Server, ServingExecutor

SEED = 2900000017
S = 128  # the kernels want whole 128-position tiles


def _cfg(dtype="float32"):
    return dict(DEEPSEEK_V3_TINY, assumed={
        "init_std": 0.05, "norm_scale_half_width": 0.05,
        "e_bias_half_width": 0.05, "router_dtype": "float32",
        "param_dtype": dtype})


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


def test_full_forward_logits_match_the_reference():
    """The training graph (einsum attention, ``ragged_dot`` experts)."""
    cfg = _cfg()
    ff, params = _model(cfg, 2, 32)
    toks = _tokens(2, 32)
    ex = Executor(ff, config=ff.config, devices=jax.devices()[:1])
    _loss, outs = ex.forward_step(params, {}, {"tokens": toks, "label": toks})
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t)) for t in toks])
    np.testing.assert_allclose(np.asarray(outs["lm_head:out"]), want, atol=1e-5)


def _serve_logits(cfg, params, ff, toks, plen, kernel):
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
    got = []
    for j in range(plen, t):
        caches, _, _, out = dec(params, {}, caches, pos.copy(),
                                toks[:, j].copy())
        got.append(np.asarray(out[2])[0])
        pos += 1
    return np.stack(got, axis=1), sex


@pytest.mark.parametrize("dtype,kernel,atol", [
    ("float32", True, 1e-5),
    ("float32", False, 1e-5),
    # bf16 weights, activations and cache against the f32 reference on
    # the same (bf16-rounded) weights: logits of magnitude ~2 carry 8
    # bits through three blocks, and a flipped near-tie between the
    # second and third expert of a token moves a logit by a few 1e-2.
    ("bfloat16", True, 0.15),
])
def test_prefill_then_decode_through_the_latent_cache(dtype, kernel, atol):
    """Expanded prefill, then absorbed decode over the cache it wrote,
    against the reference's one full forward."""
    cfg = _cfg(dtype)
    ff, params = _model(cfg, 2, S, dtype)
    toks = _tokens(2, 48)
    got, sex = _serve_logits(cfg, params, ff, toks, 40, kernel)
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t))[40:] for t in toks])
    np.testing.assert_allclose(got.astype(np.float32), want, atol=atol)
    assert {k: {e: c.shape for e, c in v.items()}
            for k, v in sex.init_cache().items()}["blk0_attn"] == \
        {"ckr": (2, 40, S)}


def test_prefill_last_row_matches_and_slims_only_big_heads():
    cfg = _cfg()
    ff, params = _model(cfg, 1, S)
    toks = _tokens(1, S)
    sex = ServingExecutor(ff, ff.config, max_batch=1, max_seq=S, buckets=[S])
    want = int(np.argmax(np.asarray(ref.logits_fn(cfg, SEED, toks[0]))[49]))
    assert not sex._slim_head(S)
    full = sex.build_prefill(S)(params, {}, toks, np.int32(50))
    sex2 = ServingExecutor(ff, ff.config, max_batch=1, max_seq=S, buckets=[S])
    sex2.SLIM_HEAD_BYTES = 0
    assert sex2._slim_head(S)
    slim = sex2.build_prefill(S)(params, {}, toks, np.int32(50))
    assert int(full[1]) == int(slim[1]) == want


def test_router_choices_equal_the_references_and_weights_sum_to_the_scale():
    cfg = _cfg()
    ff, params = _model(cfg, 2, 32)
    op = ff.find_op("blk1_moe")
    u = jnp.asarray(np.random.default_rng(1).standard_normal((64, 64)), jnp.float32)
    idx, w = op.route(params["blk1_moe"], u)
    get = ref.Leaves(cfg, SEED)
    ridx, rw = ref.route(cfg, get.at("blk1_"), u)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(np.asarray(ridx), -1))
    # A token routed nowhere is impossible: top-k always names k
    # experts, and their weights sum to the scaling factor.
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.448, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(rw).sum(-1), 2.448, rtol=1e-5)
    # The selection bias chooses but does not weigh.
    no_bias = dict(params["blk1_moe"], e_bias=jnp.zeros((8,), jnp.float32))
    idx0, _ = op.route(no_bias, u)
    assert not np.array_equal(np.asarray(idx), np.asarray(idx0))


def test_expert_shares_add_up_to_the_whole_layer():
    """The model-configs guide's share test: with ``held_experts`` set
    to each eighth of 8 experts in turn, the eight partial results, the
    shared expert counted once, add up to the uncut reference's layer."""
    cfg = _cfg()
    get = ref.Leaves(cfg, SEED)
    u = jnp.asarray(np.random.default_rng(2).standard_normal((2, 16, 64)), jnp.float32)
    whole = np.asarray(ref.experts(cfg, get.at("blk1_"), u.reshape(32, 64)))
    shared = np.asarray(ref.experts(cfg, get.at("blk1_"), u.reshape(32, 64),
                                    held=np.zeros((0,), np.int32)))
    total = np.zeros_like(whole)
    for e in range(8):
        ff = build_lm(dict(cfg, held_experts=[e]), 2, 16, FFConfig(batch_size=2))
        op = ff.find_op("blk1_moe")
        assert op.param_specs()["w_gate"].shape == (1, 64, 32)
        p = {k: get(f"blk1_moe/{k}") for k in ("gate", "e_bias", "s_gate", "s_up", "s_down")}
        p.update({k: get.expert(f"blk1_moe/{k}", e)[None] for k in ("w_gate", "w_up", "w_down")})
        (y,), _ = op.forward(p, [u], {}, training=False)
        part = np.asarray(y).reshape(32, 64)
        want = np.asarray(ref.experts(cfg, get.at("blk1_"), u.reshape(32, 64), held=[e]))
        np.testing.assert_allclose(part, want, atol=1e-5)
        total += part
    np.testing.assert_allclose(total - 7 * shared, whole, atol=1e-5)


@pytest.mark.parametrize("rows_per_expert,tm", [(3, 16), (200, 128)])
def test_grouped_matmul_kernel_against_ragged_dot(rows_per_expert, tm):
    rng = np.random.default_rng(3)
    e, k, n = 4, 128, 256
    counts = np.array([rows_per_expert, 0, 2 * rows_per_expert, 1])
    assert pallas_kernels.grouped_tile_rows(int(counts.sum()), e) == tm
    padded = -(-counts // tm) * tm
    rows = int(padded.sum()) + 2 * tm  # two tiles no expert uses
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
    wu = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
    used = int(padded.sum()) // tm
    tile_e = np.repeat(np.arange(e), padded // tm)
    tile_e = np.concatenate([tile_e, np.full(rows // tm - used, tile_e[-1])])
    assert pallas_kernels.grouped_matmul_supported(k, n, x.dtype)
    got = pallas_kernels.grouped_matmul(x, w, jnp.asarray(tile_e), jnp.int32(used), tm)
    want = jax.lax.ragged_dot(x, w, jnp.asarray(padded, jnp.int32))
    np.testing.assert_allclose(np.asarray(got)[:used * tm], np.asarray(want)[:used * tm],
                               rtol=2e-4, atol=2e-4)
    got = pallas_kernels.grouped_matmul(x, w, jnp.asarray(tile_e), jnp.int32(used), tm, w_up=wu)
    want = jax.nn.silu(want) * jax.lax.ragged_dot(x, wu, jnp.asarray(padded, jnp.int32))
    np.testing.assert_allclose(np.asarray(got)[:used * tm], np.asarray(want)[:used * tm],
                               rtol=2e-3, atol=2e-3)


def _column_written(cache, col, pos):
    """``cache`` (B, row, S) with ``col[b]`` at position ``pos[b]``."""
    out = np.array(cache)
    out[np.arange(len(pos)), :, np.asarray(pos)] = np.asarray(col)
    return jnp.asarray(out)


#: A length under test by the kernel's granule ``c`` and the cache's
#: ``s`` positions: every edge of the walk over a slot's live chunks.
_MLA_EDGES = {
    "one": lambda c, s: 1, "chunk-1": lambda c, s: c - 1,
    "chunk": lambda c, s: c, "chunk+1": lambda c, s: c + 1,
    "granule_end": lambda c, s: 2 * c, "max_seq": lambda c, s: s,
}


@pytest.mark.parametrize("edge", sorted(_MLA_EDGES))
@pytest.mark.parametrize("s", [1536, 768, 384])     # granules of 512, 256, 128
def test_mla_decode_kernel_against_the_dense_oracle(s, edge):
    """Three granules a slot; the length under test in one batch with
    an empty slot (length 1), a slot inside its second granule and a
    full one, in an order that makes the ring of chunk fetches wrap
    between slots."""
    rng = np.random.default_rng(4)
    h, row, dv = 4, 40, 32
    chunk = pallas_kernels.mla_decode_chunk(s)
    assert s == 3 * chunk
    lengths = [chunk + 7, _MLA_EDGES[edge](chunk, s), 1, s, 2 * chunk - 1]
    b = len(lengths)
    q = jnp.asarray(rng.standard_normal((b, h, row)), jnp.float32)
    cache = jnp.asarray(rng.standard_normal((b, row, s)), jnp.float32)
    col = jnp.asarray(rng.standard_normal((b, row)), jnp.float32)
    pos = jnp.asarray(lengths, jnp.int32) - 1
    assert pallas_kernels.mla_decode_supported(cache.shape, dv)
    assert not pallas_kernels.mla_decode_supported((b, row, 100), dv)
    got, _ = pallas_kernels.mla_decode(q, col, cache, pos + 1, dv, 0.2)
    want = _latent_decode(q, _column_written(cache, col, pos), pos, dv, 0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype,b", [(jnp.float32, 6), (jnp.bfloat16, 6),
                                     (jnp.float32, 130)])
def test_mla_decode_kernel_writes_the_steps_column(dtype, b):
    """The kernel puts the step's column at ``pos`` and nowhere else:
    every other column of every slot comes back bit for bit, and the
    output attends the new column (the oracle on the updated cache, not
    on the one handed in).  130 slots: the columns come slots-along-
    lanes, more than one lane tile of them."""
    rng = np.random.default_rng(5)
    h, row, dv, s = 4, 48, 32, 1024
    q = jnp.asarray(rng.standard_normal((b, h, row)), dtype)
    cache = jnp.asarray(rng.standard_normal((b, row, s)), dtype)
    col = jnp.asarray(3.0 * rng.standard_normal((b, row)), dtype)
    pos = jnp.asarray(([0, 127, 128, 511, 512, 1023] * b)[:b], jnp.int32)
    got, new = pallas_kernels.mla_decode(q, col, cache, pos + 1, dv, 0.2)
    want_cache = _column_written(cache, col, pos)
    assert new.dtype == cache.dtype
    assert np.array_equal(np.asarray(new, np.float32),
                          np.asarray(want_cache, np.float32))
    tol = dict(rtol=1e-4, atol=1e-5) if dtype == jnp.float32 else \
        dict(rtol=3e-2, atol=3e-2)
    f32 = lambda x: np.asarray(x, np.float32)
    np.testing.assert_allclose(
        f32(got), f32(_latent_decode(q, want_cache, pos, dv, 0.2)), **tol)
    stale = f32(_latent_decode(q, cache, pos, dv, 0.2))
    assert np.abs(f32(got) - stale).max() > 0.3


def test_multihead_attention_declares_the_cache_it_always_had():
    ff = build_transformer_lm(batch_size=2, seq_len=32, vocab_size=64, d_model=16,
                              num_heads=2, num_layers=2,
                              config=FFConfig(batch_size=2))
    op = ff.find_op("blk0_attn")
    ents = op.cache_entries(32)
    assert list(ents) == ["k", "v"]
    assert all(e.shape == (32, 2, 8) and e.axes == (None, "c", None)
               for e in ents.values())
    sex = ServingExecutor(ff, ff.config, max_batch=2, max_seq=32)
    shapes = jax.tree.map(lambda c: c.shape, sex.init_cache())
    assert shapes == {f"blk{i}_attn": {"k": (2, 32, 2, 8), "v": (2, 32, 2, 8)}
                      for i in range(2)}
    paged = ServingExecutor(ff, ff.config, max_batch=2, max_seq=32, kv_block=8)
    assert jax.tree.map(lambda c: c.shape, paged.init_cache())["blk0_attn"]["k"] \
        == (paged.kv_blocks, 8, 2, 8)
    assert sex._bytes_per_token == 2 * 2 * 2 * 8 * 4
    assert not sex.has_stats and sex._attention_paths(True) == "kv_decode"


def test_the_served_graph_keeps_the_expert_op_and_announces_its_paths(tmp_path):
    cfg = _cfg()
    ff, params = _model(cfg, 2, S)
    assert not ff.find_op("blk1_moe").is_loss
    sex = ServingExecutor(ff, ff.config, max_batch=2, max_seq=S, buckets=[S])
    assert "blk1_moe" in [op.name for op in sex._layers]
    assert isinstance(sex.attn_ops[0], LatentAttention)
    assert sex._bytes_per_token == 3 * 40 * 4   # 3 layers x (32 + 8) values
    reqs = [Request(id=i, prompt=_tokens(1, 20 + i)[0], max_new_tokens=6)
            for i in range(3)]
    with telemetry.Telemetry(directory=str(tmp_path)) as tel:
        results, stats = Server(sex, params, {}, decode_steps=4).run(reqs)
    assert stats["failed"] == 0 and all(len(r.tokens) == 6 for r in results.values())
    events = common.read_events(tel.path)
    progs = {e["kind"]: e for e in events if e["ev"] == "serving_program"}
    assert progs["prefill"]["attention"] == "latent_expanded"
    assert progs["decode"]["attention"] == "latent_absorbed"
    for kind in ("prefill", "decode_superstep"):
        es = [e for e in events if e["ev"] == kind]
        assert es and all(1 <= e["experts_touched"] <= 8 and e["expert_load_max"] >= 1
                          for e in es)
    # The first token of a request (prefill, expanded) and the rest
    # (decode, absorbed) are the reference's greedy choices.
    for r in reqs:
        full = np.concatenate([r.prompt, results[r.id].tokens])[:-1]
        want = np.argmax(np.asarray(ref.logits_fn(cfg, SEED, full))[len(r.prompt) - 1:], -1)
        assert list(want) == list(results[r.id].tokens)


@pytest.mark.parametrize("kw,needle", [
    (dict(kv_block=16), "no paged pool"),
    (dict(shard=(1, 2)), "sharded decode"),
])
def test_layouts_the_latent_cache_lacks_are_refused_by_name(kw, needle):
    ff = build_lm(_cfg(), 2, S, FFConfig(batch_size=2))
    with pytest.raises(ValueError, match=needle) as e:
        ServingExecutor(ff, ff.config, max_batch=2, max_seq=S, **kw)
    assert "blk0_attn" in str(e.value)


def test_sorted_experts_train():
    """The sorted formulation is differentiable on the training path
    (``ragged_dot``): one SGD step lowers the loss."""
    from flexflow_tpu.optim import SGDOptimizer

    ff = build_lm(_cfg(), 2, 16, FFConfig(batch_size=2))
    ex = Executor(ff, optimizer=SGDOptimizer(lr=0.5), config=ff.config,
                  devices=jax.devices()[:1])
    params, opt, state = ex.init(0)
    toks = _tokens(2, 16)
    batch = {"tokens": toks, "label": np.roll(toks, -1, 1)}
    losses = []
    for _ in range(3):
        params, opt, state, m = ex.train_step(params, opt, state, batch)
        losses.append(float(m["train_loss"]))
    assert losses[-1] < losses[0], losses
