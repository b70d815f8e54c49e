"""The A.X-K2 family (``model_type`` ``axk2``: the DeepSeek-V3 block with
a learned token selector over the latent cache, a gate a head, gated
norms and group-limited routing over a share of the experts) at a small
size on the CPU, seeded weights, against the plain reference
(``benchmark/references/axk2.py``, the benchmark's own, which imports
nothing of the program)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark.references.axk2 as ref
from benchmark import common
from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import (
    AXK2_SMOKE,
    AXK2_TINY,
    DEEPSEEK_V3_TINY,
    XING4_TINY,
    build_lm,
)
from flexflow_tpu.ops import attention as attention_ops
from flexflow_tpu.ops.attention import LatentAttention
from flexflow_tpu.ops.base import TensorSpec
from flexflow_tpu.ops.moe import MixtureOfExperts
from flexflow_tpu.ops.norm import RMSNorm, rms_norm
from flexflow_tpu.ops.token_select import TokenSelector
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.serving import ServingExecutor

SEED = 3300000037
S = 128
TOPK = AXK2_TINY["index_topk"]                        # 16: sequences are 4-8x
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ASSUMED = {"init_std": 0.05, "norm_scale_half_width": 0.05,
            "gated_norm_up_std": 0.5, "e_bias_half_width": 0.05,
            "router_dtype": "float32"}


def _cfg(dtype="float32", base=AXK2_TINY, **over):
    return dict(base, **over, assumed=dict(_ASSUMED, param_dtype=dtype))


def _model(cfg, batch, seq, dtype="float32", chunk=8):
    """The graph and its seeded parameters; the selectors score ``chunk``
    query rows at a time (512 as built: the tests' prefills then run
    their dense head, two key widths and several chunks)."""
    ff = build_lm(cfg, batch, seq, FFConfig(batch_size=batch,
                                            compute_dtype=dtype))
    for op in ff.layers:
        if getattr(op, "select", None) is not None:
            op.select.q_chunk = chunk
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
    kw = dict(kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
              rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
              rope_theta=cfg["rope_parameters"]["rope_theta"],
              norm_eps=cfg["rms_norm_eps"], q_rank=cfg["q_lora_rank"],
              rope_scaling=cfg["rope_parameters"], gate="per_head",
              select={"indexer_num_heads": cfg["index_n_heads"],
                      "indexer_head_dim": cfg["index_head_dim"],
                      "topk": cfg["index_topk"]})
    kw.update(over)
    x = TensorSpec("x", (b, t, cfg["hidden_size"]), jnp.float32, ("n", "s", None))
    op = LatentAttention("blk0_attn", x, cfg["num_attention_heads"], **kw)
    if op.select is not None:
        op.select.q_chunk = 8
    get = ref.Leaves(cfg, SEED).at("blk0_")
    params = {k: jnp.asarray(get(f"attn/{k}")) for k in op.param_specs()}
    a = jnp.asarray(np.random.default_rng(2).standard_normal(
        (b, t, cfg["hidden_size"])).astype(np.float32))
    return op, get, params, a


# -- the graph --------------------------------------------------------------------


def test_the_graph_and_what_the_builder_still_refuses():
    ff = build_lm(AXK2_TINY, 2, 32)
    ops = {op.name: op for op in ff.layers}
    attn, moe = ops["blk1_attn"], ops["blk1_moe"]
    assert isinstance(attn, LatentAttention) and isinstance(attn.select, TokenSelector)
    assert attn.attrs["gate"] == "per_head" and attn.attrs["q_rank"] == 24
    # The selector's query comes from the compressed query and its
    # rotary part is the layer's own width, not the selector's head.
    assert attn.select.query_dim == 24 and attn.select.turn["rotary_dim"] == 8
    assert attn.param_specs()["idx_wq"].shape == (24, 4 * 16)
    assert attn.param_specs()["wg"].shape == (64, 4)
    assert attn.serving_path(True) == "latent_select_absorbed"
    assert attn.serving_path(False) == "latent_select_expanded"
    assert (moe.attrs["n_group"], moe.attrs["topk_group"]) == (4, 2)
    for name in ("blk0_ln1", "blk2_ln2", "ln_f"):
        assert isinstance(ops[name], RMSNorm) and ops[name].attrs["gate_rank"] == 4
        assert set(ops[name].param_specs()) == {"scale", "w_down", "w_up"}
    assert "blk0_moe" not in ops and "blk0_mlp_gate" in ops
    # Held experts: the router keeps the published width.
    cut = dict(AXK2_TINY, n_routed_experts=4, held_experts=[0, 1, 2, 3],
               published={"n_routed_experts": 16})
    moe = {op.name: op for op in build_lm(cut, 2, 32).layers}["blk1_moe"]
    assert moe.attrs["num_experts"] == 16 and moe.held == (0, 1, 2, 3)
    assert moe.param_specs()["gate"].shape == (64, 16)
    assert moe.param_specs()["w_up"].shape == (4, 64, 32)
    with pytest.raises(ValueError, match="held_experts names 3"):
        build_lm(dict(cut, held_experts=[0, 1, 2]), 2, 32)
    with pytest.raises(ValueError, match="n_group"):
        build_lm(dict(AXK2_TINY, n_group=3), 2, 32)
    with pytest.raises(ValueError, match="n_group"):
        build_lm(dict(AXK2_TINY, topk_group=5), 2, 32)
    with pytest.raises(ValueError, match="moe_layer_freq"):
        build_lm(dict(AXK2_TINY, moe_layer_freq=2), 2, 32)
    with pytest.raises(ValueError, match="narrower"):
        build_lm(dict(AXK2_TINY, index_head_dim=4), 2, 32)


# -- the gated norm ---------------------------------------------------------------


def test_gated_norm_follows_the_reference_and_a_zero_gate_halves_the_norm():
    cfg = _cfg()
    x = TensorSpec("x", (2, 8, 64), jnp.float32, ("n", "s", None))
    op = RMSNorm("blk0_ln1", x, eps=1e-6, gate_rank=4)
    get = ref.Leaves(cfg, SEED).at("blk0_")
    params = {k: jnp.asarray(get(f"ln1/{k}")) for k in op.param_specs()}
    a = jnp.asarray(np.random.default_rng(3).standard_normal((2, 8, 64)), jnp.float32)
    (got,), _ = op.forward(params, [a], {}, False)
    want = np.stack([np.asarray(ref.gated_norm(cfg, get, "ln1", row)) for row in a])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
    plain = rms_norm(a, params["scale"], 1e-6)
    # The gate moves: it is not one half everywhere.
    gate = np.asarray(got) / np.asarray(plain)
    assert gate.min() < 0.35 and gate.max() > 0.65
    (half,), _ = op.forward(dict(params, w_up=jnp.zeros_like(params["w_up"])),
                            [a], {}, False)
    np.testing.assert_allclose(np.asarray(half), 0.5 * np.asarray(plain), atol=1e-6)
    # Without a rank: the op it always was.
    bare = RMSNorm("n", x, eps=1e-6)
    assert set(bare.param_specs()) == {"scale"}
    (same,), _ = bare.forward({"scale": params["scale"]}, [a], {}, False)
    assert np.array_equal(np.asarray(same), np.asarray(plain))


# -- the selector over the latent ---------------------------------------------------


def _selector_both(t=96):
    cfg = _cfg()
    op, get, params, a = _attn_op(cfg, 1, t)
    pos = jnp.arange(t)[None]
    src = op._query_source(params, a)
    q, k, w = op._index(params, a, src, pos)
    cq = ref._rms(ref._mm(a[0], get("attn/wq_a"), False), get("attn/q_norm"),
                  cfg["rms_norm_eps"])
    rq, rk, rw = ref.indexer(cfg, get, a[0], cq, jnp.arange(t))
    return cfg, op, (q, k, w), (rq, rk, rw)


def test_selector_reads_the_compressed_query_and_turns_a_sub_width():
    """Its projections against the reference's, and that the trailing
    half of its head passes the rotary step untouched: the same key at
    two positions differs in its leading ``qk_rope_head_dim`` alone."""
    cfg, op, (q, k, w), (rq, rk, rw) = _selector_both()
    np.testing.assert_allclose(np.asarray(q[0]), np.asarray(rq), atol=1e-5)
    np.testing.assert_allclose(np.asarray(k[0]), np.asarray(rk), atol=1e-5)
    np.testing.assert_allclose(np.asarray(w[0]), np.asarray(rw), atol=1e-6)
    scores = op.select.scores(q, w, k)[0]
    np.testing.assert_allclose(np.asarray(scores),
                               np.asarray(ref.index_scores(rq, rk, rw)), atol=1e-5)
    op2, _, params, a = _attn_op(cfg, 1, 4)
    same = jnp.broadcast_to(a[:, :1], a.shape)
    _, keys, _ = op2._index(params, same, op2._query_source(params, same),
                            jnp.arange(4)[None])
    keys = np.asarray(keys[0])
    rope = cfg["qk_rope_head_dim"]
    assert np.array_equal(keys[1:, rope:], keys[:-1, rope:])
    assert np.abs(keys[1:, :rope] - keys[:-1, :rope]).max() > 1e-3


def test_selected_set_is_the_references_position_for_position():
    """The decode step's pick and the prefill's mask against
    ``lax.top_k`` of the reference's scores, for every row of a
    sequence six times ``topk``."""
    t = 96
    cfg, op, (q, k, w), (rq, rk, rw) = _selector_both(t)
    sel = op.select
    want = np.asarray(ref.selected(ref.index_scores(rq, rk, rw), 0, TOPK))
    scores = sel.scores(q, w, k)                                  # (1, t, t)
    keep = np.asarray(sel.keep(scores, jnp.arange(t)))[0]
    assert np.array_equal(keep, want)
    assert keep.sum(axis=1).tolist() == [min(i + 1, TOPK) for i in range(t)]
    for row in (3, TOPK - 1, TOPK, 40, t - 1):
        idx, valid = sel.pick(scores[:, row], jnp.asarray([row]))
        got = np.zeros((t,), bool)
        got[np.asarray(idx[0])[np.asarray(valid[0])]] = True
        assert np.array_equal(got, want[row]), row


def _through_the_caches(op, params, a, plen):
    """Outputs at positions ``plen - 1 ..`` of ``a`` (1, t, d): a cached
    prefill of ``plen`` rows in a bucket of ``plen + 8`` then one decode
    step a row."""
    t = a.shape[1]
    entries = op.cache_entries(S)
    state = {f"cache_{e}": jnp.zeros((1,) + ce.shape, ce.dtype)
             for e, ce in entries.items()}
    bucket = jnp.concatenate([a[:, :plen], jnp.ones((1, 8, a.shape[2]), a.dtype)], axis=1)
    (y,), state = op.forward(params, [bucket], dict(state, pos=jnp.zeros((1,), jnp.int32)),
                             False)
    outs = [y[:, plen - 1]]
    for j in range(plen, t):
        s = dict(state, pos=jnp.asarray([j], jnp.int32))
        (y,), state = op.forward(params, [a[:, j:j + 1]], s, False)
        outs.append(y[:, 0])
    return jnp.stack(outs, axis=1), state


def test_attention_op_prefill_and_decode_through_both_cache_entries():
    """The chunked expanded prefill, then absorbed decode steps over the
    gathered latent rows, against the reference's expanded attention of
    the whole sequence; and the cache as it is declared: positions-major,
    a position a row of whole lane tiles."""
    cfg = _cfg()
    op, get, params, a = _attn_op(cfg, 1, 96)
    assert {e: ce.shape for e, ce in op.cache_entries(S).items()} == \
        {"ckr": (S, 128), "idx": (S, 16)}        # 32 + 8 values, one lane tile
    want, _ = ref.attention(cfg, get, a[0])
    (full,), _ = op.forward(params, [a], {}, False)
    np.testing.assert_allclose(np.asarray(full[0]), np.asarray(want), atol=2e-5)
    got, state = _through_the_caches(op, params, a, 72)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[71:]), atol=2e-5)
    # The selector selects: attending everything gives another output.
    dense, _ = ref.attention(cfg, get, a[0], select=False)
    assert np.abs(np.asarray(dense) - np.asarray(want)).max() > 1e-3
    assert state["cache_ckr"].shape == (1, S, 128) and state["cache_idx"].shape == (1, S, 16)
    # Behind a position's 40 values the row holds zeros.
    assert not np.asarray(state["cache_ckr"][..., 40:]).any()
    assert np.asarray(state["cache_ckr"][0, :96, :40]).any(axis=1).all()


@pytest.mark.parametrize("topk", [96, 4096])
def test_topk_at_least_the_sequence_is_the_op_without_select(topk):
    """Nothing to drop: the selected op's outputs are the unselected
    op's (another cache layout, the same attention), prefill and decode."""
    cfg = _cfg(index_topk=topk)
    op, get, params, a = _attn_op(cfg, 1, 96)
    plain, _, _, _ = _attn_op(cfg, 1, 96, select=None)
    assert set(plain.cache_entries(S)) == {"ckr"}
    assert plain.cache_entries(S)["ckr"].shape == (40, S)
    pp = {k: params[k] for k in plain.param_specs()}
    got, _ = _through_the_caches(op, params, a, 72)
    state = {"cache_ckr": jnp.zeros((1, 40, S), jnp.float32), "pos": jnp.zeros((1,), jnp.int32)}
    bucket = jnp.concatenate([a[:, :72], jnp.ones((1, 8, 64), jnp.float32)], axis=1)
    (y,), state = plain.forward(pp, [bucket], state, False)
    outs = [y[:, 71]]
    for j in range(72, 96):
        (y,), state = plain.forward(pp, [a[:, j:j + 1]],
                                    dict(state, pos=jnp.asarray([j], jnp.int32)), False)
        outs.append(y[:, 0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.stack(outs, axis=1)),
                               atol=2e-5)


def test_per_head_gate_multiplies_each_heads_values():
    cfg = _cfg()
    op, get, params, a = _attn_op(cfg, 1, 32, select=None)
    bare, _, _, _ = _attn_op(cfg, 1, 32, select=None, gate=None)
    assert "wg" not in bare.param_specs()
    # A gate weight of zero is one half on every head.
    zero = dict(params, wg=jnp.zeros_like(params["wg"]))
    (half,), _ = op.forward(zero, [a], {}, False)
    (whole,), _ = bare.forward({k: params[k] for k in bare.param_specs()}, [a], {}, False)
    np.testing.assert_allclose(np.asarray(half), 0.5 * np.asarray(whole), atol=1e-6)
    with pytest.raises(ValueError, match="per_head"):
        _attn_op(cfg, 1, 32, gate=True)


def test_regimes_that_refuse_a_selector_over_the_latent_name_the_roadmap():
    ff = build_lm(AXK2_TINY, 2, S)
    with pytest.raises(ValueError, match="no paged pool"):
        ServingExecutor(ff, ff.config, max_batch=2, max_seq=S, kv_block=16)
    cfg = _cfg()
    op, _, params, a = _attn_op(cfg, 1, 16)
    state = {"cache_ckr": jnp.zeros((1, S, 128)), "cache_idx": jnp.zeros((1, S, 16)),
             "pos": jnp.zeros((1,), jnp.int32)}
    for extra in ({"chunk": 8}, {"block_table": jnp.zeros((1, 8), jnp.int32)}):
        with pytest.raises(NotImplementedError, match="ROADMAP B-M1"):
            op.forward(params, [a], dict(state, **extra), False)


# -- group-limited routing ----------------------------------------------------------


def _router(groups=(4, 2), experts=16, top_k=3, held=None, tokens=8):
    x = TensorSpec("x", (1, tokens, 64), jnp.float32, ("n", "s", None))
    return MixtureOfExperts(
        "blk1_moe", x, experts, 32, top_k=top_k, dispatch="sorted", router="sigmoid",
        gated=True, activation="silu", shared_experts=1, selection_bias=True,
        routed_scale=2.5, n_group=groups[0], topk_group=groups[1], held_experts=held)


def test_router_drops_a_top_expert_that_lies_in_a_dropped_group():
    """Rows built so that the single best expert sits in a group whose
    second best is poor: the group falls, and the expert with it.  A
    router that ignores the groups picks it and differs from the
    reference; the op's choices and weights are the reference's."""
    cfg = _cfg()
    get = ref.Leaves(cfg, SEED).at("blk1_")
    op = _router()
    params = {"gate": get("moe/gate"), "e_bias": get("moe/e_bias")}
    # Logits by expert: group 2 (experts 8-11) holds the best one and
    # nothing else; groups 0 and 1 hold two good ones each.
    logits = np.full((16,), -3.0, np.float32)
    logits[[0, 1, 4, 5]] = [1.0, 0.9, 0.8, 0.7]
    logits[9] = 2.0
    gate = np.asarray(params["gate"])
    u = np.linalg.lstsq(gate.T, logits, rcond=None)[0][None].astype(np.float32)
    rows = jnp.asarray(np.concatenate(
        [u, np.random.default_rng(1).standard_normal((63, 64)).astype(np.float32)]))
    idx, w = op.route(params, rows)
    ridx, rw = ref.route(cfg, get, rows)
    assert np.array_equal(np.sort(np.asarray(idx), axis=1), np.sort(np.asarray(ridx), axis=1))
    np.testing.assert_allclose(np.sort(np.asarray(w), axis=1), np.sort(np.asarray(rw), axis=1),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.5, atol=1e-5)
    assert 9 not in np.asarray(idx[0]) and set(np.asarray(idx[0]).tolist()) <= {0, 1, 4, 5}
    # Every chosen expert lies in one of two groups, on every row.
    assert all(len({e // 4 for e in row}) <= 2 for row in np.asarray(idx).tolist())
    # Ignoring the groups: expert 9 is chosen, and the reference notices.
    flat_idx, _ = _router(groups=(1, 1)).route(params, rows)
    assert 9 in np.asarray(flat_idx[0])
    assert np.array_equal(np.asarray(flat_idx), np.asarray(ref.route(cfg, get, rows, groups=False)[0]))
    differs = np.any(np.sort(np.asarray(flat_idx), axis=1)
                     != np.sort(np.asarray(ridx), axis=1), axis=1)
    assert differs[0] and differs.mean() > 0.2


def test_expert_layer_that_ignores_groups_fails_the_reference():
    cfg = _cfg()
    get = ref.Leaves(cfg, SEED).at("blk1_")
    u = jnp.asarray(np.random.default_rng(4).standard_normal((1, 64, 64)), jnp.float32)
    want = np.asarray(ref.experts(cfg, get, u[0]))
    for groups, same in (((4, 2), True), ((1, 1), False)):
        op = _router(groups=groups, tokens=64)
        params = {k: jnp.asarray(get(f"moe/{k}")) for k in op.param_specs()}
        (got,), _ = op.forward(params, [u], {}, False)
        close = np.allclose(np.asarray(got[0]), want, atol=2e-5)
        assert close == same, groups


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One expert layer of 256 experts in 8 groups cut into sixteen
    shares of 16 (each half a group): the shares' routed terms plus the
    shared expert, counted once, are the whole layer's output, in the
    program and in the reference."""
    base = dict(AXK2_TINY, n_routed_experts=256, n_group=8, topk_group=4,
                num_experts_per_tok=8)
    whole_cfg = dict(base, assumed=dict(_ASSUMED, param_dtype="float32"))
    get = ref.Leaves(whole_cfg, SEED).at("blk1_")
    u = jnp.asarray(np.random.default_rng(6).standard_normal((1, 32, 64)), jnp.float32)
    want = np.asarray(ref.experts(whole_cfg, get, u[0]))
    shared = np.asarray(ref._gated(u[0], get("moe/s_gate"), get("moe/s_up"),
                                   get("moe/s_down"), False))
    x = TensorSpec("x", (1, 32, 64), jnp.float32, ("n", "s", None))
    kw = dict(top_k=8, dispatch="sorted", router="sigmoid", gated=True, activation="silu",
              shared_experts=1, selection_bias=True, routed_scale=2.5, n_group=8, topk_group=4)
    full = {k: np.asarray(get(f"moe/{k}")) for k in
            MixtureOfExperts("blk1_moe", x, 256, 32, **kw).param_specs()}
    total, total_ref = np.zeros_like(want), np.zeros_like(want)
    for share in range(16):
        held = list(range(16 * share, 16 * share + 16))
        op = MixtureOfExperts("blk1_moe", x, 256, 32, held_experts=held, **kw)
        params = {k: jnp.asarray(v[held] if v.ndim == 3 else v) for k, v in full.items()}
        (got,), _ = op.forward(params, [u], {}, False)
        total += np.asarray(got[0]) - shared
        # The reference's share: its leaves are the held rows of the
        # whole layer's, so the cut configuration is walked with them.
        cut = dict(whole_cfg, n_routed_experts=16, held_experts=held,
                   published={"n_routed_experts": 256})
        idx, w = ref.route(cut, get, u[0])
        for j, e in enumerate(held):
            gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
            total_ref += np.asarray(gate[:, None] * ref._gated(
                u[0], get.expert("moe/w_gate", e), get.expert("moe/w_up", e),
                get.expert("moe/w_down", e), False))
    np.testing.assert_allclose(total + shared, want, atol=3e-5)
    np.testing.assert_allclose(total_ref + shared, want, atol=3e-5)


def test_held_rows_under_groups_keep_the_uniform_expectation():
    """Half of group 0 held of 8 groups of 32: a uniform router puts
    ``top_k x 16 / 256`` of a token's choices here, as without groups
    (a group is kept half the time and then holds a quarter of the
    choices, half of them on this half); what grows is a token's spread.
    ``held_rows_bound`` stays ``HELD_ROWS_MARGIN`` over that mean."""
    x = TensorSpec("x", (1, 2048, 64), jnp.float32, ("n", "s", None))
    op = MixtureOfExperts("m", x, 256, 32, top_k=8, dispatch="sorted", router="sigmoid",
                          gated=True, selection_bias=True, n_group=8, topk_group=4,
                          held_experts=list(range(16)))
    flat = MixtureOfExperts("m", x, 256, 32, top_k=8, dispatch="sorted", router="sigmoid",
                            gated=True, selection_bias=True, held_experts=list(range(16)))
    rng = np.random.default_rng(7)
    rows = jnp.asarray(rng.standard_normal((8192, 64)), jnp.float32)
    params = {"gate": jnp.asarray(rng.standard_normal((64, 256)) * 0.2, jnp.float32),
              "e_bias": jnp.zeros((256,), jnp.float32)}
    here = np.asarray(op.route(params, rows)[0]) < 16
    there = np.asarray(flat.route(params, rows)[0]) < 16
    assert abs(here.sum(axis=1).mean() - 0.5) < 0.05
    assert abs(there.sum(axis=1).mean() - 0.5) < 0.05
    assert here.sum(axis=1).var() > there.sum(axis=1).var()
    assert op.held_rows_bound(2048 * 8) == 1536 == flat.held_rows_bound(2048 * 8)
    # A segment of 2048 tokens stays far under the bound.
    assert here.reshape(4, 2048, 8).sum(axis=(1, 2)).max() < 1536 * 0.8


# -- experts too wide for VMEM ----------------------------------------------------------


def test_grouped_block_cols_keeps_every_accepted_cells_experts_whole():
    """Whole columns wherever two (or, gated, four) double-buffered expert
    blocks fit 48 MB: every configuration the benchmark had; this one's
    7168 x 2048 experts in blocks of 512 (gated) and 3584 (down)."""
    from flexflow_tpu.ops import pallas_kernels as pk

    for d, f in ((2048, 768), (3072, 1024), (4096, 1280), (3584, 1024)):
        assert pk.grouped_block_cols(d, f, 2, 2) == f
        assert pk.grouped_block_cols(f, d, 1, 2) == d
    assert pk.grouped_block_cols(7168, 2048, 2, 2) == 512
    assert pk.grouped_block_cols(2048, 7168, 1, 2) == 3584


@pytest.mark.parametrize("rows_per_expert,tm", [(3, 16), (200, 128)])
def test_grouped_matmul_in_blocks_of_columns_against_ragged_dot(rows_per_expert, tm,
                                                                 monkeypatch):
    """The kernel's second grid axis: with room for 128 columns of the
    expert blocks at a time, three blocks of columns over the tiles give
    what the whole-column walk and ``lax.ragged_dot`` give; tiles no
    expert uses move nothing in either."""
    from flexflow_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(3)
    e, k, n = 4, 128, 384
    counts = np.array([rows_per_expert, 0, 2 * rows_per_expert, 1])
    padded = -(-counts // tm) * tm
    rows = int(padded.sum()) + 2 * tm
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
    wu = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
    used = int(padded.sum()) // tm
    tile_e = np.repeat(np.arange(e), padded // tm)
    tile_e = jnp.asarray(np.concatenate([tile_e, np.full(rows // tm - used, tile_e[-1])]))
    whole = pk.grouped_matmul(x, w, tile_e, jnp.int32(used), tm, w_up=wu)
    monkeypatch.setattr(pk, "_GMM_WEIGHT_BYTES", 2 * 2 * k * 128 * 4)
    assert pk.grouped_block_cols(k, n, 2, 4) == 128
    got = pk.grouped_matmul(x, w, tile_e, jnp.int32(used), tm, w_up=wu)
    np.testing.assert_allclose(np.asarray(got)[:used * tm], np.asarray(whole)[:used * tm],
                               rtol=1e-3, atol=1e-3)    # f32 sums in another order
    lens = jnp.asarray(padded, jnp.int32)
    want = jax.nn.silu(jax.lax.ragged_dot(x, w, lens)) * jax.lax.ragged_dot(x, wu, lens)
    np.testing.assert_allclose(np.asarray(got)[:used * tm], np.asarray(want)[:used * tm],
                               rtol=2e-3, atol=2e-3)
    monkeypatch.setattr(pk, "_GMM_WEIGHT_BYTES", 2 * k * 128 * 4)
    down = pk.grouped_matmul(x, w, tile_e, jnp.int32(used), tm)
    np.testing.assert_allclose(np.asarray(down)[:used * tm],
                               np.asarray(jax.lax.ragged_dot(x, w, lens))[:used * tm],
                               rtol=2e-4, atol=2e-4)


# -- the whole model ------------------------------------------------------------------


@pytest.mark.parametrize("gain", [None, {"0": 3.0}])
def test_full_forward_logits_match_the_reference(gain):
    cfg = _cfg()
    if gain:
        cfg["assumed"]["q_norm_gain"] = gain
        spec = ref.leaf_spec(cfg)
        assert [spec[f"blk{i}_attn/q_norm"][2] for i in range(3)] == [3.0, 1.0, 1.0]
    ff, params = _model(cfg, 2, 64)
    toks = _tokens(2, 64)
    ex = Executor(ff, config=ff.config, devices=jax.devices()[:1])
    _loss, outs = ex.forward_step(params, {}, {"tokens": toks, "label": toks})
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t)) for t in toks])
    np.testing.assert_allclose(np.asarray(outs["lm_head:out"]), want, atol=2e-5)
    dense = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t, select=False))
                      for t in toks])
    assert np.abs(dense - want).max() > 0.01      # the selector selects


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


@pytest.mark.parametrize("dtype,atol", [("float32", 2e-5), ("bfloat16", None)])
def test_prefill_then_decode_through_both_caches(dtype, atol):
    """The chunked prefill (its dense head, two key widths, several
    chunks; the bucket's pad rows beyond the prompt) then one-token
    steps over the two caches it left, at sequences 4.5-6x ``topk``,
    against the reference's one full forward: logits, not tokens."""
    cfg = _cfg(dtype)
    ff, params = _model(cfg, 2, S, dtype)
    toks = _tokens(2, 96)
    got, sex = _serve_logits(params, ff, toks, 72, 80)
    want = np.stack([np.asarray(ref.logits_fn(cfg, SEED, t))[72:] for t in toks])
    if atol is None:
        # bf16 against the f32 walk on the same (bf16-rounded) weights:
        # round-off of 8 bits through the blocks, and where a position,
        # a group or an expert near a threshold flips, that token's
        # logits move more: the median and the share far off, not the
        # widest.
        gap = np.abs(got.astype(np.float32) - want)
        assert np.median(gap) < 0.03 and np.mean(gap > 0.15) < 0.15
    else:
        np.testing.assert_allclose(got.astype(np.float32), want, atol=atol)
    shapes = {k: {e: c.shape for e, c in v.items()}
              for k, v in sex.init_cache().items()}
    assert shapes["blk1_attn"] == {"ckr": (2, S, 128), "idx": (2, S, 16)}
    assert sex._attention_paths(True) == "latent_select_absorbed"
    assert sex._attention_paths(False) == "latent_select_expanded"


def test_kv_rows_count_the_gathered_latent_rows_and_the_selectors_keys():
    ff = build_lm(AXK2_TINY, 4, S)
    sex = ServingExecutor(ff, ff.config, max_batch=4, max_seq=S, buckets=[S])
    assert [op.decode_fetch_block(4, S, None) for op in sex.attn_ops] == [1, 1, 1]
    rows = sex.kv_rows(np.asarray([100, 5, 64, 0]), 8)
    assert rows == {"kv_rows_fetched": 4 * 8 * TOPK, "kv_rows_cache": 4 * 8 * S,
                    "idx_rows_fetched": 4 * 8 * S}
    # The unselected latent op counts what it always did.
    plain = build_lm(DEEPSEEK_V3_TINY, 4, S)
    sex = ServingExecutor(plain, plain.config, max_batch=4, max_seq=S, buckets=[S])
    assert "idx_rows_fetched" not in sex.kv_rows(np.zeros((4,), np.int32), 2)


def test_server_run_reports_the_routing_counters_of_the_held_share(tmp_path):
    from flexflow_tpu.runtime import telemetry
    from flexflow_tpu.runtime.serving import Request, Server

    cfg = _cfg(n_routed_experts=8, held_experts=list(range(8)),
               published={"n_routed_experts": 16})
    ff, params = _model(cfg, 2, S)
    sex = ServingExecutor(ff, ff.config, max_batch=2, max_seq=S, buckets=[64])
    srv = Server(sex, params, {}, decode_steps=4)
    reqs = [Request(id=i, prompt=_tokens(1, 40 + i)[0], max_new_tokens=6) for i in range(2)]
    with telemetry.Telemetry(directory=str(tmp_path)) as tel:
        results, stats = srv.run(reqs)
    assert stats["failed"] == 0 and all(len(r.tokens) == 6 for r in results.values())
    steps = [e for e in common.read_events(tel.path) if e["ev"] == "decode_superstep"]
    assert steps and all(0 <= e["experts_touched"] <= 8 for e in steps)
    assert all(e["kv_rows_fetched"] == 2 * e["k"] * TOPK for e in steps)
    assert all(e["idx_rows_fetched"] == 2 * e["k"] * S for e in steps)


# -- what the other families build ------------------------------------------------------


@pytest.mark.parametrize("name", ["deepseek_v3", "xing4"])
def test_programs_without_the_new_arguments_reach_none_of_the_new_paths(name, monkeypatch):
    """kanana2's and xing4's graphs: the same parameters and cache as
    before, and their serving programs trace without touching the
    selector, the gate, the gated norm or the group step."""
    base = {"deepseek_v3": DEEPSEEK_V3_TINY, "xing4": XING4_TINY}[name]
    ff = build_lm(base, 2, 64)
    ops = {op.name: op for op in ff.layers}
    attn, moe, ln = ops["blk1_attn"], ops["blk1_moe"], ops["blk1_ln1"]
    assert attn.select is None and attn.attrs["gate"] is None
    query = {"wq_a", "q_norm", "wq_b"} if base.get("q_lora_rank") else {"wq"}
    assert set(attn.param_specs()) == {"wkv_a", "kv_norm", "wkv_b", "wo"} | query
    assert {e: ce.shape for e, ce in attn.cache_entries(64).items()} == {"ckr": (40, 64)}
    assert attn.serving_path(True) == "latent_absorbed"
    assert (moe.attrs["n_group"], moe.attrs["topk_group"]) == (1, 1)
    assert set(ln.param_specs()) == {"scale"}

    def boom(*a, **k):
        raise AssertionError("a new path was reached")

    for mod, fn in ((attention_ops, "_attend_selected"), (attention_ops, "_gate_heads"),
                    (attention_ops, "_latent_decode_rows"),
                    (LatentAttention, "_forward_selected"), (LatentAttention, "_index"),
                    (MixtureOfExperts, "_kept_groups"), (TokenSelector, "project")):
        monkeypatch.setattr(mod, fn, boom)
    sex = ServingExecutor(ff, ff.config, max_batch=2, max_seq=64, buckets=[64])
    abstract, _, _ = jax.eval_shape(Executor(ff, config=ff.config).init)
    caches = sex._cache_tree(sex._cache_specs, lambda ce: jax.ShapeDtypeStruct(
        (2,) + ce.shape, ce.dtype))
    text = str(jax.make_jaxpr(sex.build_decode_superstep(2))(
        abstract, {}, caches, jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32)))
    assert "ff_gnorm" not in text and "ff_route_group" not in text
    jax.eval_shape(sex.build_prefill(64), abstract, {},
                   jax.ShapeDtypeStruct((1, 64), jnp.int32),
                   jax.ShapeDtypeStruct((), jnp.int32))


def test_compiled_serving_programs_carry_the_four_scopes():
    """``ff_index`` and ``ff_select`` inside the latent attention op,
    ``ff_gnorm`` inside every gated norm and ``ff_route_group`` inside
    the expert layers, in the prefill and in the decode superstep, as a
    trace's ``tf_op`` holds them."""
    import re

    from flexflow_tpu.obs.events import SCOPE_CATALOG

    ff = build_lm(AXK2_TINY, 2, 64)
    sex = ServingExecutor(ff, ff.config, max_batch=2, max_seq=64, buckets=[64])
    abstract, _, _ = jax.eval_shape(Executor(ff, config=ff.config).init)
    caches = sex._cache_tree(sex._cache_specs, lambda ce: jax.ShapeDtypeStruct(
        (2,) + ce.shape, ce.dtype))
    vec = jax.ShapeDtypeStruct((2,), jnp.int32)
    programs = {
        "decode": sex.build_decode_superstep(2).lower(abstract, {}, caches, vec, vec),
        "prefill": sex.build_prefill(64).lower(
            abstract, {}, jax.ShapeDtypeStruct((1, 64), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))}
    for kind, lowered in programs.items():
        names = set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))
        paths = [[c for c in re.split(r"[/()]", n) if c] for n in names]
        for scope, op in (("ff_index", "blk1_attn"), ("ff_select", "blk1_attn"),
                          ("ff_gnorm", "blk1_ln1"), ("ff_gnorm", "ln_f"),
                          ("ff_route_group", "blk1_moe")):
            assert scope in SCOPE_CATALOG
            assert any(scope in p and op in p for p in paths), (kind, scope, op)
        assert not any("ff_gnorm" in p and "blk1_attn" in p for p in paths)


# -- the configuration ------------------------------------------------------------------


def test_published_and_held_parameter_counts_from_the_leaf_recipe():
    """689.03 B and 32.54 B active a token as published (the row's
    688B-A33B), 4.272 G held on this chip, all from ``leaf_spec``."""
    cfg = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "a.x-k2-688b-l5e16.json")))
    held = ref.parameter_counts(cfg)["total"]
    assert round(held / 1e9, 3) == 4.272 and round(held * 2 / 1e9, 2) == 8.54
    whole = ref.parameter_counts(
        {k: v for k, v in dict(cfg, **cfg["published"]).items() if k != "held_experts"})
    assert round(whole["total"] / 1e9, 2) == 689.03
    assert round(whole["active"] / 1e9, 2) == 32.54
    # The program declares the same leaves at the same shapes.
    ff = build_lm(cfg, 1, 128, FFConfig(batch_size=1, compute_dtype="bfloat16"))
    abstract, _, _ = jax.eval_shape(Executor(ff, config=ff.config).init)
    spec = ref.leaf_spec(cfg)
    got = {f"{o}/{k}": tuple(a.shape) for o, ls in abstract.items() for k, a in ls.items()}
    assert got == {k: tuple(v[0]) for k, v in spec.items()}
    assert {k for k, a in ((f"{o}/{k}", a) for o, ls in abstract.items()
                           for k, a in ls.items()) if a.dtype == jnp.float32} == \
        {k for k in spec if ref.stored_dtype(cfg, k) == "float32"}


def test_smoke_preset_takes_the_kernels_widths():
    from flexflow_tpu.ops import pallas_kernels as pk

    m = AXK2_SMOKE
    assert pk.grouped_matmul_supported(m["hidden_size"], m["moe_intermediate_size"],
                                       jnp.bfloat16)
    q = (1, m["num_attention_heads"], 512, m["qk_nope_head_dim"] + m["qk_rope_head_dim"])
    assert pk.flash_uneven_supported(q, m["v_head_dim"])
    assert m["index_topk"] == 512 and m["index_head_dim"] >= m["qk_rope_head_dim"]


def test_prefill_through_the_kept_kernel_equals_the_plain_path(monkeypatch):
    """The smoke preset's prefill (1024 rows under an ``index_topk`` of
    512: the second chunk is masked, at whole 128-row blocks) through
    ``pallas_kernels.attend_kept`` and, with its gate turned off, through
    ``_attend_kept_heads``: the same first token and the same rows for
    both cache entries of every layer."""
    from flexflow_tpu.ops import pallas_kernels as pk

    seq, plen = 1024, 900
    ff, params = _model(_cfg(base=AXK2_SMOKE), 1, seq, chunk=512)
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
    layers = AXK2_SMOKE["num_hidden_layers"]
    heads = AXK2_SMOKE["num_attention_heads"]
    assert kept == dict(kept_kernel=True, kept_key_blocks=2,
                        kept_key_blocks_square=2)
    assert calls == [(1, heads, 512, 96)] * layers
    monkeypatch.setattr(pk, "attend_kept_supported", lambda *a: False)
    plain_kept, plain_rows, plain_tok = prefill()
    assert len(calls) == layers and plain_kept["kept_kernel"] is False
    assert tok == plain_tok
    assert set(rows["blk1_attn"]) == {"ckr", "idx"}
    # Round-off of the streamed softmax apart, a later layer's rows move
    # only where a near-tie of a router or a selector falls the other way.
    for got, want in zip(jax.tree.leaves(rows), jax.tree.leaves(plain_rows)):
        gap = np.abs(np.asarray(got[:plen]) - np.asarray(want[:plen]))
        assert np.median(gap) < 1e-6 and np.mean(gap > 1e-4) < 0.005
