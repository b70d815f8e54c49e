"""Decoder-only transformer LM — the long-context flagship.

The reference has no transformer (2018 codebase); SURVEY.md §2.7
directs the rebuild to generalize its sequence parallelism (chunked
LSTM ops with P2P state handoff) to ring-attention context parallelism.
This model family is that generalization: pre-LN GPT-style blocks whose
attention runs the ring path of ``ops/attention.py`` under an ``s``
strategy degree, composing with data parallelism (``n``) and
Megatron-style tensor parallelism (``c`` on the MLP/projection dims).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore


def build_transformer_lm(
    batch_size: int = 8,
    seq_len: int = 2048,
    vocab_size: int = 32 * 1024,
    d_model: int = 512,
    num_heads: int = 8,
    num_layers: int = 6,
    d_ff: Optional[int] = None,
    moe_experts: int = 0,
    moe_capacity_factor: float = 1.25,
    config: Optional[FFConfig] = None,
) -> FFModel:
    """The GPT-2 block family from positional widths: the configuration
    below handed to :func:`build_lm`.  ``moe_experts > 0`` swaps every
    block's dense MLP for a switch-style mixture-of-experts FFN
    (``ops/moe.py``) — expert parallelism at transformer scale (a 'c'
    degree on the moe ops shards experts across the mesh)."""
    return build_lm(
        {"model_type": "gpt2", "vocab_size": vocab_size, "n_embd": d_model,
         "n_head": num_heads, "n_layer": num_layers, "n_inner": d_ff,
         "moe_experts": moe_experts,
         "moe_capacity_factor": moe_capacity_factor},
        batch_size, seq_len, config,
    )


def build_lm(model: Dict[str, Any], batch_size: int, seq_len: int,
             config: Optional[FFConfig] = None) -> FFModel:
    """A decoder-only LM from a configuration's keys, as the model's
    own ``config.json`` names them; ``model_type`` picks the block
    family.  What a key cannot say here is an error, not a default."""
    kind = model.get("model_type", "gpt2")
    if kind not in _BLOCKS:
        raise ValueError(
            f"no block family for model_type {kind!r}: {sorted(_BLOCKS)}")
    ff = FFModel(config or FFConfig(batch_size=batch_size))
    tok = ff.create_tensor((batch_size, seq_len), dtype=jnp.int32,
                           name="tokens", dim_axes=("n", "s"))
    lbl = ff.create_tensor((batch_size, seq_len), dtype=jnp.int32,
                           name="label", dim_axes=("n", "s"))
    logits = _BLOCKS[kind](ff, tok, model)
    ff.softmax(logits, lbl, name="softmax")
    return ff


def _gpt2_lm(ff: FFModel, tok, m: Dict[str, Any]):
    """GPT-2 (Radford et al. 2019): learned positions, pre-LN blocks of
    full multi-head attention and a 4x GELU MLP, LayerNorm, a head."""
    d_model, moe_experts = m["n_embd"], m.get("moe_experts", 0)
    d_ff = m.get("n_inner") or 4 * d_model
    x = ff.word_embedding(tok, m["vocab_size"], d_model, name="embed")
    x = ff.position_embedding(x, name="pos")
    for i in range(m["n_layer"]):
        a = ff.layer_norm(x, name=f"blk{i}_ln1")
        a = ff.multihead_attention(a, m["n_head"], causal=True,
                                   name=f"blk{i}_attn")
        x = ff.add(x, a, name=f"blk{i}_res1")
        h = ff.layer_norm(x, name=f"blk{i}_ln2")
        if moe_experts:
            h = ff.moe(h, moe_experts, d_ff,
                       capacity_factor=m.get("moe_capacity_factor", 1.25),
                       name=f"blk{i}_moe")
        else:
            h = ff.dense(h, d_ff, activation="gelu", name=f"blk{i}_mlp_up")
            h = ff.dense(h, d_model, name=f"blk{i}_mlp_down")
        x = ff.add(x, h, name=f"blk{i}_res2")
    x = ff.layer_norm(x, name="ln_f")
    return ff.dense(x, m["vocab_size"], name="lm_head")


def _residual(ff: FFModel, m: Dict[str, Any], x, i: int, k: int, sublayer,
              close: bool = False):
    """Sublayer ``k`` (1 attention, 2 feed-forward) of block ``i`` round
    the residual path: ``x + sublayer(x)``, or where the configuration
    carries ``hc_mult`` streams the hyper-connection pair
    (``ops/hyper_connection.py``): the sublayer reads a mixture of the
    streams and is written back into all of them.  The first pair opens
    the stream from the table's row; ``close`` sums it for the last norm."""
    n = m.get("hc_mult")
    if not n:
        return ff.add(x, sublayer(x), name=f"blk{i}_res{k}")
    hc = dict(iters=m["hc_sinkhorn_iters"], eps=m["hc_eps"],
              clamp=(m["mhc_h_res_clamp_min"], m["mhc_h_res_clamp_max"]))
    u = ff.hyper_connection_pre(x, n, name=f"blk{i}_hc{k}_pre", **hc)
    return ff.hyper_connection_post(x, sublayer(u), n, close=close,
                                    name=f"blk{i}_hc{k}_post", **hc)


def _deepseek_v3_lm(ff: FFModel, tok, m: Dict[str, Any]):
    """The DeepSeek-V3 block family (``transformers``' ``DeepseekV3``):
    RMSNorm, latent attention with rotary positions on a sub-width of
    the head (the query compressed too under ``q_lora_rank``, the
    positions YaRN's under ``rope_scaling``), ``first_k_dense_replace``
    leading gated-SiLU dense layers, then expert layers under a sigmoid
    top-k router with a selection bias (among the ``topk_group`` best of
    ``n_group`` groups of experts where there are groups) and shared
    experts; no bias anywhere, an untied head.  ``model_type``
    ``xing4_0`` is the same block round a residual of ``hc_mult``
    streams (``_residual``); its multi-token-prediction module
    (``num_nextn_predict_layers``) belongs to training and to
    self-drafting and is not built.  ``model_type`` ``axk2`` is the same
    block with DeepSeek-V3.2's learned token selector over the latent
    cache (``index_n_heads``, ``index_head_dim``, ``index_topk``), a
    sigmoid gate a head on the attended values
    (``attention_output_gate``), a low-rank gate on the block's two norms
    and the last one (``gated_norm``, ``gated_norm_rank``) and its
    rotary keys under ``rope_parameters``.  ``held_experts`` (not a key
    of the source: the deployment's) names the routed experts this chip
    holds, default all; where ``published.n_routed_experts`` stands
    beside it, that is the router's width and ``n_routed_experts`` the
    number held."""
    for key, want in (("moe_layer_freq", 1), ("attention_bias", False),
                      ("hidden_act", "silu"), ("rope_interleave", True),
                      ("tie_word_embeddings", False)):
        if m.get(key, want) != want:
            raise ValueError(
                f"deepseek_v3 builder: {key}={m[key]!r} is not built yet "
                f"(only {want!r})")
    if m.get("scoring_func", "sigmoid") not in ("sigmoid", "softmax"):
        raise ValueError(f"scoring_func {m['scoring_func']!r}")
    d, eps = m["hidden_size"], m["rms_norm_eps"]
    layers = m["num_hidden_layers"]
    rope = m.get("rope_parameters") or {}
    scaling = m.get("rope_scaling")
    if rope.get("rope_type", "default") != "default":
        scaling = rope                       # the type's own keys (YaRN's)
    held, routed = m.get("held_experts"), m["n_routed_experts"]
    if held is not None and "n_routed_experts" in m.get("published", {}):
        routed = m["published"]["n_routed_experts"]
        if len(held) != m["n_routed_experts"]:
            raise ValueError(
                f"deepseek_v3 builder: held_experts names {len(held)} "
                f"experts, n_routed_experts (the number held) is "
                f"{m['n_routed_experts']}")
    norm = dict(eps=eps)
    if m.get("gated_norm"):
        norm["gate_rank"] = m["gated_norm_rank"]
    more = {}
    if m.get("attention_output_gate"):
        more["gate"] = "per_head"
    if "index_topk" in m:
        more["select"] = {"indexer_num_heads": m["index_n_heads"],
                          "indexer_head_dim": m["index_head_dim"],
                          "topk": m["index_topk"]}
    # A table in the compute dtype (the family policy keeps it f32 for
    # the sparse-update kernels, which this family does not train with).
    x = ff.word_embedding(tok, m["vocab_size"], d, name="embed",
                          dtype=jnp.dtype(ff.config.compute_dtype))
    for i in range(layers):
        def attention(u, i=i):
            a = ff.rms_norm(u, name=f"blk{i}_ln1", **norm)
            return ff.latent_attention(
                a, m["num_attention_heads"], kv_rank=m["kv_lora_rank"],
                nope_dim=m["qk_nope_head_dim"], rope_dim=m["qk_rope_head_dim"],
                v_dim=m["v_head_dim"],
                rope_theta=rope.get("rope_theta", m.get("rope_theta")),
                norm_eps=eps, q_rank=m.get("q_lora_rank"),
                rope_scaling=scaling, name=f"blk{i}_attn", **more)

        def feed_forward(u, i=i):
            h = ff.rms_norm(u, name=f"blk{i}_ln2", **norm)
            if i < m["first_k_dense_replace"]:
                g = ff.dense(h, m["intermediate_size"], activation="silu",
                             use_bias=False, name=f"blk{i}_mlp_gate")
                up = ff.dense(h, m["intermediate_size"], use_bias=False,
                              name=f"blk{i}_mlp_up")
                return ff.dense(ff.multiply(g, up, name=f"blk{i}_mlp_act"), d,
                                use_bias=False, name=f"blk{i}_mlp_down")
            return ff.moe(
                h, routed, m["moe_intermediate_size"],
                top_k=m["num_experts_per_tok"], dispatch="sorted",
                router=m.get("scoring_func", "sigmoid"), gated=True,
                activation="silu", shared_experts=m["n_shared_experts"],
                selection_bias=m.get("topk_method") == "noaux_tc",
                norm_topk_prob=m["norm_topk_prob"],
                routed_scale=m["routed_scaling_factor"],
                n_group=m.get("n_group", 1), topk_group=m.get("topk_group", 1),
                held_experts=held, name=f"blk{i}_moe")

        x = _residual(ff, m, x, i, 1, attention)
        x = _residual(ff, m, x, i, 2, feed_forward, close=i == layers - 1)
    x = ff.rms_norm(x, name="ln_f", **norm)
    return ff.dense(x, m["vocab_size"], use_bias=False, name="lm_head")


def _solar_open2_lm(ff: FFModel, tok, m: Dict[str, Any]):
    """The Solar-Open2 block family: RMSNorm pre-norm blocks whose mixer
    is grouped-query softmax attention with an elementwise sigmoid output
    gate on the layers ``gqa_layers`` names and Kimi Delta Attention (the
    gated delta rule, ``linear_attn_config``) on the others, every layer
    an expert layer under a sigmoid top-k router with a selection bias
    and shared experts; no positional signal anywhere (``use_rope``
    false), no bias, an untied head.  ``held_experts`` (the
    deployment's) names the routed experts this chip holds; the
    router's width is then ``published.n_routed_experts`` and
    ``n_routed_experts`` the number held."""
    lin = m["linear_attn_config"]
    for key, got, built in (
            ("use_rope", m.get("use_rope", False), (False,)),
            ("first_k_dense_replace", m.get("first_k_dense_replace", 0), (0,)),
            ("kda_use_full_proj", m.get("kda_use_full_proj", False), (False,)),
            ("tie_word_embeddings", m.get("tie_word_embeddings", False),
             (False,)),
            ("linear_attn_config.num_kv_heads", lin.get("num_kv_heads"),
             (None, lin["num_heads"]))):
        if got not in built:
            raise ValueError(
                f"solar_open2 builder: {key}={got!r} is not built yet "
                f"(only {built[0]!r})")
    d, eps = m["hidden_size"], m["rms_norm_eps"]
    held = m.get("held_experts")
    routed = m["n_routed_experts"]
    if held is not None:
        routed = m.get("published", {}).get("n_routed_experts", routed)
        if len(held) != m["n_routed_experts"]:
            raise ValueError(
                f"solar_open2 builder: held_experts names {len(held)} experts, "
                f"n_routed_experts (the number held) is {m['n_routed_experts']}")
    x = ff.word_embedding(tok, m["vocab_size"], d, name="embed",
                          dtype=jnp.dtype(ff.config.compute_dtype))
    for i in range(m["num_hidden_layers"]):
        a = ff.rms_norm(x, eps=eps, name=f"blk{i}_ln1")
        if i in m["gqa_layers"]:
            a = ff.multihead_attention(
                a, m["num_attention_heads"], causal=True, use_bias=False,
                num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                gate=m["use_gqa_gate"], name=f"blk{i}_attn")
        else:
            a = ff.delta_attention(
                a, lin["num_heads"], lin["head_dim"],
                conv_size=lin["short_conv_kernel_size"], norm_eps=eps,
                neg_eigval=m["kda_allow_neg_eigval"], name=f"blk{i}_kda")
        x = ff.add(x, a, name=f"blk{i}_res1")
        h = ff.rms_norm(x, eps=eps, name=f"blk{i}_ln2")
        h = ff.moe(
            h, routed, m["moe_intermediate_size"],
            top_k=m["num_experts_per_tok"], dispatch="sorted",
            router="sigmoid", gated=True, activation="silu",
            shared_experts=m["n_shared_experts"], selection_bias=True,
            norm_topk_prob=m["norm_topk_prob"],
            routed_scale=m["routed_scaling_factor"],
            held_experts=held, name=f"blk{i}_moe")
        x = ff.add(x, h, name=f"blk{i}_res2")
    x = ff.rms_norm(x, eps=eps, name="ln_f")
    return ff.dense(x, m["vocab_size"], use_bias=False, name="lm_head")


def _keye_vl2_lm(ff: FFModel, tok, m: Dict[str, Any]):
    """The language model of the Keye-VL-2.0 family (``model_type``
    ``KeyeVL2``): the Qwen3-MoE block (RMSNorm pre-norm, grouped-query
    attention with an RMSNorm over each head of q and k and rotary
    positions on the whole head under ``mrope_section``, every layer an
    expert layer under a softmax top-k router, no shared expert, no bias,
    an untied head) with a learned token selector inside the attention
    (``sa_config``: ``ops/token_select.py``).  The vision tower is not
    built: the graph takes token ids, for which the three position
    components are the token's index."""
    rope = m.get("rope_scaling") or {}
    for key, got, want in (
            ("attention_bias", m.get("attention_bias", False), False),
            ("use_sliding_window", m.get("use_sliding_window", False), False),
            ("mlp_only_layers", list(m.get("mlp_only_layers", [])), []),
            ("decoder_sparse_step", m.get("decoder_sparse_step", 1), 1),
            ("tie_word_embeddings", m.get("tie_word_embeddings", False), False),
            ("hidden_act", m.get("hidden_act", "silu"), "silu"),
            ("rope_scaling.rope_type",
             rope.get("rope_type", rope.get("type", "default")), "default")):
        if got != want:
            raise ValueError(
                f"KeyeVL2 builder: {key}={got!r} is not built yet "
                f"(only {want!r})")
    experts = m["num_experts"]
    if m.get("num_local_experts", experts) != experts:
        raise ValueError(
            f"KeyeVL2 builder: num_local_experts={m['num_local_experts']!r} "
            f"of num_experts={experts!r}: every expert is held")
    d, eps = m["hidden_size"], m["rms_norm_eps"]
    x = ff.word_embedding(tok, m["vocab_size"], d, name="embed",
                          dtype=jnp.dtype(ff.config.compute_dtype))
    for i in range(m["num_hidden_layers"]):
        a = ff.rms_norm(x, eps=eps, name=f"blk{i}_ln1")
        a = ff.multihead_attention(
            a, m["num_attention_heads"], causal=True, use_bias=False,
            num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
            qk_norm=eps,
            rope={"theta": float(m["rope_theta"]),
                  "sections": rope.get("mrope_section")},
            select=m.get("sa_config"), name=f"blk{i}_attn")
        x = ff.add(x, a, name=f"blk{i}_res1")
        h = ff.rms_norm(x, eps=eps, name=f"blk{i}_ln2")
        h = ff.moe(h, experts, m["moe_intermediate_size"],
                   top_k=m["num_experts_per_tok"], dispatch="sorted",
                   router="softmax", gated=True, activation="silu",
                   norm_topk_prob=m["norm_topk_prob"], name=f"blk{i}_moe")
        x = ff.add(x, h, name=f"blk{i}_res2")
    x = ff.rms_norm(x, eps=eps, name="ln_f")
    return ff.dense(x, m["vocab_size"], use_bias=False, name="lm_head")


def _laguna_lm(ff: FFModel, tok, m: Dict[str, Any]):
    """The Laguna block family (``model_type`` ``laguna``): RMSNorm
    pre-norm blocks of grouped-query attention whose kind and head count
    go by layer (``layer_types``: ``full_attention`` over the whole
    causal past, ``sliding_attention`` over the last ``sliding_window``
    positions; ``num_attention_heads_per_layer`` query heads over
    ``num_key_value_heads``), rotary positions by kind
    (``rope_parameters``: a sub-width of the head under
    ``partial_rotary_factor``, YaRN's frequencies under ``rope_type``
    ``yarn``), a sigmoid gate a head on the attended values
    (``gating_types``: ``per_head``), and a feed-forward that is a gated
    SiLU MLP on the ``dense`` layers of ``mlp_layer_types`` and an expert
    layer under a sigmoid top-k router with one shared expert on the
    others; no bias, an untied head.  ``held_experts`` (the
    deployment's) names the routed experts this chip holds; the router's
    width is then ``published.num_experts`` and ``num_experts`` the
    number held."""
    layers = m["num_hidden_layers"]
    kinds, mlps = m["layer_types"][:layers], m["mlp_layer_types"][:layers]
    gates = m.get("gating_types", ["per_head"] * layers)[:layers]
    heads = m.get("num_attention_heads_per_layer",
                  [m["num_attention_heads"]] * layers)[:layers]
    shared = m.get("shared_expert_intermediate_size", 0)
    dense = [i for i, k in enumerate(m["mlp_layer_types"]) if k == "dense"]
    for key, got, built in (
            ("attention_bias", m.get("attention_bias", False), (False,)),
            ("tie_word_embeddings", m.get("tie_word_embeddings", False),
             (False,)),
            ("moe_apply_router_weight_on_input",
             m.get("moe_apply_router_weight_on_input", False), (False,)),
            ("moe_router_logit_softcapping",
             m.get("moe_router_logit_softcapping", 0), (0, None)),
            ("decoder_sparse_step", m.get("decoder_sparse_step", 1), (1,)),
            ("gating", m.get("gating", "per-head"), ("per-head",)),
            ("gating_types", sorted(set(gates)), (["per_head"],)),
            ("layer_types", sorted(set(kinds) - {"full_attention",
                                                 "sliding_attention"}), ([],)),
            ("mlp_layer_types", sorted(set(mlps) - {"dense", "sparse"}),
             ([],)),
            ("mlp_only_layers", sorted(m.get("mlp_only_layers", dense)),
             (dense,)),
            ("shared_expert_intermediate_size",
             shared % m["moe_intermediate_size"], (0,))):
        if got not in built:
            raise ValueError(
                f"laguna builder: {key}={got!r} is not built yet "
                f"(only {built[0]!r})")
    if len(kinds) < layers or len(mlps) < layers or len(heads) < layers:
        raise ValueError(
            f"laguna builder: layer_types, mlp_layer_types and "
            f"num_attention_heads_per_layer must name all {layers} layers")
    d, eps, hd = m["hidden_size"], m["rms_norm_eps"], m["head_dim"]
    held = m.get("held_experts")
    routed = m["num_experts"]
    if held is not None:
        routed = m.get("published", {}).get("num_experts", routed)
        if len(held) != m["num_experts"]:
            raise ValueError(
                f"laguna builder: held_experts names {len(held)} experts, "
                f"num_experts (the number held) is {m['num_experts']}")

    def rope(kind):
        r = m["rope_parameters"][kind]
        turn = {"theta": float(r["rope_theta"])}
        if r.get("partial_rotary_factor", 1) != 1:
            turn["rotary_dim"] = int(round(hd * r["partial_rotary_factor"]))
        if r.get("rope_type", "default") != "default":
            turn["scaling"] = r        # the type's own keys (YaRN's)
        return turn

    x = ff.word_embedding(tok, m["vocab_size"], d, name="embed",
                          dtype=jnp.dtype(ff.config.compute_dtype))
    for i in range(layers):
        a = ff.rms_norm(x, eps=eps, name=f"blk{i}_ln1")
        a = ff.multihead_attention(
            a, heads[i], causal=True, use_bias=False,
            num_kv_heads=m["num_key_value_heads"], head_dim=hd,
            gate="per_head", rope=rope(kinds[i]),
            window=m["sliding_window"] if kinds[i] == "sliding_attention"
            else None, name=f"blk{i}_attn")
        x = ff.add(x, a, name=f"blk{i}_res1")
        h = ff.rms_norm(x, eps=eps, name=f"blk{i}_ln2")
        if mlps[i] == "dense":
            g = ff.dense(h, m["intermediate_size"], activation="silu",
                         use_bias=False, name=f"blk{i}_mlp_gate")
            up = ff.dense(h, m["intermediate_size"], use_bias=False,
                          name=f"blk{i}_mlp_up")
            h = ff.dense(ff.multiply(g, up, name=f"blk{i}_mlp_act"), d,
                         use_bias=False, name=f"blk{i}_mlp_down")
        else:
            h = ff.moe(
                h, routed, m["moe_intermediate_size"],
                top_k=m["num_experts_per_tok"], dispatch="sorted",
                router="sigmoid", gated=True, activation="silu",
                shared_experts=shared // m["moe_intermediate_size"],
                norm_topk_prob=m["norm_topk_prob"],
                routed_scale=m["moe_routed_scaling_factor"],
                held_experts=held, name=f"blk{i}_moe")
        x = ff.add(x, h, name=f"blk{i}_res2")
    x = ff.rms_norm(x, eps=eps, name="ln_f")
    return ff.dense(x, m["vocab_size"], use_bias=False, name="lm_head")


def _lfm2_moe_lm(ff: FFModel, tok, m: Dict[str, Any]):
    """The LFM2-MoE block family (``model_type`` ``lfm2_moe``): RMSNorm
    pre-norm blocks whose mixer goes by layer (``layer_types``: ``conv``
    the gated short convolution of ``conv_L_cache`` taps,
    ``ops/short_conv.py``; ``full_attention`` grouped-query attention
    with an RMSNorm over each head of q and k and rotary positions on
    the whole head, the head's width ``hidden_size /
    num_attention_heads``), ``num_dense_layers`` leading gated SiLU MLPs
    of ``intermediate_size``, then expert layers under a sigmoid top-k
    router (a selection bias under ``use_expert_bias``, the chosen
    scores divided by their sum + 1e-6 under ``norm_topk_prob``) with no
    shared expert; no bias, one more RMSNorm, and a head tied to the
    token table (``tie_embedding``, the family's default: one leaf)."""
    layers = m["num_hidden_layers"]
    kinds = list(m["layer_types"][:layers])
    rope = m.get("rope_parameters") or {}
    for key, got, built in (
            ("conv_bias", m.get("conv_bias", False), (False,)),
            ("tie_embedding", m.get("tie_embedding", True), (True,)),
            ("tie_word_embeddings", m.get("tie_word_embeddings", True),
             (True,)),
            ("rope_parameters.rope_type", rope.get("rope_type", "default"),
             ("default",)),
            ("layer_types", sorted(set(kinds) - {"conv", "full_attention"}),
             ([],))):
        if got not in built:
            raise ValueError(
                f"lfm2_moe builder: {key}={got!r} is not built yet "
                f"(only {built[0]!r})")
    if len(kinds) < layers:
        raise ValueError(
            f"lfm2_moe builder: layer_types must name all {layers} layers")
    d, eps, heads = m["hidden_size"], m["norm_eps"], m["num_attention_heads"]
    if d % heads:
        raise ValueError(
            f"lfm2_moe builder: hidden_size {d} is not num_attention_heads "
            f"{heads} heads of one width")
    x = ff.word_embedding(tok, m["vocab_size"], d, name="embed",
                          dtype=jnp.dtype(ff.config.compute_dtype))
    for i in range(layers):
        a = ff.rms_norm(x, eps=eps, name=f"blk{i}_ln1")
        if kinds[i] == "conv":
            a = ff.short_conv(a, m["conv_L_cache"], name=f"blk{i}_conv")
        else:
            a = ff.multihead_attention(
                a, heads, causal=True, use_bias=False,
                num_kv_heads=m["num_key_value_heads"], head_dim=d // heads,
                qk_norm=eps, rope={"theta": float(rope["rope_theta"])},
                name=f"blk{i}_attn")
        x = ff.add(x, a, name=f"blk{i}_res1")
        h = ff.rms_norm(x, eps=eps, name=f"blk{i}_ln2")
        if i < m["num_dense_layers"]:
            g = ff.dense(h, m["intermediate_size"], activation="silu",
                         use_bias=False, name=f"blk{i}_mlp_gate")
            up = ff.dense(h, m["intermediate_size"], use_bias=False,
                          name=f"blk{i}_mlp_up")
            h = ff.dense(ff.multiply(g, up, name=f"blk{i}_mlp_act"), d,
                         use_bias=False, name=f"blk{i}_mlp_down")
        else:
            h = ff.moe(
                h, m["num_experts"], m["moe_intermediate_size"],
                top_k=m["num_experts_per_tok"], dispatch="sorted",
                router="sigmoid", gated=True, activation="silu",
                selection_bias=bool(m.get("use_expert_bias", False)),
                norm_topk_prob=m["norm_topk_prob"], norm_topk_eps=1e-6,
                routed_scale=m["routed_scaling_factor"],
                name=f"blk{i}_moe")
        x = ff.add(x, h, name=f"blk{i}_res2")
    x = ff.rms_norm(x, eps=eps, name="ln_f")
    return ff.dense(x, m["vocab_size"], use_bias=False, name="lm_head",
                    tied_to="embed")


_BLOCKS = {"gpt2": _gpt2_lm, "deepseek_v3": _deepseek_v3_lm,
           "xing4_0": _deepseek_v3_lm, "axk2": _deepseek_v3_lm,
           "solar_open2": _solar_open2_lm,
           "KeyeVL2": _keye_vl2_lm, "laguna": _laguna_lm,
           "lfm2_moe": _lfm2_moe_lm}

#: The DeepSeek-V3 family at unit-test size (tests, chip_smoke.py, the
#: audit catalog): every mechanism of the block, no published width.
DEEPSEEK_V3_TINY: Dict[str, Any] = {
    "model_type": "deepseek_v3", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 128, "num_attention_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 1e6, "rms_norm_eps": 1e-6, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "moe_intermediate_size": 32, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "routed_scaling_factor": 2.448,
}


#: The same family at the smallest widths every serving kernel takes on
#: the chip (whole 128-lane tiles in the expert products): chip_smoke.py.
DEEPSEEK_V3_SMOKE: Dict[str, Any] = {
    **DEEPSEEK_V3_TINY, "vocab_size": 2048, "hidden_size": 256,
    "intermediate_size": 512, "moe_intermediate_size": 128,
    "kv_lora_rank": 128, "qk_nope_head_dim": 64, "qk_rope_head_dim": 32,
    "v_head_dim": 64,
}

#: The Solar-Open2 family at unit-test size: five layers, so that a
#: whole period (grouped-query, delta, delta, delta) and a second
#: grouped-query layer occur; widths no kernel takes.
SOLAR_OPEN2_TINY: Dict[str, Any] = {
    "model_type": "solar_open2", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 5, "gqa_layers": [0, 4], "gqa_interval": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "use_gqa_gate": True, "use_rope": False,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "first_k_dense_replace": 0, "moe_intermediate_size": 32,
    "n_routed_experts": 16, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
}

#: The same family at the smallest widths every serving kernel takes on
#: the chip (heads of one whole lane tile): chip_smoke.py.
SOLAR_OPEN2_SMOKE: Dict[str, Any] = {
    **SOLAR_OPEN2_TINY, "vocab_size": 2048, "hidden_size": 256,
    "head_dim": 128, "moe_intermediate_size": 128,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 2, "num_kv_heads": None},
}

#: The Xing4.0 family at unit-test size: the DeepSeek-V3 block with a
#: compressed query and YaRN positions round a residual of four
#: streams, one dense and two expert layers.
XING4_TINY: Dict[str, Any] = {
    **DEEPSEEK_V3_TINY, "model_type": "xing4_0", "q_lora_rank": 24,
    "rope_theta": 10000.0, "routed_scaling_factor": 2.0,
    "rope_scaling": {"type": "yarn", "factor": 8, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32},
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "num_nextn_predict_layers": 1,
}

#: The same family at the smallest widths every serving kernel takes on
#: the chip: chip_smoke.py.
XING4_SMOKE: Dict[str, Any] = {
    **XING4_TINY, "vocab_size": 2048, "hidden_size": 256,
    "intermediate_size": 512, "moe_intermediate_size": 128,
    "q_lora_rank": 128, "kv_lora_rank": 128, "qk_nope_head_dim": 64,
    "qk_rope_head_dim": 32, "v_head_dim": 64,
}

#: The Keye-VL-2.0 language model at unit-test size: widths no kernel
#: takes, a ``topk`` smaller than the tests' sequences (so that the
#: selector selects) and a chunk smaller still (so that the chunked
#: prefill runs its dense head, two key widths and several chunks).
KEYE_VL2_TINY: Dict[str, Any] = {
    "model_type": "KeyeVL2", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "attention_bias": False,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                  "q_chunk_size": 8, "topk": 16},
    "num_experts": 8, "num_local_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "use_sliding_window": False, "tie_word_embeddings": False,
}

#: The same family at the smallest widths every serving kernel takes on
#: the chip (heads of one whole lane tile, expert products of whole
#: tiles): chip_smoke.py.
KEYE_VL2_SMOKE: Dict[str, Any] = {
    **KEYE_VL2_TINY, "vocab_size": 2048, "hidden_size": 256,
    "head_dim": 128, "moe_intermediate_size": 128,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 128,
                  "q_chunk_size": 128, "topk": 256},
}

#: The Laguna family at unit-test size: five layers (dense then sparse
#: feed-forwards; full, window, window, window, full attention with 4
#: and 6 query heads over 2), a window far smaller than the tests'
#: sequences (so that a ring wraps more than twice), both rotary
#: settings; widths no kernel takes.
LAGUNA_TINY: Dict[str, Any] = {
    "model_type": "laguna", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "attention_bias": False, "rms_norm_eps": 1e-6, "num_experts": 16,
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 16,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 32, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.2079441541679836,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "gating_types": ["per_head"] * 5,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
}

#: The same family at the smallest widths every serving kernel takes on
#: the chip (heads of one whole lane tile, groups of 9 and 6 query heads
#: a cached head as published, a window of one ``flash_decode`` chunk,
#: expert products of whole tiles): chip_smoke.py.
LAGUNA_SMOKE: Dict[str, Any] = {
    **LAGUNA_TINY, "vocab_size": 2048, "hidden_size": 256,
    "intermediate_size": 512, "head_dim": 128, "num_attention_heads": 12,
    "num_attention_heads_per_layer": [12, 18, 18, 18, 12],
    "moe_intermediate_size": 128, "shared_expert_intermediate_size": 128,
    "sliding_window": 512,
}

#: The A.X-K2 family at unit-test size: the DeepSeek-V3 block with a
#: token selector over the latent cache (a ``topk`` smaller than the
#: tests' sequences, its rotary part half its head), a gate a head,
#: gated norms, four groups of four experts of which two stay, one
#: dense and two expert layers.
AXK2_TINY: Dict[str, Any] = {
    **{k: v for k, v in DEEPSEEK_V3_TINY.items() if k != "rope_theta"},
    "model_type": "axk2", "q_lora_rank": 24, "routed_scaling_factor": 2.5,
    "n_routed_experts": 16, "n_group": 4, "topk_group": 2,
    "num_experts_per_tok": 3,
    "rope_parameters": {"rope_type": "yarn", "rope_theta": 10000.0,
                        "factor": 2, "beta_fast": 32, "beta_slow": 1,
                        "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 32},
    "attention_output_gate": True, "attn_gate_fused": True,
    "gated_norm": True, "gated_norm_rank": 4,
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": 16,
    "num_nextn_predict_layers": 0,
}

#: The same family at the smallest widths every serving kernel takes on
#: the chip: chip_smoke.py.
AXK2_SMOKE: Dict[str, Any] = {
    **AXK2_TINY, "vocab_size": 2048, "hidden_size": 256,
    "intermediate_size": 512, "moe_intermediate_size": 128,
    "q_lora_rank": 128, "kv_lora_rank": 128, "qk_nope_head_dim": 64,
    "qk_rope_head_dim": 32, "v_head_dim": 64, "gated_norm_rank": 16,
    "index_head_dim": 64, "index_topk": 512,
}

#: The LFM2-MoE family at unit-test size: six layers (two dense then
#: four expert feed-forwards; conv, conv, attention, conv, conv,
#: attention, so that a convolution follows an attention layer and an
#: attention layer an expert layer), 4 query heads over 2 cached ones, a
#: selection bias; widths no kernel takes.
LFM2_TINY: Dict[str, Any] = {
    "model_type": "lfm2_moe", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 6, "num_dense_layers": 2,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "full_attention"],
    "conv_L_cache": 3, "conv_bias": False, "num_attention_heads": 4,
    "num_key_value_heads": 2, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True,
}

#: The same family at the smallest widths every serving kernel takes on
#: the chip (heads of 64 in groups of four as published, expert products
#: of whole tiles): tests/test_chip_compile.py.
LFM2_SMOKE: Dict[str, Any] = {
    **LFM2_TINY, "vocab_size": 2048, "hidden_size": 512,
    "intermediate_size": 1024, "num_attention_heads": 8,
    "num_key_value_heads": 2, "moe_intermediate_size": 128,
}

PRESETS = {"axk2-tiny": AXK2_TINY,
           "axk2-smoke": AXK2_SMOKE,
           "lfm2-tiny": LFM2_TINY,
           "lfm2-smoke": LFM2_SMOKE,
           "laguna-tiny": LAGUNA_TINY,
           "laguna-smoke": LAGUNA_SMOKE,
           "keye-vl2-tiny": KEYE_VL2_TINY,
           "keye-vl2-smoke": KEYE_VL2_SMOKE,
           "deepseek-v3-tiny": DEEPSEEK_V3_TINY,
           "deepseek-v3-smoke": DEEPSEEK_V3_SMOKE,
           "xing4-tiny": XING4_TINY,
           "xing4-smoke": XING4_SMOKE,
           "solar-open2-tiny": SOLAR_OPEN2_TINY,
           "solar-open2-smoke": SOLAR_OPEN2_SMOKE}


def load_model_config(name_or_path: str) -> Dict[str, Any]:
    """A preset by name, else the JSON file at the path."""
    if name_or_path in PRESETS:
        return dict(PRESETS[name_or_path])
    import json

    with open(name_or_path) as f:
        return json.load(f)


def transformer_strategy(
    num_devices: int,
    num_layers: int,
    dp: int = 1,
    sp: int = 1,
    tp: int = 1,
    moe: bool = False,
) -> StrategyStore:
    """dp × sp (ring/context) × tp (Megatron) hybrid; attention and
    token-level ops get (n=dp, s=sp); MLP and lm_head get (n=dp, c=tp).
    With ``moe``, each block's MoE op gets (n=dp, c=tp) — the 'c'
    degree shards EXPERTS (expert parallelism over ICI)."""
    assert dp * sp <= num_devices and dp * tp <= num_devices
    store = StrategyStore(num_devices)
    seq_pc = ParallelConfig(n=dp, s=sp)
    tp_pc = ParallelConfig(n=dp, c=tp)
    store.set("embed", seq_pc)
    store.set("pos", seq_pc)
    for i in range(num_layers):
        store.set(f"blk{i}_ln1", seq_pc)
        store.set(f"blk{i}_attn", seq_pc)
        store.set(f"blk{i}_res1", seq_pc)
        store.set(f"blk{i}_ln2", seq_pc)
        if moe:
            store.set(f"blk{i}_moe", tp_pc)
        else:
            store.set(f"blk{i}_mlp_up", tp_pc)
            store.set(f"blk{i}_mlp_down", seq_pc)
        store.set(f"blk{i}_res2", seq_pc)
    store.set("ln_f", seq_pc)
    store.set("lm_head", tp_pc)
    store.set("softmax", seq_pc)
    return store
