"""CPU rehearsals of the A.X-K2 family's cell (run by hand with the rest:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``): the tiny mix
of ``data/config.tiny-axk2.json`` (three layers, ``index_topk`` 16 under
prompts of 24-100, 8 of 16 experts held in 4 groups) under
``data/traffic.tiny-closed-axk2.json`` through ``run.py`` in a copy of
the benchmark, the lower-precision control, a broken timed path, the
selection switched off in the program's place, the cost functions by
hand, and what the configuration file states against the catalog row and
the published parameter counts.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common, manifest  # noqa: E402
from benchmark.tests import sandbox  # noqa: E402

CELL = "tiny.axk2.serve"
REAL = "axk2.serve.closed8.p8k-31k"

#: The catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: ``A.X-K2``), as published.
CATALOG = {
    "attention_bias": False, "attention_output_gate": True, "attn_gate_fused": True,
    "first_k_dense_replace": 1, "gated_norm": True, "gated_norm_rank": 16, "hidden_act": "silu",
    "hidden_size": 7168, "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048,
    "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "axk2", "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 64, "num_nextn_predict_layers": 0, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_parameters": {"rope_type": "yarn", "rope_theta": 1000000, "factor": 2, "beta_fast": 32,
                        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 131072},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840,
}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``sandbox.make``'s copy (which drops every tiny file of
    ``data/`` beside the real ones) with this family's tiny cell entered
    wherever the real cell is."""
    root = sandbox.make(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "tiny-axk2", "source": "rehearsal", "reduced": [],
                             "file": "benchmark/configs/tiny-axk2.json", "why": "rehearsal"})
    bench["workloads"].append({"name": CELL, "config": "tiny-axk2",
                               "traffic": "tiny-closed-axk2", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root


def _tiny(kind, name):
    return json.load(open(os.path.join(HERE, "data", f"{kind}.{name}.json")))


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_axk2_cell_is_correct(copy, trace):
    p = sandbox.run_cell(copy, CELL, seed=3300000041, trace=trace)
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-3000:]
    line = sandbox.last_line(p)
    assert line["correct"] is True and line["failed"] == 0, p.stdout[-2000:]
    assert "[check] served_logit_gap" in p.stdout
    if trace:
        # What the CPU can read: the counters (never a device metric).
        # 8 experts held: the file's scale is the real cell's, 100 / 16.
        assert 0 < line["metrics"]["moe_experts_touched_pct.axk2"]["value"] <= 100 / 16 * 8
        assert not [m for m in line["metrics"] if m.startswith("kernel_roofline.")]
        assert not [m for m in line["metrics"] if "share_pct.axk2" in m]
        assert line["metrics"]["window_compiles.serve"]["value"] == 0
        # topk 16 of max_seq 128 a slot a step.
        assert line["metrics"]["serve_kv_fetch_pct"]["value"] == 12.5
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_broken_timed_path_is_incorrect(copy):
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, os.path.join(HERE, "drive_broken.py"), copy, CELL,
                        "altered_token"], cwd=copy, env=e, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    assert sandbox.last_line(p)["correct"] is False, p.stdout[-2000:]
    assert " OUT" in p.stdout


def test_selection_switched_off_in_the_program_is_seen_by_the_judged_number(copy):
    """``drive_unselected.py`` raises the one selector class's ``topk``
    past any sequence, so the latent layers attend every live position
    where the reference keeps 16 of 24-116: ``served_logit_gap`` falls
    outside the tiny mix's limit.  (Whether it does at the real cell's
    size and precision is a chip reading: PERF.md.)"""
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, os.path.join(HERE, "drive_unselected.py"), copy, CELL,
                        "--cpu"], cwd=copy, env=e, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = sandbox.last_line(p)
    assert line["failed"] == 0
    assert line["correct"] is False, p.stdout[-2000:]
    assert "served_logit_gap" in [l for l in p.stdout.splitlines() if " OUT" in l][0]


def test_lower_precision_control_fails_serving():
    """The reference's own greedy tokens stand for a sound server; the
    reference with fp8 products lies outside the tiny mix's limit."""
    import jax.numpy as jnp

    from benchmark.families import axk2 as fam

    cfg, tr = _tiny("config", "tiny-axk2"), _tiny("traffic", "tiny-closed-axk2")
    prompt = np.random.default_rng(0).integers(0, cfg["vocab_size"], size=40, dtype=np.int32)
    toks = []
    for _ in range(12):
        full = np.concatenate([prompt, np.asarray(toks, np.int32)])
        full = np.pad(full, (0, 64 - len(full)))          # one program for every length
        toks.append(int(jnp.argmax(fam.reference.logits_fn(cfg, 5, full)[len(prompt) + len(toks) - 1])))
    sample = [{"prompt": prompt, "tokens": toks}]
    sound = fam.reference.served_gaps(cfg, 5, tr["max_seq"], sample)
    ctl = fam.reference.served_gaps(cfg, 5, tr["max_seq"], sample, quant=True)
    assert sound["widest_gap"] <= tr["limits"]["served_logit_gap"]
    assert 0 <= sound["selection_flip_share"] <= 1
    assert 0 <= sound["position_replaced_share"] <= 1
    assert ctl["widest_gap"] > tr["limits"]["served_logit_gap"], ctl


def test_reference_draws_any_held_expert_alone():
    from benchmark import weights
    from benchmark.references import axk2 as ref

    cfg = _tiny("config", "tiny-axk2")
    get = ref.Leaves(cfg, 7)
    whole = np.asarray(get("blk1_moe/w_gate"))
    assert whole.shape == (8, 64, 32) and ref.router_width(cfg) == 16
    for e in (0, 5):
        assert np.array_equal(np.asarray(get.expert("blk1_moe/w_gate", e)), whole[e])
    spec = ref.leaf_spec(cfg)
    assert spec["blk1_moe/gate"][0] == (64, 16) and "blk0_moe/gate" not in spec
    assert np.array_equal(
        whole, weights.leaf_values(7, "blk1_moe/w_gate", *spec["blk1_moe/w_gate"]))
    both = dict(cfg, assumed={"router_dtype": "float32", "param_dtype": "bfloat16"})
    assert ref.stored_dtype(both, "blk1_attn/idx_ww") == "float32"
    assert ref.stored_dtype(both, "blk1_moe/e_bias") == "float32"
    assert ref.stored_dtype(both, "blk1_ln1/w_up") == "bfloat16"


def test_flops_and_bytes_against_hand_counts():
    from benchmark.costs import axk2 as costs

    cfg = common.load_json(REPO, "benchmark", "configs", "a.x-k2-688b-l5e16.json")
    tr = common.load_json(REPO, "benchmark", "traffic", "closed8.p8k-31k.axk2.json")
    events = [
        {"ev": "prefill", "bucket": 8704, "length": 8300, "experts_touched": 16.0},
        {"ev": "decode_superstep", "k": 8, "slots": [0, 1], "experts_touched": 3.0,
         "kv_rows_fetched": 8 * 8 * 2048, "idx_rows_fetched": 8 * 8 * 32768},
    ]
    rctx = {"config": cfg, "traffic": tr, "events": events, "result": {"backlog": []}}
    # The kernel takes the leading 2048 rows of the bucket, five layers,
    # 64 heads at q.k width 192 and v width 128.
    f, b = costs.kernel_cost("flash_fwd_uneven", rctx, 5)
    assert f == 5 * 64 * 2048 * 2048 / 2 * 2 * 320
    assert b == 5 * 64 * 2048 * 2 * 320 * 2
    # Four expert layers, a sixteenth of the assignments on held experts:
    # 8 slots x 8 a step for 8 steps and the bucket's 8704 x 8; an
    # expert's matrices 3 x 7168 x 2048.
    f, b = costs.kernel_cost("grouped_matmul", rctx, 0)
    assigned = (8 * 8 * 8 + 8704 * 8) / 16
    assert f == 4 * assigned * 6 * 7168 * 2048
    touched = 8 * 3 + 16
    assert b == 4 * (touched * 3 * 7168 * 2048 + assigned * 2 * (7168 + 2048)) * 2
    with pytest.raises(KeyError):
        costs.kernel_cost("mla_decode", rctx, 1)


def test_configuration_file_carries_the_catalog_rows_keys_and_the_published_counts():
    from benchmark import workload_gen
    from benchmark.references import axk2 as ref

    bench = manifest.load(REPO)
    entry = manifest.entry(bench["configs"], "a.x-k2-688b-l5e16", "config")
    cfg = common.load_json(REPO, entry["file"])
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert {k: cfg[k] for k in CATALOG if k not in reduced} == \
        {k: v for k, v in CATALOG.items() if k not in reduced}
    assert cfg["published"] == {k: CATALOG[k] for k in reduced}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 16, 20480)
    assert cfg["held_experts"] == list(range(16))
    assert entry["source"] == cfg["source"] and cfg["family"] == "axk2"
    assert "fifteen pipeline stages" in cfg["deployment"] and "sixteen chips" in cfg["deployment"]
    assert set(cfg["unused"]) >= {"attn_gate_fused", "max_position_embeddings",
                                  "num_key_value_heads"}
    for key in ("gated_norm", "gated_norm_source", "rope_interleave", "yarn", "indexer",
                "indexer_precision", "attention_output_gate", "router", "param_dtype",
                "cache_dtype", "router_dtype", "float32", "init_std", "gated_norm_up_std",
                "init", "decoding", "cache", "parameter_count", "q_norm_gain",
                "q_norm_gain_why"):
        assert key in cfg["assumed"], key
    # Layer 0's queries alone are drawn peaked: what lets a served logit
    # depend on the positions the selector kept (PERF.md section 6 PR 48).
    spec = ref.leaf_spec(cfg)
    gain = cfg["assumed"]["q_norm_gain"]["0"]
    assert [spec[f"blk{i}_attn/q_norm"][2] for i in range(5)] == [gain, 1, 1, 1, 1]
    cell = manifest.entry(bench["workloads"], REAL, "workload")
    assert cell["chips"] == 1 and cell["config"] == entry["name"]
    assert len(bench["workloads"]) >= 10
    tr = common.load_json(REPO, "benchmark", "traffic", cell["traffic"] + ".json")
    keye = common.load_json(REPO, "benchmark", "traffic", "closed8.p8k-31k.json")
    # keye2.serve's two laws and its shapes, letter for letter.
    for key in ("kind", "flags", "slots", "max_seq", "buckets", "decode_steps", "decode_kernel",
                "prompt_len", "budget", "pairing_seed", "check_requests", "trace_seconds",
                "end_to_end"):
        assert tr[key] == keye[key], key
    # Between the chip's readings (``limits_why``): sound at most 0.0704,
    # the fp8 control at least 0.748, selection off at least 3.05.
    assert 0.0704 < tr["limits"]["served_logit_gap"] == 0.2 < 0.748 and tr["limits_why"]
    assert tr["requests_per_second"] == 0.626
    # Every prompt is at least four times index_topk: every decode step selects.
    assert tr["prompt_len"]["lo"] >= 4 * cfg["index_topk"]
    # 4.272 G = 8.54 GB held, 689.03 B / 32.54 B published, from the leaf recipe.
    held = ref.parameter_counts(cfg)["total"]
    assert round(held / 1e9, 3) == 4.272 and round(held * 2 / 1e9, 2) == 8.54
    whole = ref.parameter_counts(
        {k: v for k, v in dict(cfg, **cfg["published"]).items() if k != "held_experts"})
    assert round(whole["total"] / 1e9, 2) == 689.03 and round(whole["active"] / 1e9, 2) == 32.54
    # The two caches: 7,040 B a token, 1.85 GB for 8 slots; 10.39 GB resident.
    token = 5 * (576 + 128) * 2
    assert token == 7040 and round(8 * tr["max_seq"] * token / 1e9, 2) == 1.85
    assert round((held * 2 + 8 * tr["max_seq"] * token) / 1e9, 2) == 10.39
    p = tr["prompt_len"]
    lens = workload_gen.zipf_quantiles(1000, p["alpha"], p["lo"], p["hi"])
    assert 8100 < np.median(lens) < 8300 and 11000 < lens.mean() < 12500
