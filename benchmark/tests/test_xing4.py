"""CPU rehearsals of the Xing4.0 family's cell (run by hand with the
rest: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``): the
tiny mix of ``data/config.tiny-xing.json`` (four hyper-connected streams
round one dense and two expert layers) under
``data/traffic.tiny-closed-xing.json`` through ``run.py`` in a copy of
the benchmark, the lower-precision control, a broken timed path, the
shared kernels' costs at this configuration's widths, and what the
configuration file states against the catalog row and the published
parameter counts.
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

CELL = "tiny.xing.serve"
REAL = "xing4.serve.closed96.p256-2k"

#: The catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: ``Xing4.0-29B-A4B``), as published.
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu",
    "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
    "max_position_embeddings": 262144, "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``sandbox.make``'s copy (which drops every tiny file of
    ``data/`` beside the real ones) with this family's tiny cell entered
    wherever the real cell is."""
    root = sandbox.make(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "tiny-xing", "source": "rehearsal", "reduced": [],
                             "file": "benchmark/configs/tiny-xing.json", "why": "rehearsal"})
    bench["workloads"].append({"name": CELL, "config": "tiny-xing",
                               "traffic": "tiny-closed-xing", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root


def _tiny(kind, name):
    return json.load(open(os.path.join(HERE, "data", f"{kind}.{name}.json")))


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_xing_cell_is_correct(copy, trace):
    p = sandbox.run_cell(copy, CELL, seed=3500000023, trace=trace)
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-3000:]
    line = sandbox.last_line(p)
    assert line["correct"] is True and line["failed"] == 0, p.stdout[-2000:]
    assert "[check] served_logit_gap" in p.stdout
    assert "largest defect of an H_res after its 20 rounds" in p.stdout
    if trace:
        # What the CPU can read: the counters (never a device metric).
        # 8 experts: the file's scale is the real cell's, 100 / 64.
        assert 0 < line["metrics"]["moe_experts_touched_pct.xing"]["value"] <= 1.5625 * 8
        assert 0 < line["metrics"]["hc_defect.xing"]["value"] < 0.5
        assert not [m for m in line["metrics"]
                    if m.startswith("kernel_roofline.") or m.endswith("_share_pct.xing")]
        assert line["metrics"]["window_compiles.serve"]["value"] == 0
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


def test_lower_precision_control_fails_serving():
    """The reference's own greedy tokens stand for a sound server; the
    reference with fp8 products lies outside the tiny mix's limit."""
    import jax.numpy as jnp

    from benchmark.families import xing4 as fam

    cfg, tr = _tiny("config", "tiny-xing"), _tiny("traffic", "tiny-closed-xing")
    prompt = np.random.default_rng(0).integers(0, cfg["vocab_size"], size=24, dtype=np.int32)
    toks = []
    for _ in range(12):
        full = np.concatenate([prompt, np.asarray(toks, np.int32)])
        toks.append(int(jnp.argmax(fam.reference.logits_fn(cfg, 5, full)[-1])))
    sample = [{"prompt": prompt, "tokens": toks}]
    sound = fam.reference.served_gaps(cfg, 5, tr["max_seq"], sample)
    ctl = fam.reference.served_gaps(cfg, 5, tr["max_seq"], sample, quant=True)
    assert sound["widest_gap"] <= tr["limits"]["served_logit_gap"]
    assert 0 <= sound["selection_flip_share"] <= 1 and 0 < sound["hc_defect"] < 0.5
    assert ctl["widest_gap"] > tr["limits"]["served_logit_gap"], ctl


def test_reference_draws_any_expert_alone_and_the_hyper_connections_in_float32():
    from benchmark import weights
    from benchmark.references import xing4 as ref

    cfg = _tiny("config", "tiny-xing")
    get = ref.Leaves(cfg, 7)
    whole = np.asarray(get("blk1_moe/w_gate"))
    assert whole.shape == (8, 64, 32)
    for e in (0, 5):
        assert np.array_equal(np.asarray(get.expert("blk1_moe/w_gate", e)), whole[e])
    spec = ref.leaf_spec(cfg)
    assert np.array_equal(
        whole, weights.leaf_values(7, "blk1_moe/w_gate", *spec["blk1_moe/w_gate"]))
    # b_res is drawn round a soft identity: 2 on the diagonal, 0 off it.
    b_res = np.asarray(get("blk2_hc1_post/b_res"))
    assert np.abs(b_res - 2.0 * np.eye(4)).max() <= 0.5 and np.abs(b_res - 2.0 * np.eye(4)).max() > 0.2
    assert np.array_equal(b_res, weights.leaf_values(
        7, "blk2_hc1_post/b_res", *spec["blk2_hc1_post/b_res"]))
    alpha = np.asarray(get("blk0_hc2_post/alpha"))
    assert alpha.shape == (2,) and (alpha >= 0.5).all() and (alpha < 1.5).all()
    both = dict(cfg, assumed=dict(cfg["assumed"], param_dtype="bfloat16"))
    assert ref.stored_dtype(both, "blk1_hc2_post/phi_res") == "float32"
    assert ref.stored_dtype(both, "blk1_moe/gate") == "float32"
    assert ref.stored_dtype(both, "blk1_attn/wq_a") == "bfloat16"


def test_shared_kernels_costs_at_this_configurations_widths():
    """``costs/deepseek_v3.py`` reads its shapes from the configuration:
    the same three kernels, 64 experts of 1024 at top-4, 96 slots."""
    from benchmark.costs import deepseek_v3 as costs
    from benchmark.families import xing4 as fam

    assert fam.COSTS == "deepseek_v3"
    cfg = common.load_json(REPO, "benchmark", "configs", "xing4.0-29b-a4b-l7.json")
    tr = common.load_json(REPO, "benchmark", "traffic", "closed96.p256-2k.json")
    backlog = [{"id": 0, "prompt": [0] * 300, "max_new_tokens": 100},
               {"id": 1, "prompt": [0] * 2048, "max_new_tokens": 100}]
    events = [
        {"ev": "prefill", "bucket": 2048, "experts_touched": 64.0, "hc_defect": 1e-6},
        {"ev": "decode_superstep", "k": 8, "slots": [0, 1], "experts_touched": 60.0,
         "hc_defect": 2e-6},
    ]
    rctx = {"config": cfg, "traffic": tr, "events": events, "result": {"backlog": backlog}}
    cols = 7 * (8 * 301 + 28 + 8 * 2049 + 28 + 94 * 36)
    assert costs.live_columns(rctx) == cols
    f, b = costs.kernel_cost("mla_decode", rctx, 56)
    assert f == 2 * 32 * (576 + 512) * cols
    assert b == 1152 * cols + 56 * 96 * 32 * 1088 * 2
    # Five expert layers; a 96-slot step routes 384 assignments, the
    # prefill 8192; an expert's three matrices are 3 x 3584 x 1024.
    f, b = costs.kernel_cost("grouped_matmul", rctx, 0)
    assigned = 8 * 384 + 2048 * 4
    assert f == 5 * assigned * 6 * 3584 * 1024
    touched = 8 * 60 + 64
    assert b == 5 * (touched * 3 * 3584 * 1024 + assigned * 2 * (3584 + 1024)) * 2
    f, b = costs.kernel_cost("flash_fwd_uneven", rctx, 7)
    assert f == 7 * 32 * 2048 * 2048 / 2 * 2 * 320
    # Every expert of a layer read once: 1.41 GB, 7.05 GB a step.
    assert round(64 * 3 * 3584 * 1024 * 2 / 1e9, 2) == 1.41


def test_configuration_file_carries_the_catalog_rows_keys_and_the_published_counts():
    from benchmark.references import xing4 as ref

    bench = manifest.load(REPO)
    entry = manifest.entry(bench["configs"], "xing4.0-29b-a4b-l7", "config")
    cfg = common.load_json(REPO, entry["file"])
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    # Every key of the catalog row as published, but the depth.
    assert {k: cfg[k] for k in CATALOG if k != "num_hidden_layers"} == \
        {k: v for k, v in CATALOG.items() if k != "num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 7 and cfg["published"] == {"num_hidden_layers": 40}
    assert cfg["family"] == "xing4" and entry["source"] == cfg["source"]
    assert "pipeline stages over six such chips" in cfg["deployment"]
    for key in ("param_dtype", "router_dtype", "hc_dtype", "num_nextn_predict_layers",
                "rope_interleave", "yarn", "hc_streams", "hc_sinkhorn", "hc_alpha",
                "hc_bias_half_width", "hc_res_diagonal", "hc_init", "init_std",
                "e_score_correction_bias", "decoding", "cache"):
        assert key in cfg["assumed"], key
    cell = manifest.entry(bench["workloads"], REAL, "workload")
    assert cell["chips"] == 1 and cell["config"] == entry["name"]
    tr = common.load_json(REPO, "benchmark", "traffic", cell["traffic"] + ".json")
    assert (tr["slots"], tr["max_seq"], tr["decode_steps"], tr["buckets"]) == \
        (96, 4096, 8, [512, 1024, 2048])
    assert tr["prompt_len"] == {"law": "bounded_zipf", "alpha": 1.2, "lo": 256, "hi": 2048}
    assert tr["budget"] == {"law": "bounded_zipf", "alpha": 1.2, "lo": 256, "hi": 1024}
    assert tr["decode_kernel"] is True and tr["flags"] == ["--dtype", "bfloat16"]
    assert (tr["check_requests"], tr["trace_seconds"]) == (3, 10)
    # 29.51 B parameters, 4.40 B active; this chip's cut 4.921 G = 9.84 GB.
    pub = ref.parameter_counts({**cfg, **cfg["published"]})
    assert round(pub["total"] / 1e9, 2) == 29.51 and round(pub["active"] / 1e9, 2) == 4.40
    held = ref.parameter_counts(cfg)["total"]
    assert round(held / 1e9, 3) == 4.921 and round(held * 2 / 1e9, 2) == 9.84
    # The latent cache: 3.17 GB; with the weights 13.0 GB of the chip.
    cache = tr["slots"] * tr["max_seq"] * 576 * 2 * 7
    assert round(cache / 1e9, 2) == 3.17 and round((held * 2 + cache) / 1e9, 1) == 13.0
    # The seven cells, one on four chips.
    assert len(bench["workloads"]) == 7 and len(bench["configs"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
