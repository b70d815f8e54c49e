"""CPU rehearsals of the Keye-VL-2.0 family's cell (run by hand with the
rest: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``): the
tiny mix of ``data/config.tiny-keye.json`` (two layers, ``topk`` 16 under
prompts of 24-100) under ``data/traffic.tiny-closed-keye.json`` through
``run.py`` in a copy of the benchmark, the lower-precision control, a
broken timed path, the selection switched off in the program's place,
the cost functions by hand, and what the configuration file states
against the catalog row and the published parameter counts.
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

CELL = "tiny.keye.serve"
REAL = "keye2.serve.closed8.p8k-31k"

#: The catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: ``Keye-VL-2.0-30B-A3B``), as published.
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``sandbox.make``'s copy (which drops every tiny file of
    ``data/`` beside the real ones) with this family's tiny cell entered
    wherever the real cell is."""
    root = sandbox.make(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "tiny-keye", "source": "rehearsal", "reduced": [],
                             "file": "benchmark/configs/tiny-keye.json", "why": "rehearsal"})
    bench["workloads"].append({"name": CELL, "config": "tiny-keye",
                               "traffic": "tiny-closed-keye", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root


def _tiny(kind, name):
    return json.load(open(os.path.join(HERE, "data", f"{kind}.{name}.json")))


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_keye_cell_is_correct(copy, trace):
    p = sandbox.run_cell(copy, CELL, seed=3300000031, trace=trace)
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-3000:]
    line = sandbox.last_line(p)
    assert line["correct"] is True and line["failed"] == 0, p.stdout[-2000:]
    assert "[check] served_logit_gap" in p.stdout
    if trace:
        # What the CPU can read: the counters (never a device metric).
        # 8 experts: the file's scale is the real cell's, 100 / 128.
        assert 0 < line["metrics"]["moe_experts_touched_pct"]["value"] <= 100 / 128 * 8
        assert not [m for m in line["metrics"] if m.startswith("kernel_roofline.")]
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
    """The program attends every live position where the reference keeps
    16 of 24-116: at this size (float32, limit 0.001) the served tokens'
    logits fall short of the reference's best by more than the limit, so
    ``served_logit_gap`` sees a selector that does not select.  (Whether
    it does at the real cell's size and precision is a chip reading:
    PERF.md.)"""
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

    from benchmark.families import keye_vl2 as fam

    cfg, tr = _tiny("config", "tiny-keye"), _tiny("traffic", "tiny-closed-keye")
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


def test_reference_draws_any_expert_alone():
    from benchmark import weights
    from benchmark.references import keye_vl2 as ref

    cfg = _tiny("config", "tiny-keye")
    get = ref.Leaves(cfg, 7)
    whole = np.asarray(get("blk1_moe/w_gate"))
    assert whole.shape == (8, 64, 32)
    for e in (0, 5):
        assert np.array_equal(np.asarray(get.expert("blk1_moe/w_gate", e)), whole[e])
    spec = ref.leaf_spec(cfg)
    assert np.array_equal(
        whole, weights.leaf_values(7, "blk1_moe/w_gate", *spec["blk1_moe/w_gate"]))
    both = dict(cfg, assumed={"router_dtype": "float32", "param_dtype": "bfloat16"})
    assert ref.stored_dtype(both, "blk1_attn/idx_ww") == "float32"
    assert ref.stored_dtype(both, "blk1_moe/gate") == "float32"
    assert ref.stored_dtype(both, "blk1_attn/idx_wq") == "bfloat16"


def test_flops_and_bytes_against_hand_counts():
    from benchmark.costs import keye_vl2 as costs

    cfg = common.load_json(REPO, "benchmark", "configs", "keye-vl2-30b-a3b-l6.json")
    tr = common.load_json(REPO, "benchmark", "traffic", "closed8.p8k-31k.json")
    events = [
        {"ev": "prefill", "bucket": 8704, "length": 8300, "experts_touched": 128.0},
        {"ev": "decode_superstep", "k": 8, "slots": [0, 1], "experts_touched": 50.0,
         "kv_rows_fetched": 8 * 8 * 2048, "idx_rows_fetched": 8 * 8 * 32768},
    ]
    rctx = {"config": cfg, "traffic": tr, "events": events, "result": {"backlog": []}}
    # The kernel takes the leading 2048 rows of the bucket, six layers.
    f, b = costs.kernel_cost("gqa_prefill", rctx, 6)
    assert f == 6 * 32 * 2048 * 2048 / 2 * 4 * 128
    assert b == 6 * 2 * (32 + 4) * 2048 * 128 * 2
    # Six expert layers, every expert held: 8 slots x 8 a step for 8
    # steps and the bucket's 8704 x 8; an expert's matrices 3 x 2048 x 768.
    f, b = costs.kernel_cost("grouped_matmul", rctx, 0)
    assigned = 8 * 8 * 8 + 8704 * 8
    assert f == 6 * assigned * 6 * 2048 * 768
    touched = 8 * 50 + 128
    assert b == 6 * (touched * 3 * 2048 * 768 + assigned * 2 * (2048 + 768)) * 2
    with pytest.raises(KeyError):
        costs.kernel_cost("mla_decode", rctx, 1)


def test_configuration_file_carries_the_catalog_rows_keys_and_the_published_counts():
    from benchmark import workload_gen
    from benchmark.references import keye_vl2 as ref

    bench = manifest.load(REPO)
    entry = manifest.entry(bench["configs"], "keye-vl2-30b-a3b-l6", "config")
    cfg = common.load_json(REPO, entry["file"])
    reduced = ["num_hidden_layers"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert {k: cfg[k] for k in CATALOG if k not in reduced} == \
        {k: v for k, v in CATALOG.items() if k not in reduced}
    assert cfg["published"] == {"num_hidden_layers": 48} and cfg["num_hidden_layers"] == 6
    assert entry["source"] == cfg["source"] and cfg["family"] == "keye_vl2"
    assert "eight pipeline stages of six" in cfg["deployment"]
    assert set(cfg["unused"]) >= {"intermediate_size", "max_window_layers", "sliding_window"}
    for key in ("qk_norm", "rope", "indexer", "chunk_sizes", "indexer_precision", "router",
                "param_dtype", "cache_dtype", "router_dtype", "init_std", "decoding", "cache",
                "vision_tower", "q_norm_gain", "q_norm_gain_why"):
        assert key in cfg["assumed"], key
    # Layer 0's heads alone are drawn peaked: what lets a served logit
    # depend on the positions the selector kept (PERF.md section 6 PR 42).
    spec = ref.leaf_spec(cfg)
    assert cfg["assumed"]["q_norm_gain"] == {"0": 2.5}
    assert [spec[f"blk{i}_attn/q_norm"][2] for i in range(6)] == [2.5, 1, 1, 1, 1, 1]
    cell = manifest.entry(bench["workloads"], REAL, "workload")
    assert cell["chips"] == 1 and cell["config"] == entry["name"]
    # (No count of the manifest's cells here: benchmark/tests/test_xing4.py
    # pinned seven and fails on every cell added since.)
    tr = common.load_json(REPO, "benchmark", "traffic", cell["traffic"] + ".json")
    assert (tr["slots"], tr["max_seq"], tr["decode_steps"]) == (8, 32768, 8)
    assert tr["pairing_seed"] == 20260930 and tr["buckets"] == [8704, 16384, 32768]
    # Between the chip's readings (``limits_why``): sound at most 0.0075,
    # the fp8 control at least 0.0209, selection off at least 0.0657.
    assert 0.0075 < tr["limits"]["served_logit_gap"] == 0.02 < 0.0209 and tr["limits_why"]
    assert tr["prompt_len"] == {"law": "bounded_zipf", "alpha": 1.2, "lo": 8192, "hi": 31744}
    assert tr["budget"] == {"law": "bounded_zipf", "alpha": 1.2, "lo": 128, "hi": 1024}
    # Every prompt is at least four times topk: every decode step selects.
    assert tr["prompt_len"]["lo"] >= 4 * cfg["sa_config"]["topk"]
    # 30.64 B published, 4.375 G = 8.75 GB held, from the leaf recipe.
    held = ref.parameter_counts(cfg)["total"]
    assert round(held / 1e9, 3) == 4.375 and round(held * 2 / 1e9, 2) == 8.75
    whole = ref.parameter_counts(dict(cfg, **cfg["published"]))
    assert round(whole["total"] / 1e9, 2) == 30.64 and 3.1e9 < whole["active"] < 3.5e9
    # The three caches: 428 MB a slot, 3.42 GB for 8; 12.17 GB resident.
    slot = 6 * (2 * tr["max_seq"] * 512 + tr["max_seq"] * 64) * 2
    assert round(slot / 1e6) == 428 and round(8 * slot / 1e9, 2) == 3.42
    assert round((held * 2 + 8 * slot) / 1e9, 2) == 12.17
    # The prompt law's quantiles: median 8.2 k.. (workload_gen's own arithmetic).
    p = tr["prompt_len"]
    lens = workload_gen.zipf_quantiles(1000, p["alpha"], p["lo"], p["hi"])
    assert lens.min() == 8192 and lens.max() == 31744
    assert 8100 < np.median(lens) < 8300 and 11000 < lens.mean() < 12500
    assert 0.10 < np.mean(lens == 31744) < 0.13
