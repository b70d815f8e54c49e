"""CPU rehearsals of the DeepSeek-V3 family's cell (run by hand with the
rest: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``): the
tiny mix of ``data/config.tiny-deepseek.json`` under
``data/traffic.tiny-closed-latent.json`` through ``run.py`` in a copy of
the benchmark, the lower-precision control, a broken timed path, the
cost functions by hand, and what the configuration file states.
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

CELL = "tiny.deepseek.serve"
REAL = "kanana2.serve.closed16.p4k-15k"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``sandbox.make``'s copy (which drops every tiny file of
    ``data/`` beside the real ones) with this family's tiny cell entered
    wherever the real cell is."""
    root = sandbox.make(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "tiny-deepseek", "source": "rehearsal", "reduced": [],
                             "file": "benchmark/configs/tiny-deepseek.json", "why": "rehearsal"})
    bench["workloads"].append({"name": CELL, "config": "tiny-deepseek",
                               "traffic": "tiny-closed-latent", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root


def _tiny(kind, name):
    return json.load(open(os.path.join(HERE, "data", f"{kind}.{name}.json")))


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_latent_cell_is_correct(copy, trace):
    p = sandbox.run_cell(copy, CELL, seed=2900000023, trace=trace)
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-3000:]
    line = sandbox.last_line(p)
    assert line["correct"] is True and line["failed"] == 0, p.stdout[-2000:]
    assert "[check] served_logit_gap" in p.stdout
    if trace:
        # What the CPU can read: the counters (never a device metric).
        assert 0 < line["metrics"]["moe_experts_touched_pct"]["value"] <= 100 * 8 / 128
        assert "kernel_roofline.mla_decode" not in line["metrics"]
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

    from benchmark.families import deepseek_v3 as fam

    cfg, tr = _tiny("config", "tiny-deepseek"), _tiny("traffic", "tiny-closed-latent")
    prompt = np.random.default_rng(0).integers(0, cfg["vocab_size"], size=24, dtype=np.int32)
    toks = []
    for _ in range(12):
        full = np.concatenate([prompt, np.asarray(toks, np.int32)])
        toks.append(int(jnp.argmax(fam.reference.logits_fn(cfg, 5, full)[-1])))
    sample = [{"prompt": prompt, "tokens": toks}]
    sound = fam.reference.served_gaps(cfg, 5, tr["max_seq"], sample)
    ctl = fam.reference.served_gaps(cfg, 5, tr["max_seq"], sample, quant=True)
    assert sound["widest_gap"] <= tr["limits"]["served_logit_gap"]
    assert 0 <= sound["selection_flip_share"] <= 1
    assert ctl["widest_gap"] > tr["limits"]["served_logit_gap"], ctl


def test_reference_draws_any_expert_alone():
    """A layer's weights are made when the walk reaches it, an expert's
    inside the loop: any slice equals the whole leaf's."""
    from benchmark import weights
    from benchmark.references import deepseek_v3 as ref

    cfg = _tiny("config", "tiny-deepseek")
    get = ref.Leaves(cfg, 7)
    whole = np.asarray(get("blk1_moe/w_gate"))
    assert whole.shape == (8, 64, 32)
    for e in (0, 5):
        assert np.array_equal(np.asarray(get.expert("blk1_moe/w_gate", e)), whole[e])
    spec = ref.leaf_spec(cfg)
    assert np.array_equal(
        whole, weights.leaf_values(7, "blk1_moe/w_gate", *spec["blk1_moe/w_gate"]))
    assert ref.stored_dtype(dict(cfg, assumed={"router_dtype": "float32", "param_dtype": "bfloat16"}),
                            "blk1_moe/gate") == "float32"


def test_flops_and_bytes_against_hand_counts():
    from benchmark.costs import deepseek_v3 as costs

    cfg = common.load_json(REPO, "benchmark", "configs", "kanana-2-30b-a3b-l7.json")
    tr = common.load_json(REPO, "benchmark", "traffic", "closed16.p4k-15k.json")
    backlog = [{"id": 0, "prompt": [0] * 5000, "max_new_tokens": 100},
               {"id": 1, "prompt": [0] * 4096, "max_new_tokens": 100}]
    events = [
        {"ev": "prefill", "bucket": 8192, "experts_touched": 128.0},
        {"ev": "decode_superstep", "k": 8, "slots": [0, 1], "experts_touched": 64.0},
    ]
    rctx = {"config": cfg, "traffic": tr, "events": events, "result": {"backlog": backlog}}
    # Two live slots at positions 5000 and 4096 (their first token made
    # by the prefill), 14 empty ones, 8 steps, 7 layers.
    cols = 7 * (8 * 5001 + 28 + 8 * 4097 + 28 + 14 * 36)
    assert costs.live_columns(rctx) == cols
    f, b = costs.kernel_cost("mla_decode", rctx, 56)
    assert f == 2 * 32 * (576 + 512) * cols == 69632 * cols
    assert b == 1152 * cols + 56 * 16 * 32 * 1088 * 2
    # Six expert layers; a 16-slot step routes 96 assignments, the
    # prefill 49152; an expert's three matrices are 3 x 2048 x 768.
    f, b = costs.kernel_cost("grouped_matmul", rctx, 0)
    assigned = 8 * 96 + 8192 * 6
    assert f == 6 * assigned * 6 * 2048 * 768
    touched = 8 * 64 + 128
    assert b == 6 * (touched * 3 * 2048 * 768 + assigned * 2 * (2048 + 768)) * 2
    f, b = costs.kernel_cost("flash_fwd_uneven", rctx, 7)
    assert f == 7 * 32 * 8192 * 8192 / 2 * 2 * 320
    assert b == 7 * 32 * 8192 * 2 * 320 * 2
    with pytest.raises(KeyError):
        costs.kernel_cost("flash_decode", rctx, 1)


def test_configuration_file_carries_the_sources_widths():
    bench = manifest.load(REPO)
    entry = manifest.entry(bench["configs"], "kanana-2-30b-a3b-l7", "config")
    cfg = common.load_json(REPO, entry["file"])
    want = {"hidden_size": 2048, "num_attention_heads": 32, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128, "kv_lora_rank": 512,
            "n_routed_experts": 128, "moe_intermediate_size": 768, "num_experts_per_tok": 6,
            "n_shared_experts": 2, "intermediate_size": 6144, "vocab_size": 128256,
            "first_k_dense_replace": 1, "routed_scaling_factor": 2.448, "rope_theta": 1000000,
            "scoring_func": "sigmoid", "topk_method": "noaux_tc", "rms_norm_eps": 1e-06}
    assert {k: cfg[k] for k in want} == want
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 7 and cfg["published"] == {"num_hidden_layers": 48}
    assert entry["source"] == cfg["source"] and "pipeline stages" in cfg["deployment"]
    for key in ("param_dtype", "router_dtype", "init_std", "e_score_correction_bias", "decoding"):
        assert key in cfg["assumed"]
    # 8.86 GB of bf16 weights, 2.11 GB of latent cache: the issue's arithmetic.
    from benchmark.references import deepseek_v3 as ref

    n = sum(int(np.prod(shape)) for shape, _, _ in ref.leaf_spec(cfg).values())
    assert abs(n * 2 / 1e9 - 8.86) < 0.01
    tr = common.load_json(REPO, "benchmark", "traffic", "closed16.p4k-15k.json")
    assert tr["slots"] * tr["max_seq"] * 7 * 576 * 2 == 2113929216
