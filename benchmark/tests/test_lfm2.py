"""CPU rehearsals of the LFM2-MoE family's cell (run by hand with the
rest: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``): the
tiny mix of ``data/config.tiny-lfm2.json`` (four convolution layers and
two attention layers round two dense and four expert feed-forwards, a
tied head) under ``data/traffic.tiny-closed-lfm2.json`` through ``run.py``
in a copy of the benchmark, the lower-precision control, the two lost
windows, a broken timed path, the kernels' costs at the configuration's
widths, and what the configuration file states against the catalog row
and the published parameter counts.
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

CELL = "tiny.lfm2.serve"
REAL = "lfm2.serve.closed192.p256-2k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``sandbox.make``'s copy (which drops every tiny file of
    ``data/`` beside the real ones) with this family's tiny cell entered
    wherever the real cell is."""
    root = sandbox.make(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "tiny-lfm2", "source": "rehearsal", "reduced": [],
                             "file": "benchmark/configs/tiny-lfm2.json", "why": "rehearsal"})
    bench["workloads"].append({"name": CELL, "config": "tiny-lfm2",
                               "traffic": "tiny-closed-lfm2", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root


def _tiny(kind, name):
    return json.load(open(os.path.join(HERE, "data", f"{kind}.{name}.json")))


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_lfm2_cell_is_correct(copy, trace):
    p = sandbox.run_cell(copy, CELL, seed=3500000051, trace=trace)
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-3000:]
    line = sandbox.last_line(p)
    assert line["correct"] is True and line["failed"] == 0, p.stdout[-2000:]
    assert "[check] served_logit_gap" in p.stdout
    if trace:
        # What the CPU can read: the counters (never a device metric).
        # 8 experts at top-2 over 4 slots: the files' scales are the
        # real cell's (100 / 64, and 12 tokens the mean expert's).
        assert 0 < line["metrics"]["moe_experts_touched_pct.lfm2"]["value"] <= 1.5625 * 8
        assert line["metrics"]["moe_expert_load_max.lfm2"]["value"] >= 12.0
        assert not [m for m in line["metrics"]
                    if m.startswith("kernel_roofline.") or m.endswith("_share_pct.lfm2")]
        assert line["metrics"]["window_compiles.serve"]["value"] == 0
        assert 0 < line["metrics"]["serve_kv_fetch_pct"]["value"] <= 100
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def _drive(copy, script, *args):
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return subprocess.run([sys.executable, os.path.join(HERE, script), copy, CELL, *args],
                          cwd=copy, env=e, capture_output=True, text=True, timeout=600)


def test_broken_timed_path_is_incorrect(copy):
    p = _drive(copy, "drive_broken.py", "altered_token")
    assert p.returncode == 0, p.stderr[-2000:]
    assert sandbox.last_line(p)["correct"] is False, p.stdout[-2000:]
    assert " OUT" in p.stdout


@pytest.mark.parametrize("fault", ["window_zeroed", "window_at_bucket_end"])
def test_a_lost_convolution_window_is_incorrect(copy, fault):
    """The program with its windows zeroed at every decode step, or
    taken at the bucket's end: the judged number sees both."""
    p = _drive(copy, "drive_stateless_conv.py", fault, "--cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    assert sandbox.last_line(p)["correct"] is False, p.stdout[-2000:]
    assert "served_logit_gap" in p.stdout and " OUT" in p.stdout


def test_lower_precision_control_fails_serving():
    """The reference's own greedy tokens stand for a sound server; the
    reference with fp8 products lies outside the tiny mix's limit.
    (Sixteen tokens: under a tied head and seeded weights greedy decoding
    repeats a token for a few steps, and there the two precisions agree;
    twelve read 0.)"""
    import jax.numpy as jnp

    from benchmark.families import lfm2 as fam

    cfg, tr = _tiny("config", "tiny-lfm2"), _tiny("traffic", "tiny-closed-lfm2")
    prompt = np.random.default_rng(5).integers(0, cfg["vocab_size"], size=24, dtype=np.int32)
    toks = []
    for _ in range(16):
        full = np.concatenate([prompt, np.asarray(toks, np.int32)])
        toks.append(int(jnp.argmax(fam.reference.logits_fn(cfg, 5, full)[-1])))
    sample = [{"prompt": prompt, "tokens": toks}]
    sound = fam.reference.served_gaps(cfg, 5, tr["max_seq"], sample)
    ctl = fam.reference.served_gaps(cfg, 5, tr["max_seq"], sample, quant=True)
    assert sound["widest_gap"] <= tr["limits"]["served_logit_gap"]
    assert 0 <= sound["selection_flip_share"] <= 1
    assert ctl["widest_gap"] > tr["limits"]["served_logit_gap"], ctl


def test_reference_draws_any_expert_and_any_row_of_the_table_alone():
    from benchmark import weights
    from benchmark.references import lfm2 as ref

    cfg = _tiny("config", "tiny-lfm2")
    get = ref.Leaves(cfg, 7)
    whole = np.asarray(get("blk2_moe/w_gate"))
    assert whole.shape == (8, 64, 32)
    for e in (0, 5):
        assert np.array_equal(np.asarray(get.expert("blk2_moe/w_gate", e)), whole[e])
    spec = ref.leaf_spec(cfg)
    assert np.array_equal(
        whole, weights.leaf_values(7, "blk2_moe/w_gate", *spec["blk2_moe/w_gate"]))
    table = np.asarray(get("embed/table"))
    assert np.array_equal(np.asarray(get.rows("embed/table", np.array([3, 500, 3]))),
                          table[[3, 500, 3]])
    assert "lm_head/kernel" not in spec            # the head is the table
    taps = np.asarray(get("blk0_conv/conv"))
    assert taps.shape == (3, 64) and np.abs(taps).max() <= 0.5 and np.abs(taps[0]).mean() > 0.1
    # The attention layers' query norms are drawn round their gain.
    assert abs(float(np.mean(np.asarray(get("blk2_attn/q_norm")))) - 2.5) < 0.05
    assert abs(float(np.mean(np.asarray(get("blk2_attn/k_norm")))) - 1.0) < 0.05
    both = dict(cfg, assumed=dict(cfg["assumed"], param_dtype="bfloat16"))
    assert ref.stored_dtype(both, "blk2_moe/gate") == "float32"
    assert ref.stored_dtype(both, "blk2_moe/e_bias") == "float32"
    assert ref.stored_dtype(both, "blk0_conv/conv") == "bfloat16"


def test_kernels_costs_at_this_configurations_widths():
    from benchmark.costs import lfm2 as costs
    from benchmark.families import lfm2 as fam

    assert fam.COSTS == "lfm2"
    cfg = common.load_json(REPO, "benchmark", "configs", "lfm2-24b-a2b-l10.json")
    tr = common.load_json(REPO, "benchmark", "traffic", "closed192.p256-2k.json")
    backlog = [{"id": 0, "prompt": [0] * 300, "max_new_tokens": 100},
               {"id": 1, "prompt": [0] * 2048, "max_new_tokens": 100}]
    events = [
        {"ev": "prefill", "bucket": 2048, "experts_touched": 64.0},
        {"ev": "decode_superstep", "k": 8, "slots": [0, 1], "experts_touched": 60.0},
    ]
    rctx = {"config": cfg, "traffic": tr, "events": events, "result": {"backlog": backlog}}
    # Two attention layers of the ten; 190 empty slots read 1 .. 8.
    cols = 2 * (8 * 301 + 28 + 8 * 2049 + 28 + 190 * 36)
    assert costs.live_columns(rctx) == cols
    f, b = costs.kernel_cost("gqa_decode", rctx, 16)
    assert f == 4 * 32 * 64 * cols
    assert b == 2 * 8 * 64 * 2 * cols + 2 * 16 * 192 * 32 * 64 * 2
    # Eight expert layers; a 192-slot step routes 768 assignments, the
    # prefill 8192; an expert's three matrices are 3 x 2048 x 1536.
    f, b = costs.kernel_cost("grouped_matmul", rctx, 0)
    assigned = 8 * 768 + 2048 * 4
    assert f == 8 * assigned * 6 * 2048 * 1536
    touched = 8 * 60 + 64
    assert b == 8 * (touched * 3 * 2048 * 1536 + assigned * 2 * (2048 + 1536)) * 2
    f, b = costs.kernel_cost("flash_fwd_uneven", rctx, 2)
    assert f == 2 * 32 * 2048 * 2048 / 2 * 4 * 64
    assert b == 2 * 2 * (32 + 8) * 2048 * 64 * 2
    # Every expert of a layer read once: 1.21 GB, 9.66 GB a step.
    assert round(64 * 3 * 2048 * 1536 * 2 / 1e9, 2) == 1.21
    assert round(8 * 64 * 3 * 2048 * 1536 * 2 / 1e9, 2) == 9.66


def test_configuration_file_carries_the_catalog_rows_keys_and_the_published_counts():
    from benchmark.references import lfm2 as ref

    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = next(json.loads(l) for l in open(CATALOG) if '"LFM2-24B-A2B"' in l)
    bench = manifest.load(REPO)
    entry = manifest.entry(bench["configs"], "lfm2-24b-a2b-l10", "config")
    cfg = common.load_json(REPO, entry["file"])
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"] == row["source_url"]
    # Every key of the catalog row as published, but the depth.
    want = {k: v for k, v in row["config"].items() if k != "num_hidden_layers"}
    assert {k: cfg[k] for k in want} == want
    assert cfg["num_hidden_layers"] == 10 and cfg["published"] == {"num_hidden_layers": 40}
    assert cfg["family"] == "lfm2" and "four pipeline stages of ten" in cfg["deployment"]
    for key in ("tie_embedding", "head_dim", "qk_norm", "rope", "conv", "router",
                "param_dtype", "router_dtype", "init_std", "conv_tap_half_width",
                "e_bias_half_width", "q_norm_gain", "q_norm_gain_why", "decoding", "cache",
                "parameter_count"):
        assert key in cfg["assumed"], key
    cell = manifest.entry(bench["workloads"], REAL, "workload")
    assert cell["chips"] == 1 and cell["config"] == entry["name"]
    tr = common.load_json(REPO, "benchmark", "traffic", cell["traffic"] + ".json")
    xing = common.load_json(REPO, "benchmark", "traffic", "closed96.p256-2k.json")
    # xing4.serve's two laws letter for letter, at twice the slots.
    assert tr["prompt_len"] == xing["prompt_len"] and tr["budget"] == xing["budget"]
    assert (tr["slots"], tr["max_seq"], tr["decode_steps"], tr["buckets"]) == \
        (192, 3072, 8, [512, 1024, 2048])
    assert tr["decode_kernel"] is True and tr["flags"] == ["--dtype", "bfloat16"]
    assert (tr["check_requests"], tr["trace_seconds"], tr["pairing_seed"]) == (3, 10, 20261004)
    pub = ref.parameter_counts({**cfg, **cfg["published"]})
    assert pub == {"total": 23_843_661_440, "active": 2_326_881_920}
    held = ref.parameter_counts(cfg)["total"]
    assert held == 5_267_090_176
    # The caches: 4,096 B a token; with the weights 12.96 GB of the chip.
    cache = tr["slots"] * tr["max_seq"] * 4096 + tr["slots"] * 8 * 2 * 2048 * 2
    assert round(cache / 1e9, 2) == 2.43 and round((held * 2 + cache) / 1e9, 2) == 12.96
    # 12 tokens an expert a decode step.
    assert tr["slots"] * cfg["num_experts_per_tok"] / cfg["num_experts"] == 12
