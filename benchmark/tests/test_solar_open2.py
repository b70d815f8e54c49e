"""CPU rehearsals of the Solar-Open2 family's cell (run by hand with the
rest: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``): the
tiny mix of ``data/config.tiny-solar.json`` (five layers, 4 of 16
experts held) under ``data/traffic.tiny-closed-solar.json`` through
``run.py`` in a copy of the benchmark, the lower-precision control, a
broken timed path, the cost functions by hand, and what the
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

CELL = "tiny.solar.serve"
REAL = "solar2.serve.closed32.p4k-31k"

#: The catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: ``Solar-Open2-250B``), as published.
CATALOG = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
                           "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128,
    "num_key_value_heads": 8, "vocab_size": 196608, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 1048576,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44], "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8,
}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``sandbox.make``'s copy (which drops every tiny file of
    ``data/`` beside the real ones) with this family's tiny cell entered
    wherever the real cell is."""
    root = sandbox.make(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "tiny-solar", "source": "rehearsal", "reduced": [],
                             "file": "benchmark/configs/tiny-solar.json", "why": "rehearsal"})
    bench["workloads"].append({"name": CELL, "config": "tiny-solar",
                               "traffic": "tiny-closed-solar", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root


def _tiny(kind, name):
    return json.load(open(os.path.join(HERE, "data", f"{kind}.{name}.json")))


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_solar_cell_is_correct(copy, trace):
    p = sandbox.run_cell(copy, CELL, seed=3300000023, trace=trace)
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-3000:]
    line = sandbox.last_line(p)
    assert line["correct"] is True and line["failed"] == 0, p.stdout[-2000:]
    assert "[check] served_logit_gap" in p.stdout
    if trace:
        # What the CPU can read: the counters (never a device metric).
        # 4 experts held: the file's scale is the real cell's, 100 / 40.
        assert 0 < line["metrics"]["moe_experts_touched_pct.solar"]["value"] <= 2.5 * 4
        assert not [m for m in line["metrics"] if m.startswith("kernel_roofline.")]
        assert line["metrics"]["window_compiles.serve"]["value"] == 0
        assert 0 < line["metrics"]["serve_kv_fetch_pct"]["value"] <= 100
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

    from benchmark.families import solar_open2 as fam

    cfg, tr = _tiny("config", "tiny-solar"), _tiny("traffic", "tiny-closed-solar")
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


def test_reference_draws_any_held_expert_alone_and_the_vocabulary_slice():
    """An expert's weights are made inside the loop over experts, and a
    chip's rows of the token table and the head are the whole leaf's."""
    from benchmark import weights
    from benchmark.references import solar_open2 as ref

    cfg = _tiny("config", "tiny-solar")
    get = ref.Leaves(cfg, 7)
    whole = np.asarray(get("blk1_moe/w_gate"))
    assert whole.shape == (4, 64, 32)
    for e in (0, 3):
        assert np.array_equal(np.asarray(get.expert("blk1_moe/w_gate", e)), whole[e])
    # Experts 0-3 of the 16 and vocabulary rows 0-511 of a longer table:
    # the leading rows of the uncut leaves, value for value.
    uncut = ref.Leaves(dict(cfg, n_routed_experts=16, held_experts=None, vocab_size=2048), 7)
    assert np.array_equal(np.asarray(uncut("blk1_moe/w_gate"))[:4], whole)
    assert np.array_equal(np.asarray(uncut("lm_head/kernel"))[:512],
                          np.asarray(get("lm_head/kernel")))
    assert np.asarray(get("blk1_moe/gate")).shape == (64, 16)
    spec = ref.leaf_spec(cfg)
    assert np.array_equal(
        whole, weights.leaf_values(7, "blk1_moe/w_gate", *spec["blk1_moe/w_gate"]))
    both = dict(cfg, assumed={"router_dtype": "float32", "param_dtype": "bfloat16"})
    assert ref.stored_dtype(both, "blk1_kda/a_log") == "float32"
    assert ref.stored_dtype(both, "blk1_kda/wq") == "bfloat16"


def test_flops_and_bytes_against_hand_counts():
    from benchmark.costs import solar_open2 as costs

    cfg = common.load_json(REPO, "benchmark", "configs", "solar-open2-250b-l4e40.json")
    tr = common.load_json(REPO, "benchmark", "traffic", "closed32.p4k-31k.json")
    backlog = [{"id": 0, "prompt": [0] * 5000, "max_new_tokens": 100},
               {"id": 1, "prompt": [0] * 4096, "max_new_tokens": 100}]
    events = [
        {"ev": "prefill", "bucket": 8192, "length": 5000, "experts_touched": 40.0},
        {"ev": "decode_superstep", "k": 8, "slots": [0, 1], "experts_touched": 20.0},
    ]
    rctx = {"config": cfg, "traffic": tr, "events": events, "result": {"backlog": backlog}}
    slots = tr["slots"]
    # One grouped-query layer: two live slots at positions 5000 and 4096
    # (their first token made by the prefill), the rest empty, 8 steps.
    cols = 8 * 5001 + 28 + 8 * 4097 + 28 + (slots - 2) * 36
    assert costs.live_columns(rctx) == cols
    f, b = costs.kernel_cost("gqa_decode", rctx, 8)
    assert f == 4 * 64 * 128 * cols
    assert b == 2 * 8 * 128 * 2 * cols + 2 * 8 * slots * 64 * 128 * 2
    # Three delta layers of 64 heads; a state tile is 128 x 128 float32.
    f, b = costs.kernel_cost("kda_decode", rctx, 24)
    assert f == 24 * slots * 64 * 7 * 128 * 128
    assert b == 24 * slots * 64 * (2 * 128 * 128 + 6 * 128) * 4
    # The 8192-token bucket through three layers of 64 heads, in chunks
    # of 64: four products a chunk, six float32 operands.
    f, b = costs.kernel_cost("kda_chunk", rctx, 12)
    pairs = 8192 * 3 * 64
    assert f == pairs * (6 * 128 * 128 + 2 * 64 * 128)
    assert b == pairs * 4 * (5 * 128 + 64 + 2)
    # The Gram matrices before it: half of two 64 x 64 squares over 128
    # channels; q, k, G in, A and B out.
    f, b = costs.kernel_cost("kda_intra", rctx, 12)
    assert f == pairs * 2 * 64 * 128 and b == pairs * 4 * (3 * 128 + 2 * 64)
    f, b = costs.kernel_cost("gqa_prefill", rctx, 1)
    assert f == 64 * 8192 * 8192 / 2 * 4 * 128
    assert b == 2 * (64 + 8) * 8192 * 128 * 2
    # Four expert layers; an eighth of the assignments falls on the 40
    # held experts; an expert's three matrices are 3 x 4096 x 1280.
    f, b = costs.kernel_cost("grouped_matmul", rctx, 0)
    assigned = (8 * slots * 8 + 8192 * 8) / 8
    assert f == 4 * assigned * 6 * 4096 * 1280
    touched = 8 * 20 + 40
    assert b == 4 * (touched * 3 * 4096 * 1280 + assigned * 2 * (4096 + 1280)) * 2
    with pytest.raises(KeyError):
        costs.kernel_cost("mla_decode", rctx, 1)


def _parameters(cfg):
    from benchmark.references import solar_open2 as ref

    return {k: int(np.prod(shape)) for k, (shape, _, _) in ref.leaf_spec(cfg).items()}


def test_configuration_file_carries_the_catalog_rows_keys_and_the_published_counts():
    bench = manifest.load(REPO)
    entry = manifest.entry(bench["configs"], "solar-open2-250b-l4e40", "config")
    cfg = common.load_json(REPO, entry["file"])
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    # Every key of the catalog row as published, but the three cut.
    assert {k: cfg[k] for k in CATALOG if k not in reduced} == \
        {k: v for k, v in CATALOG.items() if k not in reduced}
    assert cfg["published"] == {k: CATALOG[k] for k in reduced}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (4, 40, 24576)
    assert cfg["held_experts"] == list(range(40))
    assert entry["source"] == cfg["source"] and "96 v5e chips" in cfg["deployment"]
    for key in ("router", "shared_expert_width", "gqa_gate", "kda", "gate_rank", "a_log",
                "dt_bias", "param_dtype", "state_dtype", "router_dtype", "init_std", "decoding"):
        assert key in cfg["assumed"], key
    cell = manifest.entry(bench["workloads"], REAL, "workload")
    assert cell["chips"] == 1 and cell["config"] == entry["name"]
    tr = common.load_json(REPO, "benchmark", "traffic", cell["traffic"] + ".json")
    assert (tr["max_seq"], tr["decode_steps"], tr["pairing_seed"]) == (32768, 8, 20260929)
    assert tr["prompt_len"] == {"law": "bounded_zipf", "alpha": 1.2, "lo": 4096, "hi": 31744}
    assert tr["budget"] == {"law": "bounded_zipf", "alpha": 1.2, "lo": 128, "hi": 1024}
    assert len(tr["buckets"]) <= 4 and tr["buckets"][-1] == 32768
    # What this chip holds: 3.308 G parameters, 6.62 GB in bf16.
    held = sum(_parameters(cfg).values())
    assert round(held / 1e9, 3) == 3.308 and abs(held * 2 / 1e9 - 6.62) < 0.01
    # The published model from the same equations: 250.3 B, 14.7 B active
    # a token (8 of 320 experts a layer; the token table and the head).
    whole = _parameters(dict(cfg, **cfg["published"], held_experts=None))
    total = sum(whole.values())
    idle = sum(n for k, n in whole.items() if k.endswith(("moe/w_gate", "moe/w_up", "moe/w_down")))
    active = total - idle * (320 - 8) // 320
    assert round(total / 1e9, 1) == 250.3 and round(active / 1e9, 1) == 14.7
    # The cell's resident bytes: KV of one layer, state and windows of three.
    kv = tr["slots"] * tr["max_seq"] * 8 * 128 * 2 * 2
    state = tr["slots"] * 3 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)
    assert kv == 32 * 134217728 and round((held * 2 + kv + state) / 1e9, 1) == 11.3
