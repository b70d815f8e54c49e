"""CPU rehearsals of the Laguna family's cell (run by hand with the rest:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``): the tiny mix
of ``data/config.tiny-laguna.json`` (five layers, a window of 16 under
prompts of 24-100, 4 of 16 experts held) under
``data/traffic.tiny-closed-laguna.json`` through ``run.py`` in a copy of
the benchmark, the lower-precision control, a broken timed path, the
window switched off and the ring shifted by one row in the program's
place, the cost functions by hand, and what the configuration file states
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

CELL = "tiny.laguna.serve"
REAL = "laguna.serve.closed16.p8k-31k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``sandbox.make``'s copy (which drops every tiny file of ``data/``
    beside the real ones) with this family's tiny cell entered wherever
    the real cell is."""
    root = sandbox.make(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "tiny-laguna", "source": "rehearsal", "reduced": [],
                             "file": "benchmark/configs/tiny-laguna.json", "why": "rehearsal"})
    bench["workloads"].append({"name": CELL, "config": "tiny-laguna",
                               "traffic": "tiny-closed-laguna", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root


def _tiny(kind, name):
    return json.load(open(os.path.join(HERE, "data", f"{kind}.{name}.json")))


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_laguna_cell_is_correct(copy, trace):
    p = sandbox.run_cell(copy, CELL, seed=4400000031, trace=trace)
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-3000:]
    line = sandbox.last_line(p)
    assert line["correct"] is True and line["failed"] == 0, p.stdout[-2000:]
    assert "[check] served_logit_gap" in p.stdout
    if trace:
        # What the CPU can read: the counters (never a device metric).
        # 4 experts held: the file's scale is the real cell's, 100 / 64.
        assert 0 < line["metrics"]["moe_experts_touched_pct.laguna"]["value"] <= 100 / 64 * 4
        assert not [m for m in line["metrics"] if m.startswith("kernel_roofline.")]
        assert line["metrics"]["window_compiles.serve"]["value"] == 0
        # No kernel at these widths: two full layers read max_seq 128 a
        # slot a step, three rings their 16 rows.
        assert line["metrics"]["serve_kv_fetch_pct"]["value"] == \
            pytest.approx(100 * (2 * 128 + 3 * 16) / (5 * 128), rel=1e-3)
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


@pytest.mark.parametrize("fault", ["window_off", "ring_off_by_one"])
def test_a_broken_window_in_the_program_is_seen_by_the_judged_number(copy, fault):
    """The program's window layers attend every live position (through
    full caches) where the reference keeps 16 of 24-116, or write each
    decode step one row further round the ring than its position's
    residue: at this size (float32, limit 0.001) the served tokens'
    logits fall short of the reference's best by more than the limit, so
    ``served_logit_gap`` sees either.  (Whether it does at the real
    cell's size and precision is a chip reading: PERF.md.)"""
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, os.path.join(HERE, "drive_unwindowed.py"), copy, CELL,
                        fault, "--cpu"] + (["--slots", "2"] if fault == "window_off" else []),
                       cwd=copy, env=e, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = sandbox.last_line(p)
    assert line["failed"] == 0
    assert line["correct"] is False, p.stdout[-2000:]
    assert "served_logit_gap" in [l for l in p.stdout.splitlines() if " OUT" in l][0]
    if fault == "window_off":
        assert "gqa_window" not in p.stdout


def test_lower_precision_control_fails_serving():
    """The reference's own greedy tokens stand for a sound server; the
    reference with fp8 products lies outside the tiny mix's limit."""
    import jax.numpy as jnp

    from benchmark.families import laguna as fam

    cfg, tr = _tiny("config", "tiny-laguna"), _tiny("traffic", "tiny-closed-laguna")
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
    assert ctl["widest_gap"] > tr["limits"]["served_logit_gap"], ctl


def test_reference_draws_any_expert_alone_and_reads_a_band_through_its_slab():
    import jax.numpy as jnp

    from benchmark import weights
    from benchmark.references import laguna as ref

    cfg = _tiny("config", "tiny-laguna")
    get = ref.Leaves(cfg, 7)
    whole = np.asarray(get("blk1_moe/w_gate"))
    assert whole.shape == (4, 64, 32)
    for e in (0, 3):
        assert np.array_equal(np.asarray(get.expert("blk1_moe/w_gate", e)), whole[e])
    spec = ref.leaf_spec(cfg)
    assert spec["blk1_moe/gate"][0] == (64, 16)
    assert np.array_equal(
        whole, weights.leaf_values(7, "blk1_moe/w_gate", *spec["blk1_moe/w_gate"]))
    both = dict(cfg, assumed={"router_dtype": "float32", "param_dtype": "bfloat16"})
    assert ref.stored_dtype(both, "blk1_moe/gate") == "float32"
    assert ref.stored_dtype(both, "blk1_attn/wg") == "bfloat16"
    # A window layer in row blocks over slabs (several blocks, a slab
    # shorter than the sequence) equals the same layer in one block over
    # every key under the same mask.
    a = jnp.asarray(np.random.default_rng(1).standard_normal((96, 64)).astype(np.float32))
    one = np.asarray(ref.attention(cfg, get.at("blk2_"), a, 2))
    rows = ref._Q_ROWS
    try:
        ref._Q_ROWS = 8
        many = np.asarray(ref.attention(cfg, get.at("blk2_"), a, 2))
    finally:
        ref._Q_ROWS = rows
    np.testing.assert_allclose(many, one, atol=2e-6)


def test_flops_and_bytes_against_hand_counts():
    from benchmark.costs import laguna as costs

    cfg = common.load_json(REPO, "benchmark", "configs", "laguna-s2.1-118b-l5e64.json")
    tr = common.load_json(REPO, "benchmark", "traffic", "closed16.p8k-31k.json")
    backlog = [{"id": 0, "prompt": np.zeros(9000, np.int32), "max_new_tokens": 300},
               {"id": 1, "prompt": np.zeros(300, np.int32), "max_new_tokens": 300}]
    events = [
        {"ev": "prefill", "bucket": 8704, "length": 8300, "experts_touched": 64.0},
        {"ev": "decode_superstep", "k": 8, "slots": [0, 1], "experts_touched": 30.0},
    ]
    rctx = {"config": cfg, "traffic": tr, "events": events, "result": {"backlog": backlog}}
    t, w = 8704, 512
    # Three window layers of 72 heads: the band's pairs; two full layers of 48.
    f, b = costs.kernel_cost("window_prefill", rctx, 3)
    assert f == 3 * 72 * (t * w - w * (w - 1) / 2) * 4 * 128
    assert b == 3 * 2 * (72 + 8) * t * 128 * 2
    f, b = costs.kernel_cost("gqa_prefill", rctx, 2)
    assert f == 2 * 48 * (t * (t + 1) / 2) * 4 * 128
    assert b == 2 * 2 * (48 + 8) * t * 128 * 2
    # One superstep of 8 over 16 slots: request 0 at 9001.., request 1 at
    # 301.. (its ring not yet full), 14 empty slots at 1..8.
    full = sum(9001 + j for j in range(8)) + sum(301 + j for j in range(8)) + 14 * 36
    ring = 8 * 512 + sum(301 + j for j in range(8)) + 14 * 36
    f, b = costs.kernel_cost("gqa_decode", rctx, 5 * 8)
    assert f == 4 * 128 * (2 * 48 * full + 3 * 72 * ring)
    qo = 2 * 8 * 16 * 128 * 2
    assert b == 2 * 8 * 128 * 2 * (2 * full + 3 * ring) + qo * (2 * 48 + 3 * 72)
    # Four expert layers, a quarter of the assignments on held experts.
    f, b = costs.kernel_cost("grouped_matmul", rctx, 0)
    assigned = (8 * 16 * 10 + 8704 * 10) / 4
    assert f == 4 * assigned * 6 * 3072 * 1024
    touched = 8 * 30 + 64
    assert b == 4 * (touched * 3 * 3072 * 1024 + assigned * 2 * (3072 + 1024)) * 2
    with pytest.raises(KeyError):
        costs.kernel_cost("mla_decode", rctx, 1)


def test_configuration_file_carries_the_catalog_rows_keys_and_the_published_counts():
    from benchmark import workload_gen
    from benchmark.references import laguna as ref

    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = next(json.loads(l) for l in open(CATALOG) if '"Laguna-S-2.1"' in l)
    bench = manifest.load(REPO)
    entry = manifest.entry(bench["configs"], "laguna-s2.1-118b-l5e64", "config")
    cfg = common.load_json(REPO, entry["file"])
    reduced = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert {k: cfg[k] for k in row["config"] if k not in reduced} == \
        {k: v for k, v in row["config"].items() if k not in reduced}
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                                "vocab_size": 100352}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (5, 64, 25088)
    assert cfg["held_experts"] == list(range(64))
    assert entry["source"] == cfg["source"] == row["source_url"] and cfg["family"] == "laguna"
    for words in ("48 v5e chips", "twelve pipeline stages", "four chips sharing each layer"):
        assert words in cfg["deployment"], words
    assert "max_position_embeddings" in cfg["unused"]
    for key in ("router", "gate", "qk_norm", "rope", "window", "shared_expert", "mlp",
                "param_dtype", "cache_dtype", "router_dtype", "init_std", "decoding", "cache",
                "parameter_count"):
        assert key in cfg["assumed"], key
    # The first five layers: dense then sparse; full, window x 3, full.
    assert cfg["layer_types"][:5] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert cfg["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    cell = manifest.entry(bench["workloads"], REAL, "workload")
    assert cell["chips"] == 1 and cell["config"] == entry["name"]
    tr = common.load_json(REPO, "benchmark", "traffic", cell["traffic"] + ".json")
    assert (tr["slots"], tr["max_seq"], tr["decode_steps"]) == (16, 32768, 8)
    assert tr["buckets"] == [8704, 16384, 32768] and tr["decode_kernel"] is True
    assert tr["limits"]["served_logit_gap"] > 0 and tr["limits_why"]
    # keye2.serve's two laws letter for letter.
    keye = common.load_json(REPO, "benchmark", "traffic", "closed8.p8k-31k.json")
    assert tr["prompt_len"] == keye["prompt_len"] and tr["budget"] == keye["budget"]
    # Every prompt is at least sixteen windows: every ring is full.
    assert tr["prompt_len"]["lo"] >= 16 * cfg["sliding_window"]
    # 117.56 B published, 8.14 B active, 3.002 G = 6.00 GB held, from the leaf recipe.
    held = ref.parameter_counts(cfg)["total"]
    assert round(held / 1e9, 3) == 3.002 and round(held * 2 / 1e9, 2) == 6.0
    whole = ref.parameter_counts(dict(cfg, **cfg["published"], held_experts=None))
    assert round(whole["total"] / 1e9, 2) == 117.56 and round(whole["active"] / 1e9, 2) == 8.14
    # Caches: two full layers and three rings, 16 slots.
    full = 2 * 16 * 8 * 128 * tr["max_seq"] * 2 * 2
    ring = 3 * 16 * 8 * 128 * cfg["sliding_window"] * 2 * 2
    assert round(full / 1e9, 2) == 4.29 and round(ring / 1e9, 2) == 0.10
    assert round((held * 2 + full + ring) / 1e9, 1) == 10.4
    p = tr["prompt_len"]
    lens = workload_gen.zipf_quantiles(1000, p["alpha"], p["lo"], p["hi"])
    assert lens.min() == 8192 and lens.max() == 31744
    assert 8100 < np.median(lens) < 8300 and 11000 < lens.mean() < 12500
