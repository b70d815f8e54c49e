"""CPU rehearsals of the harness (run by hand:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``).

Every runner goes end to end through ``run.py`` on throw-away tiny
configuration and traffic files dropped into a copy of ``benchmark/``
(``sandbox.py``): sizes come from files, so ``run.py`` has no size flag.
"""

import filecmp
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common, manifest, trace_read, workload_gen  # noqa: E402
from benchmark.tests import chip_sets, sandbox  # noqa: E402

CELLS = {"tiny.gpt2.train": 1, "tiny.gpt2.serve": 1, "tiny.dlrm.train": 1, "tiny.dlrm.c4": 4}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return sandbox.make(str(tmp_path_factory.mktemp("bench")))


def _clock(stdout):
    """``(boot_s, import_s, setup_s, process_setup_s)`` of the earlier line."""
    found = chip_sets.CLOCK.findall(stdout)
    assert len(found) == 1, stdout[-2000:]
    return tuple(float(x) for x in found[0])


def _contract(line, bench, cell, trace):
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == (keys | {"breakdown"} if trace else keys)
    assert isinstance(line["correct"], bool) and line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["kind"] and dev["count"] == CELLS[cell]
    assert "memory_peak_bytes" in dev
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and math.isfinite(m["value"])
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] >= dev["busy_s"]
        for rows in line["breakdown"].values():
            assert len(rows) <= 10 and all(len(r) == 2 for r in rows)
    else:
        want = {m["name"] for m in bench["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]}
        assert set(line["metrics"]) == want and "setup_s" in want and len(want) >= 2
        assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_runner_end_to_end(copy, cell):
    """The reference agrees with the system in f32 at a small size (the
    tiny mixes' limits are tight), and the last line keeps the contract."""
    p = sandbox.run_cell(copy, cell, seed=3000000001, devices=CELLS[cell])
    assert p.returncode == 0, p.stderr[-2000:]
    line = sandbox.last_line(p)
    _contract(line, json.load(open(os.path.join(copy, "BENCHMARK.json"))), cell, trace=False)
    assert line["correct"] is True, p.stdout[-2000:]
    assert "compiles in window 0" in p.stdout
    # The set-up clock: started when jax.devices() had returned; what lies
    # before it is boot_s, on an earlier line and never in the last one.
    boot_s, import_s, setup_s, process_setup_s = _clock(p.stdout)
    assert boot_s > 0 and 0 < import_s < setup_s
    assert boot_s + setup_s == pytest.approx(process_setup_s, abs=2e-3)
    assert line["metrics"]["setup_s"]["value"] == pytest.approx(setup_s, abs=1e-3)
    assert not any(k in p.stdout.strip().splitlines()[-1]
                   for k in ("boot_s", "import_s", "process_setup_s"))


@pytest.mark.parametrize("cell", ["tiny.gpt2.train", "tiny.gpt2.serve"])
def test_traced_run(copy, cell):
    """``--trace 1``: per-layer metrics found through their own files (the
    dropped-in ``tiny_step_ms`` among them), busy and window seconds, the
    breakdown; no device metric is written from a CPU run."""
    p = sandbox.run_cell(copy, cell, trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    line = sandbox.last_line(p)
    _contract(line, None, cell, trace=True)
    _clock(p.stdout)  # a traced run prints the earlier line too
    if cell == "tiny.gpt2.train":
        # The metric and the reducer that the copy added, found by name.
        assert set(line["metrics"]) == {"tiny_step_ms"}
    assert not any("roofline" in k or "mfu" in k for k in line["metrics"])


def test_nothing_of_the_program_or_the_benchmark_before_the_clock_starts():
    """A child process imports ``run.py`` with ``jax.devices`` watched: at
    the instant it returns (the next statement takes ``T_UP``) neither
    ``flexflow_tpu`` nor any ``benchmark`` module has been imported."""
    code = """
import importlib.util, json, sys, jax
real = jax.devices
seen = []
def watched(*a, **k):
    out = real(*a, **k)
    seen.append(sorted(m for m in sys.modules if m.split('.')[0] in ('flexflow_tpu', 'benchmark')))
    return out
jax.devices = watched
spec = importlib.util.spec_from_file_location('the_run_file', sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
print(json.dumps({'seen': seen[0], 'order': mod.T_PROC <= mod.T_UP}))
"""
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, "-c", code, os.path.join(REPO, "benchmark", "run.py")],
                       env=e, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == {"seen": [], "order": True}


def test_the_copy_added_files_and_edited_none(copy):
    """Cells, configurations, mixes, a metric and a reducer came as new
    files plus entries: every file of the harness is in the copy, byte
    for byte."""
    for d, _, files in os.walk(os.path.join(REPO, "benchmark")):
        if "__pycache__" in d:
            continue
        for f in files:
            if f.endswith(".pyc"):
                continue
            src = os.path.join(d, f)
            dst = os.path.join(copy, os.path.relpath(src, REPO))
            assert filecmp.cmp(src, dst, shallow=False), dst
    for added in ("configs/tiny-gpt2.json", "traffic/tiny-closed.json",
                  "metrics/tiny_step_ms.json", "reducers/tiny_median.py"):
        assert os.path.exists(os.path.join(copy, "benchmark", added))
        assert not os.path.exists(os.path.join(REPO, "benchmark", added))
    real = manifest.load(REPO)
    mine = manifest.load(copy)
    assert mine["workloads"][:len(real["workloads"])] == real["workloads"]
    assert mine["per_layer"][:len(real["per_layer"])] == real["per_layer"]


@pytest.mark.parametrize("where,key,value", [
    ("workloads", "name", "tiny gpt2"), ("workloads", "traffic", "tiny/train"),
    ("workloads", "name", "x" * 65), ("end_to_end", "unit", "tokens per second"),
    ("per_layer", "unit", "\u00b5s"), ("per_layer", "name", "step,ms"),
    ("configs", "name", "-tiny")])
def test_manifest_refuses_a_bad_name_or_unit_before_any_run(copy, tmp_path, where, key, value):
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    bench[where][-1][key] = value
    with pytest.raises(SystemExit):
        manifest.check(bench)
    # ... and through run.py: no result line, nothing run.
    if (where, key) == ("per_layer", "unit"):
        os.symlink(os.path.join(copy, "benchmark"), tmp_path / "benchmark")
        json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
        p = sandbox.run_cell(str(tmp_path), "tiny.dlrm.train")
        assert p.returncode != 0 and "BENCHMARK.json" in p.stderr
        assert "[run]" not in p.stdout and '"correct"' not in p.stdout


def test_same_seed_same_inputs():
    mix = json.load(open(os.path.join(HERE, "data", "traffic.tiny-closed.json")))
    mix["max_seq"] = 64
    a = workload_gen.closed_backlog(mix, 24, 5, 257)
    b = workload_gen.closed_backlog(mix, 24, 5, 257)
    c = workload_gen.closed_backlog(mix, 24, 6, 257)
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    sizes = lambda rows: [(len(r["prompt"]), r["max_new_tokens"]) for r in rows]
    assert sizes(a) == sizes(c)  # the same work in the same order for every seed
    assert not all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, c))
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 64 for r in a)


def test_zipf_quantiles_follow_the_programs_law():
    """The copied length law against draws of the program's own
    ``_bounded_zipf`` recipe (numpy's zipf, clamped, shifted)."""
    rng = np.random.default_rng(0)
    draws = np.minimum(rng.zipf(1.2, 200000), 768 - 32 + 1) + 31
    q = workload_gen.zipf_quantiles(4000, 1.2, 32, 768)
    assert abs(q.mean() - draws.mean()) / draws.mean() < 0.02
    assert q.min() == 32 and q.max() == 768
    assert abs(np.mean(q == 768) - np.mean(draws == 768)) < 0.01


def test_refuses_to_run_without_a_tpu(monkeypatch):
    """jax is on the CPU here; unless the CPU was asked for by name the
    harness exits non-zero before any result."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as e:
        common.require_device(1)
    assert e.value.code == 2
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert common.require_device(1)["platform"] == "cpu"
    with pytest.raises(SystemExit):
        common.require_device(4096)


def test_bare_directory_gives_no_result(tmp_path):
    """Only ``BENCHMARK.json`` and ``benchmark/``: the program under test
    is absent, so the run fails and prints no result line."""
    root = sandbox.make(str(tmp_path))
    p = sandbox.run_cell(root, "tiny.dlrm.train", env={"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not any(l.startswith('{"correct"') for l in p.stdout.splitlines())


@pytest.mark.parametrize("cell,fault", [("tiny.gpt2.train", "frozen_step"),
                                        ("tiny.dlrm.train", "frozen_step"),
                                        ("tiny.gpt2.serve", "altered_token")])
def test_broken_timed_path_is_incorrect(copy, cell, fault):
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, os.path.join(HERE, "drive_broken.py"), copy, cell, fault],
                       cwd=copy, env=e, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = sandbox.last_line(p)
    assert line["correct"] is False, p.stdout[-2000:]
    assert " OUT" in p.stdout


def _tiny(kind, name):
    return json.load(open(os.path.join(HERE, "data", f"{kind}.{name}.json")))


def test_lower_precision_control_fails_training():
    """The reference in the nearest precision below the configuration's
    (fp8 products for GPT-2, bfloat16 throughout for DLRM), put in the
    program's place, lies outside the tiny mixes' limits."""
    from benchmark.families import dlrm, gpt2

    for fam, cfg, tr in ((gpt2, _tiny("config", "tiny-gpt2"), _tiny("traffic", "tiny-train")),
                         (dlrm, _tiny("config", "tiny-dlrm"), _tiny("traffic", "tiny-dlrm"))):
        batch = int(tr["flags"][1])
        host = fam.host_batches(cfg, tr, 5, 3, batch)
        want = fam.reference.train(cfg, tr, 5, host)
        ctl = fam.reference.train(cfg, tr, 5, host, quant=True)
        gaps = {
            "loss_gap": max(abs(g - w) / abs(w) for g, w in zip(ctl["losses"], want["losses"])),
            "grad_norm_gap": common.worst_leaf_gap(ctl["grad_norms"], want["grad_norms"]),
            "param_change_gap": common.worst_leaf_gap(ctl["delta_norms"], want["delta_norms"]),
        }
        assert any(not gaps[k] <= tr["limits"][k] for k in gaps), gaps  # nan has failed too


def test_lower_precision_control_fails_serving():
    from benchmark.families import gpt2

    cfg, tr = _tiny("config", "tiny-gpt2"), _tiny("traffic", "tiny-closed")
    rng = np.random.default_rng(0)
    # Greedy tokens of the reference itself stand for a sound server.
    import jax.numpy as jnp

    p = gpt2.reference.init(cfg, 5, tr["max_seq"])
    prompt = rng.integers(0, cfg["vocab_size"], size=24, dtype=np.int32)
    toks = []
    for _ in range(16):
        full = np.concatenate([prompt, np.asarray(toks, np.int32)])[None]
        toks.append(int(jnp.argmax(gpt2.reference.logits_fn(cfg, p, full)[0, -1])))
    sample = [{"prompt": prompt, "tokens": toks}]
    sound = gpt2.reference.served_gaps(cfg, 5, tr["max_seq"], sample)
    ctl = gpt2.reference.served_gaps(cfg, 5, tr["max_seq"], sample, quant=True)
    assert sound["widest_gap"] <= tr["limits"]["served_logit_gap"]
    assert ctl["widest_gap"] > tr["limits"]["served_logit_gap"], ctl


# -- the yardstick's arithmetic ------------------------------------------------

def test_trace_reduction_on_a_recorded_trace(tmp_path):
    """The small recorded trace of ``data/`` (four device operations, two
    harness spans, as a text proto): busy is the union of the intervals,
    idle gaps go to the harness span over their middle, operation time is
    found by pattern."""
    from jax.profiler import ProfileData

    text = open(os.path.join(HERE, "data", "trace.small.xspace.txt")).read()
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    tr = trace_read.load(str(path))
    assert sorted(tr.devices) == [0] and len(tr.devices[0]) == 4  # "Steps" is not an operation
    lo, hi = trace_read.window_ns(tr)
    assert (lo, hi) == (0.0, 20000.0)
    ops = trace_read.clip(tr.devices[0], lo, hi)
    # [0,4) u [2,6) = 6 us; [10,12) = 2; [16,18) = 2  -> 10 us busy of 20
    assert trace_read.busy_seconds(ops) == pytest.approx(10e-6)
    secs, n = trace_read.op_seconds(ops, ["^fusion"])
    assert n == 2 and secs == pytest.approx(6e-6)
    secs, n = trace_read.op_seconds(ops, ["all-reduce", "all-gather"])
    assert n == 1 and secs == pytest.approx(2e-6)
    assert trace_read.top_ops(ops)[0] == ["fusion", pytest.approx(6e-6)]
    gaps = dict(map(tuple, trace_read.idle_gaps(tr.devices[0], tr.host_spans, lo, hi)))
    # gaps [6,10) (middle 8: bench/fence), [12,16) and [18,20): no span
    assert gaps == {"bench/fence": pytest.approx(4e-6), "bench/between_calls": pytest.approx(6e-6)}


def test_flops_and_bytes_against_hand_counts():
    from benchmark.costs import dlrm as cd
    from benchmark.costs import gpt2 as cg
    from benchmark.costs import peaks

    med = common.load_json(REPO, "benchmark", "configs", "gpt2-medium.json")
    tr = common.load_json(REPO, "benchmark", "traffic", "train.b8s1024.json")
    # 24 layers x 12 x 1024^2 + 50257 x 1024 matmul weights
    assert cg.matmul_params(med) == 301989888 + 51463168
    per_token = 6 * 353453056 + 6 * 24 * 1024 * 1024
    assert cg.train_flops_per_item(med, tr) == per_token == 2271713280
    assert per_token * 8192 == pytest.approx(18.61e12, rel=1e-3)  # a step of 8 x 1024
    rctx = {"config": med, "traffic": tr, "result": {"batch": 8}, "cell": {"chips": 1}}
    f, b = cg.kernel_cost("flash_fwd", rctx, 1)
    # 128 heads x 1024^2 x 64 x 4 flops, half of it under the causal mask
    assert f == 128 * 1024 * 1024 * 64 * 4 / 2 == 17179869184
    assert b == 4 * 128 * 1024 * 64 * 2 + 128 * 1024 * 4
    assert cg.kernel_cost("flash_dq", rctx, 2)[0] == 2 * 1.5 * f
    assert cg.kernel_cost("flash_dkv", rctx, 1)[0] == 2 * f
    rnd = common.load_json(REPO, "benchmark", "configs", "dlrm-random.json")
    rctx = {"config": rnd, "result": {"batch": 1024}, "cell": {"chips": 1}}
    assert cd.kernel_cost("gather_rows", rctx, 1) == (0.0, 2.0 * 8192 * 256)
    assert cd.kernel_cost("scatter_add_rows", rctx, 1) == (8192 * 64, 3.0 * 8192 * 256)
    # 64-512-512-64 and 576-1024-1024-1024-1
    assert cd.train_flops_per_item(rnd, {}) == 6 * (64 * 512 + 512 * 512 + 512 * 64
                                                   + 576 * 1024 + 2 * 1024 * 1024 + 1024)
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 1.97e14
    with pytest.raises(KeyError):
        peaks.peak("TPU v9")


def test_decode_live_rows_by_hand():
    from benchmark.costs import gpt2 as cg

    rctx = {"config": {"n_layer": 2}, "traffic": {"slots": 2},
            "result": {"backlog": [{"id": 0, "prompt": [0] * 10, "max_new_tokens": 9}]},
            "events": [{"ev": "decode_superstep", "k": 4, "slots": [0]},
                       {"ev": "decode_superstep", "k": 4, "slots": [0]}]}
    # slot 0: first superstep at position 10 reads 11+12+13+14 = 50 rows,
    # the second at position 14 reads 15+16+17+18 = 66; the empty slot
    # reads 1+2+3+4 = 10 each time; two layers.
    assert cg.live_rows(rctx) == 2 * (50 + 66 + 10 + 10)


def test_weights_agree_between_numpy_and_jax():
    import jax.numpy as jnp

    from benchmark import weights

    a = weights.leaf_values(3000000001, "x/y", (5, 7, 4), 0.5, 1.0, np)
    b = np.asarray(weights.leaf_values(3000000001, "x/y", (5, 7, 4), 0.5, 1.0, jnp))
    assert np.array_equal(a, b)
    rows = weights.leaf_rows(3000000001, "x/y", [33, 2], 4, 0.5, 1.0, np)
    assert np.array_equal(rows, a.reshape(35, 4)[[33, 2]])
    big = weights.leaf_values(1, "t", (2000, 64), 1.0)
    assert abs(big.mean()) < 0.01 and abs(big.std() - 1 / math.sqrt(3)) < 0.01


@pytest.mark.parametrize("config", ["dlrm-random", "dlrm-random-8m"])
def test_no_seed_puts_every_prediction_at_one_half(config):
    """The bias under the sigmoid sets every prediction at seeded
    weights; drawn near zero, the gradient over random labels cancels
    and no comparison is conditioned (PERF.md section 4 (c))."""
    from benchmark.references import dlrm as reference

    cfg = common.load_json(REPO, "benchmark", "configs", config + ".json")
    spec = reference.leaf_spec(cfg)
    last = f"top_linear{len(cfg['mlp_top']) - 2}/bias"
    shape, half_width, offset = spec[last]
    assert shape == (1,) and offset - half_width >= 1.0
    others = [v for k, v in spec.items() if k.endswith("/bias") and k != last]
    assert others and all(off == 0.0 for _, _, off in others)


def test_emulation_reads_the_tiny_cell(capsys):
    """``emulate_dlrm.py`` end to end at the tiny size: the sound
    stand-in inside the limits, the control and the half batch outside."""
    from benchmark.tests import emulate_dlrm

    data = os.path.join(HERE, "data")
    assert emulate_dlrm.main(["--config", os.path.join(data, "config.tiny-dlrm.json"),
                              "--traffic", os.path.join(data, "traffic.tiny-dlrm.json"),
                              "--seeds", "3", "--base", "2147483900"]) == 0
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert got["seeds"] == 3
    assert got["control_smallest"][2] > 10 * got["sound_largest"][2]
    assert got["half_batch_smallest"][0] > 3 * got["sound_largest"][0]
