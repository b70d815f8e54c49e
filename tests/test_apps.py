"""CLI app smoke tests (the reference's per-model binaries,
``dlrm.cc``/``nmt.cc``/``cnn.cc``/``candle_uno.cc``, as modules)."""

import numpy as np
import pytest

from flexflow_tpu.apps import (
    alexnet,
    candle_uno,
    cnn,
    dlrm,
    nmt,
    serve,
    transformer,
)
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore


def test_alexnet_app(capsys):
    assert alexnet.main(["-b", "4", "-i", "1", "-ll:tpu", "4",
                         "--image-size", "67"]) == 0
    out = capsys.readouterr().out
    assert "tp =" in out and "images/s" in out


def test_dlrm_app_reference_arch_flags(capsys):
    assert dlrm.main([
        "-b", "16", "-i", "2",
        "--arch-sparse-feature-size", "8",
        "--arch-embedding-size", "100-100-100-100",
        "--arch-mlp-bot", "8-16-8",
        "--arch-mlp-top", "40-16-1",
    ]) == 0
    assert "THROUGHPUT =" in capsys.readouterr().out


def test_dlrm_app_zc_dataset(capsys):
    """--zc-dataset routes batches through the device-resident loader
    (the reference's ZC staging + in-step gather, dlrm.cc:226-330)."""
    assert dlrm.main([
        "-b", "16", "-i", "2", "--zc-dataset",
        "--arch-sparse-feature-size", "8",
        "--arch-embedding-size", "100-100-100-100",
        "--arch-mlp-bot", "8-16-8",
        "--arch-mlp-top", "40-16-1",
    ]) == 0
    assert "THROUGHPUT =" in capsys.readouterr().out


def test_dlrm_app_loads_reference_pb_strategy(tmp_path, capsys):
    # A reference-format .pb driving table placement end-to-end.
    store = StrategyStore(8)
    store.set("embeddings", ParallelConfig(c=4))
    pb = tmp_path / "dlrm.pb"
    store.save_pb(str(pb))
    assert dlrm.main([
        "-b", "16", "-i", "1", "-s", str(pb),
        "--arch-sparse-feature-size", "8",
        "--arch-embedding-size", "100-100-100-100",
        "--arch-mlp-bot", "8-16-8",
        "--arch-mlp-top", "40-16-1",
    ]) == 0
    assert "THROUGHPUT =" in capsys.readouterr().out


def test_nmt_app(capsys):
    assert nmt.main([
        "-b", "32", "-i", "1", "--hidden", "16", "--vocab", "64",
        "--src-len", "8", "--tgt-len", "8",
    ]) == 0
    assert "time =" in capsys.readouterr().out


def test_candle_uno_app(capsys):
    assert candle_uno.main([
        "-b", "8", "-i", "1",
        "--dense-layers", "64-64", "--dense-feature-layers", "32",
    ]) == 0
    assert "THROUGHPUT =" in capsys.readouterr().out


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_candle_uno_app_resilient_superstep(tmp_path, capsys):
    """--resilient --save-every --steps-per-call wired together: the
    ResilientTrainer loop drives superstep dispatch with periodic
    checkpoints (runtime/resilience.py; RESILIENCE.md)."""
    assert candle_uno.main([
        "-b", "8", "-i", "4",
        "--dense-layers", "64-64", "--dense-feature-layers", "32",
        "--resilient", "--save-every", "2", "--steps-per-call", "2",
        "--ckpt-dir", str(tmp_path / "ck"),
    ]) == 0
    out = capsys.readouterr().out
    assert "THROUGHPUT =" in out and "restarts = 0" in out


def test_transformer_app_hybrid(capsys):
    assert transformer.main([
        "-b", "8", "-i", "1", "--seq", "64", "--vocab", "64",
        "--d-model", "32", "--heads", "2", "--layers", "1",
        "--dp", "2", "--sp", "2", "--tp", "2",
    ]) == 0
    assert "tokens/s" in capsys.readouterr().out


def test_transformer_app_moe_expert_parallel(capsys):
    """--experts N: switch-MoE blocks with the tp degree sharding
    experts (expert parallelism through the app surface)."""
    assert transformer.main([
        "-b", "4", "-i", "1", "--seq", "16", "--vocab", "64",
        "--d-model", "16", "--heads", "2", "--layers", "1",
        "--experts", "4", "--dp", "2", "--tp", "4", "-ll:tpu", "8",
    ]) == 0
    assert "tokens/s" in capsys.readouterr().out


def test_dlrm_app_reads_criteo_h5(tmp_path, capsys):
    """-d <criteo.h5> end-to-end through the reference H5 schema."""
    import h5py

    n, T = 128, 4
    r = np.random.default_rng(0)
    with h5py.File(tmp_path / "criteo.h5", "w") as f:
        f["X_int"] = r.standard_normal((n, 8)).astype(np.float32)
        f["X_cat"] = r.integers(0, 100, size=(n, T)).astype(np.int64)
        f["y"] = r.integers(0, 2, size=n).astype(np.float32)
    assert dlrm.main([
        "-b", "16", "-i", "2", "-d", str(tmp_path / "criteo.h5"),
        "--arch-sparse-feature-size", "8",
        "--arch-embedding-size", "100-100-100-100",
        "--arch-mlp-bot", "8-16-8",
        "--arch-mlp-top", "40-16-1",
    ]) == 0
    assert "THROUGHPUT =" in capsys.readouterr().out


def test_candle_app_reads_csv_dir(tmp_path, capsys):
    """-d <dir> with one CSV per input tensor."""
    from flexflow_tpu.models.candle_uno import CandleConfig, build_candle_uno

    ff = build_candle_uno(batch_size=4, candle=CandleConfig())
    r = np.random.default_rng(0)
    n = 16
    for t in ff.input_tensors:
        rows = "\n".join(
            ",".join(f"{v:.3f}" for v in r.standard_normal(t.shape[1]))
            for _ in range(n)
        )
        (tmp_path / f"{t.name}.csv").write_text(rows + "\n")
    assert candle_uno.main([
        "-b", "4", "-i", "2", "-d", str(tmp_path),
        "--dense-layers", "64-64", "--dense-feature-layers", "32",
    ]) == 0
    assert "THROUGHPUT =" in capsys.readouterr().out


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_nmt_app_pipeline_placement(capsys):
    """--pipeline: encoder on the first half of devices, decoder on the
    second (``nmt.cc:269-308``), driven through PipelineExecutor."""
    assert nmt.main([
        "-b", "16", "-i", "1", "--hidden", "16", "--vocab", "64",
        "--src-len", "8", "--tgt-len", "8", "--pipeline",
        "-ll:tpu", "8", "--microbatches", "2",
    ]) == 0
    assert "time =" in capsys.readouterr().out


def test_candle_uno_app_hybrid_granules(capsys):
    """The BASELINE multi-host pod hybrid: --granules 2 (DCN-outer
    mesh) + the default hybrid n x c trunk strategy + --optimizer adam."""
    assert candle_uno.main([
        "-b", "16", "-i", "1", "--granules", "2", "-ll:tpu", "8",
        "--optimizer", "adam",
        "--dense-layers", "64-64", "--dense-feature-layers", "32",
    ]) == 0
    assert "THROUGHPUT =" in capsys.readouterr().out


@pytest.mark.slow  # ~82s (auto picks a deep layer-wise pipeline);
# tier-1 keeps -s auto covered by the candle_uno e2e below
def test_alexnet_app_auto_strategy(capsys):
    """``-s auto`` (ISSUE 6): the execution-config autotuner runs at
    launch (search-then-run), prints the chosen config and the
    predicted-vs-measured step time, and the run completes under the
    winner — on every app via apps/common.py."""
    assert alexnet.main([
        "-b", "8", "-i", "2", "-ll:tpu", "8", "--image-size", "67",
        "-s", "auto", "--search-iters", "200",
    ]) == 0
    out = capsys.readouterr().out
    assert "auto: chose" in out
    assert "predicted" in out and "measured" in out
    assert "tp =" in out  # trained under the winner


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_candle_uno_app_auto_strategy_with_telemetry(tmp_path, capsys):
    """``-s auto`` + ``--telemetry``: the choice lands in the JSONL as
    a ``search`` event (reconstructable from the log alone), and a
    SECOND run calibrates from the first run's log via --calibration."""
    import json

    args = ["-b", "8", "-i", "2", "-s", "auto", "--search-iters", "100",
            "--dense-layers", "64-64", "--dense-feature-layers", "32",
            "--telemetry", str(tmp_path)]
    assert candle_uno.main(args) == 0
    logs = sorted(tmp_path.glob("run-*.jsonl"))
    assert logs
    events = [json.loads(l) for l in logs[-1].read_text().splitlines()]
    search_evs = [e for e in events if e["ev"] == "search"]
    assert len(search_evs) == 1
    ev = search_evs[0]
    assert ev["chosen"]["steps_per_call"] >= 1
    assert ev["baseline"]["label"] == "app-default"
    assert ev["predicted_ms"] > 0 and ev["candidates"] > 1
    # run 2: calibrated from run 1's telemetry log.
    assert candle_uno.main(
        args[:-2] + ["--calibration", str(logs[-1])]
    ) == 0
    assert "calibrated from" in capsys.readouterr().out


def test_alexnet_app_inline_search(capsys):
    """--search: launch-time automatic parallelization (the reference's
    offline simulator run folded into the app); the searched table must
    drive a real dry-run (or training) step table."""
    assert alexnet.main([
        "-b", "8", "-i", "1", "-ll:tpu", "8", "--image-size", "67",
        "--search-iters", "400", "--dry-run",
    ]) == 0
    out = capsys.readouterr().out
    assert "search: dp =" in out and "speedup =" in out
    assert "DRY RUN OK" in out


def test_alexnet_app_accum_steps(capsys):
    assert alexnet.main([
        "-b", "8", "-i", "1", "-ll:tpu", "4", "--accum-steps", "2",
        "--image-size", "67",
    ]) == 0
    assert "tp =" in capsys.readouterr().out


def test_reference_readme_alexnet_strategy_executes(capsys):
    """The reference README's example per-layer AlexNet strategy
    (README.md:42-51: mixed n / h x w / flat n=2 / linear c=3 on
    explicit device lists) loads from strategies/ and trains a real
    step on 4 virtual devices via the pipeline executor."""
    assert alexnet.main([
        "-b", "8", "-i", "1", "-ll:tpu", "4", "--image-size", "67",
        "-s", "strategies/alexnet_readme_4dev.json",
    ]) == 0
    assert "tp =" in capsys.readouterr().out


def test_shipped_strategy_files_load():
    """strategies/ mirrors the reference's example-strategies folder;
    every shipped file must parse (JSON and reference .pb)."""
    assert StrategyStore.load(
        "strategies/alexnet_readme_4dev.json"
    ).find("linear1").c == 3
    assert StrategyStore.load("strategies/dlrm_8chip.json").num_devices == 8
    pb = StrategyStore.load_pb("strategies/dlrm_8chip.pb", num_devices=8)
    assert pb.num_devices == 8


def test_serve_app_dry_run(capsys):
    """apps/serve.py --dry-run: the serving program table (prefill
    buckets, decode superstep, cache layout) validates via eval_shape
    with zero device compute — the DISABLE_COMPUTATION contract of the
    training apps, for the serving stack (ISSUE 7)."""
    assert serve.main([
        "--max-seq", "16", "--max-batch", "2", "--decode-steps", "4",
        "--vocab", "64", "--d-model", "32", "--heads", "2",
        "--layers", "1", "--dry-run",
    ]) == 0
    out = capsys.readouterr().out
    assert "DRY RUN OK" in out
    assert "decode k=4" in out and "prefill" in out
    assert "cache blk0_attn" in out


@pytest.mark.parametrize(
    "mod", [alexnet, cnn, dlrm, nmt, candle_uno, transformer, serve]
)
def test_apps_print_help(mod, capsys):
    """-h/--help prints the app docstring + common flag table and
    exits 0 instead of being swallowed by Legion-style pass-through."""
    with pytest.raises(SystemExit) as e:
        mod.main(["--help"])
    assert e.value.code == 0
    out = capsys.readouterr().out
    assert "Common flags" in out and "-ll:tpu" in out


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_alexnet_app_eval_iters(capsys):
    assert alexnet.main([
        "-b", "4", "-i", "1", "--image-size", "67", "--eval-iters", "2",
    ]) == 0
    out = capsys.readouterr().out
    assert "EVAL loss =" in out and "accuracy =" in out
