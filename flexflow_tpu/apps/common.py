"""Shared app harness.

The reference builds each model into its own Legion binary whose
``top_level_task`` parses flags, builds the graph, and drives the
training loop with fenced timing printouts (``dlrm.cc:77-167``,
``nmt.cc:44-83``, ``cnn.cc:42-129``).  Here every app is a
``python -m flexflow_tpu.apps.<name>`` entry sharing this harness:
FFConfig flags (``-e -b --lr --wd -d -s -ll:tpu -i``), strategy-file
loading (JSON, or the reference's ``.pb`` wire format via the native
codec), synthetic-or-dataset batches, and the reference's throughput
formulas.
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Optional

import jax
import numpy as np

from flexflow_tpu.config import FFConfig
from flexflow_tpu.data.loader import ArrayDataLoader, PrefetchLoader, synthetic_arrays
from flexflow_tpu.graph import FFModel
from flexflow_tpu.optim import AdamOptimizer, SGDOptimizer
from flexflow_tpu.parallel.strategy import AXES, StrategyStore
from flexflow_tpu.runtime.pipeline import PipelineExecutor, make_executor
from flexflow_tpu.runtime.trainer import Trainer


COMMON_FLAGS = """\
Common flags (reference: model.cc:729-785 + README.md flag table):
  -e/--epochs N         -b/--batch-size N    --lr F        --wd F
  -i/--iterations N     -d/--dataset PATH    -s FILE       -p/--print-freq N
  -ll:tpu N (devices)   -ll:cpu N (loaders)  --nodes N     --seed N
  --dtype float32|bfloat16   --optimizer sgd|adam   --momentum F
  --lr-schedule constant|cosine|step  --warmup N  --decay-steps N
  --min-lr F  --lr-gamma F (adam only)
  --profiling   --dry-run   --remat   --trace DIR   --ones-init   --zc-dataset
  --stream-dataset (out-of-core streaming tier: background chunk
                    reader -> windowed shuffle -> H2D prefetch; the
                    dataset is never host-materialized; DATA.md)
  --shuffle-window W (streaming shuffle width; 0 = whole host shard,
                    which matches the in-memory loader bit-for-bit)
  --shard-embeddings (row/vocab-range-shard embedding tables over the
                    mesh c axis: per-device HBM holds rows/c, the
                    lookup is the owning-shard gather + psum; the
                    capacity hatch for tables past FF_DEVICE_MEM_BYTES;
                    SHARDING.md)
  --accum-steps N   --microbatches N   --pipeline-schedule 1f1b|gpipe
  --pipeline-chunk C (scan C microbatches per stage program)
  --pipeline-compiled (ONE jitted program per pipeline step: fence-free
                       compiled IR; makes --steps-per-call fuse and
                       --resilient compose at K>1 on layer-wise
                       strategies)
  --granules N   --zero-opt
  --steps-per-call K (superstep: fused scan on full-mesh strategies
                      and compiled pipelines, one-fence-per-K
                      amortization on host-driven pipeline ones)
  --eval-iters N (held-out eval after training)   --clip-norm F
  --lazy-sparse-opt (row-sparse tables under momentum/Adam, lazy)
  --search | --search-iters N (inline strategy autotuning)
  -s auto (execution-config autotuner: strategy x stages x chunk x
           superstep k x compiled x accum searched against the
           telemetry-calibrated dispatch/fence cost model, winner
           applied to this run; SEARCH.md)
  --calibration PATH (telemetry JSONL file/dir feeding -s auto's
           dispatch/fence constants; default: latest run under the
           telemetry dir, else uncalibrated constants)
  --resilient (detection + checkpoint rollback + SIGTERM emergency save)
  --save-every N   --ckpt-dir PATH   --max-restarts N   --sync-ckpt
  --elastic (multi-host elastic mode: world-failure gate + world
             ledger + per-host batch shards; exits 76 on a torn world
             for the external supervisor — RESILIENCE.md, requires
             --resilient)
  --coordinator HOST:PORT   --num-processes N   --process-id I
             (jax.distributed bootstrap; JAX_* env fallback)
  --telemetry DIR (JSONL run telemetry + heartbeat + stall watchdog,
                   OBSERVABILITY.md)   --stall-deadline S (0 = no watchdog)
  --stall-notify-pid PID (stall escalation: SIGUSR1 to an external
                   supervisor pid on stall; never kills anything)"""


#: The checkout's compile-cache directory (ffcompile.sh's launcher and
#: .gitignore name the same path).  A fixed path: the directory is part
#: of the cache key, so one that moves never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".ffcache",
)


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at a stable place and
    return it: where ``JAX_COMPILATION_CACHE_DIR`` says when it is set
    (jax reads the variable itself — nothing is set in code), else
    ``<checkout>/.ffcache``.  Called at ``main`` time by every app
    and ``chip_smoke.py`` — never at package import, so
    the test suite (tests/conftest.py) stays off the cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def check_help(argv, doc: Optional[str]) -> None:
    """-h/--help: print the app's docstring (its specific flags) plus
    the common flag table, then exit 0 — FFConfig.parse_args otherwise
    ignores unknown flags Legion-style, which must not swallow a help
    request."""
    if "-h" in argv or "--help" in argv:
        if doc:
            print(doc.strip())
            print()
        print(COMMON_FLAGS)
        raise SystemExit(0)


def _pop(argv, flag, default, cast, what):
    """Extract an app-specific ``--flag V`` from argv (the FFConfig
    parser passes unknown flags through, Legion-style)."""
    if flag not in argv:
        return default
    i = argv.index(flag)
    try:
        val = cast(argv[i + 1])
    except (IndexError, ValueError):
        raise SystemExit(f"{flag} expects {what}")
    del argv[i:i + 2]
    return val


def pop_int(argv, flag, default):
    return _pop(argv, flag, default, int, "an integer")


def pop_float(argv, flag, default):
    return _pop(argv, flag, default, float, "a number")


def make_optimizer(cfg: FFConfig):
    """``--optimizer sgd|adam`` (sgd matches the reference's only
    optimizer, ``optimizer_kernel.cu:28-129``; adam is the rebuild's
    addition)."""
    if cfg.lr_schedule not in ("constant", "cosine", "step"):
        raise SystemExit(
            f"unknown --lr-schedule {cfg.lr_schedule!r} "
            f"(constant|cosine|step)"
        )
    if cfg.lr_schedule != "constant" and cfg.optimizer != "adam":
        raise SystemExit(
            "--lr-schedule requires --optimizer adam (SGD keeps the "
            "reference's fixed-lr semantics)"
        )
    if cfg.lr_schedule != "cosine" and (cfg.warmup_steps or cfg.min_lr):
        raise SystemExit(
            "--warmup/--min-lr apply to --lr-schedule cosine only"
        )
    if cfg.optimizer == "sgd":
        return SGDOptimizer(
            lr=cfg.learning_rate, momentum=cfg.momentum,
            weight_decay=cfg.weight_decay,
            lazy_sparse=cfg.lazy_sparse_optimizer,
        )
    if cfg.optimizer == "adam":
        return AdamOptimizer(
            lr=cfg.learning_rate, weight_decay=cfg.weight_decay,
            schedule=cfg.lr_schedule, warmup_steps=cfg.warmup_steps,
            decay_steps=cfg.decay_steps, min_lr=cfg.min_lr,
            gamma=cfg.lr_gamma,
            lazy_sparse=cfg.lazy_sparse_optimizer,
        )
    raise SystemExit(f"unknown --optimizer {cfg.optimizer!r} (sgd|adam)")


def load_image_dataset(cfg: FFConfig, image_size: int):
    """-d DIR for the CNN apps: folder-of-images ingestion (host
    decode + normalize, the reference's JPEG path, ``model.cu:45-257``).
    Returns the arrays dict, or None when no dataset is given — or
    under ``--dry-run``, which performs no compute and must not decode
    a whole image folder first."""
    if not cfg.dataset_path or cfg.dry_run:
        return None
    from flexflow_tpu.data.images import load_image_folder

    return load_image_folder(cfg.dataset_path, image_size)


def load_strategy(cfg: FFConfig, num_devices: int) -> Optional[StrategyStore]:
    """``-s file.pb`` reads the reference protobuf format; anything
    else is our JSON schema (``parallel/strategy.py``).  ``-s auto``
    returns None here — the app's default strategy stays the search
    BASELINE, and ``run_training`` replaces it with the
    execution-config autotuner's winner (search-then-run)."""
    if not cfg.strategy_file or cfg.strategy_file.lower() == "auto":
        return None
    if cfg.strategy_file.endswith(".pb"):
        return StrategyStore.load_pb(cfg.strategy_file, num_devices=num_devices)
    return StrategyStore.load(cfg.strategy_file, num_devices=num_devices)


def _dry_run(ff: FFModel, ex, strategy: Optional[StrategyStore]) -> Dict[str, float]:
    """``--dry-run``: the reference's DISABLE_COMPUTATION mode —
    exercise the whole graph/strategy/trace machinery with zero device
    compute (abstract_step = jax.eval_shape of the full train step)
    and print the op table.  Works for both full-mesh and layer-wise
    (PipelineExecutor) strategies."""
    store = strategy if strategy is not None else ex.strategy
    # For layer-wise strategies the authoritative placement is the
    # derived stage (unplaced ops inherit their producer's stage),
    # not the raw strategy table.
    stage_devices = {
        op.name: st.device_ids
        for st in getattr(ex, "stages", [])
        for op in st.ops
    }
    avals = ex.abstract_step()
    total = 0
    print(f"{'op':<24} {'strategy':<18} {'devices':<12} outputs")
    for op in ff.layers:
        pc = store.find(op.name)
        deg = "x".join(
            f"{a}{pc.degree(a)}" for a in AXES if pc.degree(a) > 1
        ) or "replicated"
        if op.name in stage_devices:
            devs = " ".join(str(d) for d in stage_devices[op.name])
        elif pc.device_ids is not None:
            devs = " ".join(str(d) for d in pc.device_ids)
        else:
            devs = "all"
        outs = ", ".join(f"{t.shape}" for t in op.outputs) or "(loss)"
        print(f"{op.name:<24} {deg:<18} {devs:<12} {outs}")
        for spec in op.param_specs().values():
            total += int(np.prod(spec.shape))
    metrics = avals[3]
    print(f"parameters = {total}")
    print(f"metrics = {sorted(metrics)}")
    # The program audit over the EXACT programs this run would build
    # (trace-only: AD-reachability, purity, dispatch accounting —
    # ANALYSIS.md); violations are named, not fatal, so a dry run
    # stays a diagnostic.
    from flexflow_tpu import analysis

    violations = analysis.audit_executor(ex)
    print(analysis.summary_line(violations))
    for v in violations:
        print(f"  {v}")
    from flexflow_tpu.runtime import telemetry as _telemetry

    _telemetry.current().emit(
        "analysis", clean=not violations,
        violations=[str(v) for v in violations],
    )
    print("DRY RUN OK (no device compute)")
    return {"parameters": float(total), "elapsed_s": 0.0,
            "samples_per_s": 0.0, "dry_run": True,
            "audit_violations": len(violations)}


def make_batch_fn(
    ff: FFModel,
    cfg: FFConfig,
    arrays: Optional[Dict[str, np.ndarray]] = None,
    int_high: Optional[Dict[str, int]] = None,
):
    """Deterministic per-step batches for the resilient loop:
    ``batch_fn(step)`` must return the SAME batch every time a step is
    (re)played, so rollback-replay after a fault reproduces the
    unfaulted trajectory bit for bit.  With a dataset, each step draws
    a with-replacement sample keyed by ``(seed, step)``; synthetic mode
    draws fresh random inputs under the same key (through the shared
    ``synthetic_host_batch`` rules, so the data distribution matches
    the non-resilient loop's)."""
    if arrays is not None:
        n = len(next(iter(arrays.values())))

        def batch_fn(step: int) -> Dict[str, np.ndarray]:
            rng = np.random.default_rng((cfg.seed, step))
            idx = rng.integers(0, n, size=cfg.batch_size)
            return {k: v[idx] for k, v in arrays.items()}

        return batch_fn

    from flexflow_tpu.data.loader import synthetic_host_batch

    def batch_fn(step: int) -> Dict[str, np.ndarray]:
        return synthetic_host_batch(
            ff, np.random.default_rng((cfg.seed, step)), int_high
        )

    return batch_fn


def _holdout_split(cfg: FFConfig, arrays: Dict[str, np.ndarray]):
    """--eval-iters with a dataset: reserve the trailing rows
    (batch-aligned, at most 20% of the data) as a true holdout BEFORE
    the training loader sees them.  Returns (train, eval) arrays."""
    n = len(next(iter(arrays.values())))
    want = min(cfg.eval_iters * cfg.batch_size,
               max(cfg.batch_size, n // 5))
    hold = (want // cfg.batch_size) * cfg.batch_size
    if 0 < hold < n:
        return ({k: v[: n - hold] for k, v in arrays.items()},
                {k: v[n - hold:] for k, v in arrays.items()})
    print("eval: dataset too small to hold out; evaluating in-sample")
    return arrays, arrays


def _run_eval(trainer: Trainer, params, state, cfg: FFConfig,
              eval_arrays: Optional[Dict[str, np.ndarray]]):
    """--eval-iters: read-only pass on the trained params (the
    reference computes metrics only inside the training backward,
    ``mse_loss.cu:61-112``).  One implementation shared by the plain
    and resilient paths so their EVAL numbers stay comparable: rows
    held out before training with a dataset, fresh synthetic batches
    per iteration otherwise."""
    if eval_arrays is not None:
        eval_batches = iter(ArrayDataLoader(
            eval_arrays, cfg.batch_size, shuffle=False,
            seed=cfg.seed + 1, nthreads=cfg.loaders_per_node,
        ))
    else:
        eval_batches = (
            trainer.synthetic_batch(seed=cfg.seed + 1 + i)
            for i in range(cfg.eval_iters)
        )
    ev = trainer.evaluate(params, state, eval_batches,
                          iterations=cfg.eval_iters)
    print(f"EVAL loss = {ev['loss']:.6f} "
          f"accuracy = {100.0 * ev['accuracy']:.2f}%")
    return ev


def _make_stream_loader(cfg: FFConfig, arrays, stream_source):
    """--stream-dataset: build the out-of-core streaming loader
    (data/stream.py; tiering table + determinism contract in DATA.md).
    ``stream_source`` is an app-provided StreamSource (HDF5 / trace);
    otherwise the app's arrays back an ArrayStreamSource."""
    if cfg.zc_dataset:
        raise SystemExit(
            "--stream-dataset (out-of-core) and --zc-dataset "
            "(whole-dataset device staging) are opposite ends of the "
            "data tiering table; pick one (DATA.md)"
        )
    from flexflow_tpu.data.stream import ArrayStreamSource, StreamingLoader

    src = stream_source
    if src is None:
        if arrays is None:
            raise SystemExit(
                "--stream-dataset needs a dataset: -d PATH, an "
                "app-provided stream source, or synthetic arrays"
            )
        src = ArrayStreamSource(arrays)
    return StreamingLoader(
        src, cfg.batch_size, shuffle=True, seed=cfg.seed,
        shuffle_window=cfg.shuffle_window,
    )


def _run_resilient(
    ff: FFModel,
    cfg: FFConfig,
    executor_factory,
    first_ex,
    arrays: Optional[Dict[str, np.ndarray]],
    int_high: Optional[Dict[str, int]],
    label: str,
    stream_source=None,
) -> Dict[str, float]:
    """--resilient: the ResilientTrainer loop (runtime/resilience.py) —
    failure detection, checkpoint rollback with deterministic replay,
    and SIGTERM/SIGINT emergency saves; composes with --steps-per-call
    (detection at the single per-superstep fence).  See RESILIENCE.md."""
    import time

    from flexflow_tpu.runtime.checkpoint import CheckpointManager
    from flexflow_tpu.runtime.resilience import FailurePolicy, ResilientTrainer

    if (isinstance(first_ex, PipelineExecutor) and cfg.steps_per_call > 1
            and not first_ex.superstep_fused):
        raise SystemExit(
            "--resilient --steps-per-call K>1 requires a fused "
            "superstep (full-mesh strategies, or a layer-wise one "
            "with --pipeline-compiled); host-driven layer-wise "
            "strategies compose with --resilient at steps-per-call 1"
        )
    if cfg.accum_steps > 1:
        raise SystemExit(
            "--resilient does not compose with --accum-steps yet"
        )
    if cfg.zc_dataset:
        raise SystemExit(
            "--resilient replays batches via a deterministic host "
            "batch_fn; --zc-dataset (device-resident staging) is not "
            "wired into that path yet"
        )
    if cfg.elastic and cfg.stream_dataset:
        raise SystemExit(
            "--elastic needs the world-invariant deterministic batch "
            "schedule; --stream-dataset's checkpointed cursor is "
            "host-local and does not survive an elastic resize"
        )
    eval_arrays = None
    if cfg.eval_iters > 0 and arrays is not None:
        # The same true holdout as the non-resilient path: EVAL numbers
        # stay comparable across the two modes.
        arrays, eval_arrays = _holdout_split(cfg, arrays)
    loader = batch_fn = None
    if cfg.stream_dataset:
        # The resilient loop drives the StreamingLoader DIRECTLY (no
        # PrefetchLoader wrapper; disk overlap still comes from the
        # reader thread) so the checkpointed consumer-side cursor
        # matches the step count exactly — rollback rewinds the stream
        # for bit-identical replay (DATA.md).
        loader = _make_stream_loader(cfg, arrays, stream_source)
    else:
        batch_fn = make_batch_fn(ff, cfg, arrays, int_high)
    iters = cfg.iterations * max(cfg.epochs, 1)
    ckdir = cfg.ckpt_dir or os.path.join(os.getcwd(), "ckpts")
    if cfg.elastic:
        # Multi-host elastic mode (RESILIENCE.md "Host loss & elastic
        # resize"): world-failure gate + world ledger + per-host slice
        # of the deterministic global batch schedule.  The generation
        # comes from the external supervisor (tools/elastic_rig.py env
        # protocol); a bare launch is generation 1.
        from flexflow_tpu.parallel.distributed import world as _world
        from flexflow_tpu.runtime.elastic import (
            LedgeredCheckpointManager,
            WorldLedger,
            classify_world_failure,
            worldify,
        )

        host_id, num_hosts = _world()
        generation = int(os.environ.get("FF_ELASTIC_GENERATION", "1"))
        ledger = WorldLedger(ckdir)
        ledger.claim(generation, num_hosts, primary=(host_id == 0))
        inner_factory = executor_factory

        def executor_factory():
            return worldify(inner_factory())

        if num_hosts > 1 and batch_fn is not None:
            from flexflow_tpu.data.stream import shard_for_host

            lo, hi = shard_for_host(cfg.batch_size, host_id, num_hosts)
            global_fn, gb = batch_fn, cfg.batch_size

            def batch_fn(step):
                # Every host derives the same deterministic GLOBAL
                # batch and serves its contiguous slice (process-major,
                # matching the DCN-outer mesh's batch layout) — the
                # schedule is world-invariant, so a resized world
                # replays the identical global trajectory.
                return {
                    k: v[lo:hi]
                    if getattr(v, "ndim", 0) and len(v) == gb else v
                    for k, v in global_fn(step).items()
                }

        policy = FailurePolicy(max_restarts=cfg.max_restarts,
                               fatal=classify_world_failure)
        ck = LedgeredCheckpointManager(
            ckdir, ledger, generation,
            async_save=cfg.async_checkpointing,
        )
    else:
        policy = FailurePolicy(max_restarts=cfg.max_restarts)
        ck = CheckpointManager(ckdir, async_save=cfg.async_checkpointing)
    # NOT a `with` block: in a multi-process world ``ck.close()`` is a
    # COLLECTIVE (orbax barriers the world), so running it while
    # unwinding a world failure would block forever against the dead
    # peer.  Close explicitly on the healthy path; a classified world
    # failure hard-exits with the supervisor contract's code instead.
    try:
        rt = ResilientTrainer(executor_factory, ck, policy=policy)
        start = time.perf_counter()
        try:
            out = rt.fit(
                iterations=iters,
                batch_fn=batch_fn,
                save_every=cfg.save_every,
                seed=cfg.seed,
                steps_per_call=cfg.steps_per_call,
                loader=loader,
            )
        finally:
            if loader is not None:
                loader.close()
        elapsed = time.perf_counter() - start
        completed = len(out["losses"])
        throughput = completed * cfg.batch_size / max(elapsed, 1e-9)
        print(f"time = {elapsed:.4f}s")
        print(f"tp = {throughput:.2f} samples/s")
        print(f"ELAPSED TIME = {elapsed:.4f}s")
        print(f"THROUGHPUT = {throughput:.2f} {label}/s")
        print(f"restarts = {out['restarts']}")
        if completed == 0:
            # A restarted job whose checkpoint already reached the
            # target: nothing ran, nothing to evaluate meaningfully.
            print(f"resumed at step {out['step']}: already complete")
        if out["preempted"]:
            # Clean exit BEFORE any eval pass: the kill-grace window is
            # for the emergency save, not a metrics run on half-trained
            # params.  The scheduler restarts us and the same
            # --ckpt-dir resumes from the emergency snapshot.
            print(f"PREEMPTED: emergency checkpoint at step {out['step']}")
            raise SystemExit(0)
        stats = {
            "elapsed_s": elapsed,
            "samples_per_s": throughput,
            "iterations": out["step"],
            "batch_size": cfg.batch_size,
            "loss": out["loss"],
            "restarts": out["restarts"],
            # Steps executed by THIS process (a checkpoint-resumed run
            # reports its absolute step in "iterations"): the right
            # denominator for this run's elapsed_s.
            "steps_this_run": completed,
        }
        if "telemetry" in out:
            stats["telemetry"] = out["telemetry"]
        if cfg.eval_iters > 0 and rt.executor is not None:
            stats["eval"] = _run_eval(
                Trainer(rt.executor), out["params"], out["state"], cfg,
                eval_arrays,
            )
    except BaseException as e:
        if cfg.elastic:
            import sys

            from flexflow_tpu.runtime import telemetry as _telemetry
            from flexflow_tpu.runtime.elastic import (
                EXIT_WORLD_FAILURE,
                classify_world_failure as _classify,
            )

            if _classify(e):
                # The world died under us: record it (the log is the
                # postmortem evidence), skip the collective close, and
                # hand the resize decision to the external supervisor
                # via the exit-code contract.
                _telemetry.current().emit(
                    "fault", kind="world_failure",
                    error=f"{type(e).__name__}: {e}"[:500],
                )
                print(f"elastic: world failure ({type(e).__name__}); "
                      f"exiting {EXIT_WORLD_FAILURE} for the supervisor",
                      file=sys.stderr)
                sys.stderr.flush()
                os._exit(EXIT_WORLD_FAILURE)
        ck.close()
        raise
    ck.close()
    return stats


def run_training(
    ff: FFModel,
    cfg: FFConfig,
    strategy: Optional[StrategyStore] = None,
    int_high: Optional[Dict[str, int]] = None,
    label: str = "samples",
    num_samples: Optional[int] = None,
    arrays: Optional[Dict[str, np.ndarray]] = None,
    stream_source=None,
) -> Dict[str, float]:
    """Build the executor, feed batches, run ``cfg.epochs x
    cfg.iterations`` fenced steps, and print the reference throughput
    lines (``cnn.cc:128-129``, ``dlrm.cc:159-166``).

    ``arrays`` is an app-loaded dataset (``-d``); otherwise synthetic
    arrays are generated when ``num_samples`` is set, else one fixed
    device-resident synthetic batch (the reference's syntheticInput).

    With ``--telemetry DIR`` the whole run — executor build, training,
    checkpoint I/O, the resilient loop's faults/rollbacks — reports
    into one run-scoped JSONL event stream (OBSERVABILITY.md).
    """
    from flexflow_tpu.runtime import telemetry as _telemetry

    enable_compile_cache()
    if (cfg.elastic or cfg.coordinator_address
            or cfg.num_processes is not None
            or cfg.process_id is not None):
        # Bring the world up BEFORE telemetry opens (the run_start
        # fingerprint records process_id/process_count) and before any
        # backend touch fixes the device set.
        from flexflow_tpu.parallel.distributed import initialize

        initialize(cfg.coordinator_address, cfg.num_processes,
                   cfg.process_id)
    if cfg.elastic and not cfg.resilient:
        raise SystemExit(
            "--elastic is the multi-host arm of the resilient loop; "
            "add --resilient (RESILIENCE.md 'Host loss & elastic "
            "resize')"
        )
    with _telemetry.maybe_run(cfg, meta={"app": label}):
        return _run_training(ff, cfg, strategy, int_high, label,
                             num_samples, arrays, stream_source)


def _resolve_calibration(cfg: FFConfig):
    """Dispatch/fence calibration for ``-s auto``: ``--calibration
    PATH`` (file or dir) wins; else the latest run-*.jsonl under the
    telemetry dir (EXCLUDING the active run's own file, which holds no
    steps yet); else the uncalibrated measured-host defaults."""
    from flexflow_tpu.runtime import telemetry as _telemetry
    from flexflow_tpu.search import Calibration

    active = _telemetry.current().path
    if cfg.search_calibration:
        if os.path.isdir(cfg.search_calibration):
            # --calibration pointed at a DIRECTORY (possibly the
            # telemetry dir itself): the active run's just-opened file
            # is the newest there and holds no steps yet — same
            # exclusion as the default path below.
            return Calibration.from_dir(cfg.search_calibration,
                                        exclude=active)
        return Calibration.from_jsonl(cfg.search_calibration)
    d = cfg.telemetry_dir or os.environ.get("FF_TELEMETRY_DIR")
    if d:
        return Calibration.from_dir(d, exclude=active)
    return Calibration()


def _auto_execution_search(ff: FFModel, cfg: FFConfig,
                           default_strategy: Optional[StrategyStore],
                           ndev: int):
    """``-s auto``: search the FULL execution-config space (strategy x
    stage partition x chunk x superstep k x compiled x accum) against
    the telemetry-calibrated dispatch/fence cost model, apply the
    winner to this run, and emit a ``search`` telemetry event so the
    choice is reconstructable from the log (SEARCH.md).  Returns
    ``(store, chosen ExecutionConfig)``."""
    from flexflow_tpu.runtime import telemetry as _telemetry
    from flexflow_tpu.search import search_execution_config
    from flexflow_tpu.search.execution import ExecutionConfig

    cal = _resolve_calibration(cfg)
    base_store = default_strategy or StrategyStore.data_parallel(ndev)
    n_stages = 1
    if base_store.layer_wise:
        from flexflow_tpu.runtime.pipeline import derive_stages

        n_stages = len(derive_stages(ff, base_store))
    baseline = ExecutionConfig(
        store=base_store, microbatches=cfg.microbatches,
        chunk=cfg.pipeline_chunk, steps_per_call=cfg.steps_per_call,
        compiled=cfg.pipeline_compiled, accum_steps=cfg.accum_steps,
        schedule=cfg.pipeline_schedule,  # survives a baseline win
        stages=n_stages, label="app-default",
    )
    res = search_execution_config(
        ff, ndev,
        iters=cfg.search_iters if cfg.search_iters >= 0 else 20_000,
        seed=cfg.seed,
        calibration=cal, clip_norm=cfg.clip_norm,
        accum_steps=cfg.accum_steps, resilient=cfg.resilient,
        allow_layer_wise=not (cfg.zc_dataset or cfg.granules > 1),
        baseline=baseline,
    )
    choice = res.best
    if choice is res.baseline:
        print("auto: the app's default config already wins the "
              "searched space; keeping it")
    elif default_strategy is not None:
        print("auto: overriding the app's default strategy")
    print(f"auto: chose {choice.describe()}")
    print(f"auto: predicted {choice.predicted_ms:.3f} ms/step vs "
          f"default {res.baseline.predicted_ms:.3f} ms/step "
          f"({res.speedup:.2f}x simulated); {cal.describe()}; "
          f"searched {len(res.candidates)} configs in {res.wall_s:.1f}s")
    choice.apply_to(cfg)
    _telemetry.current().emit(
        "search", chosen=choice.to_json(),
        baseline=res.baseline.to_json(),
        predicted_ms=round(choice.predicted_ms, 4),
        baseline_predicted_ms=round(res.baseline.predicted_ms, 4),
        dispatch_ms=round(res.calibration.dispatch_ms, 4),
        fence_ms=round(res.calibration.fence_ms, 4),
        compute_scale=round(res.compute_scale, 6),
        calibrated=res.calibration.calibrated,
        calibration_source=res.calibration.source,
        candidates=len(res.candidates),
        wall_s=round(res.wall_s, 3),
    )
    return choice.store, choice


def _fold_auto_stats(stats: Dict[str, float], choice) -> Dict[str, float]:
    """``-s auto`` epilogue: predicted-vs-measured ms/step, printed and
    folded into the stats dict under ``"search"``.  The denominator is
    the steps THIS process ran (``steps_this_run`` on the resilient
    path — a resumed run's absolute "iterations" would shrink the
    measured number by the checkpointed prefix it never executed)."""
    if choice is None:
        return stats
    steps = stats.get("steps_this_run", stats.get("iterations"))
    if not steps:
        return stats
    measured = stats["elapsed_s"] / steps * 1e3
    print(f"auto: predicted {choice.predicted_ms:.3f} ms/step, "
          f"measured {measured:.3f} ms/step")
    stats["search"] = {
        "config": choice.describe(),
        "predicted_ms_per_step": round(choice.predicted_ms, 4),
        "measured_ms_per_step": round(measured, 4),
    }
    return stats


def _run_training(
    ff: FFModel,
    cfg: FFConfig,
    strategy: Optional[StrategyStore],
    int_high: Optional[Dict[str, int]],
    label: str,
    num_samples: Optional[int],
    arrays: Optional[Dict[str, np.ndarray]],
    stream_source=None,
) -> Dict[str, float]:
    ndev = cfg.resolve_num_devices()
    if strategy is None:
        strategy = load_strategy(cfg, ndev)
    auto_choice = None
    if (cfg.strategy_file or "").lower() == "auto":
        # -s auto: execution-config autotuning, search-then-run — the
        # app's default strategy (still in ``strategy``) is the
        # baseline the searched config must beat.
        strategy, auto_choice = _auto_execution_search(
            ff, cfg, strategy, ndev
        )
    if cfg.search_iters > 0 and cfg.strategy_file is None:
        # --search: inline automatic parallelization — the reference's
        # offline simulator+MCMC run (scripts/simulator.cc) folded into
        # app launch, its emitted table applied directly.
        from flexflow_tpu.search import search_strategy

        res = search_strategy(ff, num_devices=ndev, iters=cfg.search_iters,
                              seed=cfg.seed)
        if strategy is not None:
            print("search: overriding the app's default strategy")
        print(f"search: dp = {res.dp_time_us:.1f} us, best = "
              f"{res.best_time_us:.1f} us, speedup = {res.speedup:.2f}x "
              f"(simulated, {cfg.search_iters} MCMC iters)")
        strategy = res.store
    mesh_plan = None
    if cfg.granules > 1:
        # Multi-host pod layout: DCN-spanning axes outermost so data
        # parallelism rides the slow links and tp/sp stay on ICI.
        from flexflow_tpu.parallel.distributed import build_hybrid_mesh_plan

        mesh_plan = build_hybrid_mesh_plan(cfg.granules)
    ex = make_executor(
        ff,
        strategy,
        config=cfg,
        optimizer=make_optimizer(cfg),
        mesh_plan=mesh_plan,
        microbatches=cfg.microbatches,
        schedule=cfg.pipeline_schedule,
        chunk=cfg.pipeline_chunk,
        compiled=cfg.pipeline_compiled,
        accum_steps=cfg.accum_steps,
    )
    if isinstance(ex, PipelineExecutor):
        if mesh_plan is not None:
            raise SystemExit(
                "--granules (hybrid mesh) and device-subset placement "
                "cannot combine yet"
            )
        if cfg.zc_dataset:
            raise SystemExit(
                "--zc-dataset stages onto the full mesh; layer-wise "
                "(device-subset) strategies use the host loader path"
            )
    if cfg.dry_run:
        return _dry_run(ff, ex, strategy)
    if arrays is None and cfg.dataset_path:
        raise SystemExit(
            "this app has no -d loader; drop -d for synthetic input"
        )
    if arrays is None and num_samples is not None:
        arrays = synthetic_arrays(ff, num_samples, seed=cfg.seed,
                                  int_high=int_high)
    if cfg.resilient:
        def executor_factory(_first=[ex]):
            # First call reuses the executor built above (strategy
            # validation already ran on it); recovery from a raised
            # fault rebuilds fresh (new mesh/jit).
            if _first:
                return _first.pop()
            return make_executor(
                ff, strategy, config=cfg, optimizer=make_optimizer(cfg),
                mesh_plan=mesh_plan, microbatches=cfg.microbatches,
                schedule=cfg.pipeline_schedule, chunk=cfg.pipeline_chunk,
                compiled=cfg.pipeline_compiled,
                accum_steps=cfg.accum_steps,
            )

        return _fold_auto_stats(
            _run_resilient(ff, cfg, executor_factory, ex, arrays,
                           int_high, label, stream_source),
            auto_choice,
        )
    trainer = Trainer(ex)
    batches = None
    eval_arrays = None
    if cfg.eval_iters > 0 and arrays is not None:
        arrays, eval_arrays = _holdout_split(cfg, arrays)
    if cfg.stream_dataset:
        # --stream-dataset: three-stage disk -> host-batch -> device
        # pipeline.  The StreamingLoader's reader thread double-buffers
        # chunk windows ahead of the PrefetchLoader's H2D stage; its
        # queue_depths gauge nests into the prefetcher's, so
        # --telemetry shows starvation at BOTH queue edges (DATA.md).
        batches = PrefetchLoader(
            iter(_make_stream_loader(cfg, arrays, stream_source)),
            ex.shard_batch,
        )
    elif arrays is not None:
        if cfg.zc_dataset:
            # --zc-dataset: the reference DLRM's zero-copy staging —
            # whole dataset device-resident, per-step on-device gather
            # (dlrm.cc:226-330); only an index vector crosses H2D.
            from flexflow_tpu.data.loader import DeviceResidentLoader

            source = iter(DeviceResidentLoader(
                arrays, cfg.batch_size, ex, shuffle=True, seed=cfg.seed))
        else:
            source = iter(ArrayDataLoader(arrays, cfg.batch_size,
                                          shuffle=True, seed=cfg.seed,
                                          nthreads=cfg.loaders_per_node))
        # Background prefetch overlaps the host/gather dispatch path
        # with the device step (the reference's double-buffered ZC
        # staging); shard_batch is a no-op on already-placed batches.
        batches = PrefetchLoader(source, ex.shard_batch)
    iters = cfg.iterations * max(cfg.epochs, 1)
    import contextlib

    ckpt_ctx = contextlib.nullcontext()
    if cfg.ckpt_dir or cfg.save_every > 0:
        # --ckpt-dir / --save-every without --resilient: plain periodic
        # saves + resume through Trainer.fit (and its SIGTERM emergency
        # save).  Same ./ckpts default as the resilient path.
        from flexflow_tpu.runtime.checkpoint import CheckpointManager

        ckpt_ctx = CheckpointManager(
            cfg.ckpt_dir or os.path.join(os.getcwd(), "ckpts"),
            async_save=cfg.async_checkpointing,
        )
    with ckpt_ctx as ck:
        stats = trainer.fit(iterations=iters, batches=batches, warmup=1,
                            log_every=cfg.print_freq,
                            checkpoint=ck,  # None from the nullcontext
                            save_every=cfg.save_every,
                            accum_steps=cfg.accum_steps,
                            steps_per_call=cfg.steps_per_call)
    print(f"ELAPSED TIME = {stats['elapsed_s']:.4f}s")
    print(f"THROUGHPUT = {stats['samples_per_s']:.2f} {label}/s")
    if stats.get("preempted"):
        print(f"PREEMPTED: emergency checkpoint at step "
              f"{stats['checkpoint_step']}")
        raise SystemExit(0)
    if cfg.eval_iters > 0:
        params, _, state = trainer.final
        stats["eval"] = _run_eval(trainer, params, state, cfg, eval_arrays)
    return _fold_auto_stats(stats, auto_choice)
