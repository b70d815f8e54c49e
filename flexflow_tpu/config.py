"""Run configuration and CLI flag parsing.

Mirrors the reference's two-tier flag system (reference:
``src/runtime/model.cc:695-785`` defaults + ``parse_args``, and
``include/config.h:50-77`` for the FFConfig fields).  The Legion
``-ll:gpu`` worker count becomes ``-ll:tpu`` (number of TPU chips to use;
defaults to all visible devices), and the strategy file is JSON rather
than protobuf (see ``flexflow_tpu/parallel/strategy.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence


@dataclasses.dataclass
class FFConfig:
    """Global training configuration.

    Field defaults mirror ``FFConfig::FFConfig`` (reference:
    ``src/runtime/model.cc:695-708``): batch 64, lr 0.01, wd 0.0001,
    1 epoch, profiling off.
    """

    epochs: int = 1
    batch_size: int = 64
    learning_rate: float = 0.01
    weight_decay: float = 0.0001
    iterations: int = 10
    # Device topology.  num_devices == the reference's workersPerNode *
    # numNodes (reference: model.cc:765-779 re-reads -ll:gpu / --nodes).
    num_devices: int = 0  # 0 = use all visible jax devices
    num_nodes: int = 1
    # Host data-loader threads (the reference's -ll:cpu loadersPerNode,
    # model.cc:765-779); 0 = auto (min(8, cores)).
    loaders_per_node: int = 0
    # Data / strategy files.
    dataset_path: Optional[str] = None  # -d; None => synthetic input
    # -s FILE loads a strategy table (JSON, or the reference .pb); the
    # special value ``-s auto`` runs the execution-config autotuner at
    # launch instead (search/execution.py): strategy x stage partition
    # x pipeline chunk x superstep k x compiled x accum searched
    # against the telemetry-calibrated dispatch/fence cost model, the
    # winner applied to this run (search-then-run; SEARCH.md).
    strategy_file: Optional[str] = None  # -s
    # -p/--print-freq: metric-print frequency in iterations (reference
    # README.md flag table; default 10 there, 0 = quiet here to keep
    # benchmark stdout clean).
    print_freq: int = 0
    profiling: bool = False
    # Numerics.  Activations/params follow the input tensors' dtype,
    # which defaults to this (FFModel.create_tensor).
    compute_dtype: str = "float32"  # "bfloat16" for the TPU fast path
    # Rematerialization: recompute per-op activations in the backward
    # pass instead of keeping them in HBM (jax.checkpoint per layer) —
    # trades MXU FLOPs for HBM footprint on memory-bound models.
    remat: bool = False
    seed: int = 1234  # the reference NMT fixed seed (nmt/rnn.cu:345-349)
    # Synthetic input (reference: config.h:73 syntheticInput)
    synthetic_input: bool = True
    # Optimizer selection (reference ships SGD only; Adam is the TPU
    # rebuild's addition — see flexflow_tpu/optim.py).
    optimizer: str = "sgd"
    momentum: float = 0.9
    # --lr-schedule constant|cosine|step (+ --warmup/--decay-steps/
    # --min-lr): Adam learning-rate schedules; the reference trains at
    # a fixed lr, and SGD keeps those semantics.
    lr_schedule: str = "constant"
    warmup_steps: int = 0
    decay_steps: int = 10_000
    min_lr: float = 0.0
    lr_gamma: float = 0.1  # --lr-gamma: step-schedule decay factor
    # Gradient accumulation: microbatches per optimizer step
    # (Executor.accum_train_step).
    accum_steps: int = 1
    # --steps-per-call K: superstep execution — K full train steps
    # compiled into ONE jitted lax.scan dispatch with a single host
    # readback fence per superstep (Executor.build_superstep).  The
    # dispatch-overhead amortization path (what a dispatch costs is
    # not measured on the chip, ROADMAP A2); full-mesh strategies only
    # (pipeline strategies refuse).  1 = off; Trainer clamps at
    # MAX_STEPS_PER_CALL.
    steps_per_call: int = 1
    # Row-sparse embedding updates: differentiate w.r.t. gathered rows
    # and scatter the row grads into the (donated) table instead of
    # materializing a table-sized dense gradient.  Exact plain-SGD
    # numerics; applies only when the optimizer reports
    # ``supports_sparse_rows`` (see flexflow_tpu/ops/base.py).
    sparse_embedding_updates: bool = True
    # --shard-embeddings: row/vocab-range-shard embedding TABLES over
    # the mesh c axis (SHARDING.md "Sharded embedding tables") —
    # per-device HBM holds rows/c of each table instead of a full
    # replica, the lookup becomes the owning-shard gather + psum
    # (never a full-table all-gather), and the row-sparse backward
    # stays a local per-shard scatter-add.  The capacity escape hatch
    # when a replicated table exceeds FF_DEVICE_MEM_BYTES; needs a
    # strategy c degree on the embedding op to take effect
    # (apps/dlrm's default strategy supplies one).
    shard_embeddings: bool = False
    # Hybrid mesh granules: number of slow-interconnect islands for
    # build_hybrid_mesh_plan (0/1 = flat single-slice mesh).
    granules: int = 0
    # Pipeline microbatches for device-subset (layer-wise) strategies.
    microbatches: int = 1
    # --pipeline-schedule 1f1b|gpipe: stage-program dispatch order
    # (1f1b bounds live activations per stage; gpipe = fill then drain).
    pipeline_schedule: str = "1f1b"
    # --pipeline-chunk C: microbatch chunk factor for layer-wise
    # strategies — each stage's fwd/bwd runs as ONE jitted lax.scan
    # over C stacked microbatches, cutting host programs per step from
    # 2*S*m to 2*S*ceil(m/C) (the pipeline's dispatch-amortization
    # knob; C=m is dispatch-minimal, numerics bit-identical across C).
    # Memory: the 1F1B live-activation bound becomes chunk-granular
    # ((S-si)*C microbatches per stage).
    pipeline_chunk: int = 1
    # --pipeline-compiled: compile the WHOLE multi-stage pipeline step
    # into ONE jitted program on a shared stage mesh (every stage's
    # microbatch scan, the boundary exchange, clip-norm and the
    # optimizer updates — fence-free compiled IR; host programs per
    # step drop from 2*S*ceil(m/C) to 1).  Makes layer-wise strategies
    # genuinely superstep-capable: --steps-per-call K then fuses K
    # steps into one dispatch + one device_get (superstep_mode
    # "fused"), and --resilient composes at K>1.  Numerics are
    # bit-identical to the host-driven path (the fallback + numerics
    # oracle, kept; unsupported combinations fall back loudly).
    pipeline_compiled: bool = False
    # Compute-free graph/shape validation (the reference's
    # DISABLE_COMPUTATION build, ``ops.h:19``): trace the full train
    # step under jax.eval_shape and print the op/param table, running
    # nothing on any device.
    dry_run: bool = False
    # --zc-dataset: stage the whole dataset on device once (replicated)
    # and gather batches on device per step — the reference DLRM's
    # zero-copy staging + in-step gather (dlrm.cc:226-330); use when
    # the dataset fits HBM.  Off = host gather + prefetched H2D.
    zc_dataset: bool = False
    # --stream-dataset: drive training from the out-of-core streaming
    # data plane (data/stream.py, DATA.md) — a background reader thread
    # pulls chunked windows from the source (HDF5 / synthetic / trace)
    # ahead of the H2D prefetch stage; the dataset is never
    # materialized on the host.  Composes with --resilient (the loader
    # cursor+rng checkpoint as a ``loader`` item; rollback rewinds the
    # stream for bit-identical replay).
    stream_dataset: bool = False
    # --shuffle-window W: windowed-shuffle width for --stream-dataset.
    # 0 (default) = whole host shard, which matches ArrayDataLoader
    # bit-for-bit (composed epoch permutations); W < shard bounds
    # shuffle memory to W rows with per-window memoryless shuffles
    # (the out-of-core mode; determinism contract in DATA.md).
    shuffle_window: int = 0
    # --search: run the MCMC strategy autotuner at launch when no -s
    # file is given (the reference runs its simulator offline and feeds
    # the result back via -s; this folds the two steps into one run).
    # Value = MCMC iterations; 0 = off; -1 = unset.  Also the MCMC
    # budget of the ``-s auto`` execution-config search, where unset
    # means the 20k default and an explicit 0 disables the MCMC leg
    # (DP + stage-partition candidates only).
    search_iters: int = -1
    # --calibration PATH: dispatch/fence calibration source for the
    # ``-s auto`` execution search — a telemetry JSONL file (or a
    # directory holding run-*.jsonl, latest wins).  Unset: the latest
    # run under --telemetry DIR / FF_TELEMETRY_DIR when present,
    # else the uncalibrated measured-host defaults
    # (search/cost_model.Calibration).
    search_calibration: Optional[str] = None
    # --trace DIR: capture an XProf/TensorBoard trace of the timed
    # training loop (the fused step as XLA executes it — fusions,
    # collectives, device timelines; view with tensorboard --logdir).
    trace_dir: Optional[str] = None
    # --ones-init: deterministic-parameter mode — every parameter
    # initializes to ones for reproducible numerics across runs and
    # strategies (the reference's ``#ifdef PARAMETER_ALL_ONES``,
    # ``conv_2d.cu:394-399``).
    parameter_all_ones: bool = False
    # --clip-norm F: clip gradients to a global L2 norm before the
    # optimizer step (0 = off).  Applied to the fully-reduced gradient
    # tree, so the clip decision is identical under every sharding.
    # With row-sparse embedding updates the exact norm comes from
    # per-unique-id segment sums of the row cotangents (never a
    # table-sized gradient).
    clip_norm: float = 0.0
    # --lazy-sparse-opt: keep the row-sparse embedding path under
    # momentum SGD / Adam with torch-SparseAdam lazy semantics (decay
    # and moments advance only for rows the step touches; documented
    # deviation from the dense update).  Off = those optimizers force
    # dense table gradients.
    lazy_sparse_optimizer: bool = False
    # --eval-iters N: after training, run N read-only evaluation
    # batches and print loss/accuracy (the reference computes metrics
    # only inside the training backward, ``mse_loss.cu:61-112``; a
    # held-out eval pass is this rebuild's addition).
    eval_iters: int = 0
    # --resilient: drive training through ResilientTrainer — failure
    # detection (raised + non-finite loss), checkpoint rollback with
    # deterministic batch replay, and SIGTERM/SIGINT emergency saves
    # (runtime/resilience.py; RESILIENCE.md).  Composes with
    # --steps-per-call: detection happens at the single per-superstep
    # fence.  Layer-wise (pipeline) strategies compose at
    # --steps-per-call 1 (per-stage {si: ...} trees checkpoint like any
    # pytree); the fused superstep path stays full-mesh only.
    resilient: bool = False
    # --save-every N: checkpoint every N steps (0 = end-of-run only).
    # On the superstep path saves land at the first superstep boundary
    # past each multiple; also the finiteness-fence period of the
    # resilient per-step path (silent-failure detection latency).
    save_every: int = 0
    # --ckpt-dir PATH: checkpoint directory for --resilient /
    # --save-every (default ./ckpts).  A restarted run with the same
    # dir resumes from the latest (or emergency) snapshot.
    ckpt_dir: Optional[str] = None
    # --max-restarts N: crash-loop budget — consecutive recoveries
    # without durable progress before giving up (FailurePolicy).
    max_restarts: int = 3
    # --elastic: multi-host elastic mode (RESILIENCE.md "Host loss &
    # elastic resize").  Requires --resilient.  Arms the world-failure
    # gate (a dead peer/coordinator re-raises IMMEDIATELY instead of
    # burning in-process restarts), claims the checkpoint dir's world
    # ledger (single-writer rule), shards the deterministic batch
    # schedule per host, and exits with EXIT_WORLD_FAILURE (76) on a
    # torn world so an EXTERNAL supervisor (tools/elastic_rig.py, or a
    # real scheduler speaking the same env protocol) can relaunch the
    # survivors at the resized world against the same --ckpt-dir.
    elastic: bool = False
    # --coordinator HOST:PORT / --num-processes N / --process-id I:
    # explicit jax.distributed bootstrap (parallel/distributed.py
    # initialize()); fall back to JAX_COORDINATOR_ADDRESS /
    # JAX_NUM_PROCESSES / JAX_PROCESS_ID, then cluster auto-detection.
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    # --sync-ckpt: disable async checkpointing (saves then block the
    # train loop until durable; default is non-blocking background
    # writes with a flush fence at restore/exit).
    async_checkpointing: bool = True
    # --telemetry DIR: structured run telemetry (runtime/telemetry.py;
    # OBSERVABILITY.md) — one JSONL event stream per run under DIR
    # (per-step/superstep wall time + loss, fences, pipeline
    # host-program counts, checkpoint I/O, faults/rollbacks/replays),
    # step-time percentiles folded into the fit stats under
    # "telemetry", a heartbeat file (DIR/heartbeat, or
    # FF_HEARTBEAT_FILE, for an external supervisor) and the
    # stall watchdog.  None = off: zero overhead, no extra fences,
    # stats/numerics bit-identical.  FF_TELEMETRY_DIR in the
    # environment enables it without touching flags.
    telemetry_dir: Optional[str] = None
    # --stall-deadline S: watchdog deadline in seconds — a gap between
    # telemetry heartbeats (every completed step and fence edge)
    # exceeding it logs ONE loud last-known-event warning + a `stall`
    # event (a device_get that never returns is otherwise silent).
    # Observe-and-warn only, NEVER kills.  0 disables the monitor
    # thread; only active when telemetry is on.
    stall_deadline_s: float = 300.0
    # --stall-notify-pid PID: watchdog ESCALATION hook — on a stall the
    # watchdog additionally sends SIGUSR1 to this external supervisor
    # pid, so an operator process learns about a silent stall without
    # polling the JSONL.  The watchdog still NEVER kills anything,
    # least of all its own process; notification of an external
    # observer is the only action.  0 = off.  FF_STALL_NOTIFY_PID in
    # the environment sets it without flags.
    stall_notify_pid: int = 0
    # --zero-opt: ZeRO-1-style optimizer-state sharding — each
    # parameter's optimizer moments (Adam m/v, SGD momentum) shard
    # their leading dim across the mesh axes the op's strategy assigns
    # to data parallelism, instead of replicating with the weights.
    # GSPMD gathers the update slices; numerics are unchanged (pinned
    # by tests/test_zero_opt.py).  Full-mesh Executor only.
    zero_sharded_optimizer: bool = False

    @staticmethod
    def parse_args(argv: Sequence[str]) -> "FFConfig":
        """Parse the reference's CLI surface.

        Flags (reference ``src/runtime/model.cc:729-785``):
        ``-e`` epochs, ``-b`` batch size, ``--lr`` learning rate,
        ``--wd`` weight decay, ``-d`` dataset, ``-s`` strategy file,
        ``-ll:tpu`` devices (was ``-ll:gpu``), ``--nodes``,
        ``--profiling``, ``-i``/``--iterations``.
        Unknown flags are ignored (Legion-style pass-through).
        """
        cfg = FFConfig()
        i = 0
        argv = list(argv)
        while i < len(argv):
            a = argv[i]

            def _next() -> str:
                nonlocal i
                i += 1
                if i >= len(argv):
                    raise ValueError(f"flag {a} expects a value")
                return argv[i]

            if a == "-e" or a == "--epochs":
                cfg.epochs = int(_next())
            elif a == "-b" or a == "--batch-size":
                cfg.batch_size = int(_next())
            elif a == "--lr" or a == "--learning-rate":
                cfg.learning_rate = float(_next())
            elif a == "--wd" or a == "--weight-decay":
                cfg.weight_decay = float(_next())
            elif a == "-d" or a == "--dataset":
                cfg.dataset_path = _next()
                cfg.synthetic_input = False
            elif a == "-s" or a == "--strategy":
                cfg.strategy_file = _next()
            elif a == "-ll:cpu":
                cfg.loaders_per_node = int(_next())
            elif a in ("-ll:tpu", "-ll:gpu"):
                cfg.num_devices = int(_next())
            elif a == "--nodes":
                cfg.num_nodes = int(_next())
            elif a == "-p" or a == "--print-freq":
                cfg.print_freq = int(_next())
            elif a == "--profiling":
                cfg.profiling = True
            elif a == "--dry-run":
                cfg.dry_run = True
            elif a == "--zc-dataset":
                cfg.zc_dataset = True
            elif a == "--stream-dataset":
                cfg.stream_dataset = True
            elif a == "--shard-embeddings":
                cfg.shard_embeddings = True
            elif a == "--shuffle-window":
                cfg.shuffle_window = int(_next())
                if cfg.shuffle_window < 0:
                    raise SystemExit(
                        f"--shuffle-window must be >= 0 (0 = whole "
                        f"shard), got {cfg.shuffle_window}"
                    )
            elif a == "--remat":
                cfg.remat = True
            elif a in ("-i", "--iterations"):
                cfg.iterations = int(_next())
            elif a == "--dtype":
                cfg.compute_dtype = _next()
            elif a == "--seed":
                cfg.seed = int(_next())
            elif a == "--optimizer":
                cfg.optimizer = _next().lower()
            elif a == "--momentum":
                cfg.momentum = float(_next())
            elif a == "--lr-schedule":
                cfg.lr_schedule = _next().lower()
            elif a == "--warmup":
                cfg.warmup_steps = int(_next())
            elif a == "--decay-steps":
                cfg.decay_steps = int(_next())
            elif a == "--min-lr":
                cfg.min_lr = float(_next())
            elif a == "--lr-gamma":
                cfg.lr_gamma = float(_next())
            elif a == "--accum-steps":
                cfg.accum_steps = int(_next())
            elif a == "--steps-per-call":
                cfg.steps_per_call = int(_next())
                if cfg.steps_per_call < 1:
                    raise SystemExit(
                        f"--steps-per-call must be >= 1, got "
                        f"{cfg.steps_per_call}"
                    )
            elif a == "--granules":
                cfg.granules = int(_next())
            elif a == "--microbatches":
                cfg.microbatches = int(_next())
            elif a == "--pipeline-schedule":
                cfg.pipeline_schedule = _next()
                if cfg.pipeline_schedule not in ("1f1b", "gpipe"):
                    raise SystemExit(
                        f"--pipeline-schedule must be 1f1b or gpipe, "
                        f"got {cfg.pipeline_schedule!r}"
                    )
            elif a == "--pipeline-chunk":
                cfg.pipeline_chunk = int(_next())
                if cfg.pipeline_chunk < 1:
                    raise SystemExit(
                        f"--pipeline-chunk must be >= 1, got "
                        f"{cfg.pipeline_chunk}"
                    )
            elif a == "--pipeline-compiled":
                cfg.pipeline_compiled = True
            elif a == "--search":
                cfg.search_iters = (cfg.search_iters
                                    if cfg.search_iters > 0 else 20_000)
            elif a == "--search-iters":
                cfg.search_iters = int(_next())
            elif a == "--calibration":
                cfg.search_calibration = _next()
            elif a == "--trace":
                cfg.trace_dir = _next()
            elif a == "--ones-init":
                cfg.parameter_all_ones = True
            elif a == "--zero-opt":
                cfg.zero_sharded_optimizer = True
            elif a == "--eval-iters":
                cfg.eval_iters = int(_next())
            elif a == "--clip-norm":
                cfg.clip_norm = float(_next())
            elif a == "--lazy-sparse-opt":
                cfg.lazy_sparse_optimizer = True
            elif a == "--resilient":
                cfg.resilient = True
            elif a == "--save-every":
                cfg.save_every = int(_next())
            elif a == "--ckpt-dir":
                cfg.ckpt_dir = _next()
            elif a == "--max-restarts":
                cfg.max_restarts = int(_next())
            elif a == "--elastic":
                cfg.elastic = True
            elif a == "--coordinator":
                cfg.coordinator_address = _next()
            elif a == "--num-processes":
                cfg.num_processes = int(_next())
            elif a == "--process-id":
                cfg.process_id = int(_next())
            elif a == "--sync-ckpt":
                cfg.async_checkpointing = False
            elif a == "--telemetry":
                cfg.telemetry_dir = _next()
            elif a == "--stall-deadline":
                cfg.stall_deadline_s = float(_next())
                if cfg.stall_deadline_s < 0:
                    raise SystemExit(
                        f"--stall-deadline must be >= 0, got "
                        f"{cfg.stall_deadline_s}"
                    )
            elif a == "--stall-notify-pid":
                cfg.stall_notify_pid = int(_next())
            i += 1
        return cfg

    def resolve_num_devices(self) -> int:
        if self.num_devices > 0:
            return self.num_devices
        import jax

        return len(jax.devices())
