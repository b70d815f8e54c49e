"""Multi-host elastic training: the jax.distributed rig + resize path.

The reference scales across nodes with GASNet under Legion but has no
failure handling at all — a lost node is a lost run (SURVEY.md §5).
This module is the multi-host half of the resilience subsystem
(RESILIENCE.md "Host loss & elastic resize"):

- **The rig** — :func:`run_rig` launches an N-process CPU
  ``jax.distributed`` world (coordinator + workers, each a FRESH
  subprocess with its own 4-device virtual slice, the chaos_smoke
  pattern) running real training through ``build_hybrid_mesh_plan``
  with per-host loader shards end to end.
- **World-failure classification** — a lost peer surfaces on the
  survivors as an ``XlaRuntimeError`` out of the gloo collective
  (instant TCP RST, a catchable RuntimeError);
  :func:`classify_world_failure` recognizes it so
  ``FailurePolicy.fatal`` re-raises IMMEDIATELY instead of burning the
  restart budget on in-process replays into the same dead world.  A
  dead COORDINATOR can additionally hard-abort survivors through the
  coordination client's fatal handler (uncatchable), so the
  authoritative classification is LAUNCHER-side: the first child to
  die by SIGKILL names the failure class (process 0 →
  ``coordinator_loss``, else ``host_loss``); survivor exit codes
  (:data:`EXIT_WORLD_FAILURE`) are best-effort corroboration.
- **Elastic resize** — on host loss the launcher restarts the
  survivors into a SMALLER world (fresh subprocesses, new coordinator
  port, generation+1): re-``initialize()``, mesh rebuilt via the
  executor factory, the SAME strategy-portable checkpoint restored,
  and the per-host batch schedule re-derived deterministically from
  the new ``(host_id, num_hosts)`` by :class:`ElasticHostLoader` —
  the post-resize trajectory is bit-identical to a fresh run launched
  at the smaller world from that checkpoint.  Scale-up on host return
  is the same path in reverse (relaunch at the larger world against
  the same checkpoint directory).  Coordinator loss cannot be resized
  around by survivors alone; it restarts the SAME world with a new
  coordinator under the ``max_restarts`` budget.
- **Torn-world guard** — :class:`WorldLedger`: a generation file in
  the checkpoint directory, claimed by process 0 of each generation;
  every save first asserts the claim, so a stale half-world that
  missed its own death can never overwrite the resized world's
  checkpoints (the single-writer rule made explicit).

In-process re-``initialize()`` of a torn jax.distributed world is not
reliable; "survivors restart into a smaller world" is SUPERVISED
restart — the launcher relaunches fresh worker subprocesses, exactly
how chaos scenarios already isolate state.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from flexflow_tpu.data.stream import loader_state_template, shard_for_host
from flexflow_tpu.parallel.distributed import (
    build_hybrid_mesh_plan,
    initialize,
    world,
)
from flexflow_tpu.runtime import telemetry as _telemetry
from flexflow_tpu.runtime.checkpoint import CheckpointManager

#: Exit code a worker uses for "my world died under me — resize me".
#: Distinct from crash (1) and clean (0) so the launcher can
#: corroborate its SIGKILL-based classification.
EXIT_WORLD_FAILURE = 76

#: Sentinel in ``cursor[2]`` marking a world-invariant elastic cursor
#: (vs a StreamingLoader cursor, whose third slot is rows_served).
ELASTIC_CURSOR_TAG = 0x454C


class TornWorldError(RuntimeError):
    """A stale world generation tried to write checkpoints after a
    newer generation claimed the directory (two half-worlds must never
    both write — RESILIENCE.md single-writer rule)."""


# -- world-failure classification -------------------------------------------

#: Substrings that mark a distributed-runtime failure (peer loss,
#: coordinator loss, torn collective) as seen from a surviving
#: process.  Matched case-insensitively against the exception text.
_WORLD_FAILURE_MARKERS = (
    "gloo",                     # CPU collective: peer TCP RST/EOF
    "connection reset",
    "connection refused",
    "connection closed",
    "broken pipe",
    "coordination service",     # jax coordination client
    "distributed service",
    "heartbeat",
    "barrier timed out",
    "deadline exceeded",
    "unavailable",
    "peer closed",
    "socket closed",
)


def classify_world_failure(exc: BaseException) -> bool:
    """True when ``exc`` is a distributed-WORLD failure (a peer or the
    coordinator died) rather than a step-local fault.  Only
    RuntimeError/OSError families qualify — the same recoverable
    envelope as :class:`FailurePolicy` — so programmer errors never
    get misread as host loss."""
    if isinstance(exc, TornWorldError):
        return True
    if not isinstance(exc, (RuntimeError, OSError)):
        return False
    text = f"{type(exc).__name__}: {exc}".lower()
    return any(m in text for m in _WORLD_FAILURE_MARKERS)


# -- torn-world guard --------------------------------------------------------


class WorldLedger:
    """Generation claim file (``world.json``) in the checkpoint dir.

    Process 0 of each launched generation claims the directory
    (atomic tmp+rename); every checkpoint save asserts the claim
    first.  A surviving process of generation g that somehow missed
    its world's death raises :class:`TornWorldError` at its next save
    once generation g+1 has claimed — the torn-world write window is
    closed at the only place it matters (the write)."""

    FILENAME = "world.json"

    def __init__(self, directory: str):
        self.directory = os.path.abspath(directory)
        self.path = os.path.join(self.directory, self.FILENAME)

    def read(self) -> Dict[str, Any]:
        try:
            with open(self.path) as f:
                data = json.load(f)
            return data if isinstance(data, dict) else {}
        except (FileNotFoundError, json.JSONDecodeError, OSError):
            return {}

    def claim(self, generation: int, world_size: int,
              primary: bool = True) -> None:
        """Claim the directory for ``generation`` (primary process
        only writes; everyone validates).  Claiming an OLDER
        generation than the one on disk is itself a torn world."""
        on_disk = int(self.read().get("generation", 0))
        if on_disk > generation:
            raise TornWorldError(
                f"generation {generation} cannot claim {self.directory}: "
                f"generation {on_disk} already owns it"
            )
        if not primary:
            return
        os.makedirs(self.directory, exist_ok=True)
        tmp = self.path + f".tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"generation": int(generation),
                       "world": int(world_size),
                       "writer": 0}, f)
        os.replace(tmp, self.path)

    def assert_current(self, generation: int) -> None:
        on_disk = int(self.read().get("generation", generation))
        if on_disk != generation:
            raise TornWorldError(
                f"stale world generation {generation} refusing to write "
                f"checkpoints: generation {on_disk} owns {self.directory}"
            )


class LedgeredCheckpointManager(CheckpointManager):
    """CheckpointManager whose every save first asserts the world
    ledger — the enforcement point of the single-writer rule."""

    def __init__(self, directory: str, ledger: WorldLedger,
                 generation: int, **kwargs):
        super().__init__(directory, **kwargs)
        self._ledger = ledger
        self._generation = int(generation)

    def save(self, *args, **kwargs) -> bool:
        self._ledger.assert_current(self._generation)
        return super().save(*args, **kwargs)


# -- world-invariant per-host data schedule ----------------------------------


def elastic_dataset(seed: int = 0, samples: int = 128,
                    features: int = 16, classes: int = 4,
                    ) -> Dict[str, np.ndarray]:
    """The rig's deterministic dataset (seed-derived, so every process
    and every world size materializes identical global arrays)."""
    rng = np.random.default_rng(seed)
    return {
        "x": rng.standard_normal((samples, features)).astype(np.float32),
        "label": rng.integers(0, classes, size=(samples,)).astype(np.int32),
    }


class ElasticHostLoader:
    """World-invariant per-host batch schedule over a global dataset.

    The global schedule is fixed by ``(seed, global_batch)`` alone:
    epoch e shuffles the sample indices with ``default_rng((seed, e))``
    and batch t is global rows ``perm[t*B:(t+1)*B]``.  Each host then
    serves its :func:`shard_for_host` slice OF THAT GLOBAL BATCH — so
    the concatenation over hosts (process-major, exactly how the
    hybrid mesh shards the batch dim) is byte-identical at EVERY world
    size.  That is the property the elastic resize leans on: a resized
    world re-derives its per-host rows from the new ``(host_id,
    num_hosts)`` and the global trajectory cannot tell the difference.

    ``state_dict``/``load_state_dict`` speak the checkpoint's loader
    slot (same pytree as ``stream.loader_state_template()``), with the
    cursor encoded world-invariantly as ``[global_step, global_batch,
    ELASTIC_CURSOR_TAG]`` — a checkpoint written by a 2-host world
    restores into a 1-host world (and back) with no translation.
    """

    def __init__(self, arrays: Dict[str, np.ndarray], global_batch: int,
                 *, seed: int = 0, host_id: Optional[int] = None,
                 num_hosts: Optional[int] = None):
        self.arrays = arrays
        self.samples = len(next(iter(arrays.values())))
        self.global_batch = int(global_batch)
        self.seed = int(seed)
        if host_id is None or num_hosts is None:
            host_id, num_hosts = world()
        self.host_id, self.num_hosts = int(host_id), int(num_hosts)
        if self.global_batch % self.num_hosts:
            raise ValueError(
                f"global_batch {self.global_batch} does not divide over "
                f"{self.num_hosts} host(s)"
            )
        if self.samples < self.global_batch:
            raise ValueError(
                f"{self.samples} samples < global_batch {self.global_batch}"
            )
        # This host's slice of every global batch (contiguous,
        # process-major — matching how the DCN-outer mesh lays the
        # batch dim across processes).
        self._lo, self._hi = shard_for_host(
            self.global_batch, self.host_id, self.num_hosts
        )
        self.global_step = 0
        self._perm_cache: tuple = (-1, None)  # (epoch, permutation)

    def _perm(self, epoch: int) -> np.ndarray:
        if self._perm_cache[0] != epoch:
            perm = np.random.default_rng(
                (self.seed, epoch)).permutation(self.samples)
            self._perm_cache = (epoch, perm)
        return self._perm_cache[1]

    def __iter__(self) -> "ElasticHostLoader":
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        per_epoch = self.samples // self.global_batch
        epoch, idx = divmod(self.global_step, per_epoch)
        start = idx * self.global_batch
        rows = self._perm(epoch)[start + self._lo:start + self._hi]
        self.global_step += 1
        return {k: v[rows] for k, v in self.arrays.items()}

    def state_dict(self) -> Dict[str, np.ndarray]:
        return {
            "cursor": np.array(
                [self.global_step, self.global_batch, ELASTIC_CURSOR_TAG],
                np.int64,
            ),
            "rng": np.zeros(6, np.uint64),  # schedule is stateless
        }

    def load_state_dict(self, state: Dict[str, np.ndarray]) -> None:
        cursor = np.asarray(state["cursor"])
        if int(cursor[2]) != ELASTIC_CURSOR_TAG:
            raise ValueError(
                "not an elastic loader cursor (checkpoint written by a "
                "StreamingLoader run?)"
            )
        if int(cursor[1]) != self.global_batch:
            raise ValueError(
                f"checkpoint global_batch {int(cursor[1])} != configured "
                f"{self.global_batch}: the elastic schedule is only "
                f"world-invariant at a fixed global batch"
            )
        self.global_step = int(cursor[0])

    def close(self) -> None:
        pass


# -- world-aware data placement ----------------------------------------------


def worldify(ex):
    """Make an Executor's data-placement entry points world-aware.

    In a multi-process world each host holds only ITS rows of the
    global batch; ``jax.device_put`` of local rows would build a
    wrong-shaped global array.  ``jax.make_array_from_process_local_data``
    assembles the global array from per-process rows under the input's
    consumer sharding — same call sites (``shard_batch``,
    ``stack_steps``), so ResilientTrainer and the superstep path run
    unchanged.  Single-process worlds are untouched (no new code on
    the non-elastic path)."""
    import jax

    if jax.process_count() <= 1:
        return ex
    from jax.sharding import NamedSharding, PartitionSpec

    pcount = jax.process_count()
    sh = ex.batch_shardings()

    def shard_batch(batch: Dict[str, Any]) -> Dict[str, Any]:
        out = {}
        for k, v in batch.items():
            if k in sh:
                v = np.asarray(v)
                gshape = (v.shape[0] * pcount,) + v.shape[1:]
                out[k] = jax.make_array_from_process_local_data(
                    sh[k], v, gshape
                )
            else:
                out[k] = v
        return out

    def stack_steps(batches, accum_steps: int = 1):
        if accum_steps > 1:
            raise NotImplementedError(
                "accum_steps > 1 is not wired through the multi-process "
                "batch assembly"
            )
        out = {}
        for name in batches[0]:
            stacked = np.stack([np.asarray(b[name]) for b in batches])
            if name in sh:
                spec = PartitionSpec(None, *sh[name].spec)
                gshape = (
                    (stacked.shape[0], stacked.shape[1] * pcount)
                    + stacked.shape[2:]
                )
                stacked = jax.make_array_from_process_local_data(
                    NamedSharding(ex.plan.mesh, spec), stacked, gshape
                )
            out[name] = stacked
        return out

    ex.shard_batch = shard_batch
    ex.stack_steps = stack_steps
    return ex


def elastic_executor_factory(global_batch: int = 8,
                             ) -> Callable[[], Any]:
    """Executor factory for the rig: the chaos tiny MLP on the hybrid
    DCN-outer/ICI-inner mesh, data parallelism spanning the processes
    (``n = num_processes``, consumed from the left = DCN) and tensor
    parallelism on the per-host devices (``c``, from the right = ICI).
    At world=1 it degrades to the pure tensor-parallel strategy on the
    local slice — the shape the post-resize bit-identity pin compares
    against."""

    def make():
        import jax

        from flexflow_tpu.config import FFConfig
        from flexflow_tpu.graph import FFModel
        from flexflow_tpu.optim import SGDOptimizer
        from flexflow_tpu.parallel.strategy import (
            ParallelConfig,
            StrategyStore,
        )
        from flexflow_tpu.runtime.executor import Executor

        ff = FFModel(FFConfig(batch_size=global_batch))
        x = ff.create_tensor((global_batch, 16), name="x")
        lbl = ff.create_tensor((global_batch,), dtype=np.int32, name="label")
        t = ff.dense(x, 32, activation="relu", name="fc1")
        t = ff.dense(t, 4, name="fc2")
        ff.softmax(t, lbl, name="softmax")
        pcount = max(jax.process_count(), 1)
        devs = jax.device_count()
        if pcount > 1:
            cfg = ParallelConfig(n=pcount, c=devs // pcount)
        else:
            cfg = ParallelConfig(c=devs)
        store = StrategyStore(devs, {"fc1": cfg})
        plan = build_hybrid_mesh_plan()
        ex = Executor(ff, strategy=store, mesh_plan=plan,
                      optimizer=SGDOptimizer(lr=0.1))
        return worldify(ex)

    return make


# -- the worker --------------------------------------------------------------


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name, "")
    return int(v) if v else default


def worker_main() -> None:
    """One process of the rig's world.  Protocol is environment-driven
    (the launcher owns the argv): ``JAX_COORDINATOR_ADDRESS`` /
    ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` bring up the world
    through the standard ``initialize()`` ladder; ``FF_ELASTIC_*``
    carries the run shape.  Exits via ``os._exit`` always — a poisoned
    world must not hang in atexit/teardown."""
    ckpt_dir = os.environ["FF_ELASTIC_CKPT_DIR"]
    result_path = os.environ.get("FF_ELASTIC_RESULT", "")
    iters = _env_int("FF_ELASTIC_ITERS", 16)
    k = _env_int("FF_ELASTIC_K", 8)
    save_every = _env_int("FF_ELASTIC_SAVE_EVERY", 8)
    seed = _env_int("FF_ELASTIC_SEED", 0)
    global_batch = _env_int("FF_ELASTIC_GLOBAL_BATCH", 8)
    kill_at = _env_int("FF_ELASTIC_KILL_AT", 0)
    generation = _env_int("FF_ELASTIC_GENERATION", 1)
    prev_world = _env_int("FF_ELASTIC_PREV_WORLD", 0)
    max_restarts = _env_int("FF_ELASTIC_MAX_RESTARTS", 3)
    reason = os.environ.get("FF_ELASTIC_REASON", "launch")

    from flexflow_tpu.runtime.resilience import (
        FailurePolicy,
        ResilientTrainer,
    )

    try:
        initialize()  # env-driven; multi-process CPU gets gloo
        host_id, num_hosts = world()
        with _telemetry.maybe_run(
            None, meta={"app": "elastic_rig", "generation": generation}
        ):
            tel = _telemetry.current()
            tel.emit(
                "distributed_init",
                process_id=host_id, process_count=num_hosts,
                coordinator=os.environ.get("JAX_COORDINATOR_ADDRESS"),
                generation=generation,
            )
            if generation > 1 and prev_world and prev_world != num_hosts:
                tel.emit(
                    "elastic_resize",
                    generation=generation, from_world=prev_world,
                    to_world=num_hosts, reason=reason,
                )
            ledger = WorldLedger(ckpt_dir)
            ledger.claim(generation, num_hosts, primary=(host_id == 0))

            injector = None
            if kill_at:
                def injector(step: int, _at: int = kill_at) -> None:
                    if step == _at:
                        # Mid-superstep host loss: fires during the
                        # superstep group assembly, instant and
                        # unflushable — the honest failure shape.
                        os.kill(os.getpid(), signal.SIGKILL)

            loader = ElasticHostLoader(
                elastic_dataset(seed), global_batch, seed=seed
            )
            # NOT a `with` block: CheckpointManager.close() is a
            # COLLECTIVE (orbax barriers the world) — running it while
            # unwinding a world failure blocks forever against the dead
            # peer.  Close explicitly on the healthy path only; saves
            # are already durable (sync save waits before returning).
            ck = LedgeredCheckpointManager(ckpt_dir, ledger, generation)
            try:
                rt = ResilientTrainer(
                    elastic_executor_factory(global_batch), ck,
                    policy=FailurePolicy(
                        max_restarts=max_restarts,
                        fatal=classify_world_failure,
                    ),
                    fault_injector=injector,
                )
                out = rt.fit(
                    iterations=iters, save_every=save_every,
                    steps_per_call=k, seed=seed, loader=loader,
                )
                ck.close()
            except BaseException as e:
                if classify_world_failure(e):
                    # The reconstruction story: the world's death is an
                    # event in the log, not just a truncated file.
                    tel.emit(
                        "fault", kind="world_failure",
                        generation=generation, world=num_hosts,
                        error=f"{type(e).__name__}: {e}"[:500],
                    )
                raise
            finally:
                loader.close()
            if host_id == 0 and result_path:
                payload = {
                    "generation": generation,
                    "world": num_hosts,
                    "step": int(out["step"]),
                    "restarts": int(out["restarts"]),
                    "losses": {str(s): float(v)
                               for s, v in out["losses"].items()},
                }
                tmp = result_path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(payload, f)
                os.replace(tmp, result_path)
    except BaseException as e:  # noqa: BLE001 — classify, then exit hard
        if classify_world_failure(e):
            print(f"elastic worker: world failure "
                  f"({type(e).__name__})", file=sys.stderr)
            sys.stderr.flush()
            os._exit(EXIT_WORLD_FAILURE)
        traceback.print_exc()
        sys.stderr.flush()
        os._exit(1)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


# -- the launcher ------------------------------------------------------------

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env(base: Dict[str, str], *, port: int, world_size: int,
                process_id: int, devices_per_host: int) -> Dict[str, str]:
    env = dict(base)
    # Fresh CPU subprocess: the rig's workers are virtual-device CPU
    # processes whatever the launching environment names.
    env["PYTHONPATH"] = _REPO_ROOT
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_host}"
    )
    env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
    env["JAX_NUM_PROCESSES"] = str(world_size)
    env["JAX_PROCESS_ID"] = str(process_id)
    return env


class RigFailure(RuntimeError):
    """The rig could not drive the run to completion (restart budget
    exhausted, or a worker died in a way the supervisor cannot
    classify as a world failure)."""


def run_rig(
    world_size: int,
    ckpt_dir: str,
    *,
    iters: int = 16,
    k: int = 8,
    save_every: int = 8,
    seed: int = 0,
    global_batch: int = 8,
    devices_per_host: int = 4,
    kill_process: Optional[int] = None,
    kill_at_step: int = 0,
    max_restarts: int = 3,
    telemetry_dir: Optional[str] = None,
    log_dir: Optional[str] = None,
    timeout_s: float = 420.0,
    grace_s: float = 30.0,
) -> Dict[str, Any]:
    """Launch and supervise an elastic multi-process training run.

    Spawns ``world_size`` fresh worker subprocesses (generation 1),
    waits, classifies any failure, and relaunches (generation+1, new
    coordinator port) until the run completes or the restart budget is
    spent: a SIGKILLed worker with ``process_id > 0`` is a
    ``host_loss`` → the next generation is one process SMALLER; a
    SIGKILLed ``process_id == 0`` is a ``coordinator_loss`` → the
    next generation keeps the world size under a new coordinator.
    ``kill_process``/``kill_at_step`` arm the victim's self-SIGKILL
    (generation 1 only — the fault fires once).

    Returns the supervision record: per-generation history, the final
    generation's ``result.json`` payload, and the merged
    ``{step: loss}`` trajectory across generations.
    """
    os.makedirs(ckpt_dir, exist_ok=True)
    log_dir = log_dir or os.path.join(ckpt_dir, "rig-logs")
    os.makedirs(log_dir, exist_ok=True)
    result_path = os.path.join(ckpt_dir, "result.json")
    base_env = {
        k_: v for k_, v in os.environ.items()
        if not k_.startswith(("JAX_", "FF_ELASTIC_", "XLA_FLAGS"))
    }
    if telemetry_dir:
        base_env["FF_TELEMETRY_DIR"] = telemetry_dir
    else:
        base_env.pop("FF_TELEMETRY_DIR", None)

    history: List[Dict[str, Any]] = []
    merged_losses: Dict[int, float] = {}
    generation = 0
    restarts = 0
    cur_world = int(world_size)
    prev_world = 0
    reason = "launch"
    deadline = time.monotonic() + timeout_s

    while True:
        generation += 1
        port = _free_port()
        if os.path.exists(result_path):
            os.remove(result_path)
        procs = []
        logs = []
        for pid in range(cur_world):
            env = _worker_env(base_env, port=port, world_size=cur_world,
                              process_id=pid,
                              devices_per_host=devices_per_host)
            env.update({
                "FF_ELASTIC_CKPT_DIR": ckpt_dir,
                "FF_ELASTIC_RESULT": result_path,
                "FF_ELASTIC_ITERS": str(iters),
                "FF_ELASTIC_K": str(k),
                "FF_ELASTIC_SAVE_EVERY": str(save_every),
                "FF_ELASTIC_SEED": str(seed),
                "FF_ELASTIC_GLOBAL_BATCH": str(global_batch),
                "FF_ELASTIC_GENERATION": str(generation),
                "FF_ELASTIC_PREV_WORLD": str(prev_world),
                "FF_ELASTIC_MAX_RESTARTS": str(max_restarts),
                "FF_ELASTIC_REASON": reason,
            })
            if (generation == 1 and kill_at_step
                    and kill_process is not None and pid == kill_process):
                env["FF_ELASTIC_KILL_AT"] = str(kill_at_step)
            log = open(os.path.join(
                log_dir, f"gen{generation}-p{pid}.log"), "w")
            logs.append(log)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "flexflow_tpu.runtime.elastic"],
                env=env, stdout=log, stderr=subprocess.STDOUT,
                cwd=_REPO_ROOT,
            ))
        try:
            root, rcs, reclaimed = _supervise(procs, deadline, grace_s)
        finally:
            for log in logs:
                log.close()
        gen_record = {
            "generation": generation, "world": cur_world,
            "reason": reason, "rcs": rcs, "root_dead": root,
            "reclaimed": reclaimed,
        }
        history.append(gen_record)
        if all(rc == 0 for rc in rcs):
            break
        if root is None:
            raise RigFailure(f"workers failed without a classifiable "
                             f"death: rcs={rcs}")
        restarts += 1
        if restarts > max_restarts:
            raise RigFailure(
                f"restart budget ({max_restarts}) exhausted; "
                f"history={history}"
            )
        prev_world = cur_world
        if root == 0:
            reason = "coordinator_loss"    # same world, new coordinator
        else:
            reason = "host_loss"
            cur_world -= 1                 # survivors resize down
            if cur_world < 1:
                raise RigFailure("no survivors to resize into")
        gen_record["classified"] = reason

    final = {}
    if os.path.exists(result_path):
        with open(result_path) as f:
            final = json.load(f)
        merged_losses.update(
            {int(s): v for s, v in final.get("losses", {}).items()}
        )
    return {
        "generations": history,
        "restarts": restarts,
        "final": final,
        "losses": merged_losses,
        "ckpt_dir": ckpt_dir,
        "telemetry_dir": telemetry_dir,
    }


def _supervise(procs: List[subprocess.Popen], deadline: float,
               grace_s: float):
    """Wait for all children; on the first failure, give the rest a
    grace window, then SIGKILL leftovers — XLA's CPU gloo collectives
    have NO timeout, so a survivor blocked in an all-reduce against a
    dead peer wedges forever (measured; the raised-error surface only
    appears for some kill phases).  Classification uses only deaths
    the supervisor did NOT inflict: among the failures observed in the
    first failing poll, a SIGKILLed child (the self-kill / OOM-kill
    shape of host loss) outranks others.  Returns ``(root_dead_index,
    [returncode, ...], [reclaimed indices])``."""
    root: Optional[int] = None
    first_death_t: Optional[float] = None
    reclaimed: List[int] = []
    while True:
        alive = [p for p in procs if p.poll() is None]
        now = time.monotonic()
        if root is None:
            batch = [i for i, p in enumerate(procs)
                     if p.poll() is not None and p.returncode != 0]
            if batch:
                killed = [i for i in batch
                          if procs[i].returncode == -signal.SIGKILL]
                root = killed[0] if killed else batch[0]
                first_death_t = now
        if not alive:
            break
        hard_deadline = deadline if first_death_t is None else min(
            deadline, first_death_t + grace_s
        )
        if now >= hard_deadline:
            for i, p in enumerate(procs):
                if p.poll() is None:
                    reclaimed.append(i)
                    p.kill()
            for p in procs:
                p.wait()
            if first_death_t is None:
                raise RigFailure(
                    "rig timed out with every worker still running"
                )
            break
        time.sleep(0.1)
    return root, [p.returncode for p in procs], reclaimed


if __name__ == "__main__":
    worker_main()
