"""Host-resident data pipeline.

Reference: the DLRM DataLoader (``examples/DLRM/dlrm.cc:226-330``)
loads the ENTIRE dataset once into zero-copy pinned DRAM
(``MAP_TO_ZC_MEMORY``) and per iteration index-launches gather tasks
that copy each shard's rows to its GPU (``dlrm.cc:427-512``,
``dlrm.cu:20-50``).  The TPU-native shape of that pattern: the dataset
stays in host RAM as numpy arrays; ``next_batch`` slices a batch and
``Executor.shard_batch`` device-puts each tensor directly in its
consumer op's sharding, so each chip receives only its shard over PCIe
— no full-batch staging on device.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Callable, Dict, Iterable, Iterator, Optional

import numpy as np


class ArrayDataLoader:
    """Batches a dict of equal-length host arrays keyed by input-tensor
    name.  ``reset()`` reshuffles per epoch (reference:
    ``data_loader.reset()`` + ``ff.reset_metrics()``, ``dlrm.cc:141-143``)."""

    def __init__(
        self,
        arrays: Dict[str, np.ndarray],
        batch_size: int,
        shuffle: bool = False,
        seed: int = 0,
        nthreads: int = 0,
    ):
        #: gather threads (the reference's -ll:cpu loadersPerNode);
        #: 0 = auto in the native gather.
        self.nthreads = nthreads
        # Tail rows beyond the last full batch are dropped each epoch:
        # jit recompiles per batch shape, so ragged final batches are
        # hostile on TPU (and the reference's loaders are fixed-shape).
        sizes = {k: len(v) for k, v in arrays.items()}
        assert len(set(sizes.values())) == 1, f"ragged arrays: {sizes}"
        self.arrays = arrays
        self.batch_size = batch_size
        self.shuffle = shuffle
        self._rng = np.random.default_rng(seed)
        self.num_samples = next(iter(sizes.values()))
        assert self.num_samples >= batch_size, (
            f"dataset has {self.num_samples} rows < batch {batch_size}"
        )
        self._order = np.arange(self.num_samples)
        self._pos = 0
        if shuffle:
            self._rng.shuffle(self._order)

    @property
    def batches_per_epoch(self) -> int:
        return self.num_samples // self.batch_size

    def reset(self) -> None:
        self._pos = 0
        if self.shuffle:
            self._rng.shuffle(self._order)

    def _next_indices(self) -> np.ndarray:
        """The shared epoch contract: wrap at epoch end (reshuffling
        when enabled), full batches only."""
        if self._pos + self.batch_size > self.num_samples:
            self.reset()
        idx = self._order[self._pos : self._pos + self.batch_size]
        self._pos += self.batch_size
        return idx

    def next_batch(self) -> Dict[str, np.ndarray]:
        """Wraps around at epoch end (callers doing epoch accounting use
        ``batches_per_epoch`` + ``reset``).  Rows are gathered by the
        native threaded copy (``native/ffdata.cc``, the reference DLRM
        loader's host-gather, ``dlrm.cu:20-50``)."""
        idx = self._next_indices()
        from flexflow_tpu.native import gather_rows

        return {
            k: gather_rows(v, idx, nthreads=self.nthreads)
            for k, v in self.arrays.items()
        }

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        while True:
            yield self.next_batch()


class PrefetchLoader:
    """Background-thread batch prefetch with a bounded device queue.

    The reference overlaps input staging with compute by double-buffering
    dataset rows through zero-copy DRAM ahead of the step's gather tasks
    (``dlrm.cc:447-512``).  Here a daemon thread pulls host batches from
    ``source``, runs ``place_fn`` (typically ``Executor.shard_batch`` —
    the H2D transfer) and parks up to ``depth`` device-resident batches,
    so the accelerator never waits on the host path.

    Iteration ends when ``source`` does; errors in the worker re-raise
    at the consuming ``next()`` call.
    """

    _DONE = object()

    def __init__(
        self,
        source: Iterable[Dict[str, np.ndarray]],
        place_fn: Callable[[Dict[str, np.ndarray]], Dict],
        depth: int = 2,
    ):
        assert depth >= 1
        self._terminal: Optional[BaseException] = None
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._source = iter(source)
        self._place = place_fn
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._worker, daemon=True, name="ff-prefetch"
        )
        self._thread.start()

    def _worker(self):
        try:
            for batch in self._source:
                if self._stop.is_set():
                    return
                self._q.put(self._place(batch))
            self._q.put(self._DONE)
        except BaseException as e:  # surfaced at next()
            self._q.put(e)

    def __iter__(self) -> Iterator[Dict]:
        return self

    def __next__(self) -> Dict:
        # Terminal states are sticky: once exhausted, errored, or
        # closed, every further next() raises instead of blocking on a
        # queue with no producer left.
        if self._terminal is not None:
            if isinstance(self._terminal, BaseException):
                raise self._terminal
            raise StopIteration
        item = self._q.get()
        if item is self._DONE:
            self._terminal = StopIteration()
            raise StopIteration
        if isinstance(item, BaseException):
            self._terminal = item
            raise item
        return item

    def queue_depths(self) -> Dict[str, int]:
        """Staged-batch gauge for the input_wait telemetry event; folds
        in the source's own depths (e.g. a StreamingLoader's reader
        queue) so both edges of the pipeline are visible."""
        depths = {"h2d": self._q.qsize()}
        nested = getattr(self._source, "queue_depths", None)
        if callable(nested):
            depths.update(nested())
        return depths

    def close(self, join_timeout_s: float = 5.0) -> None:
        self._stop.set()
        self._terminal = self._terminal or StopIteration()
        # Unblock a worker stuck on a full queue.
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        # Join with a bounded timeout so a closed loader can't leave a
        # _place H2D in flight during interpreter teardown.  One more
        # drain after the worker's final put (it may have been blocked
        # on a full queue again between our drain and its stop check).
        deadline = time.monotonic() + join_timeout_s
        while self._thread.is_alive():
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            self._thread.join(timeout=min(remaining, 0.1))


class DeviceMemoryError(RuntimeError):
    """Staging the dataset would not fit per-device memory.

    Raised by ``DeviceResidentLoader`` BEFORE any ``device_put`` (an
    up-front estimate, not a mid-staging OOM), with the two escape
    hatches named: the host loader path (drop ``--zc-dataset``) or the
    streaming tier (``--stream-dataset``, DATA.md)."""


def _device_bytes_limit() -> Optional[int]:
    """Per-device memory budget for the zc staging estimate.

    ``FF_DEVICE_MEM_BYTES`` overrides (tests, capacity A/Bs); otherwise
    the device's own ``memory_stats()['bytes_limit']`` when the backend
    reports one (CPU backends report none -> check is inert)."""
    env = os.environ.get("FF_DEVICE_MEM_BYTES")
    if env:
        return int(env)
    import jax

    try:
        stats = jax.devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    limit = stats.get("bytes_limit")
    return int(limit) if limit else None


class DeviceResidentLoader(ArrayDataLoader):
    """The reference's zero-copy dataset pattern, TPU-native: the
    ENTIRE dataset is staged on device ONCE (replicated over the mesh —
    the analogue of the pinned ZC DRAM region every GPU gathers from,
    ``dlrm.cc:226-330``), and per step only a batch-size index vector
    crosses host→device; rows gather ON DEVICE (``jnp.take``, the
    ``dlrm.cu:20-50`` gather) and ``Executor.shard_batch`` moves each
    gathered batch device-to-device into its consumer's sharding.

    Use when the dataset fits HBM (it is resident for the run); the
    host-path ``ArrayDataLoader`` + ``PrefetchLoader`` remains the
    out-of-core path.  Epoch semantics are inherited (full batches
    only, reshuffle per epoch — ``_next_indices``)."""

    def __init__(
        self,
        arrays: Dict[str, np.ndarray],
        batch_size: int,
        executor,
        shuffle: bool = False,
        seed: int = 0,
    ):
        import jax
        import jax.numpy as jnp

        super().__init__(arrays, batch_size, shuffle=shuffle, seed=seed)
        if not hasattr(executor, "plan"):
            raise ValueError(
                "DeviceResidentLoader needs a full-mesh Executor (its "
                "staging replicates over executor.plan); layer-wise "
                "PipelineExecutor strategies use the host loader path"
            )
        self._ex = executor
        self._rep = executor.plan.replicated()
        # Up-front staging estimate: the dataset is REPLICATED, so every
        # device holds all of it.  Refuse with a named error before the
        # first device_put rather than OOMing mid-staging.
        staged = sum(int(np.asarray(v).nbytes) for v in arrays.values())
        limit = _device_bytes_limit()
        if limit is not None:
            # Params share the device: count each weight at its
            # PER-DEVICE (sharded) size — a row-sharded embedding
            # table (--shard-embeddings) holds only vocab/c rows per
            # device, so the estimate credits exactly the escape
            # hatch the refusal names.  eval_shape only, no device
            # touched.
            pavals, _, _ = executor._abstract_init()
            pshard = executor.params_shardings()
            param_bytes = sum(
                int(np.prod(pshard[op][k].shard_shape(v.shape)))
                * v.dtype.itemsize
                for op, tree in pavals.items()
                for k, v in tree.items()
                if op in pshard and k in pshard[op]
            )
            if staged + param_bytes > limit:
                raise DeviceMemoryError(
                    f"--zc-dataset would stage {staged / 1e9:.2f} GB "
                    f"replicated per device (+ {param_bytes / 1e9:.2f} "
                    f"GB per-device params), over the "
                    f"{limit / 1e9:.2f} GB per-device budget.  Use the "
                    f"host loader path (drop --zc-dataset), the "
                    f"streaming tier (--stream-dataset with "
                    f"--shuffle-window, DATA.md), or shrink the "
                    f"per-device tables with --shard-embeddings "
                    f"(SHARDING.md)."
                )
        #: the staged (replicated) dataset — one H2D per array, total.
        self.device_arrays = {
            k: jax.device_put(v, self._rep) for k, v in arrays.items()
        }
        # ONE jitted gather per step, with the consumers' shardings as
        # out_shardings — gather + reshard fuse into a single dispatch
        # (a per-key take loop would pay one eager dispatch per key;
        # what a dispatch costs is not measured on the chip).
        batch_sh = executor.batch_shardings()
        out_sh = {k: batch_sh.get(k, self._rep) for k in arrays}
        self._gather = jax.jit(
            lambda data, idx: {
                k: jnp.take(v, idx, axis=0) for k, v in data.items()
            },
            out_shardings=out_sh,
        )

    def next_batch(self) -> Dict:
        import jax

        idx_host = self._next_indices()
        idx = jax.device_put(
            np.ascontiguousarray(idx_host.astype(np.int32)), self._rep
        )
        return self._gather(self.device_arrays, idx)


def synthetic_host_batch(
    model,
    rng: np.random.Generator,
    int_high: Optional[Dict[str, int]] = None,
) -> Dict[str, np.ndarray]:
    """One host batch of random inputs matching ``model``'s input
    tensors — the single source of the int-range / dtype-rounding
    rules shared by ``Trainer.synthetic_batch`` and the resilient
    loop's deterministic ``batch_fn`` (apps/common.make_batch_fn), so
    the two paths draw identically-distributed data."""
    int_high = int_high or {}
    out = {}
    for t in model.input_tensors:
        if np.issubdtype(np.dtype(t.dtype), np.integer):
            # Index-like input: labels or embedding ids.  Bounded by
            # int_high[name] when given, else the tensor's own
            # max_value (small conservative default).
            hi = int_high.get(t.name, getattr(t, "max_value", 2))
            out[t.name] = rng.integers(0, hi, size=t.shape).astype(np.int32)
        else:
            arr = rng.standard_normal(size=t.shape).astype(np.float32)
            # ml_dtypes handles bf16: round through np.asarray, not a
            # direct float64 astype.
            out[t.name] = np.asarray(arr, dtype=np.dtype(t.dtype))
    return out


def synthetic_arrays(
    model,
    num_samples: int,
    seed: int = 0,
    int_high: Optional[Dict[str, int]] = None,
) -> Dict[str, np.ndarray]:
    """Random host data matching a model's input tensors (reference:
    synthetic-input mode, ``config.h:73``; DLRM random dataset,
    ``dlrm.cc:234-236``).  ``int_high[name]`` bounds integer inputs
    (vocab sizes / class counts)."""
    rng = np.random.default_rng(seed)
    int_high = int_high or {}
    out = {}
    for t in model.input_tensors:
        shape = (num_samples,) + tuple(t.shape[1:])
        if np.issubdtype(np.dtype(t.dtype), np.integer):
            hi = int_high.get(t.name, 2)
            out[t.name] = rng.integers(0, hi, size=shape).astype(np.int32)
        else:
            out[t.name] = rng.standard_normal(size=shape).astype(np.dtype(t.dtype))
    return out
