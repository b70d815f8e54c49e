"""Streaming data plane: out-of-core chunked sources + windowed shuffle.

The streaming tier of the data plane (DATA.md) is a three-stage
pipeline -- disk -> host batch -> device -- that never materializes the
dataset:

- A ``StreamSource`` serves contiguous row ranges (``read(start, stop)``)
  from disk (``H5StreamSource``), memory (``ArrayStreamSource``, the
  parity/test source), or thin air (``SyntheticStreamSource``, block-
  deterministic generation so reads are reproducible at any boundary).
- ``StreamingLoader`` partitions the source into a deterministic
  per-host contiguous shard (``host_id``/``num_hosts``), walks it in
  contiguous *windows* of ``shuffle_window`` rows per epoch, and
  shuffles each window CONSUMER-side with one continuing
  ``np.random.default_rng(seed)``.  A background reader thread
  double-buffers raw window reads through a bounded queue; because the
  thread only performs raw contiguous reads (no RNG), determinism is
  independent of thread timing.
- The existing ``PrefetchLoader`` stays the H2D stage on top.

Epoch/wrap contract (the DP==strategy + deterministic-replay invariant,
pinned by tests/test_data_stream.py): with ``shuffle_window >= shard``
the per-epoch RNG call sequence -- one ``shuffle(arange(n))`` at init
and one per wrap, tail-batch dropped -- is IDENTICAL to
``ArrayDataLoader._next_indices``, so streamed batches are bit-identical
to the array loader on the same arrays/seed, across epoch wraps.

Checkpointing: ``state_dict()`` is a fixed-shape numpy snapshot
(cursor ``int64[3]`` = epoch / windows admitted / rows served this
epoch, plus the *construction-time* PCG64 state packed into
``uint64[6]``) so it rides the CheckpointManager "loader" item.
``load_state_dict`` replays every epoch's shuffles from that origin
(index-only for past epochs), re-reads the current epoch's admitted
windows from the source (reads are deterministic), drops the
already-served rows, and re-arms a fresh reader thread -- required
after a reader fault killed the old one.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

__all__ = [
    "StreamSource",
    "ArrayStreamSource",
    "H5StreamSource",
    "SyntheticStreamSource",
    "ThrottledSource",
    "StreamingLoader",
    "StreamReaderError",
    "shard_for_host",
]

# Reader thread shutdown grace; a blocked put() polls the stop event at
# this granularity so close() never hangs on a full queue.
_READER_POLL_S = 0.1
_READER_JOIN_S = 5.0


class StreamReaderError(RuntimeError):
    """A background reader thread died; surfaced at the next ``next()``.

    Subclasses RuntimeError so FailurePolicy.recoverable catches it and
    ResilientTrainer rolls back + replays through the restored loader.
    """


def shard_for_host(num_samples: int, host_id: int, num_hosts: int
                   ) -> Tuple[int, int]:
    """Deterministic per-host contiguous shard ``[lo, hi)``.

    Equal-size contiguous blocks of ``num_samples // num_hosts`` rows;
    the remainder tail is dropped (every host sees the same shard size,
    keeping global batch shapes uniform).
    """
    if num_hosts < 1:
        raise ValueError(f"num_hosts must be >= 1, got {num_hosts}")
    if not 0 <= host_id < num_hosts:
        raise ValueError(
            f"host_id {host_id} out of range for num_hosts {num_hosts}")
    size = num_samples // num_hosts
    return host_id * size, (host_id + 1) * size


class StreamSource:
    """Protocol: a random-access source of contiguous row ranges.

    Implementations provide ``num_samples``, ``specs()`` (per-key
    ``(row_shape, dtype)``) and ``read(start, stop)`` returning fresh
    host arrays for rows ``[start, stop)``.  Reads must be
    deterministic: the same range always returns the same bytes (the
    checkpoint-restore replay depends on it).
    """

    num_samples: int = 0

    def specs(self) -> Dict[str, Tuple[Tuple[int, ...], np.dtype]]:
        raise NotImplementedError

    def read(self, start: int, stop: int) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    def close(self) -> None:  # pragma: no cover - trivial default
        pass


class ArrayStreamSource(StreamSource):
    """In-memory source over host numpy arrays (parity + tests).

    ``read`` copies, like a real disk read -- consumers may trim the
    returned arrays in place without aliasing the backing store.
    """

    def __init__(self, arrays: Dict[str, np.ndarray]):
        if not arrays:
            raise ValueError("ArrayStreamSource needs at least one array")
        self.arrays = {k: np.asarray(v) for k, v in arrays.items()}
        lengths = {len(v) for v in self.arrays.values()}
        if len(lengths) != 1:
            raise ValueError(f"ragged arrays: lengths {sorted(lengths)}")
        self.num_samples = lengths.pop()

    def specs(self):
        return {k: (v.shape[1:], v.dtype) for k, v in self.arrays.items()}

    def read(self, start, stop):
        return {k: np.array(v[start:stop]) for k, v in self.arrays.items()}


class H5StreamSource(StreamSource):
    """Chunked HDF5 reads via h5py -- the out-of-core disk source.

    ``keys`` selects datasets (default: every dataset whose leading
    dimension matches the longest one); ``max_samples`` caps the
    addressable rows without ever reading past the cut.
    """

    def __init__(self, path: str, keys: Optional[List[str]] = None,
                 max_samples: Optional[int] = None):
        try:
            import h5py
        except ImportError as exc:  # pragma: no cover - h5py is baked in
            raise RuntimeError(
                "H5StreamSource requires h5py; use ArrayStreamSource or "
                "SyntheticStreamSource instead") from exc
        self._file = h5py.File(path, "r")
        if keys is None:
            keys = [k for k, v in self._file.items()
                    if getattr(v, "ndim", 0) >= 1]
        if not keys:
            raise ValueError(f"no datasets found in {path}")
        self._keys = list(keys)
        n = min(int(self._file[k].shape[0]) for k in self._keys)
        if max_samples is not None:
            n = min(n, int(max_samples))
        self.num_samples = n

    def specs(self):
        return {k: (tuple(self._file[k].shape[1:]), self._file[k].dtype)
                for k in self._keys}

    def read(self, start, stop):
        stop = min(stop, self.num_samples)
        return {k: np.asarray(self._file[k][start:stop]) for k in self._keys}

    def close(self):
        self._file.close()


class SyntheticStreamSource(StreamSource):
    """Deterministic generated rows, no backing store.

    Rows are generated in fixed blocks of ``block`` rows; block ``b``
    uses ``np.random.default_rng([seed, b])``, so ``read`` returns the
    same bytes for a row regardless of chunk boundaries -- the property
    the checkpoint-restore replay and the reader re-arm rely on.
    ``specs`` maps key -> (row_shape, dtype); integer keys draw from
    ``[0, int_high[key])`` (default 2).
    """

    def __init__(self, specs: Dict[str, Tuple[Tuple[int, ...], np.dtype]],
                 num_samples: int, seed: int = 0,
                 int_high: Optional[Dict[str, int]] = None,
                 block: int = 4096):
        self._specs = {k: (tuple(s), np.dtype(d)) for k, (s, d) in
                       sorted(specs.items())}
        self.num_samples = int(num_samples)
        self.seed = int(seed)
        self.block = int(block)
        self.int_high = dict(int_high or {})

    def specs(self):
        return dict(self._specs)

    def _gen_block(self, b: int) -> Dict[str, np.ndarray]:
        lo = b * self.block
        rows = min(self.block, self.num_samples - lo)
        rng = np.random.default_rng([self.seed, b])
        out = {}
        for k, (shape, dtype) in self._specs.items():
            size = (rows,) + shape
            if np.issubdtype(dtype, np.integer):
                high = self.int_high.get(k, 2)
                out[k] = rng.integers(0, high, size=size, dtype=dtype)
            else:
                out[k] = rng.standard_normal(size=size).astype(dtype)
        return out

    def read(self, start, stop):
        stop = min(stop, self.num_samples)
        parts: Dict[str, List[np.ndarray]] = {k: [] for k in self._specs}
        b = start // self.block
        while b * self.block < stop:
            blk = self._gen_block(b)
            lo = max(start - b * self.block, 0)
            hi = min(stop - b * self.block, self.block)
            for k, v in blk.items():
                parts[k].append(v[lo:hi])
            b += 1
        return {k: (p[0] if len(p) == 1 else np.concatenate(p))
                for k, p in parts.items()}


class ThrottledSource(StreamSource):
    """Wrap a source with per-read latency -- a disk-bound stand-in.

    ``delay_s`` is a fixed cost per read; ``per_row_s`` scales with the
    range.  Used by the starvation tests to make input-bound runs
    reproducible on the CPU box.
    """

    def __init__(self, source: StreamSource, delay_s: float = 0.0,
                 per_row_s: float = 0.0):
        self.source = source
        self.delay_s = float(delay_s)
        self.per_row_s = float(per_row_s)
        self.num_samples = source.num_samples
        self.reads = 0

    def specs(self):
        return self.source.specs()

    def read(self, start, stop):
        self.reads += 1
        pause = self.delay_s + self.per_row_s * max(stop - start, 0)
        if pause > 0:
            time.sleep(pause)
        return self.source.read(start, stop)

    def close(self):
        self.source.close()


def _pack_rng(state: dict) -> np.ndarray:
    """PCG64 bit_generator state -> fixed-shape uint64[6] (orbax-safe)."""
    if state.get("bit_generator") != "PCG64":
        raise ValueError(
            f"streaming loader requires PCG64 (np.random.default_rng), "
            f"got {state.get('bit_generator')!r}")
    mask = (1 << 64) - 1
    s = state["state"]["state"]
    inc = state["state"]["inc"]
    return np.array(
        [s & mask, (s >> 64) & mask, inc & mask, (inc >> 64) & mask,
         int(state["has_uint32"]), int(state["uinteger"])],
        dtype=np.uint64)


def _unpack_rng(packed: np.ndarray) -> dict:
    a = [int(x) for x in np.asarray(packed, dtype=np.uint64).reshape(6)]
    return {
        "bit_generator": "PCG64",
        "state": {"state": a[0] | (a[1] << 64), "inc": a[2] | (a[3] << 64)},
        "has_uint32": a[4],
        "uinteger": a[5],
    }


def loader_state_template() -> Dict[str, np.ndarray]:
    """Shape/dtype template for CheckpointManager restore."""
    return {"cursor": np.zeros(3, np.int64), "rng": np.zeros(6, np.uint64)}


class StreamingLoader:
    """Out-of-core windowed-shuffle loader over a ``StreamSource``.

    Yields host batch dicts forever (epoch wrap like ``ArrayDataLoader``:
    reshuffle per wrap, sub-batch tail dropped).  The background reader
    thread stays strictly RNG-free; every shuffle happens consumer-side
    in deterministic window order on one continuing rng, which is what
    makes ``state_dict``/``load_state_dict`` exact.
    """

    def __init__(self, source: StreamSource, batch_size: int, *,
                 shuffle: bool = True, seed: int = 0,
                 shuffle_window: int = 0, host_id: int = 0,
                 num_hosts: int = 1, depth: int = 2):
        self.source = source
        self.batch_size = int(batch_size)
        if self.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self._lo, hi = shard_for_host(source.num_samples, host_id, num_hosts)
        self._shard = hi - self._lo
        if self._shard < self.batch_size:
            raise ValueError(
                f"host shard has {self._shard} rows < batch_size "
                f"{self.batch_size} ({source.num_samples} samples over "
                f"{num_hosts} host(s))")
        self.shuffle = bool(shuffle)
        w = int(shuffle_window) if shuffle_window else self._shard
        if w < 1:
            raise ValueError(f"shuffle_window must be >= 1, got {w}")
        self.window = min(w, self._shard)
        self._windows = [(s, min(s + self.window, self._shard))
                         for s in range(0, self._shard, self.window)]
        self._depth = max(int(depth), 1)
        self._rng = np.random.default_rng(seed)
        #: rng state at construction — the replay origin for
        #: load_state_dict (restore re-applies every epoch's shuffles
        #: from here, so no per-epoch snapshots are needed).
        self._init_rng = dict(self._rng.bit_generator.state)
        # Single-window mode (window >= shard) matches ArrayDataLoader
        # bit-for-bit: reset() there reshuffles the EXISTING order in
        # place, composing permutations across epochs, so we keep a
        # persistent order array and do the same.  Multi-window mode is
        # memoryless (fresh arange per window per epoch) — a persistent
        # per-window order would cost O(shard) index memory, defeating
        # out-of-core (contract documented in DATA.md).
        self._composed = self.shuffle and self.window >= self._shard
        self._order = (np.arange(self._shard) if self._composed else None)
        self._epoch = 0
        self._win_idx = 0        # windows admitted (consumer-side) this epoch
        self._rows_served = 0    # rows handed out in batches this epoch
        self._buf: List[Dict[str, np.ndarray]] = []
        self._buf_rows = 0
        self._queue: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._start_reader(self._win_idx)

    # ----- background reader (raw contiguous reads only, no RNG) -----

    def _start_reader(self, win_idx: int) -> None:
        self._stop = threading.Event()
        self._queue = queue.Queue(self._depth)
        stop, q = self._stop, self._queue
        windows, lo, source = self._windows, self._lo, self.source

        def work(idx: int = win_idx) -> None:
            try:
                while not stop.is_set():
                    if idx >= len(windows):
                        idx = 0  # epoch wrap: same raw reads every epoch
                    s, e = windows[idx]
                    chunk = source.read(lo + s, lo + e)
                    idx += 1
                    while not stop.is_set():
                        try:
                            q.put(("ok", chunk), timeout=_READER_POLL_S)
                            break
                        except queue.Full:
                            continue
            except BaseException as exc:  # surfaces at the next next()
                while not stop.is_set():
                    try:
                        q.put(("err", exc), timeout=_READER_POLL_S)
                        break
                    except queue.Full:
                        continue

        self._thread = threading.Thread(
            target=work, name="ff-stream-reader", daemon=True)
        self._thread.start()

    def _next_raw_window(self) -> Dict[str, np.ndarray]:
        kind, payload = self._queue.get()
        if kind == "err":
            self.close()
            if isinstance(payload, (RuntimeError, OSError)):
                raise payload
            raise StreamReaderError(
                f"stream reader thread failed: {payload!r}") from payload
        return payload

    # ----- consumer side -----

    def _admit(self, raw: Dict[str, np.ndarray]) -> None:
        n = len(next(iter(raw.values())))
        if self.shuffle:
            if self._composed:
                self._rng.shuffle(self._order)
                perm = self._order
            else:
                perm = np.arange(n)
                self._rng.shuffle(perm)
            raw = {k: v[perm] for k, v in raw.items()}
        self._buf.append(raw)
        self._buf_rows += n
        self._win_idx += 1

    def _take(self, count: int) -> Dict[str, np.ndarray]:
        parts: Dict[str, List[np.ndarray]] = {k: [] for k in self._buf[0]}
        need = count
        while need:
            head = self._buf[0]
            n = len(next(iter(head.values())))
            take = min(need, n)
            for k, v in head.items():
                parts[k].append(v[:take])
            if take == n:
                self._buf.pop(0)
            else:
                self._buf[0] = {k: v[take:] for k, v in head.items()}
            self._buf_rows -= take
            need -= take
        return {k: (np.ascontiguousarray(p[0]) if len(p) == 1
                    else np.concatenate(p))
                for k, p in parts.items()}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        b = self.batch_size
        while self._buf_rows < b:
            if self._win_idx >= len(self._windows):
                # Epoch end: drop the sub-batch tail (ArrayDataLoader's
                # reset()); the wrap reshuffle happens at the next
                # window admit, same rng call sequence as reset().
                self._buf, self._buf_rows = [], 0
                self._epoch += 1
                self._win_idx = 0
                self._rows_served = 0
            self._admit(self._next_raw_window())
        batch = self._take(b)
        self._rows_served += b
        return batch

    # ----- observability -----

    def queue_depths(self) -> Dict[str, int]:
        return {"reader": self._queue.qsize() if self._queue else 0}

    # ----- checkpoint protocol (fixed-shape, orbax-friendly) -----

    def state_dict(self) -> Dict[str, np.ndarray]:
        return {
            "cursor": np.array(
                [self._epoch, self._win_idx, self._rows_served], np.int64),
            "rng": _pack_rng(self._init_rng),
        }

    def load_state_dict(self, state: Dict[str, np.ndarray]) -> None:
        self.close()
        epoch, win_idx, served = (
            int(x) for x in np.asarray(state["cursor"]).reshape(3))
        self._rng = np.random.default_rng()
        self._rng.bit_generator.state = _unpack_rng(state["rng"])
        self._init_rng = dict(self._rng.bit_generator.state)
        if self._composed:
            self._order = np.arange(self._shard)
        self._epoch = epoch
        self._win_idx = 0
        self._rows_served = served
        self._buf, self._buf_rows = [], 0
        # Replay from the construction-time rng: past epochs advance the
        # rng (and the composed order) without touching data; then the
        # current epoch's admitted windows rebuild the buffer from the
        # source's deterministic raw reads.  O(epochs * shard) index
        # shuffles, restore-time only.
        if self.shuffle:
            for _ in range(epoch):
                if self._composed:
                    self._rng.shuffle(self._order)
                else:
                    for s, e in self._windows:
                        self._rng.shuffle(np.arange(e - s))
        for w in range(win_idx):
            s, e = self._windows[w]
            self._admit(self.source.read(self._lo + s, self._lo + e))
        if served:
            self._take(served)  # discard rows already handed out
        self._start_reader(self._win_idx)

    def close(self) -> None:
        self._stop.set()
        if self._queue is not None:
            while True:
                try:
                    self._queue.get_nowait()
                except queue.Empty:
                    break
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(timeout=_READER_JOIN_S)
        self._thread = None
