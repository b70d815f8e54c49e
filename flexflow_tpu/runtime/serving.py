"""Inference serving stack: ServingExecutor, KV-cache decode, and a
continuous-batching scheduler.

Everything before this subsystem trains; this is the serving half of
the north star (ROADMAP: "millions of users"), and it is where the
reference lineage itself went — FlexFlow Serve / SpecInfer built
low-latency LLM serving on top of the FlexFlow runtime.  The design
here was shaped by a per-dispatch host cost large enough to make
per-request — even per-token — dispatch a non-starter (that cost is
not measured on the chip, ROADMAP A2), so the serving loop reuses the
superstep discipline the training runtime already has (PRs 1/3/5):

- **Prefill**: the whole full-sequence forward over a request's
  prompt, pad-to-bucket, as ONE jitted program that also populates a
  per-layer (B, max_seq, heads, d_head) KV cache and returns the first
  greedy token — one dispatch + one fence per admission.
- **Decode superstep**: K single-token decode steps fused into one
  jitted ``lax.scan`` dispatch (greedy sampling INSIDE the program, so
  no host round-trip per token) with one ``jax.device_get`` fence per
  superstep — the same one-dispatch-one-fence shape as
  ``Executor.build_superstep``, under the same k <= 20 bound
  (``trainer.MAX_STEPS_PER_CALL``).
- **Continuous batching**: a request queue feeds ``max_batch`` fixed
  decode slots; admission (prefill + cache-row install) and eviction
  happen BETWEEN decode supersteps, so one dispatch always serves the
  whole active batch.  A slot finishing mid-superstep discards its
  tail tokens (bounded speculation waste — the fused-dispatch
  tradeoff, K tokens max).
- **Speculative decoding** (SERVING.md "Speculative decoding"): the
  fused superstep buys at most K<=20 tokens per dispatch;
  :meth:`ServingExecutor.build_spec_step` multiplies
  tokens per VERIFIED dispatch instead (the SpecInfer move, built on
  Leviathan et al.).  One jitted program runs d cheap DRAFT steps (a
  truncated-layer self-draft or a separate draft checkpoint of the
  same architecture, ``draft_layers``/``draft_params``), then
  verifies the whole draft with d+1 full-model steps whose scan body
  IS the decode-superstep body fed the draft tokens instead of its
  own feedback — so every emitted token is computed from a correct
  accepted history and the output sequence is BIT-IDENTICAL to
  sequential decode regardless of the acceptance pattern (greedy AND
  the keyed-sampling variant; acceptance only changes how many
  dispatches the sequence costs).  The longest matching prefix is
  accepted IN-PROGRAM; the single fence reads back
  ``(tokens (d+1, B), finite (d+1, B), accepted (B,))``.  Rejected
  draft rows need no explicit rollback: stale K/V at positions past
  a slot's ``pos`` is masked by the ``<= pos`` decode attention
  contract and overwritten as the position advances (padded and
  paged alike — out-of-reservation paged writes land in scratch
  block 0).

Three layers in this module: :class:`ServingExecutor` builds the
programs, :class:`ServingEngine` holds the caches and is the only code
that dispatches or fences one, and :class:`Server` (like the
scheduler's ``ScheduledServer``) is admission policy and bookkeeping
over the engine.

The KV-cache protocol lives on the op layer (``ops/attention.py``):
``MultiHeadAttention.forward`` takes a cached path when ``state``
carries ``cache_k``/``cache_v``/``pos``, with a Pallas flash *decode*
kernel (``ops/pallas_kernels.flash_decode``: q_len=1 streaming softmax
over cache blocks, per-slot length masking) and the pure-jnp
``_einsum_decode`` as numerics oracle + fallback.  Params come from
training checkpoints via the strategy-portable ``CheckpointManager``
restore — the train->serve handoff (SERVING.md).

Two capacity regimes extend the PR-7 single-mesh pad-to-max_seq
engine (SERVING.md "Cache layout"):

- **Sharded decode** (``shard=(n, c)``): the slot batch shards over
  mesh axis ``n`` and heads over ``c`` (the training strategy axes,
  via ``build_mesh_plan`` + ``ParallelConfig``) so per-layer caches
  are ``NamedSharding``-placed and the fused decode superstep runs as
  one sharded whole-graph program; ``flash_decode`` is shard_map-
  wrapped per local shard (the ``_flash_dense`` discipline), the
  einsum oracle stays the single-mesh fallback.
- **Paged KV caches** (``kv_block > 0``): per-layer caches become a
  global pool of fixed-size KV blocks ``(kv_blocks, kv_block, h, hd)``
  plus a per-slot block table, so HBM per slot scales with the
  request's ACTUAL reserved length (``KVBlockLedger.blocks_for``) —
  not worst-case ``max_seq`` — and admission is gated by the
  host-side :class:`KVBlockLedger` free list.  Block 0 is a reserved
  scratch block: inactive slots and bounded-speculation overflow
  writes land there and are never read by an active slot's masked
  attention, keeping survivors byte-identical under chaos.

The two COMPOSE: block tables are host-side int arithmetic with no
batch axis on the pool, so paged + sharded shards the pool's HEAD
axis on ``c`` (``NamedSharding (None, None, 'c', None)``) while the
paged decode path — pure-jnp scatter/gather + the einsum oracle —
partitions via plain GSPMD; per-(slot, head) softmax is independent,
so sharded-paged tokens are bit-identical to the single-mesh paged
oracle.  The ``n`` axis replicates the pool (the pool has no batch
dimension to shard), so the per-device capacity win of paged+sharded
comes from ``c`` alone.

Fault isolation (chaos matrix: ``runtime/chaos.py`` serving scenario):
slots are independent in the batch dimension, per-slot logits carry an
in-program finiteness flag read at the superstep fence, and a faulted
slot errors out its request WITHOUT touching its neighbors' sequences.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import logging
import math
import re
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.ops.attention import MultiHeadAttention, PositionEmbedding
from flexflow_tpu.ops.base import SERVING_STATS_LARGEST, op_params
from flexflow_tpu.ops.linear import Linear
from flexflow_tpu.ops.tensor_ops import Add
from flexflow_tpu.runtime import telemetry as _telemetry

#: Bound on the fused decode superstep — THE training supersteps'
#: fused-step bound, shared so the two dispatch regimes cannot drift
#: if it is ever retuned.
from flexflow_tpu.runtime.trainer import (
    MAX_STEPS_PER_CALL as MAX_DECODE_STEPS_PER_CALL,
    clamp_fused_steps,
)

_log = logging.getLogger("ff.serving")


class ServingFault(RuntimeError):
    """A raised (device-class) fault attributed to one decode slot —
    the scheduler errors out that slot's request and keeps serving the
    rest (see :class:`ServingFaultInjector`)."""

    def __init__(self, slot: int, msg: str = ""):
        super().__init__(msg or f"injected serving fault in slot {slot}")
        self.slot = slot


class ServingEngineFault(RuntimeError):
    """An ENGINE-class serving fault: a compiled program raised, the
    cache pool is suspect, a kernel failed repeatedly — nothing a
    single slot owns.  The scheduled loop answers with an engine
    restart (rebuild programs/caches/ledger, requeue in-flight work
    with carried tokens — SERVING.md "Failure model"); the legacy
    closed loop lets it propagate, which is the crash the request
    journal recovers from."""


class ServingCrashLoop(RuntimeError):
    """The engine-restart budget is exhausted — the serving analogue
    of the training crash-loop guard (``FailurePolicy.max_restarts``).
    ``apps/serve.py`` maps it to :data:`EXIT_SERVING_FAILURE` for an
    external supervisor, mirroring ``EXIT_WORLD_FAILURE``."""


#: Process exit code for an unrecoverable serving engine (crash-loop
#: budget exhausted): the supervisor-facing signal that restarting the
#: SAME process is pointless, next to ``elastic.EXIT_WORLD_FAILURE``'s
#: 76 in the supervisor's decision table (RESILIENCE.md).
EXIT_SERVING_FAILURE = 77


class ServingFaultInjector:
    """Scheduled chaos for the serving loop (the FaultInjector pattern
    from ``runtime/resilience.py``, keyed by decode-superstep index).

    - ``nan_cache_at``: ``{superstep_index: slot}`` — that slot's
      layer-0 K cache row becomes NaN before the superstep, so its
      logits go non-finite and the finiteness flag at the fence errors
      the request out.  A *silent per-request* fault: neighbors'
      cache rows are untouched.
    - ``raise_at``: ``{superstep_index: slot}`` — a host-side raise
      attributed to the slot before the dispatch (the raised-failure
      class); the superstep never runs, so neighbors lose nothing.
    - ``engine_raise_at``: ``{superstep_index: message}`` — an
      ENGINE-class :class:`ServingEngineFault` before the dispatch
      (compiled-program death, poisoned pool): no slot to blame, the
      whole engine restarts (or the process dies, in the legacy loop).
    - ``preempt_at``: ``{superstep_index}`` — SIGTERM to our own
      process before the dispatch (the ``FaultInjector.preempt_at``
      pattern): with a drain-armed server the run drains at the next
      boundary and exits cleanly.

    The same schedule drives the REAL loop (device caches NaN'd) and
    the scheduler's compute-free simulate loop (``caches=None``: the
    target slot is returned for the sim to mark non-finite) — keyed
    by superstep index, both fire identically, which is what keeps
    sim-vs-real dispatch exactness through faults.
    """

    def __init__(self, nan_cache_at: Optional[Dict[int, int]] = None,
                 raise_at: Optional[Dict[int, int]] = None,
                 engine_raise_at: Optional[Dict[int, str]] = None,
                 preempt_at: Optional[Sequence[int]] = None):
        self.nan_cache_at = dict(nan_cache_at or {})
        self.raise_at = dict(raise_at or {})
        self.engine_raise_at = dict(engine_raise_at or {})
        self.preempt_at = set(preempt_at or ())
        #: Log of ("nan_cache"|"raise"|"engine"|"preempt",
        #: superstep, slot-or--1) fired.
        self.fired: List[Tuple[str, int, int]] = []

    def before_superstep(self, idx: int, caches, block_table=None):
        """Returns ``(caches, nan_slot)``; may raise
        :class:`ServingFault` / :class:`ServingEngineFault` or SIGTERM
        the process.  ``nan_slot`` is the slot whose cache was NaN'd
        (None otherwise) — the real loop ignores it (the device
        finiteness flag detects the fault), the simulate loop flips
        that slot's fabricated flag.

        ``block_table`` (host (B, nblk) int32) switches the NaN
        injection to the paged layout: the target slot's FIRST owned
        pool block goes NaN — the paged analogue of NaNing the slot's
        padded cache row (never the shared scratch block 0, which
        would leak the fault across slots)."""
        if idx in self.preempt_at:
            self.preempt_at.discard(idx)
            self.fired.append(("preempt", idx, -1))
            import os
            import signal

            os.kill(os.getpid(), signal.SIGTERM)
        if idx in self.engine_raise_at:
            msg = self.engine_raise_at.pop(idx)
            self.fired.append(("engine", idx, -1))
            _telemetry.current().emit("fault", mode="serving_engine",
                                      superstep=idx, slot=None)
            raise ServingEngineFault(
                msg or f"injected engine fault at superstep {idx}"
            )
        if idx in self.raise_at:
            slot = self.raise_at.pop(idx)
            self.fired.append(("raise", idx, slot))
            _telemetry.current().emit("fault", mode="serving_raise",
                                      superstep=idx, slot=slot)
            raise ServingFault(slot)
        if idx in self.nan_cache_at:
            slot = self.nan_cache_at.pop(idx)
            self.fired.append(("nan_cache", idx, slot))
            _telemetry.current().emit("fault", mode="serving_nan",
                                      superstep=idx, slot=slot)
            if caches is None:
                return None, slot  # simulate mode: no device caches
            name = next(iter(caches))
            entry = next(iter(caches[name]))
            k = caches[name][entry]
            if block_table is not None:
                dest = int(block_table[slot][0])
                if dest == 0:  # slot owns no blocks: nothing to corrupt
                    return caches, None
                k = k.at[dest].set(jnp.nan)
            else:
                k = k.at[slot].set(jnp.nan)
            caches = dict(caches)
            caches[name] = {**caches[name], entry: k}
            return caches, slot
        return caches, None


@dataclasses.dataclass
class Request:
    """One generation request.

    ``arrival_ms`` / ``priority`` / ``slo_ms`` are the open-loop
    scheduling fields (``flexflow_tpu/serving/``, SERVING.md): arrival
    on the scheduler's virtual clock, priority tier (0 = highest), and
    the end-to-end deadline in virtual ms (inf = best-effort).

    The PR-7 closed-loop ``arrival`` superstep-index field is GONE
    (its one-release deprecation grace is up): constructing a Request
    with ``arrival=`` raises ``TypeError``.  Arrivals are workload-
    driven ``arrival_ms`` (``serving/workload.py``) everywhere."""

    id: int
    prompt: np.ndarray  # 1-D int32 token ids
    max_new_tokens: int = 16
    arrival_ms: float = 0.0
    priority: int = 0
    slo_ms: float = float("inf")

    @property
    def deadline_ms(self) -> float:
        return self.arrival_ms + self.slo_ms


def prefix_digests(tokens, block: int) -> List[bytes]:
    """Chained per-block content hashes of a prompt's FULL blocks —
    the prefix-cache index key (SERVING.md "Prefix sharing").

    Digest j covers tokens ``[0, (j+1)*block)``: ``h_0 =
    sha1(block_0)``, ``h_j = sha1(h_{j-1} ‖ block_j)``, token ids
    normalized to int64 bytes.  Chaining is what makes a digest a
    sound key for CAUSAL KV content: K/V at row r depends only on
    tokens ``[0, r]``, so two prompts agreeing on the first
    ``(j+1)*block`` tokens have bit-equal KV in block j."""
    import hashlib

    toks = np.asarray(tokens, np.int64)
    out: List[bytes] = []
    prev = b""
    for j in range(len(toks) // int(block)):
        blk = toks[j * block:(j + 1) * block].tobytes()
        out.append(hashlib.sha1(prev + blk).digest())
        prev = out[-1]
    return out


@dataclasses.dataclass(frozen=True)
class PrefixPlan:
    """Host-side admission plan from :meth:`KVBlockLedger.plan_prefix`.

    ``use`` resident full-prefix blocks will be SHARED (refcount++);
    ``cow`` matched blocks are recomputed privately instead (the
    copy-on-write clamp: the prefill must compute at least the last
    prompt token's logits, so a fully-covered prompt without a
    memoized first token re-runs its final block); ``offset`` =
    ``use * block`` is the first token row the offset prefill
    computes.  ``full_hit`` means the whole prompt is covered AND the
    first token is memoized — ZERO prefill dispatches; ``tok0`` is
    that memoized token.  ``shared`` are the pool block ids to
    reference, donor order."""

    use: int
    cow: int
    offset: int
    full_hit: bool
    tok0: Optional[int] = None
    shared: Tuple[int, ...] = ()


class KVBlockLedger:
    """Host-side free-list accounting for the paged KV pool.

    PURE integer arithmetic, deliberately device-free: the SAME ledger
    gates admission in both loops over the real :class:`ServingEngine`
    (:class:`Server`, ``ScheduledServer``) and in the scheduler's
    compute-free ``simulated`` mode, so the simulation stays
    dispatch-for-dispatch exact on the paged path by construction.

    Block 0 is the SCRATCH block — never allocated.  Inactive slots'
    table rows point at it, and decode writes past a slot's
    reservation (the bounded-speculation tail of a fused K-step
    superstep) land there; no active slot's masked attention ever
    reads its own reserved region from it.  Freed blocks return to
    the free list and are reused LOWEST-FIRST (the list stays
    sorted), so allocation is deterministic across replays.

    ``prefix_cache=True`` arms prefix sharing (SERVING.md "Prefix
    sharing"): every block carries a refcount, and a content-hash
    index maps a prompt's chained full-block digests
    (:func:`prefix_digests`) to resident pool blocks.
    :meth:`plan_prefix` finds the longest resident prefix at
    admission; :meth:`alloc` takes the shared block ids (refcount++)
    and allocates only the tail fresh; :meth:`free` decrements and
    returns a block to the free list only at refcount 0, dropping its
    index entry with it.  All still host integers — sim exactness is
    unchanged by construction."""

    def __init__(self, num_blocks: int, block: int, max_seq: int,
                 prefix_cache: bool = False):
        if block < 1 or max_seq % block:
            raise ValueError(
                f"kv_block must divide max_seq: block={block}, "
                f"max_seq={max_seq}"
            )
        if num_blocks < 2:
            raise ValueError(
                f"paged pool needs >= 2 blocks (scratch + 1), got "
                f"{num_blocks}"
            )
        self.num_blocks = int(num_blocks)
        self.block = int(block)
        self.max_seq = int(max_seq)
        #: Table-row width: worst-case blocks a slot could reference.
        self.blocks_per_slot = self.max_seq // self.block
        self.prefix_cache = bool(prefix_cache)
        self._free: List[int] = list(range(1, self.num_blocks))
        self._held: Dict[int, List[int]] = {}
        #: Per-block reference counts (every held block has one; 1 for
        #: privately-owned blocks, > 1 when prefix-shared).
        self._ref: Dict[int, int] = {}
        #: Chained content digest -> resident pool block (live blocks
        #: only — entries drop when their block's refcount hits 0).
        self._index: Dict[bytes, int] = {}
        #: Reverse map for index cleanup at free time.
        self._digest_of: Dict[int, bytes] = {}
        #: Full-prompt digest -> memoized greedy first token: the
        #: zero-dispatch full-hit path.  Persists past eviction
        #: (harmless: a full hit ALSO requires every block resident).
        self._next_tok: Dict[bytes, int] = {}

    @property
    def capacity_blocks(self) -> int:
        """Allocatable blocks (scratch excluded)."""
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def blocks_for(self, prompt_len: int, max_new_tokens: int) -> int:
        """Blocks to RESERVE at admission: every position the request
        can legitimately write (prompt + generated + the first-token
        feedback row), capped at the context limit.  Reserving up
        front means a slot can never exhaust the pool mid-decode."""
        toks = min(int(prompt_len) + int(max_new_tokens) + 1, self.max_seq)
        return -(-toks // self.block)

    def can_admit(self, n_blocks: int) -> bool:
        return n_blocks <= len(self._free)

    def plan_prefix(self, prompt,
                    total_len: Optional[int] = None) -> "PrefixPlan":
        """Longest-resident-prefix lookup for one admission — pure
        host arithmetic over :func:`prefix_digests` and the index.
        ``total_len`` is the re-prefill length (prompt ‖ carried) for
        journal/preemption resumes; matching is over the PROMPT only
        (carried tokens are per-request decode output, never
        indexed).  Returns the no-share plan when the cache is off or
        nothing matches."""
        plen = len(prompt)
        flen = int(total_len) if total_len is not None else plen
        if not self.prefix_cache or plen < self.block:
            return PrefixPlan(0, 0, 0, False)
        digests = prefix_digests(prompt, self.block)
        matched: List[int] = []
        for dgst in digests:
            blk = self._index.get(dgst)
            if blk is None:
                break
            matched.append(blk)
        m = len(matched)
        if m == 0:
            return PrefixPlan(0, 0, 0, False)
        if flen == plen == m * self.block:
            tok0 = self._next_tok.get(digests[m - 1])
            if tok0 is not None:
                return PrefixPlan(m, 0, m * self.block, True,
                                  int(tok0), tuple(matched))
        # The offset prefill must compute the last real token's row
        # (logits at flen - 1), so sharing clamps to offset <= flen-1:
        # a fully-covered prompt without a first-token memo recomputes
        # its final matched block privately — the copy-on-write case.
        use = min(m, (flen - 1) // self.block)
        return PrefixPlan(use, m - use, use * self.block, False,
                          None, tuple(matched[:use]))

    def alloc(self, slot: int, n_blocks: int,
              shared: Sequence[int] = ()) -> np.ndarray:
        """Reserve ``n_blocks`` TOTAL for ``slot``; returns the slot's
        full ``(blocks_per_slot,)`` int32 table row (unreserved
        entries point at scratch block 0).  ``shared`` names resident
        pool blocks the slot references instead of allocating
        (prefix sharing: refcount++, they fill the front of the row);
        only ``n_blocks - len(shared)`` fresh blocks leave the free
        list."""
        shared = list(shared)
        if slot in self._held:
            raise RuntimeError(f"slot {slot} already holds KV blocks")
        fresh_n = int(n_blocks) - len(shared)
        if fresh_n < 0:
            raise ValueError(
                f"alloc: {len(shared)} shared blocks exceed the "
                f"{n_blocks}-block reservation"
            )
        if fresh_n > len(self._free):
            raise RuntimeError(
                f"paged KV pool exhausted: need {fresh_n} blocks, "
                f"{len(self._free)} free of {self.capacity_blocks}"
            )
        got, self._free = self._free[:fresh_n], self._free[fresh_n:]
        for b in shared:
            self._ref[b] += 1
        for b in got:
            self._ref[b] = 1
        held = shared + got
        self._held[slot] = held
        row = np.zeros((self.blocks_per_slot,), np.int32)
        row[: len(held)] = held
        return row

    def free(self, slot: int) -> None:
        got = self._held.pop(slot, None)
        if not got:
            return
        released: List[int] = []
        for b in got:
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                released.append(b)
                dgst = self._digest_of.pop(b, None)
                if dgst is not None and self._index.get(dgst) == b:
                    del self._index[dgst]
        if released:
            self._free = sorted(self._free + released)

    def register_prefix(self, slot: int, digests: Sequence[bytes],
                        start: int = 0) -> None:
        """Index ``slot``'s freshly-INSTALLED full-prompt blocks
        (``digests[start:]`` onto held blocks ``start..``) so later
        admissions can share them.  Called only AFTER the prefill
        fence validated the install (never index blocks that were
        never written — an engine-fault rollback ``free()`` would
        otherwise leave dangling garbage shareable).  First writer
        wins on digest collisions."""
        if not self.prefix_cache:
            return
        held = self._held.get(slot, [])
        for j in range(int(start), len(digests)):
            if j >= len(held):
                break
            dgst = digests[j]
            if dgst in self._index:
                continue
            self._index[dgst] = held[j]
            self._digest_of[held[j]] = dgst

    def record_next(self, digest: bytes, tok: int) -> None:
        """Memoize the greedy first token after a block-aligned fresh
        prefill — what upgrades a later identical admission from
        offset-prefill to the ZERO-dispatch full hit."""
        if self.prefix_cache:
            self._next_tok[bytes(digest)] = int(tok)


@dataclasses.dataclass
class RequestResult:
    id: int
    prompt_len: int
    tokens: List[int]            # generated token ids, in order
    error: Optional[str] = None  # None = completed cleanly
    latency_s: float = 0.0       # eligible -> finished wall time
    prefill_s: float = 0.0


@dataclasses.dataclass
class _Slot:
    request: Request
    pos: int                 # position of the NEXT token to decode
    last_tok: int            # token at position pos-1... fed to decode
    tokens: List[int]        # tokens generated THIS occupancy
    t_eligible: float
    prefill_s: float
    #: Tokens carried from a previous (crashed / drained) run via the
    #: journal — the re-prefill-over-(prompt ‖ carried) resume.
    carried: List[int] = dataclasses.field(default_factory=list)

    @property
    def all_tokens(self) -> List[int]:
        return self.carried + self.tokens


class ServingExecutor:
    """Compiles forward-only serving programs for an FFModel LM.

    Two program families, both whole-graph jitted (the
    ``PipelineExecutor.build_compiled_step`` fusion discipline, minus
    backward/optimizer):

    - :meth:`build_prefill` (one per pad bucket L): ``(params, state,
      tokens (1, L), length) -> (cache_rows, first_token, finite)`` —
      the full-sequence causal forward (bit-identical to the training
      forward on the same tokens), cache rows 0..L-1 populated, greedy
      first token taken at ``length - 1``.
    - :meth:`build_decode_superstep` (one per k): K fused single-token
      decode steps as one ``lax.scan`` dispatch over the whole slot
      batch — greedy tokens and per-slot finiteness stacked (K, B),
      read back in ONE fence.

    Params restore from training checkpoints through the existing
    strategy-portable ``CheckpointManager`` (:meth:`restore`).

    Capacity knobs (SERVING.md "Cache layout"):

    - ``shard=(n, c)``: multi-chip decode — slot batch over mesh axis
      ``n``, heads over ``c`` (``build_mesh_plan(n*c)`` +
      ``ParallelConfig(n=n, c=c)``, the training strategy machinery);
      a hybrid-trained checkpoint restores and serves sharded with no
      conversion.  Falls back LOUDLY to single-mesh when the box has
      too few devices.
    - ``kv_block`` / ``kv_blocks``: paged KV caches — per-layer pools
      of ``kv_blocks`` fixed-size blocks of ``kv_block`` token
      positions, per-slot block tables, admission gated by
      :class:`KVBlockLedger`.  ``kv_block=0`` (default) keeps the
      padded ``(max_batch, max_seq, ...)`` layout; ``kv_blocks=None``
      defaults to the worst case (every slot at ``max_seq``) + the
      scratch block — the capacity win comes from setting it lower
      under an HBM budget.  Paged and sharded COMPOSE: the pool
      shards its head axis on ``c`` (the ``n`` axis replicates the
      pool — it has no batch dimension), parity-pinned to the
      single-mesh paged oracle; genuinely unsupported shapes
      (``num_heads % c``) still refuse loudly, and a box with too few
      devices still falls back loudly to the single mesh.
    - ``draft_layers``: speculative decoding's DRAFT truncation — the
      draft forward runs only the first L ``blk{i}_``-named
      transformer blocks of the (same-architecture) draft params,
      passing the residual stream through the skipped blocks.  0 (the
      default) runs the full graph as the draft: with separate
      ``draft_params`` that is the draft-checkpoint configuration;
      with the serving params themselves it is the degenerate
      full-self-draft whose acceptance is exactly 1.0 —
      compute-wasteful but dispatch-optimal, the right trade only
      where dispatch dominates.  See :meth:`build_spec_step`.
    """

    def __init__(
        self,
        model: FFModel,
        config: Optional[FFConfig] = None,
        max_batch: int = 4,
        max_seq: Optional[int] = None,
        buckets: Optional[Sequence[int]] = None,
        decode_kernel: Optional[bool] = None,
        device: Optional[jax.Device] = None,
        kv_block: int = 0,
        kv_blocks: Optional[int] = None,
        shard: Optional[Tuple[int, int]] = None,
        draft_layers: int = 0,
        prefix_cache: bool = False,
    ):
        self.model = model
        self.config = config or model.config
        self._layers = [op for op in model.layers if not op.is_loss]
        loss_ops = model.loss_ops
        if loss_ops:
            self._logits_name = loss_ops[-1].inputs[0].name
        else:
            self._logits_name = self._layers[-1].outputs[0].name
        consumed = {t.name for op in self._layers for t in op.inputs}
        feed = [t for t in model.input_tensors if t.name in consumed]
        if len(feed) != 1:
            raise ValueError(
                f"serving drives single-input token LMs (transformer "
                f"first); the non-loss graph consumes inputs "
                f"{[t.name for t in feed]}"
            )
        self._tokens_name = feed[0].name
        self.max_batch = int(max_batch)
        self.max_seq = int(max_seq or feed[0].shape[1])
        # The positions-last cache order is the padded single-mesh
        # layout's (SERVING.md "Cache layout").
        self._positions_last = not (kv_block or shard)
        for op in self._layers:
            self._bind_layout(op)
        #: What each attention-like op declares it keeps for a slot
        #: (``Op.cache_entries``): name -> {entry: CacheEntry}.  The
        #: executor allocates, installs and carries exactly this.
        declared = ((op.name, op.cache_entries(self.max_seq))
                    for op in self._layers)
        self._cache_specs: Dict[str, Dict[str, Any]] = {
            name: entries for name, entries in declared if entries
        }
        self.attn_ops = [
            op for op in self._layers if op.name in self._cache_specs
        ]
        if not self.attn_ops:
            raise ValueError(
                "serving needs at least one op that declares a cache "
                "(Op.cache_entries: the decode protocol lives there)"
            )
        #: The cache-holding ops some entry of which has no sequence
        #: axis (a recurrent state, a convolution window): what the block
        #: pool, a shared prefix and a speculative step cannot hold.
        self.stateful_ops = [
            op for op in self.attn_ops
            if not all(ce.sequence
                       for ce in self._cache_specs[op.name].values())
        ]
        #: Whether any op reports counters when serving (``Op.serving_stats``).
        self.has_stats = any(op.serving_stats for op in self._layers)
        # Pad buckets for prefill (ascending); every bucket compiles
        # its own prefill program, so keep the list short.
        bks = sorted(set(int(b) for b in (buckets or (self.max_seq,))))
        if any(b < 1 or b > self.max_seq for b in bks):
            raise ValueError(f"buckets must be in [1, max_seq]: {bks}")
        self.buckets: Tuple[int, ...] = tuple(bks)
        self.decode_kernel = decode_kernel
        self.device = device if device is not None else jax.devices()[0]
        # The ops that keep a window's ring: padded, on one device.
        ringed = [op.name for op in self.attn_ops
                  if op.decode_window is not None]
        # -- paged KV layout --
        self.kv_block = int(kv_block or 0)
        self.paged = self.kv_block > 0
        if self.paged:
            if ringed:
                raise NotImplementedError(
                    f"the paged KV layout (kv_block > 0): {ringed} keep a "
                    f"window's ring, which has no blocks in the pool "
                    f"(ROADMAP B-M4): serve them padded (kv_block=0)")
            unpaged = [op for op in self.attn_ops if not op.cache_paged]
            if unpaged:
                raise ValueError(
                    f"the paged KV layout (kv_block > 0) holds keys and "
                    f"values a head; {[op.name for op in unpaged]} "
                    f"({type(unpaged[0]).__name__}) declare another "
                    f"cache and have no paged pool yet (ROADMAP Queue B): "
                    f"serve them padded (kv_block=0)"
                )
            if self.max_seq % self.kv_block:
                raise ValueError(
                    f"kv_block must divide max_seq: kv_block="
                    f"{self.kv_block}, max_seq={self.max_seq}"
                )
            self.blocks_per_slot = self.max_seq // self.kv_block
            worst = self.max_batch * self.blocks_per_slot + 1
            self.kv_blocks = int(kv_blocks) if kv_blocks else worst
            if self.kv_blocks < 2:
                raise ValueError(
                    f"kv_blocks must be >= 2 (scratch + 1), got "
                    f"{self.kv_blocks}"
                )
        else:
            if kv_blocks:
                raise ValueError("kv_blocks needs kv_block > 0 (paged mode)")
            self.blocks_per_slot = 0
            self.kv_blocks = 0
        # -- prefix sharing (SERVING.md "Prefix sharing") --
        self.prefix_cache = bool(prefix_cache)
        if self.prefix_cache and not self.paged:
            raise ValueError(
                "prefix_cache needs the paged KV layout (kv_block > 0): "
                "sharing is block-table indirection — the padded layout "
                "has no blocks to share"
            )
        # -- sharded decode (batch on 'n', heads on 'c') --
        # Paged caches compose: the pool shards heads on 'c' only (no
        # batch axis to shard on 'n'), block tables stay host-side
        # ints, and the pure-jnp paged decode path partitions via
        # plain GSPMD — see the module docstring.
        self._plan = None
        self._pc = None
        if shard is not None:
            n, c = int(shard[0]), int(shard[1])
            if n < 1 or c < 1 or n * c < 2:
                raise ValueError(f"shard=(n, c) needs n*c >= 2, got {shard}")
            ndev = len(jax.devices())
            if ndev < n * c:
                _log.warning(
                    "sharded decode needs %d devices, have %d: falling "
                    "back to the single-mesh engine", n * c, ndev,
                )
            else:
                if not self.paged and self.max_batch % n:
                    # The padded cache shards its batch axis on 'n';
                    # the paged pool has no batch axis, so 'n' only
                    # sizes the mesh there.
                    raise ValueError(
                        f"shard batch degree n={n} must divide "
                        f"max_batch={self.max_batch}"
                    )
                if ringed:
                    raise NotImplementedError(
                        f"sharded decode (shard=): {ringed} keep a "
                        f"window's ring, which is built for one device "
                        f"(ROADMAP B-M4)")
                selecting = [
                    op.name for op in self.attn_ops
                    if getattr(op, "select", None) is not None
                ]
                if selecting:
                    raise NotImplementedError(
                        f"sharded decode (shard=): {selecting} keep a "
                        f"token selector, whose cache and gather are "
                        f"built for one device (ROADMAP B-M1)"
                    )
                unsharded = [
                    op.name for op in self.attn_ops
                    if not isinstance(op, MultiHeadAttention)
                ]
                if unsharded:
                    raise ValueError(
                        f"sharded decode (shard=) is built for "
                        f"MultiHeadAttention caches; {unsharded} declare "
                        f"another cache (ROADMAP Queue B)"
                    )
                bad = [
                    op.name for op in self.attn_ops
                    if op.attrs["num_heads"] % c
                    or op.attrs["num_kv_heads"] % c
                ]
                if bad:
                    raise ValueError(
                        f"shard head degree c={c} must divide num_heads "
                        f"(and num_kv_heads) of every attention op; "
                        f"offenders: {bad}"
                    )
                from flexflow_tpu.parallel.mesh import build_mesh_plan
                from flexflow_tpu.parallel.strategy import ParallelConfig

                self._plan = build_mesh_plan(num_devices=n * c)
                self._pc = ParallelConfig(n=n, c=c)
        self.shard = (
            (self._pc.n, self._pc.c) if self._pc is not None else None
        )
        # -- speculative drafting (SERVING.md "Speculative decoding") --
        # ``draft_layers`` truncates the DRAFT forward to the first L
        # blk{i}_-named transformer blocks; the skipped blocks pass
        # the residual stream through.  0 = full-graph draft.
        self.draft_layers = int(draft_layers or 0)
        blk_of: Dict[str, int] = {}
        for op in self._layers:
            m = re.match(r"blk(\d+)_", op.name)
            if m:
                blk_of[op.name] = int(m.group(1))
        n_blocks = max(blk_of.values()) + 1 if blk_of else 0
        if self.draft_layers:
            if not blk_of:
                raise ValueError(
                    "draft_layers needs blk{i}_-named transformer blocks "
                    "(models/transformer.py naming); this graph has none"
                )
            if not 1 <= self.draft_layers <= n_blocks:
                raise ValueError(
                    f"draft_layers must be in [1, {n_blocks}], got "
                    f"{self.draft_layers}"
                )
        self._draft_skip = frozenset(
            name for name, i in blk_of.items()
            if self.draft_layers and i >= self.draft_layers
        )
        #: Cache specs for the draft forward's OWN (always padded)
        #: KV caches — the attention ops the truncation keeps.
        self._draft_cache_specs = {
            name: spec for name, spec in self._cache_specs.items()
            if name not in self._draft_skip
        }
        self._prefill_fns: Dict[int, Any] = {}
        self._decode_fns: Dict[Tuple, Any] = {}

    # -- params / checkpoint handoff ---------------------------------------

    def _templates(self):
        """(params, opt_state, op_state) templates from a throwaway
        full-mesh Executor — the same init path training uses, so a
        training checkpoint restores into matching structure (the
        strategy-portable restore re-shards on load)."""
        from flexflow_tpu.runtime.executor import Executor

        return Executor(self.model, config=self.config).init()

    def _place(self, tree):
        if self._plan is not None:
            # Sharded mode: params/op_state replicate over the decode
            # mesh (mixing mesh-sharded caches with a single committed
            # device would reject at dispatch).
            return jax.device_put(tree, self._plan.replicated())
        return jax.device_put(tree, self.device)

    def init(self, seed: Optional[int] = None):
        """Fresh (params, op_state) on the serving device — the
        no-checkpoint path (synthetic serving benchmarks)."""
        from flexflow_tpu.runtime.executor import Executor

        params, _opt, state = Executor(self.model, config=self.config).init(
            seed
        )
        return self._place(params), self._place(state)

    def restore(self, ckpt_dir: str, step: Optional[int] = None):
        """Train->serve handoff: restore ``(step, params, op_state)``
        from a training checkpoint directory (optimizer state is
        restored into the templates and discarded — serving needs
        none of it)."""
        from flexflow_tpu.runtime.checkpoint import CheckpointManager

        templates = self._templates()
        with CheckpointManager(ckpt_dir) as ck:
            got_step, params, _opt, state = ck.restore(
                templates=templates, step=step
            )
        return got_step, self._place(params), self._place(state)

    # -- caches -------------------------------------------------------------

    def _entries(self, sequence: bool):
        return [ce for ents in self._cache_specs.values()
                for ce in ents.values() if ce.sequence == sequence]

    @property
    def _bytes_per_token(self) -> int:
        """Bytes one cached token position costs across ALL layers, over
        the declared entries that have a sequence axis (K and V, the
        latent column)."""
        return sum(
            math.prod(ce.shape) // self.max_seq * jnp.dtype(ce.dtype).itemsize
            for ce in self._entries(True)
        )

    @property
    def _bytes_fixed(self) -> int:
        """Bytes a slot's entries WITHOUT a sequence axis cost across
        all layers, whatever the length (recurrent states, convolution
        windows): 0 for a graph of attention layers alone."""
        return sum(math.prod(ce.shape) * jnp.dtype(ce.dtype).itemsize
                   for ce in self._entries(False))

    @property
    def _bytes_per_slot(self) -> int:
        """A padded slot: ``max_seq`` positions of every per-token entry
        and the fixed entries once."""
        return self.max_seq * self._bytes_per_token + self._bytes_fixed

    def cache_total_bytes(self) -> int:
        """Per-device bytes :meth:`init_cache` will allocate (the
        ``DeviceMemoryError`` budget estimate)."""
        if self.paged:
            total = self.kv_blocks * self.kv_block * self._bytes_per_token
            if self._pc is not None:
                # The pool shards heads on 'c' only; 'n' replicates it.
                total //= self._pc.c
        else:
            total = self.max_batch * self._bytes_per_slot
            if self._plan is not None:
                total //= self._plan.num_devices
        return total

    def hbm_per_slot_bytes(
        self, prompt_len: Optional[int] = None,
        max_new_tokens: Optional[int] = None,
    ) -> int:
        """Cache HBM one decode slot costs.  Padded: the full
        worst-case ``max_seq`` row of every per-token entry, regardless
        of request length, and the entries without a sequence axis once.
        Paged: the blocks :class:`KVBlockLedger` would reserve for a
        ``(prompt_len, max_new_tokens)`` request (defaults: the
        worst case, where the two layouts coincide up to rounding)."""
        if not self.paged:
            return self._bytes_per_slot
        if prompt_len is None:
            blocks = self.blocks_per_slot
        else:
            led = KVBlockLedger(self.kv_blocks, self.kv_block, self.max_seq)
            blocks = led.blocks_for(
                prompt_len,
                self.max_seq if max_new_tokens is None else max_new_tokens,
            )
        return blocks * self.kv_block * self._bytes_per_token

    def max_admissible_batch(
        self, budget_bytes: int, prompt_len: int, max_new_tokens: int
    ) -> int:
        """How many CONCURRENT decode slots a cache-HBM budget admits
        for uniform ``(prompt_len, max_new_tokens)`` requests — the
        paged-vs-padded capacity comparison, compute-free.  Padded is
        bounded by worst-case ``max_seq`` rows; paged by the block
        pool the budget can hold."""
        if not self.paged:
            return budget_bytes // self._bytes_per_slot
        block_bytes = self.kv_block * self._bytes_per_token
        pool_blocks = budget_bytes // block_bytes - 1  # scratch
        led = KVBlockLedger(self.kv_blocks, self.kv_block, self.max_seq)
        need = led.blocks_for(prompt_len, max_new_tokens)
        return max(pool_blocks, 0) // need

    def make_ledger(self) -> KVBlockLedger:
        """The paged pool's host-side accounting (raises unless
        paged) — one per serving loop; real and simulated loops build
        identical ledgers, which is what keeps simulate admission
        exact."""
        if not self.paged:
            raise ValueError("make_ledger() needs kv_block > 0 (paged mode)")
        return KVBlockLedger(self.kv_blocks, self.kv_block, self.max_seq,
                             prefix_cache=self.prefix_cache)

    def _budget_check(self):
        """Refuse BEFORE the first ``device_put`` when the KV cache
        cannot fit the per-device budget — the ``DeviceMemoryError``
        estimate machinery (``data/loader.py``), reused so serving
        capacity is measurable under ``FF_DEVICE_MEM_BYTES``."""
        from flexflow_tpu.data.loader import (
            DeviceMemoryError, _device_bytes_limit,
        )

        limit = _device_bytes_limit()
        if limit is None:
            return
        total = self.cache_total_bytes()
        if total > limit:
            layout = (
                f"paged pool ({self.kv_blocks} x {self.kv_block}-token "
                f"blocks)" if self.paged else
                f"padded ({self.max_batch} slots x {self.max_seq} rows)"
            )
            hint = (
                "shrink kv_blocks or kv_block" if self.paged else
                "switch to the paged layout (kv_block > 0, SERVING.md "
                "'Cache layout') so HBM scales with actual generated "
                "length instead of worst-case max_seq"
            )
            raise DeviceMemoryError(
                f"KV cache needs {total} bytes/device ({layout}) but the "
                f"device budget is {limit} bytes "
                f"(FF_DEVICE_MEM_BYTES / memory_stats): {hint}"
            )

    def init_cache(self):
        """Preallocated per-layer caches on the serving device(s).

        Padded: ``{op: {entry: (max_batch,) + declared shape}}`` — for
        ``MultiHeadAttention`` ``"k"``/``"v"`` of ``(max_batch,
        max_seq, kv_heads, d_head)`` (``NamedSharding``-placed
        batch-on-'n'/heads-on-'c' when sharded; ``(max_batch, kv_heads,
        d_head, max_seq)`` where ``d_head`` fills whole lane tiles), for
        ``LatentAttention`` one ``"ckr"`` of ``(max_batch, kv_rank +
        rope, max_seq)``, for ``KimiDeltaAttention`` a ``"state"`` of
        ``(max_batch, heads, d_head, d_head)`` float32 and a ``"conv"``
        window, neither with a sequence axis, for ``GatedShortConv`` its
        ``"conv"`` window of ``(max_batch, taps - 1, dim)`` alone.
        Paged: ``{op: {"k"/"v": (kv_blocks, kv_block, heads,
        d_head)}}`` — the global block pool; slot structure lives in
        the block table."""
        self._budget_check()
        return self._cache_tree(self._cache_specs, self._make_cache(
            paged=self.paged))

    def _cache_tree(self, specs, make):
        """``{op: {entry: make(CacheEntry)}}`` over ``specs``."""
        return {
            name: {e: make(ce) for e, ce in ents.items()}
            for name, ents in specs.items()
        }

    def _cache_shape(self, ce, paged: bool):
        """``(shape, axes)`` of one declared entry in the padded layout
        (a leading slot axis, on 'n') or the paged pool (``kv_blocks x
        kv_block`` in place of the leading sequence axis)."""
        if paged:
            return ((self.kv_blocks, self.kv_block) + tuple(ce.shape[1:]),
                    (None, None) + tuple(ce.axes[1:]))
        return (self.max_batch,) + tuple(ce.shape), ("n",) + tuple(ce.axes)

    def _make_cache(self, paged: bool, batch_axis: bool = True):
        """Zeros on the serving device(s) for one declared entry."""
        def make(ce):
            shape, axes = self._cache_shape(ce, paged)
            if self._plan is None:
                return self._place(jnp.zeros(shape, ce.dtype))
            if not batch_axis:
                axes = (None,) + axes[1:]
            return jax.device_put(
                jnp.zeros(shape, ce.dtype),
                self._plan.sharding(self._pc, axes, shape),
            )

        return make

    def init_draft_cache(self):
        """The DRAFT model's own per-layer KV caches for the
        speculative path — always the padded ``(max_batch, max_seq,
        h, hd)`` layout (the draft cache is an acceleration structure,
        not a capacity-accounted one: it covers only the truncation's
        kept layers, and a stale draft cache can never corrupt output
        — draft quality affects acceptance, never correctness)."""
        # Paged engines never validated max_batch % n (the pool has no
        # batch axis), so the padded draft cache shards heads only there.
        return self._cache_tree(self._draft_cache_specs, self._make_cache(
            paged=False, batch_axis=not self.paged))

    def _bind_layout(self, op) -> None:
        """Which order an attention op with heads of whole lane tiles
        declares and reads its cache in under this executor (the op
        objects are shared between executors, so this is bound before
        every declaration and trace, like ``decode_kernel``)."""
        if isinstance(op, MultiHeadAttention):
            op.positions_last = op.lane_tile_heads and self._positions_last

    def _refuse_stateful(self, what: str) -> None:
        """A recurrent state (or a convolution window) has no rows: a
        rejected draft token cannot be masked out of it and no prefix of
        it can be shared."""
        if self.stateful_ops:
            names = [op.name for op in self.stateful_ops]
            raise ValueError(
                f"{what} needs caches whose every entry has a sequence "
                f"axis; {names} ({type(self.stateful_ops[0]).__name__}) "
                f"keep a recurrent state, a convolution window or a "
                f"window's ring (ROADMAP Queue B)")

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.buckets:
            if b >= prompt_len:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest pad "
            f"bucket {self.buckets[-1]} (max_seq={self.max_seq})"
        )

    # -- the forward walk ---------------------------------------------------

    #: A residual stream wider than this is held where each add leaves
    #: it.  XLA otherwise fuses the chain of adds into every consumer and
    #: a block's norm then sums the table's rows and EVERY earlier
    #: sublayer's output again, each kept alive to the end of the
    #: program: eight 448 MiB arrays beside the weights at a 32k prefill
    #: of hidden 7168 (PERF.md section 6 PR 48).  Under it (every other
    #: served configuration's prefills, and every decode step) the
    #: programs are what they were.
    RESIDUAL_PIN_BYTES = 256 << 20

    def _forward(self, params, op_state, tokens, caches, pos,
                 block_table=None, skip=None, chunk=0, last=None,
                 stats=None, length=None):
        """Forward-only walk over the non-loss op graph in inference
        mode: attention ops get their caches + the per-slot position
        vector through the existing ``state`` mechanism
        (``ops/attention.py`` KV-cache protocol), position embeddings
        get ``pos``; everything else runs its plain eval forward.
        ``block_table`` (paged layout) rides the same state channel.
        ``skip`` (the truncated-layer DRAFT forward) names ops whose
        outputs pass their first input through unchanged — skipping a
        whole ``blk{i}_`` group forwards the residual stream past the
        block, which is safe because every skipped op's internal
        consumers are skipped with it.  ``chunk`` (static int, the
        offset-prefill path) tells multi-token attention/position ops
        that ``tokens`` starts at absolute row ``chunk`` of an
        already-populated cache — KV writes land at
        ``[chunk, chunk + t)`` and queries attend the full
        ``[0, chunk + t)`` span.  ``last`` (a traced position, the
        slim prefill) feeds the op that produces the logits that one
        row alone.  ``stats`` (a dict the caller hands in) collects
        what serving-aware ops report beside their outputs
        (``state["stats"]``: the expert layers' routing counters).
        ``length`` (a traced scalar, a prefill's) tells the
        cache-holding ops how many of ``tokens`` are the prompt's and
        not the bucket's padding (``state["length"]``): an attention
        layer may ignore it (its pad rows are overwritten before a mask
        admits them), a recurrent layer must stop its state there.
        Returns ``(logits, new_caches)``."""
        env: Dict[str, Any] = {self._tokens_name: tokens}
        new_caches: Dict[str, Any] = {}
        for op in self._layers:
            if skip and op.name in skip:
                passed = env[op.inputs[0].name]
                for t in op.outputs:
                    env[t.name] = passed
                continue
            # Single-mesh serving binds a mesh-less placement so
            # strategy-bound paths (ring attention, TP linear pinning)
            # stay off regardless of what a training executor last
            # bound on these shared op objects.  Sharded decode binds
            # the serving plan to the ATTENTION ops only: they own the
            # shard_map'd flash_decode and the c-split projections;
            # every other op partitions via plain GSPMD.
            if self._plan is not None and isinstance(op, MultiHeadAttention):
                op.bind_mesh(self._plan, self._pc)
            else:
                op.bind_mesh(None, None)
            if op.name in self._cache_specs:
                op.decode_kernel = self.decode_kernel
                self._bind_layout(op)
            xs = [env[t.name] for t in op.inputs]
            if last is not None and op.outputs[0].name == self._logits_name:
                xs = [jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
                      for x in xs]
            s = dict(op_state.get(op.name, {}))
            if op.serving_aware:
                s["serving"] = True
            if op.name in caches:
                for entry, c in caches[op.name].items():
                    s[f"cache_{entry}"] = c
                s["pos"] = pos
                if length is not None:
                    s["length"] = length
                if block_table is not None:
                    s["block_table"] = block_table
                if chunk:
                    s["chunk"] = int(chunk)
            elif isinstance(op, PositionEmbedding):
                s["pos"] = pos
                if chunk:
                    s["chunk"] = int(chunk)
            with jax.named_scope(op.name):
                ys, s_new = op.forward(op_params(op, params), xs, s,
                                       training=False)
            if isinstance(op, Add) and \
                    ys[0].size * ys[0].dtype.itemsize > self.RESIDUAL_PIN_BYTES:
                ys = jax.lax.optimization_barrier(ys)
            if op.name in caches:
                new_caches[op.name] = {
                    entry: s_new[f"cache_{entry}"]
                    for entry in caches[op.name]
                }
            if stats is not None and "stats" in s_new:
                stats[op.name] = s_new["stats"]
            for t, y in zip(op.outputs, ys):
                env[t.name] = y
        return env[self._logits_name], new_caches

    # -- compiled programs ---------------------------------------------------

    def _pick_first(self, sample: Optional[Tuple[float, int, int]]):
        """THE prefill first-token closure, shared by
        :meth:`build_prefill` and :meth:`build_prefill_from` so the
        two can never drift: greedy argmax, or (sampled variant) the
        ``fold_in(fold_in(key(seed), req_id), length - 1)`` draw for
        RESUMED positions — a fresh admission (``length == plen``)
        stays greedy, the decode head only ever samples positions past
        the prompt."""
        base_key = (
            jax.random.key(sample[2]) if sample is not None else None
        )

        def pick_first(last, length, plen, rid):
            greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
            if sample is None:
                return greedy
            temperature, top_k, _seed = sample
            kkey = jax.random.fold_in(
                jax.random.fold_in(base_key, rid), length - 1
            )
            lg = last.astype(jnp.float32) / temperature
            if 0 < top_k < lg.shape[-1]:
                kth = jax.lax.top_k(lg, top_k)[0][-1]
                lg = jnp.where(lg >= kth, lg, -jnp.inf)
            drawn = jax.random.categorical(kkey, lg).astype(jnp.int32)
            return jnp.where(length > plen, drawn, greedy)

        return pick_first

    def build_prefill(self, bucket: int,
                      sample: Optional[Tuple[float, int, int]] = None):
        """One jitted prefill program per pad bucket: ``(params,
        op_state, tokens (1, bucket), length ()) -> (cache_rows,
        first_token, finite)``.  ``cache_rows`` are one slot's worth of
        every declared entry, ``{op: {entry: declared shape}}`` (K and V
        with positions beyond ``bucket`` zero, a latent column, a
        recurrent state and its convolution window as they stand after
        ``length`` tokens), ready for :meth:`install` into a slot.

        ``sample=(temperature, top_k, seed)`` builds the SAMPLED
        variant — ``(params, op_state, tokens, length, prompt_len,
        req_id) -> ...`` — needed by the loss-free resume primitive
        (preemption and journal recovery, SERVING.md "Failure model"):
        a re-prefill over (prompt ‖ carried) regenerates a position
        the decode head SAMPLED, so its token must be the identical
        ``fold_in(fold_in(key(seed), req_id), length - 1)`` draw the
        unresumed run made there.  A fresh admission
        (``length == prompt_len``) keeps the greedy first token — the
        decode head only ever samples positions past the prompt."""
        if sample is not None:
            temperature, top_k, sample_seed = sample
            sample = (float(temperature), int(top_k), int(sample_seed))
        key = bucket if sample is None else (bucket, sample)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        pick_first = self._pick_first(sample)
        slim = self._slim_head(bucket)

        def run(params, op_state, tokens, length, plen, rid):
            caches = self._cache_tree(
                self._cache_specs,
                lambda ce: jnp.zeros((1,) + tuple(ce.shape), ce.dtype))
            pos = jnp.zeros((1,), jnp.int32)
            stats = {} if self.has_stats else None
            logits, caches = self._forward(
                params, op_state, tokens, caches, pos,
                last=length - 1 if slim else None, stats=stats,
                length=length,
            )
            last = logits[0, 0] if slim else jax.lax.dynamic_index_in_dim(
                logits[0], length - 1, axis=0, keepdims=False
            )
            tok = pick_first(last, length, plen, rid)
            ok = jnp.all(jnp.isfinite(last.astype(jnp.float32)))
            rows = jax.tree.map(lambda c: c[0], caches)
            if stats:
                return rows, tok, ok, self._fold_stats(stats)
            return rows, tok, ok

        if sample is not None:
            def prefill(params, op_state, tokens, length, plen, rid):
                return run(params, op_state, tokens, length, plen, rid)
        else:
            def prefill(params, op_state, tokens, length):
                return run(params, op_state, tokens, length, None, None)

        fn = self._prefill_fns[key] = jax.jit(prefill)
        _telemetry.current().emit("serving_program", kind="prefill",
                                  bucket=int(bucket),
                                  sampled=sample is not None,
                                  attention=self._attention_paths(False),
                                  head_rows="last" if slim else "all",
                                  **self.kept_blocks(bucket),
                                  **self.causal_blocks(bucket))
        return fn

    #: A prefill whose whole-bucket logits would pass this many bytes
    #: projects only the row it reads (a 16k-token bucket of a 128k
    #: vocabulary is 4 GB of logits for one argmax).  Smaller ones keep
    #: the full-sequence head: bit-identical to the training forward.
    SLIM_HEAD_BYTES = 1 << 28

    def _slim_head(self, bucket: int) -> bool:
        head = self.model.find_op(self._logits_name.split(":")[0])
        out = head.outputs[0]
        return isinstance(head, Linear) and (
            bucket * out.shape[-1] * jnp.dtype(out.dtype).itemsize
            > self.SLIM_HEAD_BYTES
        )

    def kept_blocks(self, bucket: int) -> Dict[str, Any]:
        """What a prefill of ``bucket`` rows does with the masked chunks
        of its ops under a token selector, as ``serving_program``
        carries it (``ops/attention.py::kept_blocks``, asked of each op
        by shape): ``kept_kernel`` true where every such op's chunks
        attend through ``ff_attend_kept``, and one layer's key-block
        counts (the mean over those ops).  Nothing where no op
        selects."""
        found = [op.kept_blocks(bucket) for op in self.attn_ops
                 if getattr(op, "select", None) is not None]
        if not found:
            return {}
        return dict(
            kept_kernel=all(f["kept_kernel"] for f in found),
            **{k: sum(f[k] for f in found) // len(found)
               for k in ("kept_key_blocks", "kept_key_blocks_square")})

    def causal_blocks(self, bucket: int) -> Dict[str, int]:
        """What a prefill of ``bucket`` rows costs in its causal kernel
        calls, as ``serving_program`` carries it
        (``ops/attention.py::causal_blocks``, asked of each op by shape):
        ``causal_blocks``, the key blocks a head visits in one layer's
        call of ``ff_flash_fwd_uneven``, and ``causal_steps``, the grid
        steps it rides (the mean over the ops that make the call).
        Nothing where no op does."""
        found = [f for f in (op.causal_blocks(bucket) for op in self.attn_ops
                             if hasattr(op, "causal_blocks")) if f]
        return {k: sum(f[k] for f in found) // len(found)
                for k in ("causal_blocks", "causal_steps") if found}

    def decode_heads_per_step(self) -> Dict[str, int]:
        """``decode_heads_per_step`` as the decode superstep's
        ``serving_program`` carries it: the cached heads one step of
        ``ff_flash_decode``'s grouped body takes together
        (``ops/attention.py::decode_heads_per_step``, asked of each op
        by shape; the most where layers differ), so that a run's stream
        says which body was compiled.  Nothing where no op's decode step
        runs the grouped body."""
        n = (self.shard or (1, 1))[0]
        found = [op.decode_heads_per_step(self.max_batch // n, self.max_seq,
                                          self.decode_kernel)
                 for op in self.attn_ops
                 if hasattr(op, "decode_heads_per_step")]
        return {"decode_heads_per_step": max(found)} if any(found) else {}

    def _attention_paths(self, decode: bool) -> str:
        """Which attention formulation this program's cache-holding ops
        compile (``serving_program.attention``)."""
        return "+".join(sorted({
            op.serving_path(decode) for op in self.attn_ops
        }))

    def kv_rows(self, pos, k: int) -> Dict[str, int]:
        """What a decode superstep dispatched at positions ``pos``
        (every slot's, idle ones too: they run) fetches of the caches,
        as ``decode_superstep`` carries it: ``kv_rows_fetched`` sums
        over the slots and the ``k`` steps the live length rounded up
        to the block the ops' decode step reads in
        (``Op.decode_fetch_block``; the paged view and the einsum
        oracle read every row), ``kv_rows_cache`` is slots x max_seq x
        k.  An op under a token selector (``ops/token_select.py``)
        gathers its ``topk`` rows whatever the live length, and scores
        every row of its selector's keys, the padded cache's (a plain
        product): ``idx_rows_fetched``, present when some op selects.
        Host arithmetic, one layer's rows: the mean over the ops that
        read a cache by position, each counted by what it reads (an op
        under a ``window`` the ``min(live, window)`` rows of its ring,
        rounded to the ring's own block), so a graph that mixes kinds of
        layer sums to its layers' own; ``kv_rows_cache`` stays what
        layers without a window or a selector would hold.  A graph that
        also keeps recurrent state adds ``state_bytes``, the bytes of
        state the superstep reads and writes over all its layers (a
        window's ring is rows, counted above, not state)."""
        S = self.max_seq
        n, c = self.shard or (1, 1)
        live = np.minimum(np.asarray(pos)[:, None] + np.arange(k), S - 1) + 1
        fetched, picks, state = [], [], 0
        rounded = {}    # (block, window) -> rows: one sum a kind of layer
        for op in self.attn_ops:
            pick = getattr(op, "select", None)
            block = op.decode_fetch_block(self.max_batch // n, S,
                                          self.decode_kernel, c)
            if not block:
                # A recurrent state: no rows.
                state += sum(
                    math.prod(ce.shape) * jnp.dtype(ce.dtype).itemsize
                    for ce in self._cache_specs[op.name].values()
                    if not ce.sequence)
                continue
            picks.append(pick)
            if pick is not None:
                fetched.append(live.size * min(pick.topk, S))
                continue
            window = op.decode_window
            if window is None and self.paged:
                block = S
            if (block, window) not in rounded:
                rows = live if window is None else np.minimum(live, window)
                rounded[block, window] = int((-(-rows // block) * block).sum())
            fetched.append(rounded[block, window])
        if not fetched:
            fetched = [int(live.size * S)]
        rows = {"kv_rows_fetched": int(round(sum(fetched) / len(fetched))),
                "kv_rows_cache": int(live.size * S)}
        if any(pick is not None for pick in picks):
            scored = sum(live.size * S for pick in picks if pick is not None)
            rows["idx_rows_fetched"] = int(round(scored / len(picks)))
        if state:
            # Every slot's recurrent state and convolution window, read
            # and written once a step.
            rows["state_bytes"] = 2 * k * self.max_batch * state
        return rows

    @staticmethod
    def _fold_stats(stats):
        """The counters of one forward, each over the layers that report
        it: their mean, or for a counter of ``SERVING_STATS_LARGEST``
        their largest."""
        out = {}
        for k in sorted({k for s in stats.values() for k in s}):
            vals = jnp.stack([s[k] for s in stats.values() if k in s])
            out[k] = jnp.max(vals) if k in SERVING_STATS_LARGEST \
                else jnp.mean(vals)
        return out

    def build_prefill_from(
        self, bucket: int, offset: int,
        sample: Optional[Tuple[float, int, int]] = None,
    ):
        """Offset prefill for prefix sharing (SERVING.md "Prefix
        sharing"; paged + ``prefix_cache`` only): the
        :meth:`build_prefill` body started at row ``offset`` — the
        shared span's KV is GATHERED from resident pool blocks
        instead of recomputed, so the program runs ``bucket - offset``
        token positions at the same one-dispatch-one-fence
        discipline.  ``(params, op_state, pool, shared_ids
        (offset/kv_block,), tokens (1, bucket), length) ->
        (cache_rows, first_token, finite)`` — ``pool`` is the live
        paged cache dict (read-only: NOT donated), ``cache_rows``
        carry zeros for ``[0, offset)`` (the masked install writes
        those chunks into scratch block 0; the slot's table row keeps
        pointing at the shared blocks).  The sampled variant appends
        ``(prompt_len, req_id)`` exactly like :meth:`build_prefill`.

        Byte-identity to the unshared run: K/V at row r is causal —
        it depends only on tokens ``[0, r]`` — so the gathered donor
        rows are bit-equal to what this prompt's own prefill would
        have written there, and the tail attends the full
        ``[0, bucket)`` key span under the same offset-causal mask
        the dense prefill applies (``ops/attention.py`` chunk
        sub-mode)."""
        if not self.paged or not self.prefix_cache:
            raise ValueError(
                "build_prefill_from needs paged + prefix_cache "
                "(SERVING.md 'Prefix sharing')"
            )
        self._refuse_stateful("the offset prefill")
        offset = int(offset)
        if offset < self.kv_block or offset % self.kv_block or \
                offset >= bucket:
            raise ValueError(
                f"offset must be a multiple of kv_block="
                f"{self.kv_block} in [kv_block, bucket): offset="
                f"{offset}, bucket={bucket}"
            )
        if sample is not None:
            temperature, top_k, sample_seed = sample
            sample = (float(temperature), int(top_k), int(sample_seed))
        key = ("from", bucket, offset, sample)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        o = offset
        pick_first = self._pick_first(sample)

        def run(params, op_state, pool, shared_ids, tokens, length,
                plen, rid):
            caches = {
                name: {
                    e: jnp.zeros((1,) + tuple(ce.shape), ce.dtype)
                    .at[0, :o].set(pool[name][e][shared_ids].reshape(
                        (o,) + tuple(ce.shape[1:])))
                    for e, ce in ents.items()
                }
                for name, ents in self._cache_specs.items()
            }
            pos = jnp.full((1,), o, jnp.int32)
            logits, caches = self._forward(
                params, op_state, tokens[:, o:], caches, pos, chunk=o
            )
            last = jax.lax.dynamic_index_in_dim(
                logits[0], length - 1 - o, axis=0, keepdims=False
            )
            tok = pick_first(last, length, plen, rid)
            ok = jnp.all(jnp.isfinite(last.astype(jnp.float32)))
            rows = jax.tree.map(lambda c: c[0], caches)
            return rows, tok, ok

        if sample is not None:
            def prefill(params, op_state, pool, shared_ids, tokens,
                        length, plen, rid):
                return run(params, op_state, pool, shared_ids, tokens,
                           length, plen, rid)
        else:
            def prefill(params, op_state, pool, shared_ids, tokens,
                        length):
                return run(params, op_state, pool, shared_ids, tokens,
                           length, None, None)

        fn = self._prefill_fns[key] = jax.jit(prefill)
        _telemetry.current().emit("serving_program", kind="prefill_from",
                                  bucket=int(bucket), offset=o,
                                  sampled=sample is not None)
        return fn

    @functools.cached_property
    def install(self):
        """One jitted program installing a prefill's rows into a slot
        across every declared entry of every layer (K and V, a latent
        column, a recurrent state and its window; donated caches: the
        install is in-place on device)."""

        def install(caches, rows, slot):
            return jax.tree.map(
                lambda c, r: c.at[slot].set(r.astype(c.dtype)),
                caches, rows,
            )

        return jax.jit(install, donate_argnums=(0,))

    @functools.cached_property
    def install_paged(self):
        """Paged analogue of :meth:`install`: the prefilled
        ``(max_seq, h, hd)`` rows reshape into ``kv_block``-sized
        chunks and scatter into the slot's table row of pool blocks
        (unreserved entries write their all-pad chunks into scratch
        block 0 — harmless by the scratch contract, and the write
        fully re-initializes reused blocks after an eviction)."""

        def install(caches, rows, table_row):
            def put(c, r):
                chunks = r.astype(c.dtype).reshape((-1,) + c.shape[1:])
                return c.at[table_row].set(chunks)

            return jax.tree.map(put, caches, rows)

        return jax.jit(install, donate_argnums=(0,))

    def _picker(self, sample: Optional[Tuple[float, int, int]]):
        """THE in-program token-selection closure, shared by the
        decode superstep and the speculative draft/verify scans so the
        three can never drift: greedy argmax, or the keyed
        temperature/top-k draw whose key is
        ``fold_in(fold_in(key(seed), req_id), pos)`` — a pure function
        of (seed, request, position), replayable across batch
        composition, supersteps, and preemption/resume."""
        base_key = (
            jax.random.key(sample[2]) if sample is not None else None
        )

        def pick_token(logits, req_ids, pos):
            if sample is None:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            temperature, top_k, _seed = sample

            def draw(lg, rid, p):
                kkey = jax.random.fold_in(
                    jax.random.fold_in(base_key, rid), p
                )
                lg = lg.astype(jnp.float32) / temperature
                if 0 < top_k < lg.shape[-1]:
                    kth = jax.lax.top_k(lg, top_k)[0][-1]
                    lg = jnp.where(lg >= kth, lg, -jnp.inf)
                return jax.random.categorical(kkey, lg).astype(jnp.int32)

            return jax.vmap(draw)(logits, req_ids, pos)

        return pick_token

    def build_decode_superstep(
        self,
        k: int,
        return_logits: bool = False,
        sample: Optional[Tuple[float, int, int]] = None,
    ):
        """K fused single-token decode steps as ONE jitted dispatch:
        ``(params, op_state, caches, pos (B,), tok (B,)) -> (caches,
        pos, tok, (tokens (K, B), finite (K, B)))`` — token selection
        INSIDE the scan, so the host sees one program and one fence
        per K tokens across the whole slot batch.  ``return_logits``
        additionally stacks the (K, B, V) logits (test/oracle use
        only — production keeps the readback K x B ints).

        Paged layout: the program takes the per-slot block table
        after the caches — ``(params, op_state, caches, block_table
        (B, nblk), pos, tok)`` — and passes it through unchanged.

        ``sample=(temperature, top_k, seed)`` replaces the greedy
        argmax with in-program temperature/top-k sampling (top_k=0 =
        full softmax); the program then takes a trailing ``req_ids
        (B,)`` argument and every draw keys off
        ``fold_in(fold_in(key(seed), req_id), pos)`` — a pure
        function of (seed, request, position), so sampled outputs
        replay bit-identically across superstep boundaries, batch
        composition, eviction and re-admission (the
        ``default_rng([seed, req_id])`` idiom, in-program).  Greedy
        (``sample=None``) stays the default and the parity oracle."""
        if k < 1:
            raise ValueError(f"decode steps per call must be >= 1, got {k}")
        if sample is not None:
            temperature, top_k, sample_seed = sample
            temperature = float(temperature)
            top_k = int(top_k)
            if temperature <= 0.0:
                raise ValueError(
                    f"sampling needs temperature > 0, got {temperature} "
                    f"(greedy is sample=None)"
                )
            sample = (temperature, top_k, int(sample_seed))
        key = (k, return_logits, self.paged, sample)
        fn = self._decode_fns.get(key)
        if fn is not None:
            return fn
        S = self.max_seq
        pick_token = self._picker(sample)

        def run_scan(params, op_state, caches, pos, tok, block_table,
                     req_ids):
            def body(carry, _):
                caches, pos, tok = carry
                stats = {} if self.has_stats else None
                logits, caches = self._forward(
                    params, op_state, tok[:, None], caches, pos,
                    block_table=block_table, stats=stats,
                )
                logits = logits[:, 0]                      # (B, V)
                nxt = pick_token(logits, req_ids, pos)
                ok = jnp.all(
                    jnp.isfinite(logits.astype(jnp.float32)), axis=-1
                )
                pos = jnp.minimum(pos + 1, S - 1)
                out = (nxt, ok, logits) if return_logits else (nxt, ok)
                if stats:
                    out += (self._fold_stats(stats),)
                return (caches, pos, nxt), out

            (caches, pos, tok), outs = jax.lax.scan(
                body, (caches, pos, tok), None, length=k
            )
            return caches, pos, tok, outs

        if self.paged and sample is not None:
            def superstep(params, op_state, caches, block_table, pos, tok,
                          req_ids):
                return run_scan(params, op_state, caches, pos, tok,
                                block_table, req_ids)
            donate = (2, 4, 5)
        elif self.paged:
            def superstep(params, op_state, caches, block_table, pos, tok):
                return run_scan(params, op_state, caches, pos, tok,
                                block_table, None)
            donate = (2, 4, 5)
        elif sample is not None:
            def superstep(params, op_state, caches, pos, tok, req_ids):
                return run_scan(params, op_state, caches, pos, tok,
                                None, req_ids)
            donate = (2, 3, 4)
        else:
            def superstep(params, op_state, caches, pos, tok):
                return run_scan(params, op_state, caches, pos, tok,
                                None, None)
            donate = (2, 3, 4)

        fn = self._decode_fns[key] = jax.jit(
            superstep, donate_argnums=donate
        )
        _telemetry.current().emit(
            "serving_program", kind="decode", k=int(k),
            layout="paged" if self.paged else "padded",
            sharded=self.shard is not None,
            sampled=sample is not None,
            attention=self._attention_paths(True),
            **self.decode_heads_per_step(),
        )
        return fn

    def build_draft_prefill(self, bucket: int):
        """Draft-side analogue of :meth:`build_prefill`: ``(draft_params,
        op_state, tokens (1, bucket)) -> draft cache rows`` — the
        truncated draft forward over the padded prompt, populating the
        draft's OWN per-layer cache rows for :meth:`install` into a
        slot of :meth:`init_draft_cache`.  One extra dispatch per
        admission when speculating (priced by the latency model's
        ``draft_prefill_ms``).  No token/finiteness output: the draft
        never emits — a garbage draft row only costs acceptance."""
        self._refuse_stateful("the speculative draft")
        key = ("draft", bucket)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn

        def prefill(params, op_state, tokens):
            caches = self._cache_tree(
                self._draft_cache_specs,
                lambda ce: jnp.zeros((1,) + tuple(ce.shape), ce.dtype))
            pos = jnp.zeros((1,), jnp.int32)
            _logits, caches = self._forward(
                params, op_state, tokens, caches, pos,
                skip=self._draft_skip,
            )
            return jax.tree.map(lambda c: c[0], caches)

        fn = self._prefill_fns[key] = jax.jit(prefill)
        _telemetry.current().emit(
            "serving_program", kind="draft_prefill", bucket=int(bucket),
            draft_layers=self.draft_layers,
        )
        return fn

    def build_spec_step(
        self,
        d: int,
        sample: Optional[Tuple[float, int, int]] = None,
    ):
        """One speculative decode round as ONE jitted dispatch
        (SERVING.md "Speculative decoding"): d DRAFT steps against the
        draft model's own caches propose tokens t_1..t_d, then d+1
        VERIFY steps score ``[tok, t_1..t_d]`` against the full model
        and the longest matching prefix is accepted in-program.

        ``(params, draft_params, op_state, caches, dcaches, pos (B,),
        tok (B,)) -> (caches, dcaches, pos, tok, (tokens (d+1, B),
        finite (d+1, B), accepted (B,)))`` — paged inserts the block
        table after ``dcaches``; the sampled variant appends
        ``req_ids (B,)``, mirroring :meth:`build_decode_superstep`.

        PARITY BY CONSTRUCTION: the verify scan body is the decode
        superstep's body — the same :meth:`_forward` single-token
        path (same kernel routing, same clamped ``min(pos+1, S-1)``
        position walk, same :meth:`_picker` selection) — fed the
        draft tokens instead of its own feedback.  Emitted token i
        (i <= accepted) therefore saw exactly the history the
        sequential decode would have at that position, so the OUTPUT
        SEQUENCE is bit-identical to the sequential oracle (greedy
        and keyed-sampled, padded and paged) regardless of the
        acceptance pattern: acceptance decides dispatch count, never
        content.  Rejected draft rows need no rollback — K/V written
        past the accepted position is masked by the ``<= pos``
        attention contract and overwritten as ``pos`` advances (paged
        out-of-reservation writes land in scratch block 0).

        ``d`` passes through :func:`clamp_fused_steps` — the draft
        chain counts against THE clamp site; the fused program runs
        2d+2 single-token steps (d+1 draft — the +1 primes the draft
        cache at the verify token's row, making the full-self-draft
        degenerate case accept everything — plus d+1 verify), each far
        lighter than a fused train step."""
        if d < 1:
            raise ValueError(
                f"speculate depth must be >= 1, got {d} "
                f"(plain fused decode is build_decode_superstep)"
            )
        self._refuse_stateful("the speculative step")
        d = clamp_fused_steps(d, what="speculate", log=_log)
        if sample is not None:
            temperature, top_k, sample_seed = sample
            temperature = float(temperature)
            top_k = int(top_k)
            if temperature <= 0.0:
                raise ValueError(
                    f"sampling needs temperature > 0, got {temperature} "
                    f"(greedy is sample=None)"
                )
            sample = (temperature, top_k, int(sample_seed))
        key = ("spec", d, self.paged, sample)
        fn = self._decode_fns.get(key)
        if fn is not None:
            return fn
        S = self.max_seq
        pick_token = self._picker(sample)

        def run_spec(params, draft_params, op_state, caches, dcaches,
                     pos, tok, block_table, req_ids):
            # -- draft: d cheap steps on the truncated forward, own
            # padded caches, proposing t_1..t_d.  The draw (when
            # sampling) uses the SAME (seed, req_id, pos) key as the
            # verify step at that position — identical draft/full
            # logits then agree by construction (the full-self-draft
            # degenerate case accepts everything).
            def dbody(carry, _):
                dcaches, p, t = carry
                logits, dcaches = self._forward(
                    draft_params, op_state, t[:, None], dcaches, p,
                    skip=self._draft_skip,
                )
                nxt = pick_token(logits[:, 0], req_ids, p)
                return (dcaches, jnp.minimum(p + 1, S - 1), nxt), nxt

            # d+1 steps for d proposals: the extra step feeds the last
            # proposal t_d at row pos+d, PRIMING the draft cache at the
            # one position a fully-accepted round would otherwise leave
            # as a permanent zero row (the verify token's row — the
            # draft never sees it again once pos jumps past it).  Its
            # own proposal is discarded; when t_d is rejected the row
            # holds a wrong KV that the <= pos mask hides until the
            # position walk overwrites it — the same no-rollback
            # contract the main cache relies on.
            (dcaches, _dp, _dt), draft_all = jax.lax.scan(
                dbody, (dcaches, pos, tok), None, length=d + 1
            )
            draft_toks = draft_all[:d]
            # -- verify: d+1 full-model steps over [tok, t_1..t_d] —
            # the decode-superstep body fed draft tokens.
            tok_seq = jnp.concatenate([tok[None], draft_toks], axis=0)

            def vbody(carry, t_in):
                caches, p = carry
                logits, caches = self._forward(
                    params, op_state, t_in[:, None], caches, p,
                    block_table=block_table,
                )
                logits = logits[:, 0]                      # (B, V)
                y = pick_token(logits, req_ids, p)
                ok = jnp.all(
                    jnp.isfinite(logits.astype(jnp.float32)), axis=-1
                )
                return (caches, jnp.minimum(p + 1, S - 1)), (y, ok)

            (caches, _vp), (ys, oks) = jax.lax.scan(
                vbody, (caches, pos), tok_seq
            )
            # -- accept the longest matching prefix: draft token
            # t_{i+1} survives iff it equals verified token y_i; the
            # first mismatch's y is the (free) correction token, so
            # every round emits accepted+1 tokens.
            matches = (draft_toks == ys[:d]).astype(jnp.int32)
            accepted = jnp.sum(jnp.cumprod(matches, axis=0), axis=0)
            new_pos = jnp.minimum(pos + accepted + 1, S - 1)
            next_tok = jnp.take_along_axis(
                ys, accepted[None, :], axis=0
            )[0]
            return caches, dcaches, new_pos, next_tok, (ys, oks, accepted)

        if self.paged and sample is not None:
            def spec(params, draft_params, op_state, caches, dcaches,
                     block_table, pos, tok, req_ids):
                return run_spec(params, draft_params, op_state, caches,
                                dcaches, pos, tok, block_table, req_ids)
            donate = (3, 4, 6, 7)
        elif self.paged:
            def spec(params, draft_params, op_state, caches, dcaches,
                     block_table, pos, tok):
                return run_spec(params, draft_params, op_state, caches,
                                dcaches, pos, tok, block_table, None)
            donate = (3, 4, 6, 7)
        elif sample is not None:
            def spec(params, draft_params, op_state, caches, dcaches,
                     pos, tok, req_ids):
                return run_spec(params, draft_params, op_state, caches,
                                dcaches, pos, tok, None, req_ids)
            donate = (3, 4, 5, 6)
        else:
            def spec(params, draft_params, op_state, caches, dcaches,
                     pos, tok):
                return run_spec(params, draft_params, op_state, caches,
                                dcaches, pos, tok, None, None)
            donate = (3, 4, 5, 6)

        fn = self._decode_fns[key] = jax.jit(
            spec, donate_argnums=donate
        )
        _telemetry.current().emit(
            "serving_program", kind="spec", d=int(d),
            draft_layers=self.draft_layers,
            layout="paged" if self.paged else "padded",
            sharded=self.shard is not None,
            sampled=sample is not None,
        )
        return fn

    # -- compute-free mode ---------------------------------------------------

    def abstract_programs(self, decode_steps: int = 8,
                          speculate: int = 0):
        """``jax.eval_shape`` over every prefill bucket and the decode
        superstep — the serving DRY RUN (no device compute): validates
        the whole forward-only graph, the cache protocol and the scan,
        and returns the program table ``{"prefill": {bucket: logits
        aval...}, "decode": ...}``.  ``speculate=d`` additionally
        traces the draft prefill and the fused spec round, adding a
        ``"spec"`` entry (the (d+1, B) verified-token aval)."""
        from flexflow_tpu.runtime.executor import Executor

        params, _opt, op_state = Executor(
            self.model, config=self.config
        )._abstract_init()
        B = self.max_batch

        def cache_aval(ce):
            return jax.ShapeDtypeStruct(
                self._cache_shape(ce, self.paged)[0], ce.dtype)

        caches = self._cache_tree(self._cache_specs, cache_aval)
        out: Dict[str, Any] = {"prefill": {}, "cache": {}}
        for name, ents in caches.items():
            # One row a cache-holding op: its first declared entry (K
            # of K/V; the latent column).
            out["cache"][name] = next(iter(ents.values()))
        for bucket in self.buckets:
            toks = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
            ln = jax.ShapeDtypeStruct((), jnp.int32)
            tok = jax.eval_shape(
                self.build_prefill(bucket), params, op_state, toks, ln
            )[1]
            out["prefill"][bucket] = tok
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)
        tok = jax.ShapeDtypeStruct((B,), jnp.int32)
        if self.paged:
            bt = jax.ShapeDtypeStruct((B, self.blocks_per_slot), jnp.int32)
            fetch = jax.eval_shape(
                self.build_decode_superstep(decode_steps),
                params, op_state, caches, bt, pos, tok,
            )[3]
        else:
            fetch = jax.eval_shape(
                self.build_decode_superstep(decode_steps),
                params, op_state, caches, pos, tok,
            )[3]
        out["decode"] = fetch[0]
        if self.paged and self.prefix_cache:
            # Prefix sharing: trace the offset prefill at one
            # representative offset (kv_block) per bucket that can
            # host one — the dry-run coverage for the chunked forward.
            out["prefill_from"] = {}
            o = self.kv_block
            ids = jax.ShapeDtypeStruct((1,), jnp.int32)
            for bucket in self.buckets:
                if bucket <= o:
                    continue
                toks_in = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
                ln = jax.ShapeDtypeStruct((), jnp.int32)
                _rows, tok_a, _okf = jax.eval_shape(
                    self.build_prefill_from(bucket, o),
                    params, op_state, caches, ids, toks_in, ln,
                )
                out["prefill_from"][bucket] = tok_a
        if speculate:
            dcaches = self._cache_tree(
                self._draft_cache_specs,
                lambda ce: jax.ShapeDtypeStruct(
                    self._cache_shape(ce, False)[0], ce.dtype))
            for bucket in self.buckets:
                toks_in = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
                jax.eval_shape(
                    self.build_draft_prefill(bucket),
                    params, op_state, toks_in,
                )
            spec_args = (params, params, op_state, caches, dcaches)
            if self.paged:
                spec_args += (bt,)
            spec_args += (pos, tok)
            _, _, _, _, (ys, okf, acc) = jax.eval_shape(
                self.build_spec_step(speculate), *spec_args
            )
            out["spec"] = ys
        return out


class ServingEngine:
    """The device side of a serving loop: the caches and every dispatch
    and fence on them.  :class:`Server` and the scheduler's
    ``ScheduledServer`` are both policy over this one class — who is
    admitted, the ledger, token consumption, journal, events — and the
    scheduler's ``_SimEngine`` is its compute-free twin, with the same
    signatures.

    Three decisions live here and nowhere else: which
    ``SPAN_CATALOG`` span wraps which call, what rides the prefill's
    and the superstep's fence (tokens, finiteness, and the expert
    layers' routing counters, handed back to the caller), and how a
    prefill's rows reach a slot (:meth:`install`).  Prefill and install
    are separate calls because the two loops allocate the ledger row on
    different sides of the prefill's fence.  A wall runs from the
    instant the host starts on the program's arguments (a prefill's:
    once the prompt is padded) to its fence's return; that instant,
    ``t0`` on ``time.perf_counter``'s clock, is handed back beside it,
    so a loop can stamp both edges of the call on its one event.
    ``caches`` is
    public: the fault injector's ``before_superstep`` takes it and
    hands back what the engine then holds."""

    simulated = False

    def __init__(self, ex: ServingExecutor, params, op_state,
                 sample: Optional[Tuple[float, int, int]] = None,
                 speculate: int = 0, draft_params=None):
        self.ex = ex
        self.params = params
        self.op_state = op_state
        self.sample = sample
        self.speculate = speculate
        self.caches = ex.init_cache()
        if speculate:
            self.draft_params = (draft_params if draft_params is not None
                                 else params)
            self.dcaches = ex.init_draft_cache()

    @staticmethod
    def _pad(prompt, bucket: int) -> np.ndarray:
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = np.asarray(prompt, np.int32)
        return padded

    def prefill(self, prompt, bucket: int, plen: Optional[int] = None,
                rid: int = 0, offset: int = 0, shared_ids=None):
        """Pad-to-bucket prefill: ``(cache_rows, first_token, finite,
        counters, wall_s, t0)`` after one fence; the rows are for
        :meth:`install`.  ``prompt`` is the full (prompt ‖ carried)
        sequence — re-prefill over it is the loss-free resume primitive
        of journal recovery and preemption.  Sampled engines prefill
        through the sampled variant, keyed by ``plen``/``rid``, so a
        RESUMED position replays the decode head's exact draw (greedy
        when ``len(prompt) == plen``, a fresh admission).  ``offset >
        0`` runs the prefix-sharing offset prefill instead
        (``build_prefill_from``): the shared span's KV is gathered from
        the pool blocks ``shared_ids`` and only the tail is computed.
        ``counters`` are the expert layers' routing counters that rode
        the fence ({} for a graph without them)."""
        tel = _telemetry.current()
        with _telemetry.span("ff/serve/prefill_dispatch", id=rid,
                             bucket=bucket):
            padded = self._pad(prompt, bucket)
            t0 = time.perf_counter()
            if offset:
                pf = self.ex.build_prefill_from(bucket, offset,
                                                sample=self.sample)
                args = (self.params, self.op_state, self.caches,
                        np.asarray(shared_ids, np.int32), padded,
                        np.int32(len(prompt)))
            else:
                pf = self.ex.build_prefill(bucket, sample=self.sample)
                args = (self.params, self.op_state, padded,
                        np.int32(len(prompt)))
            if self.sample is not None:
                args += (np.int32(len(prompt) if plen is None else plen),
                         np.int32(rid))
            tel.program_cost("prefill", pf, args, bucket=bucket)
            rows, *fetch = pf(*args)
        with _telemetry.span("ff/serve/prefill_fence", id=rid):
            tok0, ok, *counters = tel.fence(tuple(fetch), "prefill")
        return (rows, int(tok0), bool(ok), counters[0] if counters else {},
                time.perf_counter() - t0, t0)

    def install(self, rows, slot_i: int, row: Optional[np.ndarray] = None,
                shared: int = 0, rid: int = 0) -> None:
        """Install prefilled ``rows`` into ``slot_i``: the padded cache
        row, or on the paged layout the ledger-assigned table ``row`` of
        pool blocks.  Under a shared prefix the first ``shared`` entries
        are masked to scratch block 0, where their (all-zero) chunks
        land — the donor's blocks are never written; the caller's table
        row keeps the real shared ids for decode."""
        with _telemetry.span("ff/serve/install", id=rid):
            if row is None:
                self.caches = self.ex.install(self.caches, rows, slot_i)
                return
            if shared:
                row = row.copy()
                row[:shared] = 0
            self.caches = self.ex.install_paged(self.caches, rows, row)

    def draft_prefill(self, prompt, bucket: int, slot_i: int,
                      rid: int = 0) -> float:
        """Populate the DRAFT model's own cache rows for ``slot_i`` —
        one extra dispatch per admission when speculating, priced by
        the latency model's ``draft_prefill_ms``.  No fence: nothing to
        read back, and the next spec round synchronizes."""
        t0 = time.perf_counter()
        with _telemetry.span("ff/serve/prefill_dispatch", id=rid,
                             bucket=bucket):
            dpf = self.ex.build_draft_prefill(bucket)
            dargs = (self.draft_params, self.op_state,
                     self._pad(prompt, bucket))
            _telemetry.current().program_cost("draft_prefill", dpf, dargs,
                                              bucket=bucket)
            drows = dpf(*dargs)
        with _telemetry.span("ff/serve/install", id=rid):
            self.dcaches = self.ex.install(self.dcaches, drows, slot_i)
        return time.perf_counter() - t0

    def prepare(self, k: int) -> None:
        """Build the program a closed loop's every superstep will call
        (the speculative round of the engine's depth, else the decode
        superstep of ``k`` steps) before the first admission, so that
        its ``serving_program`` event leads the run's stream as it
        always has.  A loop that chooses ``k`` a superstep skips this:
        the programs are built as they are first called."""
        if self.speculate:
            self.ex.build_spec_step(self.speculate, sample=self.sample)
        else:
            self.ex.build_decode_superstep(k, sample=self.sample)

    def _batch_args(self, pos_vec, tok_vec, block_table, req_ids) -> Tuple:
        """The tail every superstep program takes after its caches."""
        args = (pos_vec, tok_vec)
        if block_table is not None:
            # The caller rewrites its table row by row while the
            # dispatch may still be reading this one.
            args = (block_table.copy(),) + args
        if self.sample is not None:
            args += (np.asarray(req_ids, np.int32),)
        return args

    # In decode and spec the donated caches, the argument tuple, the
    # program's pos/tok carry (the loops keep theirs on the host) and
    # the fetched device arrays are all dropped inside a span: what is
    # freed between two spans a traced run counts as no span's.

    def decode(self, pos_vec: np.ndarray, tok_vec: np.ndarray, k: int,
               block_table: Optional[np.ndarray] = None,
               req_ids: Optional[np.ndarray] = None, superstep: int = 0):
        """One fused k-token superstep over the whole slot batch:
        ``(tokens (k, B), finite (k, B), counters, wall_s, t0)`` after
        one fence — ``counters`` the routing counters a step, (k,)
        each."""
        tel = _telemetry.current()
        with _telemetry.span("ff/serve/decode_dispatch",
                             superstep=superstep):
            t0 = time.perf_counter()
            fn = self.ex.build_decode_superstep(k, sample=self.sample)
            args = (self.params, self.op_state, self.caches) + \
                self._batch_args(pos_vec, tok_vec, block_table, req_ids)
            tel.program_cost("decode_superstep", fn, args, k=k)
            self.caches, _pos, _tok, fetch = fn(*args)
            del args, _pos, _tok
        with _telemetry.span("ff/serve/decode_fence", superstep=superstep):
            toks, oks, *counters = tel.fence(fetch, "decode_superstep")
            wall = time.perf_counter() - t0
            del fetch
        return toks, oks, counters[0] if counters else {}, wall, t0

    def spec(self, pos_vec: np.ndarray, tok_vec: np.ndarray, d: int,
             block_table: Optional[np.ndarray] = None,
             req_ids: Optional[np.ndarray] = None, superstep: int = 0):
        """One fused speculative round (d+1 draft steps, d+1 verify
        steps) over the whole slot batch: ``(tokens (d+1, B), finite
        (d+1, B), accepted (B,), wall_s, t0)`` after one fence."""
        tel = _telemetry.current()
        with _telemetry.span("ff/serve/decode_dispatch",
                             superstep=superstep):
            t0 = time.perf_counter()
            fn = self.ex.build_spec_step(d, sample=self.sample)
            args = (self.params, self.draft_params, self.op_state,
                    self.caches, self.dcaches) + \
                self._batch_args(pos_vec, tok_vec, block_table, req_ids)
            tel.program_cost("spec_verify", fn, args, d=d)
            self.caches, self.dcaches, _pos, _tok, fetch = fn(*args)
            del args, _pos, _tok
        with _telemetry.span("ff/serve/decode_fence", superstep=superstep):
            toks, oks, acc = tel.fence(fetch, "spec_verify")
            wall = time.perf_counter() - t0
            del fetch
        return toks, oks, acc, wall, t0


class Server:
    """Continuous-batching serving loop over a :class:`ServingExecutor`.

    ``run(requests)`` drives the closed loop to completion: admit
    eligible requests into free slots (prefill + cache install),
    dispatch one fused K-token decode superstep over the whole slot
    batch, consume the fenced tokens per slot (EOS / budget / context
    limits), evict finished slots, repeat.  Returns ``(results,
    stats)`` — per-request :class:`RequestResult` plus the latency/
    throughput stats block (request latency p50/p95 ms, tokens/s,
    decode supersteps, telemetry summary when enabled).
    """

    def __init__(
        self,
        executor: ServingExecutor,
        params,
        op_state,
        decode_steps: int = 8,
        eos_id: Optional[int] = None,
        fault_injector: Optional[ServingFaultInjector] = None,
        temperature: float = 0.0,
        top_k: int = 0,
        sample_seed: int = 0,
        journal=None,
        drain_on_preempt: bool = False,
        speculate: int = 0,
        draft_params=None,
    ):
        self.ex = executor
        self.params = params
        self.op_state = op_state
        self.decode_steps = clamp_fused_steps(
            decode_steps, what="decode_steps", log=_log
        )
        #: Speculative draft depth d (0 = the plain fused superstep).
        #: The draft chain counts against THE clamp site.
        self.speculate = (
            clamp_fused_steps(speculate, what="speculate", log=_log)
            if speculate else 0
        )
        #: Draft model params: a separate same-architecture draft
        #: checkpoint, or (default) the serving params themselves —
        #: self-drafting, truncated by the executor's ``draft_layers``.
        self.draft_params = (
            draft_params if draft_params is not None else params
        )
        self.eos_id = eos_id
        self.injector = fault_injector
        #: In-program sampling (temperature <= 0 = greedy, the default
        #: and the parity oracle; see build_decode_superstep).
        self.sample: Optional[Tuple[float, int, int]] = (
            (float(temperature), int(top_k), int(sample_seed))
            if temperature > 0.0 else None
        )
        #: Optional crash-recovery journal
        #: (``serving/journal.py::RequestJournal``): completed requests
        #: replay instead of re-running, in-flight requests resume with
        #: carried tokens.  Arming a journal also arms drain.
        self.journal = journal
        self.drain_on_preempt = bool(drain_on_preempt) or \
            journal is not None

    # -- loop ----------------------------------------------------------------

    def run(self, requests: Sequence[Request]):
        from flexflow_tpu.runtime.resilience import PreemptionHandler

        tel = _telemetry.current()
        ex = self.ex
        B, k = ex.max_batch, self.decode_steps
        spec_d = self.speculate
        # Made anew each run: every run starts on fresh caches.
        engine = ServingEngine(ex, self.params, self.op_state,
                               sample=self.sample, speculate=spec_d,
                               draft_params=self.draft_params)
        engine.prepare(k)
        ledger = ex.make_ledger() if ex.paged else None
        block_table = (
            np.zeros((B, ledger.blocks_per_slot), np.int32)
            if ledger is not None else None
        )
        slots: List[Optional[_Slot]] = [None] * B
        # Closed-loop runs have no arrival clock (the deprecated
        # superstep-index ``Request.arrival`` is retired): every
        # request is eligible at run start, in the given order.
        queue = collections.deque(requests)
        results: Dict[int, RequestResult] = {}
        superstep_idx = 0
        total_tokens = 0
        #: The run's counts: supersteps, prefills, prefix hits, ...
        n = collections.Counter()
        decode_s = 0.0
        t_run0 = time.perf_counter()

        def stamp(t: Optional[float] = None) -> float:
            # The run's own clock: ms since its start, to three
            # decimals, so every stamp is a whole number of
            # microseconds and the request fold (``obs/spans.py``)
            # reconciles exactly.  ``t`` is an instant the engine took.
            return round(((time.perf_counter() if t is None else t)
                          - t_run0) * 1e3, 3)

        # One line a run: ids restart at 0 in every run of a stream
        # (a benchmark cell's holds the warm-up and the window), and
        # so does this clock; the fold keeps the runs apart by it.
        tel.emit("serve_run", requests=len(queue), capacity=B, k=k)
        # -- journal replay: completed requests are NOT re-run,
        # in-flight requests resume with their fence-validated tokens
        # carried (re-prefill over prompt ‖ carried at admission).
        jr = self.journal
        carried_map: Dict[int, List[int]] = {}
        if jr is not None:
            st = jr.replay()
            for rid, rec in st.completed.items():
                results[rid] = RequestResult(
                    id=rid, prompt_len=int(rec.get("plen") or 0),
                    tokens=list(rec.get("tokens", [])),
                    error=rec.get("error"),
                    latency_s=float(rec.get("latency_s") or 0.0),
                )
            carried_map = {int(rid): list(t)
                           for rid, t in st.in_flight.items()}
            queue = collections.deque(
                r for r in queue if r.id not in results
            )
            if not st.empty:
                _log.info(
                    "journal replay (%s): %d completed restored, %d "
                    "in flight resume with carried tokens%s",
                    jr.path, len(st.completed), len(carried_map),
                    " [torn tail tolerated]" if st.torn_tail else "",
                )
        drained = False
        preempt = PreemptionHandler(install=self.drain_on_preempt)

        def end_request(rid: int, n_tokens: int, error: Optional[str],
                        lat: float, t_eligible: float):
            # ``e2e_ms`` from the ROUNDED stamps, as the scheduler's
            # ``finish_result``: the fold's phases sum to it exactly.
            arr, end = stamp(t_eligible), stamp()
            tel.emit("request_end", id=rid, tokens=n_tokens, error=error,
                     latency_s=round(lat, 6), arrival_ms=arr,
                     e2e_ms=round(end - arr, 3), t_ms=end)

        def finish(slot_i: int, error: Optional[str] = None):
            sl = slots[slot_i]
            toks = sl.all_tokens
            lat = time.perf_counter() - sl.t_eligible
            results[sl.request.id] = RequestResult(
                id=sl.request.id,
                prompt_len=len(sl.request.prompt),
                tokens=list(toks),
                error=error,
                latency_s=lat,
                prefill_s=sl.prefill_s,
            )
            end_request(sl.request.id, len(toks), error, lat,
                        sl.t_eligible)
            if jr is not None:
                jr.done(sl.request.id, len(sl.request.prompt),
                        len(toks), error, latency_s=round(lat, 6))
            if ledger is not None:
                ledger.free(slot_i)
                block_table[slot_i] = 0
            slots[slot_i] = None

        def rounded(counters) -> Dict[str, float]:
            # The ops' counters as an event carries them: the mean over
            # a superstep's K steps of the layers' mean (a prefill's are
            # one step's), or for a counter that says the worst seen the
            # largest of both, to four significant digits.
            return {key: float(f"{np.max(v):.4g}")
                    if key in SERVING_STATS_LARGEST
                    else round(float(np.mean(v)), 4)
                    for key, v in counters.items()}

        def slot_done(sl: _Slot) -> bool:
            toks = sl.all_tokens
            if self.eos_id is not None and toks and \
                    toks[-1] == self.eos_id:
                return True
            if len(toks) >= sl.request.max_new_tokens:
                return True
            return sl.pos >= ex.max_seq  # context limit
        def reject(r: Request, err: str):
            # Rejected requests still leave a complete start/end pair
            # in the log (the reconstructable-from-JSONL contract)
            # and an honest latency.
            plen = len(r.prompt)
            tel.emit("request_start", id=r.id, prompt_len=plen,
                     bucket=None, slot=None, t_ms=stamp())
            lat = time.perf_counter() - t_run0
            results[r.id] = RequestResult(
                id=r.id, prompt_len=plen, tokens=[],
                error=err, latency_s=lat,
            )
            end_request(r.id, 0, err, lat, t_run0)
            if jr is not None:
                jr.done(r.id, plen, 0, err, latency_s=round(lat, 6))

        def resume_complete(r: Request, prior: List[int]) -> bool:
            """A journaled in-flight sequence that is ALREADY finished
            (the crash landed between the token write and the done
            record): restore the result without re-prefilling."""
            plen = len(r.prompt)
            if len(prior) < r.max_new_tokens and \
                    plen + len(prior) < ex.max_seq and \
                    not (self.eos_id is not None and prior and
                         prior[-1] == self.eos_id):
                return False
            tel.emit("request_start", id=r.id, prompt_len=plen,
                     bucket=None, slot=None, t_ms=stamp())
            lat = time.perf_counter() - t_run0
            results[r.id] = RequestResult(
                id=r.id, prompt_len=plen, tokens=list(prior),
                error=None, latency_s=lat,
            )
            end_request(r.id, len(prior), None, lat, t_run0)
            if jr is not None:
                jr.done(r.id, plen, len(prior), None,
                        latency_s=round(lat, 6))
            return True

        preempt.__enter__()
        try:
            while queue or any(slots):
                if preempt.triggered and self.drain_on_preempt:
                    # -- drain-on-SIGTERM: stop admissions; in-flight
                    # work is already journaled at the last fence, so
                    # exiting here loses nothing — a resume from the
                    # journal serves the remainder byte-identically.
                    drained = True
                    n_flight = sum(1 for sl in slots if sl is not None)
                    tel.emit("serving_drain", signum=preempt.signum,
                             in_flight=n_flight, queued=len(queue))
                    _log.warning(
                        "drain: signal %s — %d in flight journaled, "
                        "%d queued; resume from the journal to serve "
                        "the remainder", preempt.signum, n_flight,
                        len(queue),
                    )
                    if jr is not None:
                        jr.drain(n_flight, len(queue))
                    break
                # -- admissions (between decode supersteps) --
                while queue and None in slots:
                    r = queue[0]
                    with _telemetry.span("ff/serve/admit", id=r.id):
                        plen = len(r.prompt)
                        prior = carried_map.get(r.id, [])
                        flen = plen + len(prior)
                        if prior and resume_complete(r, prior):
                            queue.popleft()
                            carried_map.pop(r.id, None)
                            continue
                        try:
                            bucket = ex.bucket_for(flen)
                        except ValueError as e:
                            queue.popleft()
                            carried_map.pop(r.id, None)
                            reject(r, str(e))
                            continue
                        plan = None
                        if ledger is not None:
                            need = ledger.blocks_for(plen, r.max_new_tokens)
                            if need > ledger.capacity_blocks:
                                queue.popleft()
                                reject(r, (
                                    f"request needs {need} KV blocks but "
                                    f"the paged pool holds "
                                    f"{ledger.capacity_blocks}"
                                ))
                                continue
                            # Prefix sharing: shared blocks don't leave the
                            # free list, so admission only needs the
                            # non-shared tail — a hit can admit where a
                            # miss would head-of-line wait.
                            plan = ledger.plan_prefix(r.prompt,
                                                      total_len=flen)
                            if not ledger.can_admit(need - plan.use):
                                # Head-of-line wait: blocks free up when an
                                # active slot finishes (deterministic FIFO —
                                # no reorder, no livelock: the whole pool
                                # covers any single admissible request).
                                break
                        queue.popleft()
                        carried_map.pop(r.id, None)
                        slot_i = slots.index(None)
                        tel.emit("request_start", id=r.id, prompt_len=plen,
                                 bucket=bucket, slot=slot_i, t_ms=stamp())
                        full = np.concatenate([
                            np.asarray(r.prompt, np.int32),
                            np.asarray(prior, np.int32),
                        ]) if prior else r.prompt
                        digests = (
                            prefix_digests(r.prompt, ledger.block)
                            if ledger is not None and ledger.prefix_cache
                            else []
                        )
                        use = plan.use if plan is not None else 0
                        rows = None
                        if plan is not None and plan.full_hit:
                            # -- ZERO-dispatch admission: the whole prompt
                            # is resident full blocks and the greedy first
                            # token is memoized — no prefill program runs
                            # at all (the prefix-sharing headline).
                            tok0, ok, pf_s = plan.tok0, True, 0.0
                            n["prefix_hits"] += 1
                            n["full_hits"] += 1
                            n["prefill_tokens_saved"] += plan.offset
                            tel.emit("prefix_hit", id=r.id,
                                     blocks=plan.use, full=True,
                                     tokens_saved=plan.offset,
                                     t_ms=stamp())
                        else:
                            # A partial hit (use > 0) gathers the shared
                            # span from the pool and computes only the
                            # tail (same fence discipline).
                            rows, tok0, ok, routed, pf_s, t0 = \
                                engine.prefill(
                                    full, bucket, plen=plen, rid=r.id,
                                    offset=plan.offset if use else 0,
                                    shared_ids=plan.shared if use else None,
                                )
                            n["prefills"] += 1
                            # Both edges of the engine's call on the one
                            # event: its ``t0`` and its fence's return.
                            edges = dict(wall_s=round(pf_s, 6),
                                         t0_ms=stamp(t0),
                                         t_ms=stamp(t0 + pf_s))
                            if use:
                                n["prefix_hits"] += 1
                                n["prefill_tokens_saved"] += plan.offset
                                tel.emit("prefill", id=r.id, bucket=bucket,
                                         length=flen, offset=plan.offset,
                                         **edges)
                                tel.emit("prefix_hit", id=r.id,
                                         blocks=plan.use, full=False,
                                         tokens_saved=plan.offset,
                                         t_ms=stamp())
                                if plan.cow:
                                    n["kv_cows"] += plan.cow
                                    tel.emit("kv_cow", id=r.id,
                                             blocks=plan.cow)
                            else:
                                tel.emit("prefill", id=r.id, bucket=bucket,
                                         length=flen, **edges,
                                         **rounded(routed))
                        if jr is not None:
                            jr.admit(r.id, plen, tok0 if ok else None,
                                     resumed=len(prior))
                        if not ok:
                            sl = _Slot(r, flen, 0, [], t_run0, pf_s,
                                       carried=list(prior))
                            slots[slot_i] = sl
                            finish(slot_i,
                                   error="non-finite logits in prefill")
                            continue
                        row = None
                        if ledger is not None:
                            row = ledger.alloc(slot_i, need,
                                               shared=plan.shared)
                            block_table[slot_i] = row
                        if rows is not None:
                            engine.install(rows, slot_i, row=row,
                                           shared=use, rid=r.id)
                        if digests:
                            # Index only AFTER the fence validated the
                            # install (never make never-written blocks
                            # shareable); memoize the first token when
                            # the prompt is exactly block-aligned and
                            # fresh — the future full-hit upgrade.
                            ledger.register_prefix(slot_i, digests,
                                                   start=use)
                            if flen == plen and \
                                    plen % ledger.block == 0 and \
                                    not plan.full_hit:
                                ledger.record_next(digests[-1], tok0)
                        if spec_d:
                            engine.draft_prefill(full, bucket, slot_i,
                                                 rid=r.id)
                            n["draft_prefills"] += 1
                        sl = _Slot(
                            request=r, pos=flen, last_tok=tok0,
                            tokens=[tok0], t_eligible=t_run0,
                            prefill_s=pf_s, carried=list(prior),
                        )
                        total_tokens += 1
                        slots[slot_i] = sl
                        if slot_done(sl):
                            finish(slot_i)

                active = [i for i, sl in enumerate(slots)
                          if sl is not None]
                if not active:
                    break

                # -- one fused decode superstep over the whole batch --
                with _telemetry.span("ff/serve/decode_pack",
                                     superstep=superstep_idx,
                                     active=len(active)):
                    if self.injector is not None:
                        try:
                            engine.caches = self.injector.before_superstep(
                                superstep_idx, engine.caches, block_table
                            )[0]
                        except ServingFault as f:
                            superstep_idx += 1
                            if slots[f.slot] is not None:
                                finish(f.slot, error=f"raised fault: {f}")
                            continue
                    pos_vec = np.array(
                        [sl.pos if sl else 0 for sl in slots], np.int32
                    )
                    tok_vec = np.array(
                        [sl.last_tok if sl else 0 for sl in slots], np.int32
                    )
                    req_vec = np.array(
                        [sl.request.id if sl else 0 for sl in slots],
                        np.int32
                    ) if self.sample is not None else None
                if spec_d:
                    # -- one fused speculative round: d+1 draft steps
                    # + d+1 verify steps, one dispatch, one fence
                    # reading (tokens, finite, accepted).
                    host_toks, host_oks, host_acc, wall, t0 = engine.spec(
                        pos_vec, tok_vec, spec_d, block_table=block_table,
                        req_ids=req_vec, superstep=superstep_idx,
                    )
                    k_eff = spec_d + 1
                else:
                    host_toks, host_oks, routed, wall, t0 = engine.decode(
                        pos_vec, tok_vec, k, block_table=block_table,
                        req_ids=req_vec, superstep=superstep_idx,
                    )
                    host_acc = None
                    k_eff = k
                with _telemetry.span("ff/serve/bookkeep",
                                     superstep=superstep_idx):
                    decode_s += wall
                    n["supersteps"] += 1
                    # Training-superstep accounting: ONE host program and
                    # one fence covered k_eff decode steps (programs/step
                    # == 1/k_eff).
                    tel.add_programs(1, steps=k_eff)
                    # The round's one event: `slots`, its occupancy by
                    # request id (captured before finish() frees slots),
                    # both edges of the engine's call on the run's clock
                    # (the request fold gives the round to each occupant
                    # as `decode`) and `superstep`, the key its
                    # dispatch and fence spans carry.  Its k_eff steps
                    # are counted, not written: the event holds `wall_s`
                    # and `k` (or `d`), and the reader divides.
                    occ = [slots[i].request.id for i in active]
                    edges = dict(superstep=superstep_idx,
                                 wall_s=round(wall, 6), t0_ms=stamp(t0),
                                 t_ms=stamp(t0 + wall))
                    superstep_idx += 1
                    if not spec_d:
                        tel.emit("decode_superstep", k=k, active=len(active),
                                 capacity=B, slots=occ, **edges,
                                 **rounded(routed),
                                 **self.ex.kv_rows(pos_vec, k))
                    tel.record_steps(k_eff, edges["wall_s"] / k_eff)
                    n_active = len(active)
                    emitted_round = 0
                    for i in active:
                        sl = slots[i]
                        err = None
                        appended: List[int] = []
                        if spec_d:
                            n_take = int(host_acc[i]) + 1
                            n["spec_accept_total"] += int(host_acc[i])
                        else:
                            n_take = k
                        for j in range(n_take):
                            if not bool(host_oks[j, i]):
                                err = "non-finite logits in decode"
                                break
                            tok = int(host_toks[j, i])
                            sl.tokens.append(tok)
                            appended.append(tok)
                            sl.pos += 1
                            total_tokens += 1
                            if slot_done(sl):
                                break
                        sl.last_tok = sl.tokens[-1] if sl.tokens else 0
                        n["decode_tokens"] += len(appended)
                        emitted_round += len(appended)
                        # Journal the fence-validated delta BEFORE any done
                        # record so replay accumulation sees tokens first —
                        # under speculation, ``appended`` holds ACCEPTED
                        # tokens only (rejected draft never reaches the
                        # host), so resume semantics are unchanged.
                        if jr is not None and appended:
                            jr.tokens(sl.request.id, appended)
                        if err is not None:
                            finish(i, error=err)
                        elif slot_done(sl):
                            finish(i)
                    if spec_d:
                        acc_round = int(sum(
                            int(host_acc[i]) for i in active
                        ))
                        n["spec_draft_total"] += spec_d * n_active
                        tel.emit("spec_verify", d=spec_d, active=n_active,
                                 accepted=acc_round,
                                 draft=spec_d * n_active,
                                 emitted=emitted_round, slots=occ, **edges)
                    # Under the span: where device_get hands back a
                    # view of the device's buffer (the CPU), this is
                    # what frees it.
                    del host_toks, host_oks, host_acc
        finally:
            preempt.__exit__(None, None, None)
            if jr is not None:
                jr.close()

        elapsed = time.perf_counter() - t_run0
        lats = sorted(
            r.latency_s for r in results.values() if r.error is None
        )

        def pct(p: float) -> float:
            if not lats:
                return 0.0
            return lats[min(len(lats) - 1, int(round(p * (len(lats) - 1))))]

        stats = {
            "requests": len(results),
            "completed": sum(1 for r in results.values() if r.error is None),
            "failed": sum(1 for r in results.values() if r.error),
            "tokens": total_tokens,
            "elapsed_s": elapsed,
            "tokens_per_s": total_tokens / max(elapsed, 1e-9),
            "decode_supersteps": n["supersteps"],
            "decode_steps_per_call": k,
            "decode_s": decode_s,
            "prefills": n["prefills"],
            "request_latency_ms_p50": round(pct(0.50) * 1e3, 3),
            "request_latency_ms_p95": round(pct(0.95) * 1e3, 3),
            # One host program per decode superstep, by construction
            # (audited by the telemetry programs/step counter).
            "programs_per_decode_superstep": 1,
            "kv_layout": "paged" if ex.paged else "padded",
            "shard": list(ex.shard) if ex.shard is not None else None,
            "sampled": self.sample is not None,
        }
        if ex.paged:
            stats["kv_block"] = ex.kv_block
            stats["kv_blocks"] = ex.kv_blocks
        if getattr(ex, "prefix_cache", False):
            stats["prefix_cache"] = True
            stats["prefix_hits"] = n["prefix_hits"]
            stats["prefix_hit_rate"] = round(
                n["prefix_hits"] / max(n["prefills"] + n["full_hits"], 1), 4
            )
            stats["prefill_tokens_saved"] = n["prefill_tokens_saved"]
            stats["kv_cows"] = n["kv_cows"]
            if n["prefix_hits"]:
                # Final-rounded into the run_end summary block;
                # reconstruct_summary recomputes both from the raw
                # prefill/prefix_hit events and must match bit-for-bit.
                tel.note_summary(
                    prefix_hit_rate=stats["prefix_hit_rate"],
                    prefill_tokens_saved=n["prefill_tokens_saved"],
                )
        if self.speculate:
            stats["speculate"] = self.speculate
            stats["draft_layers"] = ex.draft_layers
            stats["draft_prefills"] = n["draft_prefills"]
            stats["spec_acceptance_rate"] = round(
                n["spec_accept_total"] / max(n["spec_draft_total"], 1), 4
            )
            stats["spec_tokens_per_dispatch"] = round(
                n["decode_tokens"] / max(n["supersteps"], 1), 3
            )
            # Final-rounded into the run_end summary block;
            # reconstruct_summary recomputes both from the raw
            # spec_verify events and must match bit-for-bit.
            tel.note_summary(
                spec_acceptance_rate=stats["spec_acceptance_rate"],
                spec_tokens_per_dispatch=stats[
                    "spec_tokens_per_dispatch"],
            )
        if self.drain_on_preempt:
            stats["drained"] = drained
        return results, tel.fold_stats(stats)


def synthetic_requests(
    n: int,
    vocab: int,
    prompt_len: Tuple[int, int] = (4, 12),
    max_new_tokens: int = 16,
    arrival_every: int = 0,
    seed: int = 0,
) -> List[Request]:
    """Deterministic synthetic request stream for closed-loop
    benchmarking: prompt lengths uniform in ``prompt_len`` (inclusive),
    ids uniform over the vocab, all requests eligible at run start
    (the burst pattern).

    ``arrival_every`` is RETIRED (PR 12's one-release deprecation
    grace is up): any non-zero value raises ``ValueError`` pointing at
    the open-loop workload generator (``serving/workload.py``;
    ``uniform_workload`` is the direct replacement)."""
    if arrival_every:
        raise ValueError(
            "synthetic_requests(arrival_every=...) is retired (and "
            "Request.arrival is gone): superstep-index arrivals were "
            "replaced by arrival_ms-driven open-loop workloads — use "
            "flexflow_tpu.serving.workload.uniform_workload / "
            "make_workload instead"
        )
    rng = np.random.default_rng(seed)
    lo, hi = prompt_len
    out = []
    for i in range(n):
        plen = int(rng.integers(lo, hi + 1))
        out.append(Request(
            id=i,
            prompt=rng.integers(0, vocab, size=plen).astype(np.int32),
            max_new_tokens=max_new_tokens,
        ))
    return out
