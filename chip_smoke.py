#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the repo's main path once on the attached TPU, through the entry
points a user would call (``flexflow_tpu.apps.*.main(argv)``), at the
full width of models the repo supports, and checks what comes out by
the repo's own means:

    python3 chip_smoke.py            # one chip: train x3, serve x4
    python3 chip_smoke.py --chips 4  # ONLY the cross-chip paths and
                                     # their one-device comparison

One process: it touches jax itself and starts no child that needs the
chip.  With no TPU it exits non-zero at once and prints no result.  A
phase that raises, yields a non-finite loss, fails a request, degrades
the serving engine, loses a Pallas kernel from its compiled program or
disagrees with its reference makes the run exit 1; the other phases
still run, so one call shows every fault.  The last stdout line of a
passing run is ``{"ok": true, "device": {...}}``; earlier lines carry
per-phase wall times (set-up with compilation against the steady step)
as information, not as metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import re
import sys
import time
import traceback
from typing import (
    Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple,
)

import jax
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Decode logits tolerance (f32) — the bar tests/test_serving.py holds
#: the decode kernel to (``DECODE_TOL`` there; the rehearsal test pins
#: the two equal).
DECODE_TOL = 1e-4
#: Loss-trajectory tolerance of tests/test_sharding_equivalence.py's
#: ``assert_same`` (the dp / tp / spatial / hybrid family).
SHARD_RTOL, SHARD_ATOL = 2e-4, 1e-5
#: Sparse (row kernels) against dense (jnp) DLRM loss trajectory.  The
#: two differ only in how the table update rounds (scatter-add of
#: -lr*row_grad against a full-table axpy); tests/test_sparse_update.py
#: holds them to 1e-6 in exact f32 on the CPU, and the chip's default
#: f32 matmul precision leaves room for a little more.
DLRM_RTOL = 1e-4

_DLRM_ARCH = [
    "--arch-sparse-feature-size", "64",
    "--arch-embedding-size", "1000000-1000000-1000000-1000000",
    "--arch-mlp-bot", "64-512-512-64",
    "--arch-mlp-top", "320-1024-1024-1024-1",
]
_LM_SHAPE = ["--vocab", "32768", "--d-model", "512", "--heads", "8",
             "--layers", "4"]


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The argv of every phase.  ``FULL`` is what the chip runs; the
    CPU rehearsal (tests/test_chip_compile.py) passes tiny ones."""

    alexnet: Tuple[str, ...]
    transformer: Tuple[str, ...]
    dlrm: Tuple[str, ...]
    serve: Tuple[str, ...]
    #: ``apps.serve --model-config``: the DeepSeek-V3 family's preset.
    serve_latent: Tuple[str, ...]
    #: --chips 4: the cross-chip argv of each app; the comparison run
    #: is the same argv on one device (strategy / mesh flags dropped).
    serve_solar: Tuple[str, ...]
    serve_xing: Tuple[str, ...]
    #: The Keye-VL-2.0 preset: prompts longer than its ``topk``.
    serve_keye: Tuple[str, ...]
    #: The Laguna preset: prompts several windows long.
    serve_laguna: Tuple[str, ...]
    #: The A.X-K2 preset: prompts longer than its ``index_topk``.
    serve_axk2: Tuple[str, ...]
    serve_lfm2: Tuple[str, ...]
    dlrm4: Tuple[str, ...]
    alexnet4: Tuple[str, ...]
    alexnet4_strategy: Tuple[str, ...]
    transformer4: Tuple[str, ...]
    transformer4_mesh: Tuple[str, ...]
    serve4_shard: Tuple[str, ...]


FULL = Sizes(
    # README's canonical run: 229x229x3, 1000 classes.
    alexnet=("-b", "256", "--dtype", "bfloat16", "-i", "10"),
    # The app's own defaults (seq 512, vocab 32768, d 512, 8 heads,
    # 4 layers), named so the log shows them.
    transformer=("-b", "8", "--seq", "512", *_LM_SHAPE,
                 "--dtype", "bfloat16", "-i", "10"),
    # README's DLRM shape (4 of run_random.sh's 8 tables, at its 1M
    # rows: 7812 whole 128-row blocks and an edge block of 64), under
    # plain SGD: the row-sparse path — the one that reaches the Pallas row kernels —
    # is exact only without momentum and weight decay, and the
    # executor keeps the CLI's defaults (0.9, 1e-4) on the dense path.
    dlrm=("-b", "1024", "-i", "10", "--momentum", "0", "--wd", "0",
          *_DLRM_ARCH),
    serve=("--max-seq", "512", "--max-batch", "8", "--requests", "12",
           *_LM_SHAPE),
    # 256 positions: two blocks of the uneven flash forward, two lane
    # tiles of the latent cache; bf16 as the benchmark's cell runs it.
    serve_latent=("--model-config", "deepseek-v3-smoke", "--max-seq", "256",
                  "--max-batch", "4", "--requests", "6", "--max-new", "12",
                  "--prompt-len", "100:200", "--buckets", "256",
                  "--dtype", "bfloat16"),
    # 256 positions: two blocks of the streamed forward under grouped
    # queries, four chunks of the delta rule's scan (prompts end inside
    # a chunk), two lane tiles of the KV cache.
    serve_solar=("--model-config", "solar-open2-smoke", "--max-seq", "256",
                 "--max-batch", "4", "--requests", "6", "--max-new", "12",
                 "--prompt-len", "100:200", "--buckets", "256",
                 "--dtype", "bfloat16"),
    # The latent phase's sizes round four hyper-connected streams.
    serve_xing=("--model-config", "xing4-smoke", "--max-seq", "256",
                "--max-batch", "4", "--requests", "6", "--max-new", "12",
                "--prompt-len", "100:200", "--buckets", "256",
                "--dtype", "bfloat16"),
    # 512 positions under a ``topk`` of 256 and chunks of 128: prompts
    # of 300-450 select in the prefill's last chunks (its first 256 rows
    # go through the streamed forward kernel) and in every decode step.
    serve_keye=("--model-config", "keye-vl2-smoke", "--max-seq", "512",
                "--max-batch", "4", "--requests", "6", "--max-new", "12",
                "--prompt-len", "300:450", "--buckets", "512",
                "--dtype", "bfloat16"),
    # 2048 positions under a window of 512 (one ``flash_decode`` chunk):
    # prompts of 1100-1900 fill every ring before the first decode step,
    # the banded forward walks two or three key blocks a query block
    # (blocks of 256 at the 1280 bucket, of 512 at 2048), and groups of
    # 9 and 6 query heads decode through the kernel.
    serve_laguna=("--model-config", "laguna-smoke", "--max-seq", "2048",
                  "--max-batch", "4", "--requests", "6", "--max-new", "12",
                  "--prompt-len", "1100:1900", "--buckets", "1280,2048",
                  "--dtype", "bfloat16"),
    # 1024 positions under an ``index_topk`` of 512 and chunks of 512:
    # prompts of 600-900 select in the prefill's second chunk (its first
    # 512 rows go through the streamed forward kernel) and in every
    # decode step.
    serve_axk2=("--model-config", "axk2-smoke", "--max-seq", "1024",
                "--max-batch", "4", "--requests", "6", "--max-new", "12",
                "--prompt-len", "600:900", "--buckets", "1024",
                "--dtype", "bfloat16"),
    # 512 positions: prompts of 200-450 end inside the 512 bucket, so the
    # convolution windows are taken at the prompt's length and not at
    # the bucket's end; heads of 64 in groups of four through both
    # attention kernels, one chunk of the decode kernel.  Six slots: a
    # cache of 6 x 512 x 2 x 64 values has no weight's element count (at
    # four it has W_out's 512 x 512, and the relayout check counts
    # elements: my chip run PR 51).
    serve_lfm2=("--model-config", "lfm2-smoke", "--max-seq", "512",
                "--max-batch", "6", "--requests", "8", "--max-new", "12",
                "--prompt-len", "200:450", "--buckets", "512",
                "--dtype", "bfloat16"),
    # The one-chip DLRM shape with a table a chip (``dlrm_strategy``:
    # the stacked dim at c = 4), MLPs data parallel at 256 a chip.
    dlrm4=("-b", "1024", "-i", "3", "--momentum", "0", "--wd", "0",
           *_DLRM_ARCH),
    # float32 and three steps (one warm-up + two), the protocol of the
    # strategy-equivalence tests whose tolerance is applied: the
    # trajectories start equal to seven digits and round-off grows ~10x
    # a step on AlexNet's README flags (1e-3 by step four on the chip).
    alexnet4=("-b", "256", "-i", "2"),
    alexnet4_strategy=(
        "-s", os.path.join(ROOT, "strategies", "alexnet_readme_4dev.json"),
    ),
    transformer4=("-b", "8", "--seq", "512", *_LM_SHAPE, "-i", "2"),
    transformer4_mesh=("--dp", "2", "--tp", "2"),
    serve4_shard=("--shard", "2,2"),
)


# -- the device ---------------------------------------------------------------


def require_tpu() -> Dict[str, object]:
    """The device as jax reports it, or exit non-zero when it is not a
    TPU.  No ``JAX_PLATFORMS`` rewriting, no probe child."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: jax found platform {dev.platform!r}, not a "
              f"TPU; nothing was run", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def has_mosaic_call(compiled_text: str) -> bool:
    """Whether a compiled program's text holds a Mosaic kernel — the
    proof that a Pallas call neither routed to jnp nor ran under the
    interpreter (which lowers to plain HLO)."""
    return "tpu_custom_call" in compiled_text


_RELAYOUT_OPS = ("copy", "copy-start", "reshape", "transpose", "fusion")
#: ... and of a KV cache: the step's column written by an XLA scatter
#: is one more (the compiler lays it out row-major and copies the cache
#: for it; PERF.md §6 PR 32).  No ``copy-start``: a cache of a few MB
#: is moved between memory spaces in the same order, which is no
#: relayout (the sharded smoke's 2 MB shards are).
CACHE_RELAYOUT_OPS = ("copy", "reshape", "transpose", "fusion", "scatter")
_HLO_SHAPE = r"\w+\[[\d,]*\](?:\{[^}]*\})?"
_HLO_INSTRUCTION = re.compile(
    rf"^\s*(?:ROOT\s+)?%\S+ = (\(?{_HLO_SHAPE}(?:, {_HLO_SHAPE})*\)?) ([\w-]+)\(")


def has_kernel(compiled_text: str, name: str) -> bool:
    """Whether the compiled program calls the Pallas kernel ``name``
    (its instruction is named after it: ``%ff_mla_decode.3 = ``)."""
    return re.search(rf"%{re.escape(name)}[.\d]* = ", compiled_text) is not None


def table_sized_relayouts(compiled_text: str, elements: int,
                          ops: Sequence[str] = _RELAYOUT_OPS) -> List[str]:
    """The instructions of an optimised HLO text that move a whole
    table: a ``copy``, ``reshape``, ``transpose`` or fusion (or the
    opcodes ``ops`` names instead) with a result of ``elements``
    elements.  A ``bitcast`` moves nothing and the aliased scatter call
    is the update itself; what this names is a view of the table that
    is not the order the chip stores it in, paid for on every step
    (PERF.md §6, PR 28)."""
    found = []
    for line in compiled_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if not m or m.group(2) not in ops:
            continue
        sizes = (math.prod(int(x) for x in dims.split(",") if x)
                 for dims in re.findall(r"\[([\d,]*)\]", m.group(1)))
        if elements in sizes:
            found.append(line.strip()[:160])
    return found


# -- plumbing -----------------------------------------------------------------


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def info(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


@contextlib.contextmanager
def recorded(cls, method: str):
    """Record ``(instance, args, result)`` of every ``cls.method`` call
    made inside the block.  The apps' ``main(argv)`` returns an exit
    code only; the checks need the exact objects it built (the
    executor behind the trainer, the server and its results)."""
    calls: List[tuple] = []
    orig = getattr(cls, method)

    def wrapper(self, *args, **kw):
        out = orig(self, *args, **kw)
        calls.append((self, args, out))
        return out

    setattr(cls, method, wrapper)
    try:
        yield calls
    finally:
        setattr(cls, method, orig)


def _ms(seconds: float) -> str:
    return f"{seconds * 1e3:.2f}"


def build_native() -> None:
    """Compile and load the three C++ components with the machine's
    g++ — from a clean checkout nothing is prebuilt, and a
    ``NativeBuildError`` here is a failure, never a silent Python
    path."""
    from flexflow_tpu import native
    from flexflow_tpu.parallel.strategy import StrategyStore

    for name in ("ffsim", "ffproto", "ffdata"):
        native._build(name)
    native.load_ffsim()
    native.load_ffdata()
    # One call through each codec the main path can reach.
    pb = StrategyStore.load_pb(
        os.path.join(ROOT, "strategies", "dlrm_8chip.pb"), num_devices=8
    )
    js = StrategyStore.load(
        os.path.join(ROOT, "strategies", "dlrm_8chip.json"), num_devices=8
    )
    check(pb.table == js.table, "ffproto: .pb and .json strategies differ")
    src = np.arange(64, dtype=np.float32).reshape(16, 4)
    idx = np.array([3, 0, 15, 3])
    check(np.array_equal(native.gather_rows(src, idx), src[idx]),
          "ffdata: gather_rows != numpy")
    info("native", built="ffsim,ffproto,ffdata")


# -- training -----------------------------------------------------------------


def replay(trainer, steps: int, batch=None, start=None):
    """Re-run ``steps`` train steps of the trainer's executor from its
    seed's init (or from ``start``) on one fixed batch — the compiled
    program ``fit`` just ran, so nothing compiles — fencing each step.
    Returns (losses, per-step wall seconds, final (params, opt_state,
    state))."""
    ex = trainer.ex
    if batch is None:
        batch = trainer.synthetic_batch()
    params, opt_state, state = start if start is not None else ex.init()
    losses, walls = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt_state, state, m = ex.train_step(
            params, opt_state, state, batch
        )
        losses.append(float(jax.device_get(m["train_loss"])))
        walls.append(time.perf_counter() - t0)
    return losses, walls, (params, opt_state, state)


def train_phase(phase: str, app, argv: Sequence[str], kernels: bool = False):
    """Run ``app.main(argv)`` (the user's path: flags, strategy,
    executor, ``Trainer.fit`` on the fixed synthetic batch), then
    replay the same compiled step from the same seed to read the loss
    trajectory: finite, falling, and ending where ``fit`` ended.
    Falling means below where it started, not monotone: AlexNet's
    README flags (lr 0.01, momentum 0.9) overshoot on one repeated
    batch of two label values after four steps — in f32 and bf16, on
    the CPU exactly as on the chip (PERF.md "Findings").
    ``kernels``: the compiled step must hold its Pallas calls."""
    from flexflow_tpu.runtime.trainer import Trainer

    t0 = time.perf_counter()
    with recorded(Trainer, "fit") as fits:
        rc = app.main(list(argv))
    wall = time.perf_counter() - t0
    check(rc == 0, f"{phase}: main exited {rc}")
    check(len(fits) == 1, f"{phase}: expected one Trainer.fit, saw {len(fits)}")
    trainer, _, stats = fits[0]
    check(math.isfinite(stats["loss"]), f"{phase}: loss {stats['loss']}")
    # fit() ran 1 warm-up + the timed iterations, all real updates.
    steps = 1 + stats["iterations"]
    batch = trainer.synthetic_batch()
    losses, walls, final = replay(trainer, steps, batch)
    check(all(math.isfinite(x) for x in losses),
          f"{phase}: non-finite loss in {losses}")
    check(min(losses) < losses[0], f"{phase}: loss not falling: {losses}")
    check(math.isclose(losses[-1], stats["loss"], rel_tol=1e-5, abs_tol=1e-6),
          f"{phase}: replayed loss {losses[-1]} != fit's {stats['loss']}")
    if kernels:
        text = trainer.ex.train_step.lower(*final, batch).compile().as_text()
        check(has_mosaic_call(text),
              f"{phase}: no Pallas kernel in the compiled train step "
              f"(a *_supported gate routed to jnp, or interpret mode)")
        for op in trainer.ex._sparse_ops:
            for key in op.sparse_keys():
                table = final[0][op.name][key]  # what one device holds of it
                moved = table_sized_relayouts(text, math.prod(
                    table.sharding.shard_shape(table.shape)))
                check(not moved, f"{phase}: the compiled step moves the "
                      f"whole of {op.name}/{key} every step: {moved}")
    info(phase,
         setup_s=f"{wall - stats['elapsed_s']:.1f}",
         step_ms=_ms(stats["elapsed_s"] / stats["iterations"]),
         replay_step_ms=_ms(float(np.median(walls))),
         losses=[round(x, 4) for x in losses])
    return trainer, losses, final


def spanning_batch(ex, arch) -> Dict[str, np.ndarray]:
    """A host batch whose ids span the tables (the app's fixed
    synthetic batch only ever names rows 0 and 1)."""
    from flexflow_tpu.data.loader import synthetic_host_batch

    return synthetic_host_batch(
        ex.model, np.random.default_rng(ex.config.seed),
        int_high={"sparse_input": min(arch.embedding_size)},
    )


def dlrm_phase(argv: Sequence[str]) -> None:
    """DLRM through its app, then the row-sparse path (Pallas
    gather/scatter kernels on one TPU) against the dense jnp path on a
    batch whose ids span the tables."""
    from flexflow_tpu.apps import dlrm
    from flexflow_tpu.apps.common import make_optimizer
    from flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
    from flexflow_tpu.runtime.pipeline import make_executor
    from flexflow_tpu.runtime.trainer import Trainer

    trainer, _, _ = train_phase("train/dlrm", dlrm, argv, kernels=True)
    ex = trainer.ex
    check(bool(ex._sparse_ops),
          "train/dlrm: no op took the row-sparse path (dense fallback)")
    arch = DLRMConfig.parse_args(list(argv))
    host = spanning_batch(ex, arch)
    dense_cfg = dataclasses.replace(ex.config, sparse_embedding_updates=False)
    dense_ex = make_executor(
        build_dlrm(batch_size=dense_cfg.batch_size, dlrm=arch,
                   config=dense_cfg),
        ex.strategy, config=dense_cfg, optimizer=make_optimizer(dense_cfg),
    )
    check(not dense_ex._sparse_ops, "train/dlrm: dense reference went sparse")
    sparse_l, _, _ = replay(trainer, 4, ex.shard_batch(host))
    dense_l, _, _ = replay(Trainer(dense_ex), 4, dense_ex.shard_batch(host))
    np.testing.assert_allclose(
        sparse_l, dense_l, rtol=DLRM_RTOL,
        err_msg="train/dlrm: row-kernel path left the dense jnp path",
    )
    info("train/dlrm", sparse_ops=",".join(op.name for op in ex._sparse_ops),
         sparse_losses=[round(x, 6) for x in sparse_l],
         dense_losses=[round(x, 6) for x in dense_l])


# -- serving ------------------------------------------------------------------


class ServeRun(NamedTuple):
    srv: Any                      # Server or ScheduledServer
    requests: list
    tokens: Dict[int, List[int]]  # request id -> generated tokens
    stats: dict


def serve_run(phase: str, argv: Sequence[str]) -> ServeRun:
    """One ``apps.serve`` run: every request completes, none fails,
    the engine never degrades."""
    from flexflow_tpu.apps import serve
    from flexflow_tpu.runtime.serving import Server
    from flexflow_tpu.serving import ScheduledServer

    t0 = time.perf_counter()
    with recorded(Server, "run") as plain, \
            recorded(ScheduledServer, "run") as sched:
        rc = serve.main(list(argv))
    wall = time.perf_counter() - t0
    check(rc == 0, f"{phase}: main exited {rc}")
    runs = plain + sched
    check(len(runs) == 1, f"{phase}: expected one server run, saw {len(runs)}")
    srv, (requests,), (results, stats) = runs[0]
    check(stats["requests"] == len(requests)
          and stats["completed"] == len(requests),
          f"{phase}: {stats['completed']}/{len(requests)} completed")
    check(stats["failed"] == 0, f"{phase}: {stats['failed']} failed")
    check(not stats.get("degraded_rungs")
          and not getattr(srv, "degraded_rungs", None),
          f"{phase}: degraded_mode: {stats.get('degraded_rungs')}")
    tokens = {rid: list(r.tokens) for rid, r in results.items()}
    check(all(tokens[r.id] for r in requests), f"{phase}: empty generation")
    n_tok = sum(len(t) for t in tokens.values())
    info(phase, wall_s=f"{wall:.1f}", requests=len(requests), tokens=n_tok,
         decode_supersteps=stats["decode_supersteps"],
         k=stats["decode_steps_per_call"])
    return ServeRun(srv, requests, tokens, stats)


def check_decode_kernel(phase: str, run: ServeRun) -> None:
    """The decode superstep the run dispatched, as compiled, holds the
    flash_decode kernel and moves no cache: the kernel reads and writes
    the caches in the order the chip stores them, so a copy, transpose,
    scatter or fusion of a cache's size (on a mesh, of a device's
    shard) is a relayout that came back (PERF.md §6 PR 32)."""
    sex = run.srv.ex
    params, state = sex.init(sex.config.seed)
    zeros = np.zeros((sex.max_batch,), np.int32)
    # k is what the server dispatched, clamped there already.
    k = int(run.stats["decode_steps_per_call"])
    fn = sex.build_decode_superstep(k)  # fflint: disable=FF006
    caches = sex.init_cache()
    text = fn.lower(params, state, caches, zeros, zeros).compile().as_text()
    check(has_mosaic_call(text) and has_kernel(text, "ff_flash_decode"),
          f"{phase}: no flash_decode kernel in the compiled decode "
          f"superstep (einsum oracle or interpret mode)")
    elements = {math.prod(c.sharding.shard_shape(c.shape))
                for c in jax.tree.leaves(caches)}
    moved = [line for n in sorted(elements)
             for line in table_sized_relayouts(text, n, CACHE_RELAYOUT_OPS)]
    check(not moved,
          f"{phase}: the compiled decode superstep moves a whole cache: "
          f"{moved[:3]}")


def next_logits(sex, params, state, prefix: Sequence[int]) -> np.ndarray:
    """Logits of the token after ``prefix``, through the decode path:
    prefill all but the last token into slot 0, then one decode step
    (tests/test_serving.py's ``_decode_logits_vs_full`` recipe)."""
    n = len(prefix) - 1
    bucket = sex.bucket_for(n)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = prefix[:n]
    rows, _tok, ok, *_routed = sex.build_prefill(bucket)(
        params, state, padded, np.int32(n)
    )
    check(bool(ok), "prefill produced non-finite logits")
    caches = sex.install(sex.init_cache(), rows, 0)
    pos = np.zeros((sex.max_batch,), np.int32)
    tok = np.zeros((sex.max_batch,), np.int32)
    pos[0], tok[0] = n, prefix[n]
    _, _, _, fetched = sex.build_decode_superstep(
        1, return_logits=True
    )(params, state, caches, pos, tok)
    return np.asarray(fetched[2], np.float32)[0, 0]


def compare_tokens(phase: str, got: ServeRun, want: ServeRun,
                   tol: Optional[float] = None) -> None:
    """Generated tokens equal the reference run's on the same seed.
    Where one argmax flips, it must be a near-tie and not an error: at
    the first divergence the two engines' logits, recomputed at
    ``highest`` matmul precision (the chip's default f32 matmul rounds
    operands to bf16, which the CPU tolerance never saw), agree within
    ``tol`` (default ``DECODE_TOL``)."""
    tol = DECODE_TOL if tol is None else tol
    flips = []
    engines = None  # [(executor, params, state)] x2, built at the first flip
    for r in got.requests:
        a, b = got.tokens[r.id], want.tokens[r.id]
        if a == b:
            continue
        j = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                 min(len(a), len(b)))
        prefix = [int(t) for t in r.prompt] + a[:j]
        if engines is None:
            engines = [(run.srv.ex, *run.srv.ex.init(run.srv.ex.config.seed))
                       for run in (got, want)]
        with jax.default_matmul_precision("highest"):
            la, lb = (next_logits(*e, prefix) for e in engines)
        err = float(np.max(np.abs(la - lb)))
        top = np.sort(la)[-2:]
        flips.append((r.id, j, err, float(top[1] - top[0])))
        check(err <= tol,
              f"{phase}: request {r.id} diverges at token {j} and the "
              f"logits there differ by {err} > {tol}")
    info(phase, token_parity="exact" if not flips else
         "near-tie flips (id, at, |dlogits|, top-2 gap): "
         + str([(i, j, f"{e:.2e}", f"{g:.2e}") for i, j, e, g in flips]))


def serve_phase(argv: Sequence[str]) -> None:
    """Padded KV, greedy: the plain loop and the scheduled loop decode
    through the kernel and agree with the einsum oracle; one paged run
    completes."""
    plain = serve_run("serve/plain", argv)
    check_decode_kernel("serve/plain", plain)
    sched = serve_run("serve/sched", [*argv, "--sched", "slo"])
    check_decode_kernel("serve/sched", sched)
    oracle = serve_run("serve/oracle", [*argv, "--no-decode-kernel"])
    compare_tokens("serve/plain", plain, oracle)
    compare_tokens("serve/sched", sched, oracle)
    serve_run("serve/paged", [*argv, "--kv-block", "16"])


def check_program_kernels(phase: str, run: ServeRun, caches,
                          decode: Sequence[str],
                          prefill: Sequence[str]) -> str:
    """The decode superstep the run dispatched and its largest bucket's
    prefill, as compiled, hold the kernels named; returns the
    superstep's text."""
    sex = run.srv.ex
    params, state = sex.init(sex.config.seed)
    zeros = np.zeros((sex.max_batch,), np.int32)
    k = int(run.stats["decode_steps_per_call"])
    step = sex.build_decode_superstep(k).lower(  # fflint: disable=FF006
        params, state, caches, zeros, zeros).compile().as_text()
    bucket = sex.buckets[-1]
    first = sex.build_prefill(bucket).lower(  # fflint: disable=FF006
        params, state, np.zeros((1, bucket), np.int32), np.int32(bucket)
    ).compile().as_text()
    for text, kernels, what in ((step, decode, "decode superstep"),
                                (first, prefill, "prefill")):
        check(has_mosaic_call(text) and all(
            has_kernel(text, name) for name in kernels),
            f"{phase}: the compiled {what} lacks one of {kernels}")
    return step


def latent_phase(argv: Sequence[str], phase: str = "serve/latent",
                 streams: int = 0) -> None:
    """A DeepSeek-V3 family preset through ``apps.serve``: the expert op
    is in the served graph, the cache is one column of ``kv_rank + rope``
    values a token a layer, prefill compiles the expanded path and
    decode the absorbed one, each with its kernel.  With ``streams``
    (the Xing4.0 preset) the residual between the blocks is that many
    hyper-connected streams, which keep nothing for a slot, and every
    superstep reports the Sinkhorn rounds' defect."""
    from flexflow_tpu.ops import HyperConnectionPost
    from flexflow_tpu.ops.attention import LatentAttention

    run = serve_run(phase, argv)
    sex = run.srv.ex
    names = [op.name for op in sex._layers]
    check(any(n.endswith("_moe") for n in names),
          f"{phase}: the served graph dropped the expert op")
    attn = sex.attn_ops[0]
    check(isinstance(attn, LatentAttention), f"{phase}: no latent op")
    posts = [op for op in sex._layers if isinstance(op, HyperConnectionPost)]
    check(len(posts) == (2 * len(sex.attn_ops) if streams else 0)
          and all(op.attrs["streams"] == streams for op in posts)
          and bool(attn.attrs["q_rank"]) == bool(streams),
          f"{phase}: {len(posts)} hyper-connections round "
          f"{len(sex.attn_ops)} blocks, q_rank {attn.attrs['q_rank']}")
    caches = sex.init_cache()
    want = (sex.max_batch, attn.row_width, sex.max_seq)
    shapes = {tuple(c.shape) for ents in caches.values() for c in ents.values()}
    check(shapes == {want} and all(list(e) == ["ckr"] for e in caches.values())
          and len(caches) == len(sex.attn_ops),
          f"{phase}: cache {shapes}, expected one 'ckr' of {want} a layer")
    check(sex._attention_paths(False) == "latent_expanded"
          and sex._attention_paths(True) == "latent_absorbed",
          f"{phase}: prefill is not expanded or decode not absorbed")
    check_program_kernels(
        phase, run, caches,
        decode=("ff_mla_decode", "ff_grouped_matmul"),
        prefill=("ff_flash_fwd_uneven", "ff_grouped_matmul"))
    oracle = serve_run(f"{phase}-oracle", [*argv, "--no-decode-kernel"])
    compare_tokens(phase, run, oracle,
                   tol=BF16_KERNEL_TOL if streams else None)


#: How far a bfloat16 kernel path's logits may lie from the ``jnp``
#: path's at a flipped token: the attention kernels hand the value
#: product their probabilities rounded to bfloat16 (2^-9 of each), which
#: no matmul precision undoes; read on the chip at the smoke preset's
#: logits of magnitude ~2: 0.0195 (PR 33).  A wrong kernel is off by 1.
BF16_KERNEL_TOL = 0.06


def cache_or_state_relayouts(compiled_text: str, caches) -> List[str]:
    """``table_sized_relayouts`` over every KV cache and recurrent state
    of ``caches`` (the 4-d entries), by element count AND dtype: a
    float32 state of (slots, heads, 128, 128) has as many elements as
    some bf16 weight of the same model."""
    tag = {"float32": "f32", "bfloat16": "bf16"}
    return [line for c in jax.tree.leaves(caches) if c.ndim == 4
            for line in table_sized_relayouts(
                compiled_text, math.prod(c.shape), CACHE_RELAYOUT_OPS)
            if re.search(rf" = \(?{tag[c.dtype.name]}\[", line)]


def solar_phase(argv: Sequence[str]) -> None:
    """The Solar-Open2 family's preset through ``apps.serve``: two kinds
    of slot state in one executor (a grouped-query KV cache held
    positions-last, and a recurrent state with its convolution window),
    the expert op in the served graph, every layer kind through its
    kernels, no cache- or state-sized relayout in the superstep, and the
    tokens of the plain ``jnp`` paths."""
    from flexflow_tpu.ops.attention import MultiHeadAttention
    from flexflow_tpu.ops.delta_attention import KimiDeltaAttention

    run = serve_run("serve/solar", argv)
    sex = run.srv.ex
    kinds = {type(op) for op in sex.attn_ops}
    check(kinds == {MultiHeadAttention, KimiDeltaAttention}
          and any(op.name.endswith("_moe") for op in sex._layers),
          f"serve/solar: the served graph holds {sorted(map(str, kinds))}")
    caches = sex.init_cache()
    gqa = next(op for op in sex.attn_ops if isinstance(op, MultiHeadAttention))
    a = gqa.attrs
    # A head of whole lane tiles (the smoke preset's, the model's) is
    # cached positions-last; the unit-test preset's narrow head is not.
    want = (sex.max_batch, a["num_kv_heads"], a["head_dim"], sex.max_seq) \
        if a["head_dim"] % 128 == 0 else \
        (sex.max_batch, sex.max_seq, a["num_kv_heads"], a["head_dim"])
    check(caches[gqa.name]["k"].shape == want,
          f"serve/solar: KV cache {caches[gqa.name]['k'].shape}, expected "
          f"{want} over the key/value heads")
    check(sex._attention_paths(False) == "delta_chunked+gqa_dense"
          and sex._attention_paths(True) == "delta_recurrent+gqa_decode",
          "serve/solar: the programs announce other paths")
    decode = check_program_kernels(
        "serve/solar", run, caches,
        decode=("ff_flash_decode", "ff_kda_decode", "ff_grouped_matmul"),
        prefill=("ff_flash_fwd_uneven", "ff_kda_chunk", "ff_grouped_matmul"))
    moved = cache_or_state_relayouts(decode, caches)
    check(not moved, f"serve/solar: the compiled decode superstep moves a "
                     f"whole cache or state: {moved[:3]}")
    oracle = serve_run("serve/solar-oracle", [*argv, "--no-decode-kernel"])
    compare_tokens("serve/solar", run, oracle, tol=BF16_KERNEL_TOL)


def lfm2_phase(argv: Sequence[str]) -> None:
    """The LFM2-MoE family's preset through ``apps.serve``: a convolution
    window beside a grouped-query KV cache of heads of 64 (positions-
    major, the order the chip stores as ``flash_decode`` reads it), a
    head that is the token table, the attention layers through both
    kernels at that head width, no cache-sized relayout in the
    superstep, and the tokens of the plain ``jnp`` paths."""
    from flexflow_tpu.ops import GatedShortConv
    from flexflow_tpu.ops.attention import MultiHeadAttention

    run = serve_run("serve/lfm2", argv)
    sex = run.srv.ex
    kinds = {type(op) for op in sex.attn_ops}
    head = sex.model.find_op("lm_head")
    check(kinds == {MultiHeadAttention, GatedShortConv}
          and any(op.name.endswith("_moe") for op in sex._layers)
          and head.tied == {"kernel": ("embed", "table")}
          and not head.param_specs(),
          f"serve/lfm2: the served graph holds {sorted(map(str, kinds))}, "
          f"head tied {head.tied}")
    caches = sex.init_cache()
    gqa = next(op for op in sex.attn_ops if isinstance(op, MultiHeadAttention))
    conv = next(op for op in sex.attn_ops if isinstance(op, GatedShortConv))
    a, d = gqa.attrs, conv.inputs[0].shape[-1]
    want = (sex.max_batch, sex.max_seq, a["num_kv_heads"], a["head_dim"])
    got = (caches[gqa.name]["k"].shape, caches[conv.name]["conv"].shape)
    check(got == (want, (sex.max_batch, conv.attrs["kernel_size"] - 1, d)),
          f"serve/lfm2: caches {got}, expected K of {want} and a window")
    check(sex._attention_paths(False) == "gqa_dense+short_conv"
          and sex._attention_paths(True) == "gqa_decode+short_conv",
          "serve/lfm2: the programs announce other paths")
    decode = check_program_kernels(
        "serve/lfm2", run, caches,
        decode=("ff_flash_decode", "ff_grouped_matmul"),
        prefill=("ff_flash_fwd_uneven", "ff_grouped_matmul"))
    moved = cache_or_state_relayouts(decode, caches)
    check(not moved, f"serve/lfm2: the compiled decode superstep moves a "
                     f"whole cache: {moved[:3]}")
    oracle = serve_run("serve/lfm2-oracle", [*argv, "--no-decode-kernel"])
    compare_tokens("serve/lfm2", run, oracle, tol=BF16_KERNEL_TOL)


def cache_shaped_relayouts(compiled_text: str, caches) -> List[str]:
    """``table_sized_relayouts`` over caches of any rank, by the cache's
    own shape (a weight of this preset has as many elements as a cache):
    a line that names the shape and is no plain bitcast."""
    return sorted({
        line for c in jax.tree.leaves(caches)
        for line in table_sized_relayouts(
            compiled_text, math.prod(c.shape), CACHE_RELAYOUT_OPS)
        if "[" + ",".join(map(str, c.shape)) + "]" in line
        and "calls=%bitcast_fusion" not in line})


@contextlib.contextmanager
def plain_masked_chunks():
    """Inside the block a selected prefill's masked chunks take the
    plain path (``ops/attention.py::_attend_kept_heads``): the gate of
    ``ff_attend_kept`` refuses every shape.  The oracle of the kernel's
    token parity, steered here and not by an option of the program; the
    programs traced inside the block keep the path they were traced
    with."""
    from flexflow_tpu.ops import pallas_kernels

    gate = pallas_kernels.attend_kept_supported
    pallas_kernels.attend_kept_supported = lambda *a, **k: False
    try:
        yield
    finally:
        pallas_kernels.attend_kept_supported = gate


def check_kept_kernel(phase: str, run: ServeRun) -> None:
    """The largest bucket's prefill sends its masked chunks through
    ``ff_attend_kept`` (what its ``serving_program`` event announces,
    asked of the ops by shape), visiting no more key blocks than the
    runs' widths hold."""
    sex = run.srv.ex
    kept = sex.kept_blocks(sex.buckets[-1])
    check(kept.get("kept_kernel") is True
          and 0 < kept["kept_key_blocks"] <= kept["kept_key_blocks_square"],
          f"{phase}: the prefill of {sex.buckets[-1]} rows reports {kept}")


def keye_phase(argv: Sequence[str]) -> None:
    """The Keye-VL-2.0 preset through ``apps.serve``: grouped-query
    attention ops that compose a token selector, three cache entries a
    layer held positions-major (a position's heads one row), the expert
    op under its softmax router, the kernels the two programs still hold
    (the streamed forward over a prefill's leading ``topk`` rows, the
    grouped product), a decode superstep that moves no cache, and a
    superstep's event counting ``topk`` fetched rows a slot a step."""
    from flexflow_tpu.ops.attention import MultiHeadAttention

    run = serve_run("serve/keye", argv)
    sex = run.srv.ex
    check(all(isinstance(op, MultiHeadAttention) and op.select is not None
              for op in sex.attn_ops)
          and any(op.name.endswith("_moe") and op.attrs["router"] == "softmax"
                  for op in sex._layers),
          "serve/keye: the served graph lacks the selector or the router")
    caches = sex.init_cache()
    op = sex.attn_ops[0]
    row = op.attrs["num_kv_heads"] * op.attrs["head_dim"]
    want = {"k": (sex.max_batch, sex.max_seq, row),
            "v": (sex.max_batch, sex.max_seq, row),
            "idx": (sex.max_batch, sex.max_seq, op.select.head_dim)}
    got = {e: tuple(c.shape) for e, c in caches[op.name].items()}
    check(got == want, f"serve/keye: caches {got}, expected {want}")
    check(sex._attention_paths(False) == "gqa_select_dense"
          and sex._attention_paths(True) == "gqa_select_decode",
          "serve/keye: the programs announce other paths")
    check_kept_kernel("serve/keye", run)
    decode = check_program_kernels(
        "serve/keye", run, caches, decode=("ff_grouped_matmul",),
        prefill=("ff_flash_fwd_uneven", "ff_attend_kept",
                 "ff_grouped_matmul"))
    moved = cache_shaped_relayouts(decode, caches)
    check(not moved, f"serve/keye: the compiled decode superstep moves a "
                     f"whole cache: {moved[:3]}")
    k = int(run.stats["decode_steps_per_call"])
    rows = sex.kv_rows(np.full((sex.max_batch,), 400, np.int32), k)
    check(rows["kv_rows_fetched"] == sex.max_batch * k * op.select.topk
          and rows["idx_rows_fetched"] == rows["kv_rows_cache"],
          f"serve/keye: a superstep at 400 live positions reports {rows}")
    check(all(len(r.prompt) > op.select.topk for r in run.requests),
          "serve/keye: a prompt under topk: nothing was selected")
    with plain_masked_chunks():
        oracle = serve_run("serve/keye-oracle", argv)
    compare_tokens("serve/keye", run, oracle, tol=BF16_KERNEL_TOL)


def axk2_phase(argv: Sequence[str]) -> None:
    """The A.X-K2 preset through ``apps.serve``: latent attention ops that
    compose the token selector (its query from the compressed query), two
    cache entries a layer held positions-major (a position's latent row
    filled up to whole lane tiles, the selector's key), gated norms, a
    router over expert groups, the grouped product in both programs, a
    decode superstep that moves no cache, and a superstep's event
    counting ``index_topk`` fetched rows a slot a step."""
    from flexflow_tpu.ops.attention import LatentAttention
    from flexflow_tpu.ops.norm import RMSNorm

    run = serve_run("serve/axk2", argv)
    sex = run.srv.ex
    check(all(isinstance(op, LatentAttention) and op.select is not None
              and op.select.query_dim == op.attrs["q_rank"]
              and op.attrs["gate"] == "per_head" for op in sex.attn_ops)
          and any(op.name.endswith("_moe") and op.attrs["n_group"] > 1
                  for op in sex._layers)
          and all(op.attrs["gate_rank"] for op in sex._layers
                  if isinstance(op, RMSNorm)),
          "serve/axk2: the served graph lacks the selector, the gate, the "
          "groups or a gated norm")
    caches = sex.init_cache()
    op = sex.attn_ops[0]
    want = {"ckr": (sex.max_batch, sex.max_seq, op.row_width + op.row_pad),
            "idx": (sex.max_batch, sex.max_seq, op.select.head_dim)}
    got = {e: tuple(c.shape) for e, c in caches[op.name].items()}
    check(got == want and want["ckr"][2] % 128 == 0,
          f"serve/axk2: caches {got}, expected {want}")
    check(sex._attention_paths(False) == "latent_select_expanded"
          and sex._attention_paths(True) == "latent_select_absorbed",
          "serve/axk2: the programs announce other paths")
    check_kept_kernel("serve/axk2", run)
    decode = check_program_kernels(
        "serve/axk2", run, caches, decode=("ff_grouped_matmul",),
        prefill=("ff_flash_fwd_uneven", "ff_attend_kept",
                 "ff_grouped_matmul"))
    moved = cache_shaped_relayouts(decode, caches)
    check(not moved, f"serve/axk2: the compiled decode superstep moves a "
                     f"whole cache: {moved[:3]}")
    k = int(run.stats["decode_steps_per_call"])
    rows = sex.kv_rows(np.full((sex.max_batch,), 700, np.int32), k)
    check(rows["kv_rows_fetched"] == sex.max_batch * k * op.select.topk
          and rows["idx_rows_fetched"] == rows["kv_rows_cache"],
          f"serve/axk2: a superstep at 700 live positions reports {rows}")
    check(all(len(r.prompt) > op.select.topk for r in run.requests),
          "serve/axk2: a prompt under index_topk: nothing was selected")
    with plain_masked_chunks():
        oracle = serve_run("serve/axk2-oracle", [*argv, "--no-decode-kernel"])
    compare_tokens("serve/axk2", run, oracle, tol=BF16_KERNEL_TOL)


def laguna_phase(argv: Sequence[str]) -> None:
    """The Laguna preset through ``apps.serve``: window and full
    grouped-query layers with different head counts in one served graph,
    a ring of ``window`` positions beside a full cache, the kernels of
    both (the banded and the causal forward, one decode kernel over a
    ring and over a full cache), a decode superstep that moves no cache,
    a superstep's event counting two full layers and three rings, and
    the tokens of the plain ``jnp`` paths."""
    from flexflow_tpu.ops.attention import MultiHeadAttention

    run = serve_run("serve/laguna", argv)
    sex = run.srv.ex
    ops = sex.attn_ops
    windows = [op.attrs["window"] for op in ops]
    heads = [op.attrs["num_heads"] for op in ops]
    w = next(x for x in windows if x)
    check(all(isinstance(op, MultiHeadAttention)
              and op.attrs["gate"] == "per_head" for op in ops)
          and windows == [None, w, w, w, None]
          and heads[1] == heads[2] == heads[3] != heads[0] == heads[4]
          and any(op.name.endswith("_moe") and op.attrs["router"] == "sigmoid"
                  for op in sex._layers),
          f"serve/laguna: the served graph has windows {windows}, query "
          f"heads {heads}")
    caches = sex.init_cache()
    a = ops[0].attrs
    last = a["head_dim"] % 128 == 0

    def shape(positions):
        if last:
            return (sex.max_batch, a["num_kv_heads"], a["head_dim"], positions)
        return (sex.max_batch, positions, a["num_kv_heads"], a["head_dim"])

    got = [tuple(caches[op.name]["k"].shape) for op in ops]
    want = [shape(x or sex.max_seq) for x in windows]
    check(got == want, f"serve/laguna: caches {got}, expected {want}")
    check(sex._attention_paths(False) == "gqa_dense+gqa_window_dense"
          and sex._attention_paths(True) == "gqa_decode+gqa_window_decode",
          "serve/laguna: the programs announce other paths")
    decode = check_program_kernels(
        "serve/laguna", run, caches,
        decode=("ff_flash_decode", "ff_grouped_matmul"),
        prefill=("ff_flash_fwd_window", "ff_flash_fwd_uneven",
                 "ff_grouped_matmul"))
    moved = cache_shaped_relayouts(decode, caches)
    check(not moved, f"serve/laguna: the compiled decode superstep moves a "
                     f"whole cache: {moved[:3]}")
    k = int(run.stats["decode_steps_per_call"])
    at = min(2000, sex.max_seq - k - 1)
    rows = sex.kv_rows(np.full((sex.max_batch,), at, np.int32), k)
    blocks = [op.decode_fetch_block(sex.max_batch, sex.max_seq,
                                    sex.decode_kernel) for op in ops]
    live = at + 1 + np.arange(k)
    each = [int((-(-(np.minimum(live, x) if x else live) // b) * b).sum())
            for x, b in zip(windows, blocks)]
    check(rows["kv_rows_fetched"] == round(sex.max_batch * sum(each) / 5)
          and each[1] == k * (-(-w // blocks[1]) * blocks[1]) < each[0]
          and "state_bytes" not in rows,
          f"serve/laguna: a superstep at {at} live positions reports {rows}, "
          f"a slot's layers {each}")
    check(all(len(r.prompt) > 2 * w for r in run.requests),
          "serve/laguna: a prompt under two windows: no ring wrapped twice")
    oracle = serve_run("serve/laguna-oracle", [*argv, "--no-decode-kernel"])
    compare_tokens("serve/laguna", run, oracle, tol=BF16_KERNEL_TOL)


# -- four chips ---------------------------------------------------------------


def holders(tree) -> set:
    """Devices that hold a shard of any array in ``tree``."""
    return {d for x in jax.tree.leaves(tree) if hasattr(x, "devices")
            for d in x.devices()}


def start_from(ex, ref_params):
    """(params, opt_state, state) for ``ex`` holding the one-device
    run's initial values.  The full-mesh executor's own init already
    does (the RNG is sharding-invariant: one seed draws one set of
    values on any mesh); a layer-wise executor draws per stage, so
    there the reference values are split by op name onto the stages —
    tests/test_pipeline.py's recipe."""
    from flexflow_tpu.runtime.pipeline import PipelineExecutor

    params, opt_state, state = ex.init()
    if isinstance(ex, PipelineExecutor):
        for si, st in enumerate(ex.stages):
            params[si] = {
                op.name: jax.device_put(
                    ref_params[op.name],
                    {k: ex.stage_ex[si].param_sharding(op, spec)
                     for k, spec in op.param_specs().items()},
                )
                for op in st.ops if op.param_specs()
            }
            opt_state[si] = ex.optimizer.init(params[si])
    return params, opt_state, state


def four_chip_train(phase: str, app, argv: Sequence[str],
                    cross: Sequence[str]) -> None:
    """``argv + cross`` over the whole mesh against ``argv`` on one
    device of the same host, same seed and initial values: loss
    trajectories within the strategy-equivalence tolerance, and every
    device holds live buffers of the cross-chip run."""
    one, single, _ = train_phase(f"{phase}/one-device", app,
                                 [*argv, "-ll:tpu", "1"])
    mesh, _, final = train_phase(f"{phase}/mesh", app, [*argv, *cross])
    missing = set(jax.devices()) - holders(final)
    check(not missing, f"{phase}: no live buffer on {sorted(map(str, missing))}")
    ref = jax.device_get(one.ex.init()[0])
    multi, _, _ = replay(mesh, len(single), start=start_from(mesh.ex, ref))
    np.testing.assert_allclose(
        multi, single, rtol=SHARD_RTOL, atol=SHARD_ATOL,
        err_msg=f"{phase}: mesh and one-device loss trajectories differ",
    )
    info(phase, mesh_losses=[round(x, 6) for x in multi],
         one_device_losses=[round(x, 6) for x in single])


def dlrm4_phase(argv: Sequence[str]) -> None:
    """The stacked DLRM with its tables over the mesh (the app's own
    ``dlrm_strategy``: ``embeddings`` at c = 4, a table a chip) against
    one device: inside the row-sharded ``shard_map`` each chip reaches
    its own table's rows with the row kernels, in place, and on a batch
    whose ids span the tables the two loss trajectories agree."""
    from flexflow_tpu.apps import dlrm
    from flexflow_tpu.models.dlrm import DLRMConfig

    one, _, _ = train_phase("train/dlrm4/one-device", dlrm,
                            [*argv, "-ll:tpu", "1"], kernels=True)
    mesh, _, final = train_phase("train/dlrm4/mesh", dlrm, argv, kernels=True)
    ex = mesh.ex
    check([op.name for op in ex._sparse_ops] == ["embeddings"]
          and ex._pc(ex._sparse_ops[0]).c == len(jax.devices()),
          "train/dlrm4: the tables are not row-sparse at c = the mesh")
    missing = set(jax.devices()) - holders(final)
    check(not missing,
          f"train/dlrm4: no live buffer on {sorted(map(str, missing))}")
    host = spanning_batch(ex, DLRMConfig.parse_args(list(argv)))
    multi, _, _ = replay(mesh, 4, ex.shard_batch(host))
    single, _, _ = replay(one, 4, one.ex.shard_batch(host))
    np.testing.assert_allclose(
        multi, single, rtol=SHARD_RTOL, atol=SHARD_ATOL,
        err_msg="train/dlrm4: mesh and one-device loss trajectories differ",
    )
    info("train/dlrm4", mesh_losses=[round(x, 6) for x in multi],
         one_device_losses=[round(x, 6) for x in single])


def four_chip_serve(argv: Sequence[str], shard: Sequence[str]) -> None:
    """Sharded decode (batch on n, KV heads on c; flash_decode under
    shard_map) against the one-device engine."""
    mesh = serve_run("serve/shard", [*argv, *shard])
    sex = mesh.srv.ex
    check(sex.shard is not None, "serve/shard: fell back to a single mesh")
    check_decode_kernel("serve/shard", mesh)
    missing = set(jax.devices()) - holders(sex.init_cache())
    check(not missing,
          f"serve/shard: no KV shard on {sorted(map(str, missing))}")
    compare_tokens("serve/shard", mesh, serve_run("serve/one-device", argv))


# -- driver -------------------------------------------------------------------

Phase = Tuple[str, Callable[[], None]]


def one_chip_phases(sz: Sizes) -> List[Phase]:
    from flexflow_tpu.apps import alexnet, transformer

    return [
        ("native", build_native),
        ("train/alexnet",
         lambda: train_phase("train/alexnet", alexnet, sz.alexnet)),
        # The path that reaches flash_attention fwd/bwd and softmax_xent.
        ("train/transformer",
         lambda: train_phase("train/transformer", transformer,
                             sz.transformer, kernels=True)),
        ("train/dlrm", lambda: dlrm_phase(sz.dlrm)),
        ("serve", lambda: serve_phase(sz.serve)),
        ("serve/latent", lambda: latent_phase(sz.serve_latent)),
        ("serve/solar", lambda: solar_phase(sz.serve_solar)),
        ("serve/xing", lambda: latent_phase(sz.serve_xing, "serve/xing",
                                            streams=4)),
        ("serve/keye", lambda: keye_phase(sz.serve_keye)),
        ("serve/laguna", lambda: laguna_phase(sz.serve_laguna)),
        ("serve/axk2", lambda: axk2_phase(sz.serve_axk2)),
        ("serve/lfm2", lambda: lfm2_phase(sz.serve_lfm2)),
    ]


def four_chip_phases(sz: Sizes) -> List[Phase]:
    from flexflow_tpu.apps import alexnet, transformer

    return [
        ("native", build_native),
        ("train/dlrm4", lambda: dlrm4_phase(sz.dlrm4)),
        ("train/alexnet4",
         lambda: four_chip_train("train/alexnet4", alexnet, sz.alexnet4,
                                 sz.alexnet4_strategy)),
        ("train/transformer4",
         lambda: four_chip_train("train/transformer4", transformer,
                                 sz.transformer4, sz.transformer4_mesh)),
        ("serve4", lambda: four_chip_serve(sz.serve, sz.serve4_shard)),
    ]


def run_phases(phases: Sequence[Phase]) -> List[str]:
    """Run every phase; returns the names of those that failed."""
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            print(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s",
                  flush=True)
        else:
            print(f"[{name}] ok in {time.perf_counter() - t0:.1f}s",
                  flush=True)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the cross-chip phases and their "
                         "one-device comparison")
    args = ap.parse_args(argv)
    device = require_tpu()
    check(device["count"] >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, jax has "
          f"{device['count']}")

    from flexflow_tpu.apps.common import enable_compile_cache

    cache = enable_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    info("device", **device, compile_cache=cache, cache_entries=warm)
    phases = (four_chip_phases if args.chips == 4 else one_chip_phases)(FULL)
    failed = run_phases(phases)
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
