"""Inference serving stack acceptance (runtime/serving.py; SERVING.md).

Pins the subsystem's correctness contracts:

- **KV-cache numerics parity**: decode-with-cache logits match the
  full-sequence training forward at the same prefix (the tolerance
  pinned here is the acceptance bar), with the Pallas ``flash_decode``
  kernel additionally pinned against the pure-jnp ``_einsum_decode``
  oracle — directly and end-to-end through the executor.
- **Greedy-decode determinism across batch compositions**: a request's
  generated sequence is independent of its slot neighbors (slots are
  independent in the batch dim — the fault-isolation invariant the
  chaos scenario also leans on).
- **Eviction/admission slot invariants**: every queued request is
  served exactly once, generation lengths respect budget and context
  limits; on the paged layout, block-table reuse after eviction and
  ledger-gated admission preserve all of the above.
- **Paged / sharded parity matrix**: the paged block-pool layout and
  the sharded multi-chip decode both reproduce the single-mesh padded
  engine's logits (vs the full-seq forward oracle) and its greedy
  sequences under any batch composition.
- **Train->serve handoff**: params restored from a training checkpoint
  through the strategy-portable CheckpointManager drive serving.
- **Speculative decoding parity matrix**: greedy spec decode is
  byte-identical to plain fused decode for every draft depth, draft
  source (full/truncated self-draft, independent params) and cache
  layout; sampled verification replays the keyed draws; crash
  recovery resumes over the accepted prefix (SERVING.md
  "Speculative decoding").

Heavy end-to-end cases are ``@pytest.mark.slow`` (tier-1 keeps the
fast numerics/protocol cases; CLAUDE.md "Tests").
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.ops import pallas_kernels
from flexflow_tpu.ops.attention import _einsum_decode
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.serving import (
    Request,
    Server,
    ServingExecutor,
    ServingFaultInjector,
)

V, D, H, L, S = 64, 32, 2, 2, 16

#: Decode-vs-full-forward logits tolerance (f32): the cached decode
#: path reorders the softmax reduction over masked cache lanes; on the
#: CPU mesh it lands bit-identical, but the pinned bar is a tolerance,
#: not bit-equality (the Pallas kernel's block order differs).
DECODE_TOL = 1e-4


@pytest.fixture(scope="module")
def lm():
    return build_transformer_lm(
        batch_size=2, seq_len=S, vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, config=FFConfig(batch_size=2),
    )


@pytest.fixture(scope="module")
def sex(lm):
    """Oracle-decode executor (pure-jnp `_einsum_decode`)."""
    return ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8, S),
                           decode_kernel=False)


@pytest.fixture(scope="module")
def weights(sex):
    return sex.init(seed=0)


@pytest.fixture(scope="module")
def full_forward(lm):
    """Full-sequence logits from the TRAINING executor's eval path —
    the reference the cached decode must reproduce."""
    ex = Executor(lm, config=lm.config)
    params, _opt, state = ex.init(seed=0)
    toks = np.random.default_rng(0).integers(0, V, size=(1, S)).astype(
        np.int32
    )
    _, outs = ex.forward_step(
        params, state, {"tokens": toks, "label": np.zeros((1, S), np.int32)}
    )
    return toks, np.asarray(outs["lm_head:out"])


def _decode_logits_vs_full(sex, weights, full_forward, prefix: int):
    """Prefill ``prefix`` tokens, then single-step decode feeding the
    TRUE next tokens; returns max |decode logits - full-seq logits|
    over the decoded positions."""
    params, state = weights
    toks, full_logits = full_forward
    padded = np.zeros((1, 8), np.int32)
    padded[0, :prefix] = toks[0, :prefix]
    rows, tok0, ok = sex.build_prefill(8)(params, state, padded,
                                          np.int32(prefix))
    assert bool(ok)
    # Prefill's first greedy token == the full forward's argmax there.
    assert int(tok0) == int(np.argmax(full_logits[0, prefix - 1]))
    caches = sex.install(sex.init_cache(), rows, 0)
    dec = sex.build_decode_superstep(1, return_logits=True)
    pos = np.array([prefix, 0], np.int32)
    errs = []
    for t in range(prefix, S):
        tokv = np.array([toks[0, t], 0], np.int32)
        caches, pos_d, _t, (_nxt, okf, logits) = dec(
            params, state, caches, pos, tokv
        )
        assert bool(np.asarray(okf)[0, 0])
        errs.append(
            float(np.max(np.abs(np.asarray(logits)[0, 0]
                                - full_logits[0, t])))
        )
        pos = np.asarray(pos_d)
    return max(errs)


def test_decode_cache_matches_full_forward(sex, weights, full_forward):
    """The acceptance bar: cached decode ≡ full-sequence forward on
    the same prefix, every decoded position, within DECODE_TOL."""
    err = _decode_logits_vs_full(sex, weights, full_forward, prefix=6)
    assert err <= DECODE_TOL, f"decode/full-forward drift {err}"


def _serve(executor, weights, requests, **kw):
    params, state = weights
    srv = Server(executor, params, state, **kw)
    results, stats = srv.run(requests)
    return results, stats


def _req(rid, prompt, max_new=5):
    return Request(id=rid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=max_new)


# The decode kernel reads the cache positions-along-lanes, a slot's
# live chunks of whole 128-position lane tiles through one ring of DMAs
# shared by all slots, and writes the step's own K/V column
# (ops/pallas_kernels.flash_decode).  Interpret mode = the chip's code
# path.  One compile a (shape, dtype): lengths are data.

KS, KH, KHD = 1024, 2, 16
#: The granule of the plain body at that shape, and of the grouped body
#: (4 query heads a cached head of 128) at the same cache length.
KC = pallas_kernels.flash_decode_chunk(KS, KH, KHD, jnp.float32)
GHD, GROUP = 128, 4
GC = pallas_kernels.flash_decode_chunk(KS, KH, GHD, jnp.float32, GROUP)


@pytest.fixture(scope="module")
def decode_case():
    """``run(lengths, dtype, grouped, positions_last) -> (kernel
    out/caches, oracle out/caches, inputs)`` on seeded caches of
    ``len(lengths)`` slots: the oracle writes the new column with one
    ``dynamic_update_slice`` a slot and runs ``_einsum_decode``.
    ``grouped`` takes the matrix-unit body (GROUP query heads a cached
    head of GHD); ``positions_last`` hands the caches over as
    (B, h, hd, S), the order an op with wide heads declares."""
    fn = jax.jit(pallas_kernels.flash_decode,
                 static_argnames=("positions_last",))

    def run(lengths, dtype=jnp.float32, grouped=False, positions_last=False):
        r = np.random.default_rng(1)
        B = len(lengths)
        hd, group = (GHD, GROUP) if grouped else (KHD, 1)
        q = jnp.asarray(r.standard_normal((B, KH * group, hd)), dtype)
        kn, vn = (jnp.asarray(r.standard_normal((B, KH, hd)), dtype)
                  for _ in range(2))
        ck, cv = (jnp.asarray(r.standard_normal((B, KS, KH, hd)), dtype)
                  for _ in range(2))
        lens = jnp.asarray(lengths, jnp.int32)
        assert pallas_kernels.flash_decode_supported(ck.shape, dtype, group)

        def put(cache, new):
            for b, n in enumerate(lengths):
                cache = jax.lax.dynamic_update_slice(
                    cache, new[b][None, None], (b, n - 1, 0, 0))
            return cache

        ek, ev = put(ck, kn), put(cv, vn)
        want = _einsum_decode(q, ek, ev, lens - 1), ek, ev
        if positions_last:
            out, gk, gv = fn(q, kn, vn, ck.transpose(0, 2, 3, 1),
                             cv.transpose(0, 2, 3, 1), lens,
                             positions_last=True)
            got = out, gk.transpose(0, 3, 1, 2), gv.transpose(0, 3, 1, 2)
        else:
            got = fn(q, kn, vn, ck, cv, lens)
        return got, want, (ck, cv, kn, vn)

    return run


def test_decode_chunk_comes_from_the_shape():
    """Whole lane tiles that divide the cache, by the body that reads
    them (a lane tile at a time on the vector unit, a whole chunk in
    two matrix products under grouped queries) and inside the VMEM the
    two rings may take; anything else takes the einsum oracle."""
    chunk = pallas_kernels.flash_decode_chunk
    assert chunk(1024, 16, 64, jnp.bfloat16) == KC == 128
    assert chunk(128, 2, 16, jnp.float32) == 128
    assert chunk(32768, 8, 128, jnp.bfloat16, 8) == 512
    assert chunk(2048, 2, 128, jnp.float32, 4) == GC == 512
    assert chunk(256, 2, 128, jnp.float32, 4) == 256
    assert chunk(4096, 32, 128, jnp.float32, 2) == 128   # the rings' VMEM
    assert chunk(8192, 128, 512, jnp.float32) == 0       # no chunk fits
    assert chunk(1000, 2, 16, jnp.float32) == 0
    sup = pallas_kernels.flash_decode_supported
    assert not sup((4, 32, 2, 16), jnp.float32)      # not whole lane tiles
    assert not sup((4, 128, 2, 8), jnp.bfloat16)     # hd under a bf16 tile
    assert sup((4, 128, 2, 8), jnp.float32)
    assert not sup((4, 128, 2), jnp.float32)


def _same(got, want, tol=1e-5):
    (out, ck, cv), (ref, ek, ev) = got, want
    assert float(jnp.max(jnp.abs(out - ref))) < tol
    assert bool(jnp.array_equal(ck, ek)) and bool(jnp.array_equal(cv, ev))


# 1; a lane tile's last position, its edge and the first of the next;
# a chunk's last position, its edge and the first of the next, for the
# granule of either body; the last chunk; the whole cache.
@pytest.mark.parametrize(
    "length", [1, 127, 128, 129, 255, 256, 257, 511, 512, 513, 769, 1023,
               1024])
def test_decode_kernel_matches_oracle_at_length(decode_case, length):
    """Attention over ``length`` positions, the step's own among them,
    pinned against the jnp oracle -- with an idle slot (length 1) and a
    full one beside it."""
    assert {KC - 1, KC, KC + 1, GC - 1, GC, GC + 1} <= {
        127, 128, 129, 255, 256, 257, 511, 512, 513}
    _same(*decode_case([length, 1, KS])[:2])


@pytest.mark.parametrize("length", [1, 511, 512, 513, 1024])
@pytest.mark.parametrize("positions_last", [False, True])
def test_grouped_decode_kernel_matches_oracle_at_length(
        decode_case, length, positions_last):
    """The grouped body (two matrix products a chunk) at its chunk's
    edges, in both cache orders."""
    _same(*decode_case([length, 1, KS], grouped=True,
                       positions_last=positions_last)[:2], tol=2e-5)


#: Empty slots (dispatched at length 1) between full and half-full
#: ones: 1, 8, 1, 1, 5, 1, 8, 1, 3, 1, 1 chunks of 128 (1, 2, 1, 1, 2,
#: 1, 2, 1, 1, 1, 1 of 512), so that slots start at every phase of the
#: ring of four, and chunks of later slots are in flight while an
#: earlier one is scored.
_STREAM = [1, 1024, 1, 1, 600, 1, 1024, 1, 300, 1, 1]


@pytest.mark.parametrize("shift", [0, 1, 2])
@pytest.mark.parametrize("grouped,positions_last",
                         [(False, False), (False, True), (True, True)])
def test_decode_stream_crosses_slots_at_every_ring_phase(
        decode_case, shift, grouped, positions_last):
    """One stream of chunk DMAs over all slots: every slot's output and
    written column equal the oracle's whatever ring slot its first
    chunk lands in, for both bodies and both cache orders."""
    lengths = _STREAM[shift:] + _STREAM[:shift]
    _same(*decode_case(lengths, grouped=grouped,
                       positions_last=positions_last)[:2], tol=2e-5)


@pytest.mark.parametrize("grouped", [False, True])
@pytest.mark.parametrize("length", [1, 100, 128])
def test_decode_ring_deeper_than_the_stream(decode_case, grouped, length):
    """One slot of one chunk: the ring is primed with a single pair of
    DMAs and nothing follows it."""
    _same(*decode_case([length], grouped=grouped, positions_last=grouped)[:2],
          tol=2e-5)


def test_decode_columns_span_more_than_one_lane_tile_of_slots():
    """130 slots: the step's columns enter with the slots along the
    lanes, two lane tiles of them, and slot 129's comes from the
    second."""
    r = np.random.default_rng(3)
    B, S = 130, 128
    q, kn, vn = (jnp.asarray(r.standard_normal((B, 1, 8)), jnp.float32)
                 for _ in range(3))
    ck, cv = (jnp.asarray(r.standard_normal((B, S, 1, 8)), jnp.float32)
              for _ in range(2))
    lens = jnp.asarray(r.integers(1, S + 1, size=B), jnp.int32)
    rows = jnp.arange(B)
    ek, ev = ck.at[rows, lens - 1].set(kn), cv.at[rows, lens - 1].set(vn)
    got = jax.jit(pallas_kernels.flash_decode)(q, kn, vn, ck, cv, lens)
    _same(got, (_einsum_decode(q, ek, ev, lens - 1), ek, ev))


@pytest.mark.parametrize("pos", [0, 127, 128, 255, 256, 1023])
@pytest.mark.parametrize("grouped", [False, True])
def test_decode_kernel_writes_the_column_and_nothing_else(
        decode_case, pos, grouped):
    """The new K/V column reads back at ``pos``; its neighbours, the
    rest of the slot and the other slots are untouched, bit for bit."""
    (_, ck, cv), _, (ck0, cv0, kn, vn) = decode_case(
        [pos + 1, 400], grouped=grouped, positions_last=grouped)
    for got, before, new in ((ck, ck0, kn), (cv, cv0, vn)):
        got, before = np.asarray(got), np.array(before)
        np.testing.assert_array_equal(got[0, pos], np.asarray(new)[0])
        np.testing.assert_array_equal(got[1, 399], np.asarray(new)[1])
        before[0, pos], before[1, 399] = got[0, pos], got[1, 399]
        np.testing.assert_array_equal(got, before)


def test_decode_kernel_bf16_cache(decode_case):
    """bf16 caches (a (16, 128) tile): the column is stored in the
    cache's dtype and scores stay f32, so the kernel equals the oracle
    on the rounded cache to bf16's last place."""
    (out, ck, cv), (want, ek, ev), _ = decode_case(
        [300, 1, 1024, 513], jnp.bfloat16)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - want.astype(jnp.float32)))
    assert float(err) <= 2 ** -7
    assert bool(jnp.array_equal(ck, ek)) and bool(jnp.array_equal(cv, ev))


KSEQ = 128  # the smallest cache the kernel's gate takes


@pytest.fixture(scope="module")
def lm128():
    return build_transformer_lm(
        batch_size=2, seq_len=KSEQ, vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, config=FFConfig(batch_size=2),
    )


def _kernel_pair(lm128, **kw):
    """(kernel executor, oracle executor, weights) at ``max_seq`` 128."""
    kex = ServingExecutor(lm128, max_batch=2, max_seq=KSEQ, buckets=(8,),
                          decode_kernel=True, **kw)
    oex = ServingExecutor(lm128, max_batch=2, max_seq=KSEQ, buckets=(8,),
                          decode_kernel=False, **kw)
    return kex, oex, oex.init(seed=0)


def _kernel_reqs():
    return [_req(0, [5, 9, 2], max_new=6), _req(1, [3, 1, 4, 1, 5], max_new=5)]


def test_decode_kernel_end_to_end(lm128):
    """The kernel-decode executor's logits equal the oracle
    executor's through prefill, install and six single decode steps
    (the kernel writes the cache the next step reads), and its served
    tokens are the oracle's."""
    kex, oex, (params, state) = _kernel_pair(lm128)
    assert kex.kv_rows(np.zeros(2, np.int32), 1)["kv_rows_fetched"] == 256
    toks = np.random.default_rng(0).integers(0, V, size=(12,)).astype(np.int32)
    logits = []
    for ex in (kex, oex):
        padded = np.zeros((1, 8), np.int32)
        padded[0, :6] = toks[:6]
        rows, _t, ok = ex.build_prefill(8)(params, state, padded, np.int32(6))
        caches = ex.install(ex.init_cache(), rows, 0)
        dec = ex.build_decode_superstep(1, return_logits=True)
        pos, got = np.array([6, 0], np.int32), []
        for t in range(6, 12):
            caches, pos, _n, (_nxt, _ok, lg) = dec(
                params, state, caches, pos, np.array([toks[t], 0], np.int32))
            got.append(np.asarray(lg)[0, 0])
        logits.append(np.stack(got))
    assert float(np.max(np.abs(logits[0] - logits[1]))) <= DECODE_TOL
    base, _ = _serve(oex, (params, state), _kernel_reqs(), decode_steps=4)
    got, _ = _serve(kex, (params, state), _kernel_reqs(), decode_steps=4)
    assert [got[i].tokens for i in (0, 1)] == [base[i].tokens for i in (0, 1)]


@pytest.mark.parametrize("shard", [(2, 1), (1, 2), (2, 2)])
def test_decode_kernel_under_shard_map(lm128, shard):
    """Batch on 'n', heads on 'c': each device's kernel call reads and
    writes its own shard of the caches, and the served tokens are the
    single-device oracle's."""
    kex, _, _ = _kernel_pair(lm128, shard=shard)
    _, oex, weights = _kernel_pair(lm128)
    assert kex.shard == shard
    w2 = (kex._place(weights[0]), kex._place(weights[1]))
    base, _ = _serve(oex, weights, _kernel_reqs(), decode_steps=4)
    got, _ = _serve(kex, w2, _kernel_reqs(), decode_steps=4)
    for rid in (0, 1):
        assert got[rid].error is None
        assert got[rid].tokens == base[rid].tokens


def test_decode_kernel_in_the_speculative_scan(lm128):
    """The verify scan drives the same kernel step once a draft
    position: columns written past the accepted position are never
    attended, so speculation stays byte-identical to plain decode."""
    kex, oex, weights = _kernel_pair(lm128, draft_layers=1)
    base, _ = _serve(oex, weights, _kernel_reqs(), decode_steps=4)
    sp, stats = _serve(kex, weights, _kernel_reqs(), decode_steps=4,
                       speculate=3)
    assert 0.0 <= stats["spec_acceptance_rate"] <= 1.0
    for rid in (0, 1):
        assert sp[rid].error is None
        assert sp[rid].tokens == base[rid].tokens


def test_unsupported_cache_falls_back_to_the_oracle(lm, weights, caplog):
    """A cache that is not whole lane tiles (``max_seq`` 16) takes the
    einsum oracle even when the kernel is asked for -- loudly, with the
    oracle's tokens."""
    import logging

    kex = ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8, S),
                          decode_kernel=True)
    oex = ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8, S),
                          decode_kernel=False)
    with caplog.at_level(logging.WARNING, logger="ff.attention"):
        got, _ = _serve(kex, weights, _kernel_reqs(), decode_steps=4)
    assert any("flash_decode unsupported" in r.message for r in caplog.records)
    base, _ = _serve(oex, weights, _kernel_reqs(), decode_steps=4)
    assert [got[i].tokens for i in (0, 1)] == [base[i].tokens for i in (0, 1)]
    assert kex.kv_rows(np.array([3, 0]), 2) == {
        "kv_rows_fetched": 4 * S, "kv_rows_cache": 4 * S}


def test_prefill_bucket_invariance(sex, weights):
    """Pad-to-bucket is numerics-neutral: the same prompt served
    through bucket 8 and bucket 16 generates the same tokens."""
    prompt = [5, 9, 2, 41, 17]
    out = {}
    for bucket_only in ((8,), (S,)):
        ex2 = ServingExecutor(sex.model, max_batch=2, max_seq=S,
                              buckets=bucket_only, decode_kernel=False)
        results, _ = _serve(ex2, weights, [_req(0, prompt, max_new=6)],
                            decode_steps=4)
        assert results[0].error is None
        out[bucket_only] = results[0].tokens
    assert out[(8,)] == out[(S,)]


def test_slot_neighbor_independence(sex, weights):
    """Greedy-decode determinism across batch compositions: request
    X's sequence is identical served alone or alongside neighbors."""
    x = _req(7, [3, 1, 4, 1, 5], max_new=6)
    alone, _ = _serve(sex, weights, [x], decode_steps=4)
    neighbors = [
        _req(1, [2, 7, 18], max_new=8),
        _req(7, [3, 1, 4, 1, 5], max_new=6),
        _req(2, [31, 3, 3, 7, 9, 50], max_new=3),
        _req(3, [11, 6], max_new=7),
    ]
    together, _ = _serve(sex, weights, neighbors, decode_steps=4)
    assert together[7].error is None
    assert together[7].tokens == alone[7].tokens


def test_eviction_admission_invariants(sex, weights):
    """More requests than slots: every request is served exactly
    once, budgets and the context limit are honored, and one host
    program covers each K-token decode superstep."""
    reqs = [
        _req(0, [1, 2, 3], max_new=4),
        _req(1, [4, 5], max_new=9),
        _req(2, [6, 7, 8, 9], max_new=2),
        _req(3, [10] * 6, max_new=30),      # context-limited
        _req(4, [11, 12], max_new=3),
    ]
    results, stats = _serve(sex, weights, reqs, decode_steps=4)
    assert sorted(results) == [0, 1, 2, 3, 4]
    assert stats["completed"] == 5 and stats["failed"] == 0
    for r in reqs:
        got = results[r.id]
        assert got.error is None
        # Context capacity: the prefill token (predicted at prompt
        # end) plus one token per remaining cache row.
        cap = S - len(r.prompt) + 1
        assert len(got.tokens) == min(r.max_new_tokens, cap)
    assert stats["programs_per_decode_superstep"] == 1
    assert stats["tokens"] == sum(len(r.tokens) for r in results.values())


def test_serving_fault_isolation(sex, weights):
    """A NaN'd cache row fails exactly its own slot's request at the
    superstep fence; the neighbor's sequence is untouched (the chaos
    matrix runs the full two-fault timeline — runtime/chaos.py)."""
    reqs = [_req(0, [1, 2, 3], max_new=8), _req(1, [4, 5, 6], max_new=8)]
    clean, _ = _serve(sex, weights, reqs, decode_steps=4)
    inj = ServingFaultInjector(nan_cache_at={1: 0})
    faulted, stats = _serve(
        sex, weights,
        [_req(0, [1, 2, 3], max_new=8), _req(1, [4, 5, 6], max_new=8)],
        decode_steps=4, fault_injector=inj,
    )
    assert faulted[0].error is not None
    assert faulted[1].error is None
    assert faulted[1].tokens == clean[1].tokens
    assert stats["failed"] == 1 and stats["completed"] == 1


@pytest.mark.slow  # >= 6 s in the tier-1 timing run (CHANGES.md PR 21)
def test_train_serve_checkpoint_handoff(lm, tmp_path):
    """Params trained + checkpointed by the TRAINING stack restore
    into the serving executor (strategy-portable restore) and produce
    the same logits as serving the live trained params."""
    from flexflow_tpu.runtime.checkpoint import CheckpointManager
    from flexflow_tpu.runtime.trainer import Trainer

    ex = Executor(lm, config=lm.config)
    trainer = Trainer(ex)
    with CheckpointManager(str(tmp_path / "ck")) as ck:
        trainer.fit(iterations=1, warmup=1, checkpoint=ck)
    sex = ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8,),
                          decode_kernel=False)
    step, params, state = sex.restore(str(tmp_path / "ck"))
    assert step == 2  # warmup + 1 iteration, both real updates
    live_params, _opt, live_state = trainer.final[0], None, trainer.final[2]
    req = [_req(0, [1, 2, 3, 4], max_new=5)]
    from_ckpt, _ = _serve(sex, (params, state), req, decode_steps=4)
    from_live, _ = _serve(
        sex, (jax.device_put(live_params, sex.device),
              jax.device_put(live_state, sex.device)),
        req, decode_steps=4,
    )
    assert from_ckpt[0].error is None
    assert from_ckpt[0].tokens == from_live[0].tokens


def test_decode_steps_clamp(sex, weights):
    """decode_steps clamps at the fused-step bound, same as training
    supersteps."""
    params, state = weights
    srv = Server(sex, params, state, decode_steps=64)
    assert srv.decode_steps == 20


@pytest.mark.slow  # full CLI e2e: train -> checkpoint -> serve (~40s)
def test_serve_cli_train_handoff_e2e(tmp_path, capsys):
    """apps/serve.py end to end off a real training run's checkpoint:
    the train->serve handoff through the CLI surface."""
    from flexflow_tpu.apps import serve, transformer

    ck = str(tmp_path / "ck")
    assert transformer.main([
        "-b", "4", "-i", "2", "--seq", "16", "--vocab", "64",
        "--d-model", "32", "--heads", "2", "--layers", "1",
        "--ckpt-dir", ck,
    ]) == 0
    capsys.readouterr()
    assert serve.main([
        "--max-seq", "16", "--max-batch", "2", "--decode-steps", "4",
        "--vocab", "64", "--d-model", "32", "--heads", "2",
        "--layers", "1", "--requests", "3", "--prompt-len", "3:5",
        "--max-new", "4", "--ckpt-dir", ck,
    ]) == 0
    out = capsys.readouterr().out
    assert "restored training checkpoint" in out
    assert "completed = 3 failed = 0" in out
    assert "tokens/s" in out and "request latency p50" in out


@pytest.mark.slow  # closed-loop scale case (~30s): telemetry event
# stream reconstructable
def test_serve_telemetry_stream(lm, weights, tmp_path):
    """--telemetry for serving: request_start/prefill/decode_superstep/
    request_end events land in the JSONL with the programs/step
    counters honestly reading one program per K tokens."""
    import json

    from flexflow_tpu.runtime.telemetry import Telemetry

    from flexflow_tpu.serving import uniform_workload

    sex2 = ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8,),
                           decode_kernel=False)
    # Workload-trace arrivals (the closed-loop arrival_every knob is
    # deprecated); the legacy Server serves them all-at-start, which
    # still exercises eviction/admission at 4 requests over 2 slots.
    reqs = uniform_workload(4, V, prompt_len=(3, 6), max_new_tokens=6,
                            seed=5)
    with Telemetry(str(tmp_path)) as tel:
        _, stats = _serve(sex2, weights, reqs, decode_steps=4)
        path = tel.path
    events = [json.loads(l) for l in open(path)]
    kinds = {e["ev"] for e in events}
    assert {"request_start", "prefill", "decode_superstep",
            "request_end"} <= kinds
    starts = [e for e in events if e["ev"] == "request_start"]
    ends = [e for e in events if e["ev"] == "request_end"]
    assert len(starts) == len(ends) == 4
    assert all(e["error"] is None for e in ends)
    # One host program per k-token superstep: programs/step == 1/k.
    tele = stats["telemetry"]
    assert tele["programs_per_step"] == pytest.approx(0.25)
    assert stats["request_latency_ms_p95"] >= stats[
        "request_latency_ms_p50"]
    # What the superstep fetches of the caches: the einsum oracle
    # reads every row of both slots in each of the 4 steps.
    for e in events:
        if e["ev"] == "decode_superstep":
            assert e["kv_rows_fetched"] == e["kv_rows_cache"] == 2 * S * 4


def test_kv_rows_round_lengths_up_to_the_kernels_chunk(lm128):
    """``decode_superstep.kv_rows_fetched``: over every slot and the k
    steps, the live length rounded up to the chunk ``flash_decode``
    fetches in (128 at ``max_seq`` 128; ``flash_decode_chunk`` at the
    benchmark's 1024), positions clamped at the cache's end as the
    superstep clamps them."""
    kex, oex, _ = _kernel_pair(lm128)
    assert kex.kv_rows(np.array([0, 126]), 3) == {
        "kv_rows_fetched": 6 * 128, "kv_rows_cache": 6 * 128}
    big = ServingExecutor(
        build_transformer_lm(batch_size=2, seq_len=1024, vocab_size=V,
                             d_model=D, num_heads=H, num_layers=1,
                             config=FFConfig(batch_size=2)),
        max_batch=2, max_seq=1024, buckets=(8,))
    c = pallas_kernels.flash_decode_chunk(1024, H, D // H, jnp.float32)
    assert c in (128, 256)
    # lengths 1, 2, 3 | c - 1, c, c + 1 -> c x 3 | c, c, 2 c
    assert big.kv_rows(np.array([0, c - 2]), 1)["kv_rows_fetched"] == 2 * c
    assert big.kv_rows(np.array([0, c - 2]), 3) == {
        "kv_rows_fetched": 3 * c + c + c + 2 * c,
        "kv_rows_cache": 6 * 1024}
    assert big.kv_rows(np.array([1022, 1023]), 2)["kv_rows_fetched"] == 4096
    assert oex.kv_rows(np.array([0, 5]), 2)["kv_rows_fetched"] == 4 * 128


def test_kv_rows_of_a_latent_graph_round_to_the_kernels_chunk():
    """A latent-attention graph: ``kv_rows_fetched`` rounds a slot's
    live length up to the chunk ``mla_decode`` fetches and scores in
    (512 positions at ``max_seq`` 1024, 128 at 384), not to the cache;
    the dense fallback reads every row."""
    from flexflow_tpu.models.transformer import DEEPSEEK_V3_TINY, build_lm

    def sex(seq, **kw):
        cfg = FFConfig(batch_size=2)
        return ServingExecutor(build_lm(DEEPSEEK_V3_TINY, 2, seq, cfg), cfg,
                               max_batch=2, max_seq=seq, buckets=(8,), **kw)

    big = sex(1024)
    assert pallas_kernels.mla_decode_chunk(1024) == 512
    # lengths 1, 2, 3 | 511, 512, 513 -> 512 x 3 | 512, 512, 1024
    assert big.kv_rows(np.array([0, 510]), 3) == {
        "kv_rows_fetched": 3 * 512 + 512 + 512 + 1024,
        "kv_rows_cache": 6 * 1024}
    assert big.kv_rows(np.array([1022, 1023]), 2)["kv_rows_fetched"] == 4096
    # lengths 1 | 129 -> 128 | 256
    assert sex(384).kv_rows(np.array([0, 128]), 1)["kv_rows_fetched"] == 384
    assert sex(1024, decode_kernel=False).kv_rows(
        np.array([0, 510]), 1)["kv_rows_fetched"] == 2048


# -- retired closed-loop arrival knob (loud-error contract) --------------


def test_closed_loop_arrival_retired():
    """PR 12's one-release grace is up: ``Request.arrival`` is gone
    (TypeError) and ``synthetic_requests(arrival_every=...)`` raises
    with the workload-generator migration pointer."""
    from flexflow_tpu.runtime.serving import synthetic_requests

    with pytest.raises(TypeError):
        Request(id=0, prompt=np.array([1], np.int32), arrival=2)
    with pytest.raises(ValueError, match="retired"):
        synthetic_requests(3, 16, arrival_every=2)


# -- paged KV caches (SERVING.md "Cache layout") -------------------------


@pytest.fixture(scope="module")
def paged_sex(lm):
    """Paged-layout oracle executor: 4-token KV blocks, worst-case
    pool (parity config — the capacity win needs a budget)."""
    return ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8, S),
                           decode_kernel=False, kv_block=4)


def test_kv_block_ledger_reuse_lowest_first():
    """Ledger unit contract: block 0 reserved as scratch, reservation
    arithmetic caps at max_seq, freed blocks are reused lowest-first
    (deterministic across replays)."""
    from flexflow_tpu.runtime.serving import KVBlockLedger

    led = KVBlockLedger(9, 4, S)
    assert led.capacity_blocks == 8 and led.blocks_per_slot == 4
    assert led.blocks_for(3, 6) == 3          # 3+6+1 tokens -> 3 blocks
    assert led.blocks_for(10, 100) == 4       # capped at max_seq
    r0, r1 = led.alloc(0, 3), led.alloc(1, 3)
    assert list(r0) == [1, 2, 3, 0] and list(r1) == [4, 5, 6, 0]
    assert led.free_blocks == 2 and not led.can_admit(3)
    led.free(0)
    assert list(led.alloc(0, 2)) == [1, 2, 0, 0]  # lowest-first reuse
    with pytest.raises(RuntimeError, match="already holds"):
        led.alloc(0, 1)


def test_paged_decode_matches_full_forward(paged_sex, weights,
                                           full_forward):
    """The paged acceptance bar: block-pool decode logits match the
    full-sequence forward oracle at every decoded position."""
    params, state = weights
    toks, full_logits = full_forward
    prefix = 6
    padded = np.zeros((1, 8), np.int32)
    padded[0, :prefix] = toks[0, :prefix]
    rows, tok0, ok = paged_sex.build_prefill(8)(
        params, state, padded, np.int32(prefix)
    )
    assert bool(ok)
    assert int(tok0) == int(np.argmax(full_logits[0, prefix - 1]))
    led = paged_sex.make_ledger()
    row = led.alloc(0, led.blocks_for(prefix, S))
    bt = np.zeros((2, led.blocks_per_slot), np.int32)
    bt[0] = row
    caches = paged_sex.install_paged(paged_sex.init_cache(), rows, row)
    dec = paged_sex.build_decode_superstep(1, return_logits=True)
    pos = np.array([prefix, 0], np.int32)
    errs = []
    for t in range(prefix, S):
        tokv = np.array([toks[0, t], 0], np.int32)
        caches, pos_d, _t, (_nxt, okf, logits) = dec(
            params, state, caches, bt, pos, tokv
        )
        assert bool(np.asarray(okf)[0, 0])
        errs.append(float(np.max(np.abs(
            np.asarray(logits)[0, 0] - full_logits[0, t]
        ))))
        pos = np.asarray(pos_d)
    assert max(errs) <= DECODE_TOL, f"paged decode drift {max(errs)}"


def test_paged_vs_padded_greedy_parity(sex, paged_sex, weights):
    """Greedy sequences are identical between the padded and the
    paged engine, under any batch composition."""
    def reqs():
        return [
            _req(0, [5, 9, 2], max_new=6),
            _req(1, [3, 1, 4, 1, 5], max_new=4),
            _req(2, [31, 3, 3, 7], max_new=7),
        ]

    base, _ = _serve(sex, weights, reqs(), decode_steps=4)
    pg, pstats = _serve(paged_sex, weights, reqs(), decode_steps=4)
    assert pstats["kv_layout"] == "paged"
    assert pstats["kv_block"] == 4
    for rid in (0, 1, 2):
        assert pg[rid].error is None
        assert pg[rid].tokens == base[rid].tokens
    alone, _ = _serve(paged_sex, weights,
                      [_req(1, [3, 1, 4, 1, 5], max_new=4)],
                      decode_steps=4)
    assert alone[1].tokens == pg[1].tokens


def test_paged_eviction_block_table_reuse(lm, paged_sex, weights):
    """A pool too small for two concurrent requests forces ledger-
    gated admission: the waiter admits only after an eviction frees
    blocks, REUSES them (lowest-first), and still generates exactly
    the unconstrained paged engine's tokens."""
    tight_ex = ServingExecutor(lm, max_batch=2, max_seq=S,
                               buckets=(8, S), decode_kernel=False,
                               kv_block=4, kv_blocks=5)
    def reqs():
        return [
            _req(0, [1, 2, 3], max_new=6),
            _req(1, [4, 5, 6], max_new=6),
            _req(2, [7, 8, 9], max_new=6),
        ]

    tight, tstats = _serve(tight_ex, weights, reqs(), decode_steps=4)
    roomy, _ = _serve(paged_sex, weights, reqs(), decode_steps=4)
    assert sorted(tight) == [0, 1, 2]
    assert tstats["completed"] == 3 and tstats["failed"] == 0
    for rid in (0, 1, 2):
        assert tight[rid].error is None
        assert tight[rid].tokens == roomy[rid].tokens
    # A request whose reservation exceeds the WHOLE pool is rejected
    # loudly, not deadlocked (needs 4 blocks, pool holds 3).
    tiny_ex = ServingExecutor(lm, max_batch=2, max_seq=S,
                              buckets=(8, S), decode_kernel=False,
                              kv_block=4, kv_blocks=4)
    big, _ = _serve(tiny_ex, weights,
                    [_req(9, [1, 2, 3, 4, 5, 6, 7], max_new=30)],
                    decode_steps=4)
    assert "KV blocks" in big[9].error


def test_paged_fault_isolation(paged_sex, weights):
    """The chaos NaN injection on the paged layout (pool block of the
    target slot, never scratch) fails exactly its own request; the
    neighbor's tokens are byte-identical to the clean run."""
    def reqs():
        return [_req(0, [1, 2, 3], max_new=8),
                _req(1, [4, 5, 6], max_new=8)]

    clean, _ = _serve(paged_sex, weights, reqs(), decode_steps=4)
    inj = ServingFaultInjector(nan_cache_at={1: 0})
    faulted, stats = _serve(paged_sex, weights, reqs(), decode_steps=4,
                            fault_injector=inj)
    assert faulted[0].error is not None
    assert faulted[1].error is None
    assert faulted[1].tokens == clean[1].tokens
    assert stats["failed"] == 1 and stats["completed"] == 1


def test_paged_capacity_under_budget(lm, monkeypatch):
    """The DeviceMemoryError budget machinery: under a budget that
    REFUSES the padded engine, a budget-sized paged pool serves the
    same slots, and the compute-free capacity estimate admits >= 2x
    the padded batch at prompt_len << max_seq."""
    from flexflow_tpu.data.loader import DeviceMemoryError

    padded = ServingExecutor(lm, max_batch=4, max_seq=S, buckets=(8,),
                             decode_kernel=False)
    budget = padded.cache_total_bytes() // 2
    monkeypatch.setenv("FF_DEVICE_MEM_BYTES", str(budget))
    with pytest.raises(DeviceMemoryError, match="paged"):
        padded.init_cache()
    blocks = budget // (4 * padded._bytes_per_token)
    paged = ServingExecutor(lm, max_batch=4, max_seq=S, buckets=(8,),
                            decode_kernel=False, kv_block=4,
                            kv_blocks=blocks)
    paged.init_cache()  # fits the same budget
    assert paged.max_admissible_batch(budget, 2, 1) >= \
        2 * padded.max_admissible_batch(budget, 2, 1)


# -- sharded multi-chip decode -------------------------------------------


def test_sharded_decode_matches_full_forward(lm, weights, full_forward):
    """Sharded (batch-on-n) decode logits match the full-seq forward
    oracle — the single-mesh tolerance discipline."""
    shx = ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8, S),
                          decode_kernel=False, shard=(2, 1))
    assert shx.shard == (2, 1)
    w2 = (shx._place(weights[0]), shx._place(weights[1]))
    err = _decode_logits_vs_full(shx, w2, full_forward, prefix=6)
    assert err <= DECODE_TOL, f"sharded decode drift {err}"


@pytest.mark.parametrize("shard", [(2, 1), (2, 2)])
def test_sharded_vs_single_mesh_greedy(lm, sex, weights, shard):
    """Greedy sequences are identical between the sharded engine
    (batch on 'n', heads on 'c') and the single-mesh engine, under
    any batch composition."""
    shx = ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8, S),
                          decode_kernel=False, shard=shard)
    w2 = (shx._place(weights[0]), shx._place(weights[1]))

    def reqs():
        return [_req(0, [5, 9, 2], max_new=6),
                _req(1, [3, 1, 4, 1, 5], max_new=5)]

    base, _ = _serve(sex, weights, reqs(), decode_steps=4)
    sh, sstats = _serve(shx, w2, reqs(), decode_steps=4)
    assert sstats["shard"] == list(shard)
    for rid in (0, 1):
        assert sh[rid].error is None
        assert sh[rid].tokens == base[rid].tokens
    alone, _ = _serve(shx, w2, [_req(0, [5, 9, 2], max_new=6)],
                      decode_steps=4)
    assert alone[0].tokens == sh[0].tokens


def test_sharded_falls_back_without_devices(lm, caplog):
    """Asking for more shard devices than the box has falls back
    LOUDLY to the single-mesh engine instead of crashing."""
    import logging

    with caplog.at_level(logging.WARNING, logger="ff.serving"):
        shx = ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8,),
                              shard=(64, 2))
    assert shx.shard is None
    assert any("falling back" in r.message for r in caplog.records)


@pytest.mark.parametrize("shard", [(2, 1), (2, 2)])
def test_paged_sharded_greedy_parity(lm, sex, weights, shard):
    """Paged + sharded COMPOSE (SERVING.md "Cache layout"): the block
    pool shards its head axis on 'c' (no batch axis — 'n' only sizes
    the mesh), block tables stay host-side, and greedy sequences are
    byte-identical to the single-mesh padded engine's."""
    psx = ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8, S),
                          decode_kernel=False, kv_block=4, shard=shard)
    assert psx.paged and psx.shard == shard
    w2 = (psx._place(weights[0]), psx._place(weights[1]))

    def reqs():
        return [_req(0, [5, 9, 2], max_new=6),
                _req(1, [3, 1, 4, 1, 5], max_new=5)]

    base, _ = _serve(sex, weights, reqs(), decode_steps=4)
    ps, pstats = _serve(psx, w2, reqs(), decode_steps=4)
    assert pstats["kv_layout"] == "paged"
    assert pstats["shard"] == list(shard)
    for rid in (0, 1):
        assert ps[rid].error is None
        assert ps[rid].tokens == base[rid].tokens
    alone, _ = _serve(psx, w2, [_req(1, [3, 1, 4, 1, 5], max_new=5)],
                      decode_steps=4)
    assert alone[1].tokens == ps[1].tokens


# -- in-program sampling -------------------------------------------------


def test_sampling_replayable(sex, weights):
    """Temperature/top-k sampling is keyed by (seed, request, pos):
    re-runs, different batch compositions, and different superstep
    boundaries (decode_steps) all replay the exact token sequence."""
    def reqs():
        return [_req(0, [5, 9, 2], max_new=6),
                _req(1, [3, 1, 4], max_new=6)]

    kw = dict(temperature=0.8, top_k=8, sample_seed=3)
    a, astats = _serve(sex, weights, reqs(), decode_steps=4, **kw)
    b, _ = _serve(sex, weights, reqs(), decode_steps=4, **kw)
    assert astats["sampled"] is True
    assert a[0].tokens == b[0].tokens and a[1].tokens == b[1].tokens
    alone, _ = _serve(sex, weights, [_req(1, [3, 1, 4], max_new=6)],
                      decode_steps=4, **kw)
    assert alone[1].tokens == a[1].tokens
    k2, _ = _serve(sex, weights, reqs(), decode_steps=2, **kw)
    assert k2[0].tokens == a[0].tokens and k2[1].tokens == a[1].tokens
    other, _ = _serve(sex, weights, reqs(), decode_steps=4,
                      temperature=0.8, top_k=8, sample_seed=4)
    assert (other[0].tokens != a[0].tokens
            or other[1].tokens != a[1].tokens)


def test_sampling_greedy_default_is_oracle(sex, weights):
    """temperature=0 (default) keeps the greedy path: byte-identical
    across runs and identical to an explicit greedy server."""
    def reqs():
        return [_req(0, [5, 9, 2], max_new=6)]

    g1, gstats = _serve(sex, weights, reqs(), decode_steps=4)
    g2, _ = _serve(sex, weights, reqs(), decode_steps=4)
    assert gstats["sampled"] is False
    assert g1[0].tokens == g2[0].tokens


# -- speculative decoding (SERVING.md "Speculative decoding") -------------


def _spec_reqs():
    return [_req(0, [5, 9, 2], max_new=7),
            _req(1, [3, 1, 4, 1, 5], max_new=6),
            _req(2, [31, 3, 3, 7], max_new=5)]


@pytest.mark.parametrize("layout", ["padded", "paged"])
@pytest.mark.parametrize("d", [1, 3, 8])
def test_spec_greedy_parity_matrix(lm, sex, paged_sex, weights, layout, d):
    """The speculative acceptance bar: greedy spec decode is
    BYTE-IDENTICAL to plain fused decode for every draft depth and
    cache layout — the verify scan IS the decode superstep body, so
    output never depends on the acceptance pattern.  Full-graph
    self-draft is the all-accepted boundary: every draft token equals
    the verify token, so acceptance is exactly 1.0 and each round
    emits d+1 tokens."""
    ex = sex if layout == "padded" else paged_sex
    base, bstats = _serve(ex, weights, _spec_reqs(), decode_steps=4)
    sp, sstats = _serve(ex, weights, _spec_reqs(), decode_steps=4,
                        speculate=d)
    assert sstats["speculate"] == d
    assert sstats["draft_prefills"] == sstats["prefills"]
    assert sstats["spec_acceptance_rate"] == 1.0
    for rid in (0, 1, 2):
        assert sp[rid].error is None
        assert sp[rid].tokens == base[rid].tokens
    # Fully-accepting speculation multiplies tokens per dispatch:
    # never fewer decode dispatches than plain k=4 needs... strictly
    # fewer once d+1 > k.
    if d + 1 > bstats["decode_steps_per_call"]:
        assert sstats["decode_supersteps"] < bstats["decode_supersteps"]


@pytest.mark.slow  # extra draft-model program set (~5s compile)
def test_spec_rejecting_draft_still_exact(sex, weights):
    """A BAD draft (independently initialized params) costs only
    acceptance — the emitted sequence stays byte-identical to plain
    decode (rejected tokens never reach the host; the verify token at
    the first mismatch is the sequential-decode token)."""
    bad_draft, _ = sex.init(seed=99)
    base, _ = _serve(sex, weights, _spec_reqs(), decode_steps=4)
    sp, sstats = _serve(sex, weights, _spec_reqs(), decode_steps=4,
                        speculate=4, draft_params=bad_draft)
    assert sstats["spec_acceptance_rate"] < 1.0
    for rid in (0, 1, 2):
        assert sp[rid].error is None
        assert sp[rid].tokens == base[rid].tokens


def test_spec_truncated_draft_parity(lm, sex, weights):
    """Self-drafting through the first ``draft_layers`` transformer
    blocks (the checkpoint-free draft source): parity holds whatever
    the truncated model proposes, and the draft cache covers only the
    kept layers."""
    tex = ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8, S),
                          decode_kernel=False, draft_layers=1)
    assert tex.draft_layers == 1
    assert len(tex._draft_cache_specs) == 1  # blk1_attn skipped
    base, _ = _serve(sex, weights, _spec_reqs(), decode_steps=4)
    sp, sstats = _serve(tex, weights, _spec_reqs(), decode_steps=4,
                        speculate=4)
    assert sstats["draft_layers"] == 1
    assert 0.0 <= sstats["spec_acceptance_rate"] <= 1.0
    for rid in (0, 1, 2):
        assert sp[rid].error is None
        assert sp[rid].tokens == base[rid].tokens


@pytest.mark.slow  # sampled spec + sampled plain program sets
def test_spec_sampled_replayable(sex, weights):
    """Sampled speculative verification reuses the keyed
    fold_in(seed, req_id, pos) draws, so a speculating sampled run
    emits exactly the plain sampled run's tokens — across draft
    depths and batch compositions."""
    kw = dict(temperature=0.8, top_k=8, sample_seed=3)
    base, _ = _serve(sex, weights, _spec_reqs(), decode_steps=4, **kw)
    for d in (2, 4):
        sp, sstats = _serve(sex, weights, _spec_reqs(), decode_steps=4,
                            speculate=d, **kw)
        assert sstats["sampled"] is True
        for rid in (0, 1, 2):
            assert sp[rid].error is None
            assert sp[rid].tokens == base[rid].tokens
    alone, _ = _serve(sex, weights, [_req(1, [3, 1, 4, 1, 5], max_new=6)],
                      decode_steps=4, speculate=4, **kw)
    assert alone[1].tokens == base[1].tokens


def test_spec_clamp(sex, weights):
    """The draft chain counts against the fused-step bound: d clamps
    at 20 exactly like decode_steps and training supersteps."""
    params, state = weights
    srv = Server(sex, params, state, speculate=64)
    assert srv.speculate == 20
    with pytest.raises(ValueError):
        sex.build_spec_step(0)


# -- failure model: journal & crash resume (SERVING.md "Failure model") -------


def _jr(tmp_path, name="serve.jsonl"):
    from flexflow_tpu.serving import RequestJournal

    return RequestJournal(str(tmp_path / name))


def test_journal_roundtrip(tmp_path):
    """RequestJournal unit contract: admits (tok0), per-fence token
    deltas and done records fold back into completed/in_flight state;
    a drain marker flags a clean early exit."""
    jr = _jr(tmp_path)
    jr.admit(0, 3, 7)
    jr.tokens(0, [9, 2])
    jr.done(0, 3, 3, None, qw=1.5, e2e=2.5, slo_ok=True,
            latency_s=0.01)
    jr.admit(1, 4, 5)
    jr.tokens(1, [8])
    jr.drain(1, 1)
    jr.close()

    st = _jr(tmp_path).replay()
    assert st.completed[0]["tokens"] == [7, 9, 2]
    assert st.completed[0]["plen"] == 3
    assert st.completed[0]["error"] is None
    assert st.completed[0]["slo_ok"] is True
    assert st.in_flight == {1: [5, 8]}
    assert st.drained is True
    assert st.torn_tail is False and st.malformed == 0
    assert not st.empty


def test_journal_torn_tail_tolerated(tmp_path):
    """A crash mid-append leaves a torn last line: replay drops it and
    keeps everything before it (the telemetry-log tolerance, shared
    through RunLog)."""
    jr = _jr(tmp_path)
    jr.admit(0, 3, 7)
    jr.tokens(0, [9])
    jr.close()
    with open(jr.path, "a", encoding="utf-8") as f:
        f.write('{"ev":"sv_tok')  # no newline: torn mid-append

    st = _jr(tmp_path).replay()
    assert st.torn_tail is True
    assert st.in_flight == {0: [7, 9]}
    missing = _jr(tmp_path, "never_written.jsonl").replay()
    assert missing.empty and not missing.torn_tail


def _crash_resume_reqs():
    # rid 0 finishes inside superstep 0 (its done record hits the
    # journal); 1 is mid-flight at the crash; 2 was just admitted into
    # the freed slot; 3 never left the queue.
    return [_req(0, [5, 9, 2], max_new=2),
            _req(1, [3, 1, 4, 2], max_new=5),
            _req(2, [7, 7], max_new=5),
            _req(3, [2, 4, 6], max_new=5)]


def _crash_then_resume(tmp_path, executor, weights, tear=False, **kw):
    """Baseline / crashed / resumed triple on one journal; returns
    (baseline results, resume results, resume stats)."""
    from flexflow_tpu.runtime.serving import ServingEngineFault

    base, _ = _serve(executor, weights, _crash_resume_reqs(),
                     decode_steps=2, **kw)
    jr = _jr(tmp_path)
    with pytest.raises(ServingEngineFault):
        _serve(executor, weights, _crash_resume_reqs(), decode_steps=2,
               journal=jr,
               fault_injector=ServingFaultInjector(
                   engine_raise_at={1: "injected engine crash"}),
               **kw)
    st = _jr(tmp_path).replay()
    assert 0 in st.completed and st.in_flight  # real partial progress
    if tear:
        with open(jr.path, "rb") as f:
            raw = f.read()
        cut = raw.rstrip(b"\n")
        with open(jr.path, "wb") as f:
            f.write(cut[: len(cut) - len(cut.splitlines()[-1]) // 2])
        assert _jr(tmp_path).replay().torn_tail is True
    res, stats = _serve(executor, weights, _crash_resume_reqs(),
                        decode_steps=2, journal=_jr(tmp_path), **kw)
    return base, res, stats


def test_server_crash_resume_byte_identical(sex, weights, tmp_path):
    """Journaled crash recovery (padded, greedy): completed requests
    restore from the journal without re-running, in-flight requests
    resume via re-prefill over (prompt ‖ carried) — every final
    sequence byte-identical to an uncrashed run."""
    base, res, stats = _crash_then_resume(tmp_path, sex, weights)
    for rid in range(4):
        assert res[rid].error is None
        assert res[rid].tokens == base[rid].tokens
    assert stats["drained"] is False


def test_server_crash_resume_sampled(sex, weights, tmp_path):
    """Seeded sampling survives crash recovery byte-identically: the
    (seed, request, pos) keying makes the resumed draws independent of
    batch composition and of WHERE the crash fell."""
    base, res, _ = _crash_then_resume(
        tmp_path, sex, weights,
        temperature=0.7, top_k=5, sample_seed=3)
    for rid in range(4):
        assert res[rid].error is None
        assert res[rid].tokens == base[rid].tokens


def test_server_crash_resume_paged(paged_sex, weights, tmp_path):
    """The paged block-pool layout recovers identically: ledger state
    is rebuilt fresh on resume, reservations follow the journal's
    carried lengths."""
    base, res, _ = _crash_then_resume(tmp_path, paged_sex, weights)
    for rid in range(4):
        assert res[rid].error is None
        assert res[rid].tokens == base[rid].tokens


def test_server_crash_resume_torn_tail(sex, weights, tmp_path):
    """A torn journal tail only shrinks the carried prefix: the resume
    re-generates the lost delta deterministically — still
    byte-identical."""
    base, res, _ = _crash_then_resume(tmp_path, sex, weights,
                                      tear=True)
    for rid in range(4):
        assert res[rid].error is None
        assert res[rid].tokens == base[rid].tokens


def test_spec_crash_resume_mid_generation(sex, weights, tmp_path):
    """Crash recovery composes with speculation: the journal carries
    ACCEPTED tokens only, so a crash between speculative rounds
    resumes via re-prefill over (prompt ‖ accepted prefix) — final
    sequences byte-identical to the speculating uncrashed run AND to
    the plain unspeculated run (greedy parity holds through the
    resume's re-prefill, draft-cache re-prime included)."""
    plain, _ = _serve(sex, weights, _crash_resume_reqs(),
                      decode_steps=2)
    base, res, stats = _crash_then_resume(tmp_path, sex, weights,
                                          speculate=3)
    assert stats["speculate"] == 3
    for rid in range(4):
        assert res[rid].error is None
        assert res[rid].tokens == base[rid].tokens
        assert res[rid].tokens == plain[rid].tokens

# -- prefix sharing (SERVING.md "Prefix sharing") ---------------------------

@pytest.fixture(scope="module")
def prefix_sex(lm):
    """Prefix-sharing oracle executor: 4-token blocks + the
    content-hash index (ISSUE 18)."""
    return ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8, S),
                           decode_kernel=False, kv_block=4,
                           prefix_cache=True)


def _prefix_reqs(tail_lens, max_new=5):
    """Requests sharing an 8-token (two full blocks) span, each with
    its own ``tail_lens[i]``-token suffix (0 = the bare span)."""
    rng = np.random.default_rng(5)
    span = rng.integers(0, V, size=8).astype(np.int32)
    out = []
    for i, t in enumerate(tail_lens):
        tail = rng.integers(0, V, size=t).astype(np.int32)
        out.append(_req(i, np.concatenate([span, tail]), max_new=max_new))
    return out


def test_prefix_cache_requires_paged(lm):
    with pytest.raises(ValueError, match="paged"):
        ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(8, S),
                        prefix_cache=True)


@pytest.mark.parametrize("tails", [
    (0, 0),    # identical 8-token prompts: plen % B == 0, FULL hit
    (0, 1),    # hit exactly at the block boundary, 1-token tail
    (0, 3),    # partial-block tail
    (0, 4),    # sharer plen % B == 0 with a divergent final block
    (3, 3),    # identical prompts with a partial final block
])
def test_prefix_shared_greedy_parity(sex, prefix_sex, weights, tails):
    """The tentpole bar: shared-prefix decode is byte-identical to the
    unshared PADDED run at every block-boundary shape, and the second
    request actually hit the index."""
    base, _ = _serve(sex, weights, _prefix_reqs(tails), decode_steps=4)
    shared, stats = _serve(prefix_sex, weights, _prefix_reqs(tails),
                           decode_steps=4)
    assert stats["prefix_cache"] is True
    assert stats["prefix_hits"] >= 1
    for rid in range(len(tails)):
        assert shared[rid].error is None
        assert shared[rid].tokens == base[rid].tokens


def test_prefix_full_hit_zero_dispatch(sex, prefix_sex, weights):
    """An identical full-block prompt with a memoized first token
    admits with ZERO prefill dispatches (the prefix-sharing
    headline): the prefill count stays at the donor's."""
    base, _ = _serve(sex, weights, _prefix_reqs((0, 0)), decode_steps=4)
    shared, stats = _serve(prefix_sex, weights, _prefix_reqs((0, 0)),
                           decode_steps=4)
    assert stats["prefills"] == 1          # donor only
    assert stats["prefix_hits"] == 1
    assert stats["prefix_hit_rate"] == 0.5
    assert stats["prefill_tokens_saved"] == 8
    for rid in (0, 1):
        assert shared[rid].tokens == base[rid].tokens


def test_prefix_cow_divergence(sex, prefix_sex, weights):
    """Copy-on-write: a prompt fully covered by resident blocks but
    WITHOUT a memoized next token recomputes its final block privately
    (the prefill must produce the last prompt position's logits) —
    and stays byte-identical to the unshared run."""
    rng = np.random.default_rng(5)
    span = rng.integers(0, V, size=8).astype(np.int32)
    tail = rng.integers(0, V, size=4).astype(np.int32)

    def reqs():
        # Donor's prompt EXTENDS past the sharer's: the sharer's full
        # 2-block digest has no memo entry (the donor memoized its own
        # 3-block digest), forcing the CoW clamp on block 1.
        return [_req(0, np.concatenate([span, tail]), max_new=4),
                _req(1, span, max_new=4)]

    base, _ = _serve(sex, weights, reqs(), decode_steps=4)
    shared, stats = _serve(prefix_sex, weights, reqs(), decode_steps=4)
    assert stats["kv_cows"] >= 1
    assert stats["prefix_hits"] >= 1
    for rid in (0, 1):
        assert shared[rid].error is None
        assert shared[rid].tokens == base[rid].tokens


def test_prefix_sampled_parity(sex, prefix_sex, weights):
    """Sampled decode (seeded fold_in(seed, rid, pos) draws) is
    byte-identical shared vs unshared — including the FULL-hit path,
    whose memoized first token is the greedy draw a fresh admission
    takes in sampled mode too."""
    kw = dict(decode_steps=4, temperature=0.8, top_k=8, sample_seed=3)
    for tails in ((0, 0), (0, 3)):
        base, _ = _serve(sex, weights, _prefix_reqs(tails), **kw)
        shared, stats = _serve(prefix_sex, weights, _prefix_reqs(tails),
                               **kw)
        assert stats["sampled"] and stats["prefix_hits"] >= 1
        for rid in (0, 1):
            assert shared[rid].error is None
            assert shared[rid].tokens == base[rid].tokens


def test_prefix_ledger_refcount_free_at_zero():
    """Ledger unit contract (pure host integers): refcounts gate the
    free list — a donor's death keeps shared blocks resident and
    indexed; the LAST holder's free returns them (lowest-first order
    preserved) and evicts the index entries."""
    from flexflow_tpu.runtime.serving import KVBlockLedger, prefix_digests

    led = KVBlockLedger(9, 4, S, prefix_cache=True)
    prompt = np.arange(1, 9, dtype=np.int32)          # 2 full blocks
    dig = prefix_digests(prompt, 4)
    assert len(dig) == 2
    row = led.alloc(0, 3)
    led.register_prefix(0, dig)
    # Full coverage without a memo: CoW clamp recomputes block 1.
    plan = led.plan_prefix(prompt)
    assert (plan.use, plan.cow, plan.offset) == (1, 1, 4)
    assert not plan.full_hit
    assert plan.shared == (int(row[0]),)
    led.record_next(dig[-1], 7)
    plan2 = led.plan_prefix(prompt)
    assert plan2.full_hit and plan2.tok0 == 7
    assert plan2.use == 2 and plan2.offset == 8
    assert plan2.shared == (int(row[0]), int(row[1]))
    led.alloc(1, 3, shared=plan2.shared)              # refcount 2
    led.free(0)                                       # donor dies
    # Shared blocks stay resident + indexed under the live refcount.
    assert led.plan_prefix(prompt).full_hit
    assert int(row[0]) not in led._free
    led.free(1)                                       # last holder
    plan3 = led.plan_prefix(prompt)
    assert plan3.use == 0 and not plan3.full_hit      # index evicted
    assert list(led._free) == sorted(led._free)
    assert led.free_blocks == led.capacity_blocks     # all returned
    # Lowest-first reuse is unchanged by the refcount machinery.
    assert list(led.alloc(0, 2)) == [1, 2, 0, 0]


def test_prefix_donor_eviction_sharers_survive(sex, prefix_sex, weights):
    """The chaos property at unit scale: the donor request errors out
    mid-decode, the sharer keeps decoding against the shared blocks —
    byte-identical to the unshared run (refcount holds the block)."""
    def reqs():
        return _prefix_reqs((3, 4), max_new=8)

    base, _ = _serve(sex, weights, reqs(), decode_steps=4)
    inj = ServingFaultInjector(raise_at={1: 0})
    faulted, stats = _serve(prefix_sex, weights, reqs(), decode_steps=4,
                            fault_injector=inj)
    assert faulted[0].error is not None
    assert faulted[1].error is None
    assert faulted[1].tokens == base[1].tokens
    assert stats["prefix_hits"] >= 1
