"""One device engine under both serving loops (``ServingEngine``,
``runtime/serving.py``): ``Server.run`` and ``ScheduledServer.run``
under ``fifo`` serve the same requests through the same class, with the
same number of prefills, installs and decode dispatches, and give every
request the same tokens.  Every call that ends in a fence hands back its
wall and the instant it started at, and the scheduler's compute-free
twin keeps the same returns."""

import collections
import time

import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.runtime.serving import (
    Request,
    Server,
    ServingEngine,
    ServingExecutor,
)
from flexflow_tpu.serving.scheduler import (
    ScheduledServer,
    SchedulerPolicy,
    SlotShape,
    _SimEngine,
)

V, S = 64, 32
OPS = ("prefill", "install", "draft_prefill", "decode", "spec")
#: The calls that end in a fence: ``(..., wall_s, t0)``.
FENCED = ("prefill", "decode", "spec")


def _gpt(**kw):
    lm = build_transformer_lm(batch_size=2, seq_len=S, vocab_size=V, d_model=32,
                              num_heads=2, num_layers=2, config=FFConfig(batch_size=2))
    sex = ServingExecutor(lm, max_batch=2, max_seq=S, buckets=(S,),
                          decode_kernel=False, **kw)
    return (sex, V) + sex.init(seed=0)


def _latent_moe():
    from tests import test_latent_moe as t

    ff, params = t._model(t._cfg(), 2, t.S)
    sex = ServingExecutor(ff, ff.config, max_batch=2, max_seq=t.S, buckets=[t.S],
                          decode_kernel=False)
    return sex, 512, params, {}


def _requests(vocab, shared=0, n=4):
    rng = np.random.default_rng(11)
    span = rng.integers(0, vocab, size=shared).astype(np.int32)
    return [Request(id=i, max_new_tokens=5 + i % 3, prompt=np.concatenate(
        [span, rng.integers(0, vocab, size=3 + i).astype(np.int32)]))
        for i in range(n)]


CASES = {
    "padded": (_gpt, {}, {}, 0),
    "paged": (_gpt, dict(kv_block=4), {}, 0),
    # A span of two whole blocks in front of every prompt: the first
    # admission computes it, the rest gather it (offset prefill).
    "paged_prefix": (_gpt, dict(kv_block=4, prefix_cache=True), {}, 8),
    "speculate": (_gpt, dict(draft_layers=1), dict(speculate=3), 0),
    "sampled": (_gpt, {}, dict(temperature=0.8, top_k=8, sample_seed=3), 0),
    "latent_moe": (_latent_moe, {}, {}, 0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_both_loops_serve_through_the_one_engine(case, monkeypatch):
    build, ex_kw, srv_kw, shared = CASES[case]
    sex, vocab, params, state = build(**ex_kw)
    calls = collections.Counter()
    returned = {}
    for op in OPS:
        def counted(self, *a, _op=op, _inner=getattr(ServingEngine, op), **kw):
            calls[_op] += 1
            before = time.perf_counter()
            out = _inner(self, *a, **kw)
            if _op in FENCED:
                # its wall and the instant it started, on perf_counter's
                # clock: both edges of the call, for the loop's one event
                wall, t0 = out[-2:]
                assert before <= t0 <= t0 + wall <= time.perf_counter()
                returned[_op] = len(out)
            return out
        monkeypatch.setattr(ServingEngine, op, counted)
    reqs = _requests(vocab, shared)

    plain, pstats = Server(sex, params, state, decode_steps=4, **srv_kw).run(reqs)
    under_plain = dict(calls)
    calls.clear()
    sched = ScheduledServer(sex, params, state, decode_steps=4,
                            policy=SchedulerPolicy.fifo(), **srv_kw)
    assert type(sched.engine) is ServingEngine
    queued, sstats = sched.run(reqs)

    assert dict(calls) == under_plain
    # The compute-free twin keeps the engine's returns, element for element.
    sim = _SimEngine(SlotShape(max_batch=2, max_seq=S, buckets=(S,)))
    pos = np.zeros(2, np.int32)
    twin = {"prefill": sim.prefill(reqs[0].prompt, S),
            "decode": sim.decode(pos, pos, 4), "spec": sim.spec(pos, pos, 3)}
    assert returned and {op: len(twin[op]) for op in returned} == returned
    assert all(twin[op][-2:] == (0.0, 0.0) for op in returned)
    assert under_plain["prefill"] == under_plain["install"] == pstats["prefills"] \
        == sstats["prefills"]
    assert under_plain.get("decode", 0) + under_plain.get("spec", 0) \
        == pstats["decode_supersteps"] == sstats["decode_supersteps"]
    if "speculate" in srv_kw:
        assert under_plain["draft_prefill"] == len(reqs) and "decode" not in under_plain
    else:
        assert "spec" not in under_plain and "draft_prefill" not in under_plain
    if shared:
        assert pstats["prefix_hits"] == sstats["prefix_hits"] > 0
    assert pstats["failed"] == sstats["failed"] == 0
    for r in reqs:
        assert len(plain[r.id].tokens) == r.max_new_tokens
        assert plain[r.id].tokens == queued[r.id].tokens, r.id
