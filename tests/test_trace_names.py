"""The names a profiler trace is read by (OBSERVABILITY.md "Spans,
kernels, scopes"; ``obs/events.py``).

- every ``pallas_call`` reachable from ``ops/pallas_kernels.py``'s entry
  points carries a ``name`` of ``KERNEL_CATALOG``;
- the lowered text of a tiny ``train_step`` and ``sparse_train_step``
  carries ``ff_loss`` and ``ff_opt`` in its ``op_name`` locations;
- a tiny ``Server.run`` under ``jax.profiler`` on the CPU leaves an
  ``.xplane.pb`` that holds every ``ff/serve/*`` span, nested as the
  table says, tiling the loop; the scheduled loop leaves the engine's
  five, in the same order.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.obs.events import KERNEL_CATALOG, SCOPE_CATALOG, SPAN_CATALOG
from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.serving import Server, ServingExecutor
from flexflow_tpu.serving import uniform_workload
from flexflow_tpu.serving.scheduler import ScheduledServer, SchedulerPolicy


# -- kernels ---------------------------------------------------------------

def _pallas_names(jaxpr, out):
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append(e.params["name"])
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


def _qkv():
    return jnp.ones((1, 2, 128, 64), jnp.float32)


def _rows():
    return jnp.ones((64, 128), jnp.float32), jnp.arange(8, dtype=jnp.int32)


_ENTRY_POINTS = {
    "flash": (lambda q: jax.grad(lambda x: pk.flash_attention(x, x, x).sum())(q),
              _qkv, {"ff_flash_fwd", "ff_flash_dq", "ff_flash_dkv"}),
    "flash_decode": (
        lambda q: pk.flash_decode(q[:, :, 0], q[:, :, 1], q[:, :, 2], jnp.ones((1, 128, 2, 64)),
                                  jnp.ones((1, 128, 2, 64)), jnp.array([5], jnp.int32)),
        _qkv, {"ff_flash_decode"}),
    "softmax_xent": (
        lambda x: jax.grad(lambda l: pk.softmax_xent(l, jnp.zeros((128,), jnp.int32))[0].sum())(x),
        lambda: jnp.ones((128, 512), jnp.float32),
        {"ff_softmax_xent_fwd", "ff_softmax_xent_bwd"}),
    "flash_fwd_uneven": (
        lambda q: pk.flash_fwd_uneven(jnp.ones((1, 2, 128, 24)), jnp.ones((1, 2, 128, 24)),
                                      jnp.ones((1, 2, 128, 16)), 0.2),
        _qkv, {"ff_flash_fwd_uneven"}),
    "flash_fwd_window": (
        lambda q: pk.flash_fwd_window(jnp.ones((1, 4, 256, 32)), jnp.ones((1, 2, 256, 32)),
                                      jnp.ones((1, 2, 256, 32)), 0.2, 100),
        _qkv, {"ff_flash_fwd_window"}),
    "attend_kept": (
        lambda q: pk.attend_kept(jnp.ones((1, 4, 128, 32)), jnp.ones((1, 2, 256, 24)),
                                 jnp.ones((1, 2, 256, 16)), jnp.ones((1, 128, 256), bool),
                                 128, 0.2, shared_k=jnp.ones((1, 256, 8))),
        _qkv, {"ff_attend_kept"}),
    "flash_decode_ring": (
        lambda q: pk.flash_decode(jnp.ones((1, 6, 128)), jnp.ones((1, 2, 128)), jnp.ones((1, 2, 128)),
                                  jnp.ones((1, 2, 128, 128)), jnp.ones((1, 2, 128, 128)),
                                  jnp.array([128], jnp.int32), positions_last=True,
                                  write_at=jnp.array([5], jnp.int32)),
        _qkv, {"ff_flash_decode"}),
    "mla_decode": (
        lambda q: pk.mla_decode(jnp.ones((1, 2, 40)), jnp.ones((1, 40)),
                                jnp.ones((1, 40, 128)),
                                jnp.array([5], jnp.int32), 32, 0.2),
        _qkv, {"ff_mla_decode"}),
    "grouped_matmul": (
        lambda q: pk.grouped_matmul(jnp.ones((32, 128)), jnp.ones((2, 128, 128)),
                                    jnp.array([0, 1], jnp.int32), jnp.int32(2), 16,
                                    w_up=jnp.ones((2, 128, 128))),
        _qkv, {"ff_grouped_matmul"}),
    "flash_decode_grouped": (
        lambda q: pk.flash_decode(jnp.ones((1, 4, 128)), jnp.ones((1, 2, 128)), jnp.ones((1, 2, 128)),
                                  jnp.ones((1, 2, 128, 128)), jnp.ones((1, 2, 128, 128)),
                                  jnp.array([5], jnp.int32), positions_last=True),
        _qkv, {"ff_flash_decode"}),
    "kda_chunk": (
        lambda q: pk.kda_chunk(*(jnp.ones((64, 1, 128)),) * 3, -jnp.ones((64, 1, 128)),
                               jnp.ones((64, 1)), jnp.zeros((1, 128, 128))),
        _qkv, {"ff_kda_chunk"}),
    "kda_decode": (
        lambda q: pk.kda_decode(*(jnp.ones((1, 2, 128)),) * 3, -jnp.ones((1, 2, 128)),
                                jnp.ones((1, 2)), jnp.zeros((1, 2, 128, 128))),
        _qkv, {"ff_kda_decode"}),
    "gather_rows": (lambda t: pk.gather_rows(*t), _rows, {"ff_gather_rows"}),
    "scatter_add_rows": (lambda t: pk.scatter_add_rows(t[0], t[1], jnp.ones((8, 128))), _rows,
                         {"ff_scatter_add_rows"}),
}


@pytest.mark.parametrize("entry", sorted(_ENTRY_POINTS))
def test_every_pallas_call_is_named_from_the_catalog(entry):
    fn, make, want = _ENTRY_POINTS[entry]
    names = _pallas_names(jax.make_jaxpr(fn)(make()).jaxpr, [])
    assert names and set(names) == want
    assert want <= KERNEL_CATALOG


def test_the_entry_points_reach_every_name_of_the_catalog():
    # ``ff_kda_intra``: no kernel since PR 46, kept in the catalog for the
    # benchmark's metric file that still lists its pattern (obs/events.py).
    assert set().union(*(w for _, _, w in _ENTRY_POINTS.values())) == \
        KERNEL_CATALOG - {"ff_kda_intra"}


# -- scopes ----------------------------------------------------------------

def _op_names(lowered):
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


def _tiny_lm_step():
    lm = build_transformer_lm(batch_size=2, seq_len=8, vocab_size=32, d_model=16, num_heads=2,
                              num_layers=1, config=FFConfig(batch_size=2))
    ex = Executor(lm, config=lm.config)
    params, opt, state = ex.init(seed=0)
    batch = {"tokens": np.zeros((2, 8), np.int32), "label": np.zeros((2, 8), np.int32)}
    return ex, (params, opt, state, batch), None


def _tiny_dlrm_step():
    cfg = FFConfig(batch_size=4, sparse_embedding_updates=True)
    arch = DLRMConfig(sparse_feature_size=8, embedding_size=[16, 16], mlp_bot=[4, 8], mlp_top=[24, 8, 1])
    ff = build_dlrm(batch_size=4, dlrm=arch, config=cfg)
    ex = Executor(ff, config=cfg, optimizer=SGDOptimizer(lr=0.1), devices=jax.devices()[:1])
    params, opt, state = ex.init(seed=0)
    batch = {"dense_input": np.zeros((4, 4), np.float32), "label": np.zeros((4, 1), np.float32),
             "sparse_input": np.zeros((4, 2), np.int32)}
    return ex, (params, opt, state, batch), "embeddings"


@pytest.mark.parametrize("make", [_tiny_lm_step, _tiny_dlrm_step], ids=["train_step", "sparse_train_step"])
def test_lowered_step_carries_the_loss_and_optimizer_phases(make):
    ex, args, sparse_op = make()
    assert bool(ex._sparse_ops) == (sparse_op is not None)
    names = _op_names(ex.train_step.lower(*args))
    # A scope is a component of the path, under whatever autodiff wrapped
    # round it: ``jit(train_step)/transpose(jvp(ff_loss))/softmax/mul``.
    paths = [[c for c in re.split(r"[/()]", n) if c] for n in names]
    loss_op = next(op.name for op in ex.model.layers if op.is_loss)
    for scope in ("ff_loss", "ff_opt"):     # the catalog's train-step phases
        assert scope in SCOPE_CATALOG and any(scope in p for p in paths), scope
    # The loss: forward and transpose, with the op's own scope kept inside.
    assert any("ff_loss" in p and loss_op in p and "transpose" not in p for p in paths)
    assert any("ff_loss" in p and loss_op in p and "transpose" in p for p in paths)
    assert not any("ff_loss" in p and "ff_opt" in p for p in paths)
    if sparse_op:
        # The row gather and the row step sit under the embedding's own scope.
        assert any(sparse_op in p and "ff_opt" not in p for p in paths)
        assert any(sparse_op in p and "ff_opt" in p for p in paths)


def test_lowered_serving_programs_carry_the_selectors_two_phases():
    """A graph whose attention composes a token selector: ``ff_index``
    (projections and scores) and ``ff_select`` (top-k and gather) sit
    inside each ``blk<i>_attn`` of the prefill and of the decode
    superstep; with the gated norm's gate and the router's group step
    (tests/test_axk2.py) they are the catalog's other scopes."""
    from flexflow_tpu.models.transformer import KEYE_VL2_TINY, build_lm
    from flexflow_tpu.runtime.serving import ServingExecutor

    lm = build_lm(KEYE_VL2_TINY, 2, 64, FFConfig(batch_size=2))
    sex = ServingExecutor(lm, lm.config, max_batch=2, max_seq=64, buckets=(64,))
    params, _opt, state = jax.eval_shape(Executor(lm, config=lm.config).init)
    caches = sex._cache_tree(sex._cache_specs, lambda ce: jax.ShapeDtypeStruct(
        (2,) + tuple(ce.shape), ce.dtype))
    vec = jax.ShapeDtypeStruct((2,), np.int32)
    programs = {
        "decode": sex.build_decode_superstep(2).lower(params, state, caches, vec, vec),
        "prefill": sex.build_prefill(64).lower(
            params, state, jax.ShapeDtypeStruct((1, 64), np.int32),
            jax.ShapeDtypeStruct((), np.int32)),
    }
    assert SCOPE_CATALOG == {"ff_loss", "ff_opt", "ff_index", "ff_select",
                             "ff_gnorm", "ff_route_group", "ff_conv_state"}
    for kind, lowered in programs.items():
        # The compiled text's ``op_name``: what a trace's ``tf_op`` holds
        # (the lowering's ``loc`` forgets the op's scope inside a loop body).
        names = set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))
        paths = [[c for c in re.split(r"[/()]", n) if c] for n in names]
        for scope in ("ff_index", "ff_select"):
            for blk in ("blk0_attn", "blk1_attn"):
                assert any(scope in p and blk in p for p in paths), (kind, scope, blk)
        assert not any("ff_index" in p and "ff_select" in p for p in paths)
        assert not any("ff_select" in p and "blk0_moe" in p for p in paths)


# -- host spans --------------------------------------------------------------

def _serve_spans(trace_dir):
    """``(ff/serve events, the test's own run span)`` as ``(name, start, end, stats)``."""
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans, run = [], None
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ff/"):
                    spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
                elif e.name == "test/run":
                    run = (e.start_ns, e.start_ns + e.duration_ns)
    return sorted(spans, key=lambda s: s[1]), run


def _union(spans):
    total, cur = 0.0, None
    for _, a, b, _ in spans:
        if cur is None or a > cur[1]:
            total += (cur[1] - cur[0]) if cur else 0.0
            cur = [a, b]
        else:
            cur[1] = max(cur[1], b)
    return total + ((cur[1] - cur[0]) if cur else 0.0)


#: The spans the engine opens (``ServingEngine``), on either loop.
ENGINE_SPANS = {f"ff/serve/{n}" for n in (
    "prefill_dispatch", "prefill_fence", "install", "decode_dispatch", "decode_fence")}


@pytest.fixture(scope="module")
def tiny_servers():
    lm = build_transformer_lm(batch_size=2, seq_len=16, vocab_size=64, d_model=32, num_heads=2,
                              num_layers=2, config=FFConfig(batch_size=2))
    sex = ServingExecutor(lm, max_batch=2, max_seq=16, buckets=(8, 16), decode_kernel=False)
    params, state = sex.init(seed=0)
    srvs = {"server": Server(sex, params, state, decode_steps=4),
            "scheduled": ScheduledServer(sex, params, state, decode_steps=4,
                                         policy=SchedulerPolicy.fifo())}
    reqs = uniform_workload(40, 64, prompt_len=(3, 6), max_new_tokens=6, seed=5)
    srvs["server"].run(reqs)  # every program built
    return srvs, reqs


@pytest.mark.parametrize("loop", ["server", "scheduled"])
def test_server_run_spans_nest_and_tile_the_loop(tiny_servers, tmp_path, loop):
    srvs, reqs = tiny_servers
    srv = srvs[loop]
    best = 0.0
    for attempt in range(3):  # the host is shared: a pre-empted gap is not the loop's
        d = str(tmp_path / f"t{attempt}")
        jax.profiler.start_trace(d)
        try:
            with jax.profiler.TraceAnnotation("test/run"):
                _, stats = srv.run(reqs)
        finally:
            jax.profiler.stop_trace()
        spans, run = _serve_spans(d)
        assert {s[0] for s in spans} == (SPAN_CATALOG if loop == "server" else ENGINE_SPANS)
        assert ENGINE_SPANS <= SPAN_CATALOG
        by = {n: [s for s in spans if s[0] == n] for n in SPAN_CATALOG}
        # The engine's five, under either loop.  A request: dispatch, fence
        # and install under its id, each over before the next starts.
        assert len(reqs) == stats["prefills"]
        admission = {n: {s[3]["id"]: s for s in by[f"ff/serve/{n}"]}
                     for n in ("prefill_dispatch", "prefill_fence", "install")}
        for r in reqs:
            disp, fence, inst = (admission[n][r.id] for n in admission)
            assert disp[2] <= fence[1] and fence[2] <= inst[1]
        assert all(len(by[f"ff/serve/{n}"]) == len(reqs) for n in admission)
        assert by["ff/serve/prefill_dispatch"][0][3]["bucket"] == 8
        # A superstep: its dispatch, then its fence, numbered as the loop counts.
        for n in ("decode_dispatch", "decode_fence"):
            assert [s[3]["superstep"] for s in by[f"ff/serve/{n}"]] \
                == list(range(stats["decode_supersteps"]))
        steps = sorted(by["ff/serve/decode_dispatch"] + by["ff/serve/decode_fence"],
                       key=lambda s: s[1])
        assert [s[0] for s in steps] == ["ff/serve/decode_dispatch", "ff/serve/decode_fence"] \
            * stats["decode_supersteps"]
        assert all(x[2] <= y[1] for x, y in zip(steps, steps[1:]))
        assert run[0] <= spans[0][1] and max(s[2] for s in spans) <= run[1]
        if loop == "scheduled":  # policy only: the loop opens no span of its own
            return
        # One admit per request, the three inside it; four spans a superstep.
        assert len(by["ff/serve/admit"]) == len(reqs)
        for n in ("decode_pack", "bookkeep"):
            assert len(by[f"ff/serve/{n}"]) == stats["decode_supersteps"]
        admits = by["ff/serve/admit"]
        for n in ("prefill_dispatch", "prefill_fence", "install"):
            for _, a, b, st in by[f"ff/serve/{n}"]:
                assert any(a0 <= a and b <= b0 and s0["id"] == st["id"] for _, a0, b0, s0 in admits)
        # The rest neither nest nor overlap: in time order each ends before the next starts.
        flat = sorted((s for s in spans if s[0] in (
            "ff/serve/admit", "ff/serve/decode_pack", "ff/serve/decode_dispatch",
            "ff/serve/decode_fence", "ff/serve/bookkeep")), key=lambda s: s[1])
        assert all(x[2] <= y[1] for x, y in zip(flat, flat[1:]))
        # The keywords that join the trace to the stream.
        assert sorted(s[3]["id"] for s in admits) == sorted(r.id for r in reqs)
        assert [s[3]["superstep"] for s in by["ff/serve/bookkeep"]] == list(range(stats["decode_supersteps"]))
        assert all(1 <= s[3]["active"] <= 2 for s in by["ff/serve/decode_pack"])
        # All of it inside Server.run, and tiling its loop.
        loop_ns = max(s[2] for s in spans) - spans[0][1]
        best = max(best, _union(spans) / loop_ns)
        if best >= 0.98:
            break
    assert best >= 0.98, best
