"""The sorted expert layer on a chip that holds a share of the experts
(``ops/moe.py``, PR 45): a prefill-sized segment's rows, gathers and
combine are sized by the assignments that fall on the held experts
(``MixtureOfExperts.held_rows_bound``); the sorted held assignments are
walked in windows of that many, one window unless the router sends more
here.

The oracle is the formulation the op had before (``_full_size`` below:
every one of the ``A`` assignments gets a row, the combine is a masked
``(T, k, d)`` sum); an op that holds every expert must still trace to
exactly that.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops import pallas_kernels
from flexflow_tpu.ops.base import TensorSpec
from flexflow_tpu.ops.moe import MixtureOfExperts

E = 16


def _full_size(op, params, xf, serving):
    """``MixtureOfExperts._sorted_tokens`` as PR 29 wrote it and PR 44
    left it, line for line."""
    a = op.attrs
    T, d = xf.shape
    k, e, eh = a["top_k"], a["num_experts"], len(op.held)
    A = T * k
    idx, w = op.route(params, xf)
    local_of = np.full((e,), eh, np.int32)
    local_of[list(op.held)] = np.arange(eh, dtype=np.int32)
    local = jnp.asarray(local_of)[idx].reshape(A)
    here = local < eh
    counts = jnp.sum(local[:, None] == jnp.arange(eh)[None, :], axis=0,
                     dtype=jnp.int32)
    tm = pallas_kernels.grouped_tile_rows(A, eh)
    rows = -(-(A + min(eh, A) * (tm - 1)) // tm) * tm
    padded = -(-counts // tm) * tm
    p_end = jnp.cumsum(padded)
    start = jnp.cumsum(counts) - counts
    key, tok, slot = jax.lax.sort(
        (local, jnp.repeat(jnp.arange(T, dtype=jnp.int32), k),
         jnp.arange(A, dtype=jnp.int32)), num_keys=1)
    kc = jnp.minimum(key, eh - 1)
    dest = jnp.where(key < eh,
                     (p_end - padded)[kc] + jnp.arange(A) - start[kc],
                     rows)
    src_tok = jnp.zeros((rows,), jnp.int32).at[dest].set(tok, mode="drop")
    row_of = jnp.zeros((A,), jnp.int32).at[slot].set(
        jnp.minimum(dest, rows - 1))
    xs = xf[src_tok]

    f = a["ffn_dim"]
    if serving and pallas_kernels.grouped_matmul_supported(d, f, xf.dtype) \
            and pallas_kernels.grouped_matmul_supported(f, d, xf.dtype):
        n_tiles = rows // tm
        used = p_end[-1] // tm
        tile_e = jnp.sum(
            p_end[None, :] <= (jnp.arange(n_tiles) * tm)[:, None], axis=1)
        last_e = jnp.minimum(tile_e[jnp.maximum(used - 1, 0)], eh - 1)
        tile_e = jnp.where(jnp.arange(n_tiles) < used, tile_e, last_e)

        def product(x, w, w_up=None):
            return pallas_kernels.grouped_matmul(
                x, w, tile_e, used, tm, w_up=w_up)

        fused_gate = product
    else:
        fused_gate = None

        def product(x, w):
            return jax.lax.ragged_dot(x, w, padded)

    routed = ("w_gate", "w_up", "w_down") if a["gated"] else ("w1", "w2")
    ys = op._mlp(xs, params, routed, product, fused_gate)
    y_tk = ys[row_of].reshape(T, k, d).astype(jnp.float32)
    y = jnp.sum(jnp.where(here.reshape(T, k, 1), y_tk * w[..., None], 0.0),
                axis=1)
    if a["shared_experts"]:
        shared = ("s_gate", "s_up", "s_down") if a["gated"] else \
            ("s_up", "s_down")
        y = y + op._mlp(xf, params, shared,
                        lambda x, w: x @ w).astype(jnp.float32)
    return y.astype(xf.dtype), counts


def _op(tokens, d, f, top_k, held, dtype=jnp.float32):
    x = TensorSpec("x", (1, tokens, d), dtype, ("n", "s", None))
    return MixtureOfExperts(
        "moe", x, E, f, top_k=top_k, dispatch="sorted", router="sigmoid",
        gated=True, activation="silu", shared_experts=1, routed_scale=2.5,
        held_experts=held)


def _params(op, seed=0):
    rng = np.random.default_rng(seed)
    return {name: jnp.asarray(rng.normal(size=spec.shape) * 0.2, spec.dtype)
            for name, spec in op.param_specs().items()}


def _inputs(tokens, d, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(tokens, d)),
                       jnp.float32)


def _no_held_rows(monkeypatch):
    """The held-sized path, patched to raise."""
    def boom(*_a, **_k):
        raise AssertionError("the held-sized path was reached")

    monkeypatch.setattr(MixtureOfExperts, "_held_terms", boom)


def _windows(monkeypatch):
    """Counts the windows ``_held_terms`` walks (run eagerly, so that
    the loop's trips are Python's)."""
    trips = []
    real = jax.lax.fori_loop

    def loop(lo, hi, body, init):
        trips.append(int(hi) - int(lo))
        return real(lo, hi, body, init)

    monkeypatch.setattr(jax.lax, "fori_loop", loop)
    return trips


@pytest.mark.parametrize("segments", [1, 4])
@pytest.mark.parametrize("top_k", [8, 10])
@pytest.mark.parametrize("held", [2, 4, 8])
def test_held_sized_forward_equals_the_full_size_forward(
        monkeypatch, held, top_k, segments):
    """Held shares 1/8, 1/4 and 1/2 of sixteen experts; one segment and
    four.  Near-uniform routing: every segment takes the held-sized
    branch, which the counter says."""
    tokens, d = 512, 32
    op = _op(tokens, d, 32, top_k, list(range(3, 3 + held)))
    params, xf = _params(op), _inputs(tokens, d)
    monkeypatch.setattr(MixtureOfExperts, "SEGMENT_BYTES",
                        tokens * top_k * d * 4 // segments)
    seg = tokens // segments
    A = seg * top_k
    assert pallas_kernels.grouped_tile_rows(A, held) == 128
    assert op.held_rows_bound(A) == -(-3 * A * held // (2 * E)) < A
    want = jnp.concatenate([_full_size(op, params, xf[i:i + seg], False)[0]
                            for i in range(0, tokens, seg)])
    (y,), _ = op.forward(params, [xf[None]], {}, False)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    (y,), state = op.forward(params, [xf[None]], {"serving": True}, False)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert float(state["stats"]["held_rows_overflow"]) == 0.0
    # One window a segment, and never the full-size rows.
    monkeypatch.setattr(MixtureOfExperts, "_routed_terms", None)
    trips = _windows(monkeypatch)
    for i in range(0, tokens, seg):
        y, _ = op._sorted_tokens(params, xf[i:i + seg], False)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want[i:i + seg]),
                                   rtol=1e-6, atol=1e-6)
    assert trips == [1] * segments


@pytest.mark.parametrize("segments", [1, 2])
def test_a_router_that_sends_everything_here_walks_more_windows(
        monkeypatch, segments):
    """Every assignment on a held expert: more than one window of the
    held-sized rows takes, so the loop runs on (two windows of 3/4 of
    the assignments), nothing is dropped, and the counter says so."""
    tokens, d, top_k, held = 256, 32, 8, list(range(4, 12))
    op = _op(tokens, d, 32, top_k, held)
    params = _params(op)
    xf = jnp.abs(_inputs(tokens, d))
    params["gate"] = jnp.abs(params["gate"]).at[:, jnp.asarray(held)].add(1.0) \
        * jnp.where(jnp.isin(jnp.arange(E), jnp.asarray(held)), 1.0, -1.0)
    monkeypatch.setattr(MixtureOfExperts, "SEGMENT_BYTES",
                        tokens * top_k * d * 4 // segments)
    seg = tokens // segments
    want, counts = zip(*(_full_size(op, params, xf[i:i + seg], False)
                         for i in range(0, tokens, seg)))
    assert all(int(c.sum()) == seg * top_k for c in counts)
    assert op.held_rows_bound(seg * top_k) == 3 * seg * top_k // 4
    (y,), state = op.forward(params, [xf[None]], {"serving": True}, False)
    np.testing.assert_allclose(np.asarray(y[0]),
                               np.asarray(jnp.concatenate(want)),
                               rtol=1e-6, atol=1e-6)
    assert float(state["stats"]["held_rows_overflow"]) == 1.0
    assert float(state["stats"]["experts_touched"]) == len(held)
    trips = _windows(monkeypatch)
    op._sorted_tokens(params, xf[:seg], False)
    assert trips == [2]


def test_the_counter_is_the_share_of_the_segments_that_overflow(monkeypatch):
    """Two segments, the first routed here whole and the second
    nowhere near: half."""
    tokens, d, top_k, held = 256, 32, 8, list(range(8))
    op = _op(tokens, d, 32, top_k, held)
    params = _params(op)
    xf = jnp.abs(_inputs(tokens, d))
    xf = xf.at[tokens // 2:].multiply(-1.0)
    params["gate"] = (jnp.abs(params["gate"]) + 0.5) \
        * jnp.where(jnp.arange(E) < 8, 1.0, -1.0)
    monkeypatch.setattr(MixtureOfExperts, "SEGMENT_BYTES",
                        tokens * top_k * d * 4 // 2)
    (y,), state = op.forward(params, [xf[None]], {"serving": True}, False)
    assert float(state["stats"]["held_rows_overflow"]) == 0.5
    want = jnp.concatenate([_full_size(op, params, xf[i:i + 128], False)[0]
                            for i in (0, 128)])
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_the_serving_kernel_runs_over_the_held_sized_rows(monkeypatch):
    """``ff_grouped_matmul`` (interpreted here) over the smaller rows:
    the tiles it is told to use are the same, the tiles it is given
    fewer, and the full-size rows are not traced at all."""
    tokens, d, f, top_k, held = 64, 128, 128, 8, [5, 6]
    op = _op(tokens, d, f, top_k, held)
    params, xf = _params(op), _inputs(tokens, d)
    seen = []
    real = pallas_kernels.grouped_matmul

    def spy(x, w, tile_e, used, tm, **kw):
        seen.append(x.shape[0])
        return real(x, w, tile_e, used, tm, **kw)

    monkeypatch.setattr(pallas_kernels, "grouped_matmul", spy)
    want, _ = _full_size(op, params, xf, True)
    A = tokens * top_k
    full_rows = -(-(A + 2 * 127) // 128) * 128
    assert seen == [full_rows, full_rows]
    del seen[:]
    (y,), state = op.forward(params, [xf[None]], {"serving": True}, False)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    small = -(-(op.held_rows_bound(A) + 2 * 127) // 128) * 128
    assert seen == [small, small] and small <= full_rows // 2
    assert float(state["stats"]["held_rows_overflow"]) == 0.0


def test_a_decode_sized_call_keeps_the_program_it_had(monkeypatch):
    """A step's sixteen tokens are tile padding, not assignments: no
    loop, no counter, the text of before."""
    op = _op(16, 32, 32, 8, [0, 1, 2, 3])
    params, xf = _params(op), _inputs(16, 32)
    assert op.held_rows_bound(16 * 8) is None
    want = str(jax.make_jaxpr(lambda p, x: _full_size(op, p, x, True))(params, xf))
    _no_held_rows(monkeypatch)
    got = str(jax.make_jaxpr(lambda p, x: op._sorted_tokens(p, x, True))(params, xf))
    assert got == want
    _, state = op.forward(params, [xf[None]], {"serving": True}, False)
    assert sorted(state["stats"]) == ["expert_load_max", "experts_touched"]


@pytest.mark.parametrize("serving", [False, True])
@pytest.mark.parametrize("tokens,d", [(512, 32), (64, 128)])
def test_an_op_that_holds_every_expert_traces_to_the_text_it_had(
        monkeypatch, tokens, d, serving):
    """``held_experts=None`` (kanana2, xing4, keye2, every training
    graph): none of the held-sized pieces is reached, and the jaxpr is
    the one of the formulation before, equation for equation."""
    op = _op(tokens, d, d, 8, None)
    assert op.serving_stats == ("experts_touched", "expert_load_max")
    assert op.held_rows_bound(tokens * 8) is None
    params, xf = _params(op), _inputs(tokens, d)
    want = str(jax.make_jaxpr(lambda p, x: _full_size(op, p, x, serving))(params, xf))
    _no_held_rows(monkeypatch)
    got = str(jax.make_jaxpr(lambda p, x: op._sorted_tokens(p, x, serving))(params, xf))
    assert got == want
    (y,), state = op.forward(params, [xf[None]], {"serving": serving}, False)
    assert sorted(state.get("stats", {})) == (
        ["expert_load_max", "experts_touched"] if serving else [])
    np.testing.assert_array_equal(
        np.asarray(y[0]), np.asarray(_full_size(op, params, xf, serving)[0]))
