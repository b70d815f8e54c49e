"""The readers PR 25 added, on a small synthetic trace in the style of
``data/trace.small.xspace.txt`` (run by hand with the rest:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``).

``data/trace.spans.xspace.txt``: eight device operations over a window
of 100 us, five idle gaps with known owners, the eight ``ff/serve/*``
spans nested as ``Server.run`` opens them, and the scope of each
operation where the compiler keeps it: a ``tf_op`` stat of the event's
metadata record (one given by reference, one a merged pair, one the
parameter's name on a copy, one absent).
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common, manifest, trace_names, trace_read  # noqa: E402

S = "ff/serve/"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from jax.profiler import ProfileData

    text = open(os.path.join(HERE, "data", "trace.spans.xspace.txt")).read()
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace_read.load(str(path))


def _rctx(trace, platform="tpu"):
    return {"platform": platform, "trace": trace, "window_ns": trace_read.window_ns(trace)}


def _metric(name):
    spec = common.load_json(REPO, "benchmark", "metrics", name + ".json")
    return common.load_module("reducers", spec["reducer"]), spec["args"]


def test_the_harness_reads_the_same_trace_as_before(trace):
    """``trace_read`` keeps the ``bench/`` spans alone, and gives the
    whole of the idle time to them."""
    assert trace_read.window_ns(trace) == (0.0, 100000.0)
    assert {s.name for s in trace.host_spans} == {"bench/window", "bench/server_run"}
    gaps = dict(map(tuple, trace_read.idle_gaps(trace.devices[0], trace.host_spans, 0.0, 100000.0)))
    # [10,14) [30,34) [50,52) [70,80) [90,100): one owner for all of it, as in the ledger's PR 24 line
    assert gaps == {"bench/server_run": pytest.approx(30e-6)}


def test_host_spans_and_their_innermost_pieces(trace):
    spans = trace_names.host_spans(trace.path)
    assert {s.name for s in spans} == {S + n for n in (
        "admit", "prefill_dispatch", "prefill_fence", "install", "decode_pack", "decode_dispatch",
        "decode_fence", "bookkeep")}
    pieces = trace_names.innermost(spans)
    assert all(a < b for a, b, _ in pieces) and all(x[1] <= y[0] for x, y in zip(pieces, pieces[1:]))
    at = lambda us: trace_names.owner(pieces, us * 1000.0)
    assert at(8.5) == S + "admit" and at(12) == S + "prefill_dispatch" and at(20) == S + "prefill_fence"
    assert at(31.2) == S + "admit" and at(32) == S + "install" and at(35.5) == S + "admit"
    assert at(36.5) == S + "decode_pack" and at(51) == S + "decode_fence" and at(75) == S + "bookkeep"
    assert at(5) is None and at(95) is None


@pytest.mark.parametrize("metric,want", [
    ("serve_idle_pct.admit", 8.0),      # [10,14) under prefill_dispatch, [30,34) under install
    ("serve_idle_pct.decode", 2.0),     # [50,52) under decode_fence
    ("serve_idle_pct.bookkeep", 10.0),  # [70,80)
])
def test_span_idle(trace, metric, want):
    reducer, args = _metric(metric)
    assert reducer.reduce(args, _rctx(trace)) == pytest.approx(want)
    assert reducer.reduce(args, _rctx(trace, "cpu")) is None  # never a device number from a rehearsal


def test_a_gap_under_no_span_is_in_none_of_the_three(trace):
    from benchmark.reducers import span_idle

    lo, hi = trace_read.window_ns(trace)
    acc = span_idle.idle_by_span(trace.devices[0], trace_names.host_spans(trace.path), lo, hi)
    assert acc.pop(None) == pytest.approx(10000.0)  # [90,100): under bench/server_run alone
    assert sum(acc.values()) == pytest.approx(20000.0)
    assert sum(acc.values()) + 10000.0 == pytest.approx(
        (hi - lo) - trace_read.busy_seconds(trace_read.clip(trace.devices[0], lo, hi)) * 1e9)
    three = sum(_metric(m)[0].reduce(_metric(m)[1], _rctx(trace)) for m in (
        "serve_idle_pct.admit", "serve_idle_pct.decode", "serve_idle_pct.bookkeep"))
    assert three == pytest.approx(20.0)  # of an idle share of 30%


def test_scopes_from_the_event_metadata(trace):
    scopes = trace_names.op_scopes(trace.path)
    assert list(scopes) == ["/device:TPU:0"]
    by_head = {k.split(" = ")[0]: v for k, v in scopes["/device:TPU:0"].items()}
    assert by_head == {
        "%fusion.1": "jit(train_step)/jvp(blk0_mlp)/dot_general",
        "%ff_flash_fwd.2": "jit(train_step)/jvp(blk0_attn)/ff_flash_fwd/pallas_call",
        "%while.2": "jit(train_step)/jvp(ff_loss)/softmax/while",
        "%convert_reduce_fusion": "jit(train_step)/jvp(ff_loss)/softmax/reduce_max",
        "%convert_subtract_fusion": "jit(train_step)/transpose(jvp(ff_loss))/softmax/sub",
        "%fusion.7": "jit(train_step)/ff_opt/mul;jit(train_step)/ff_opt/add",  # by reference
        "%copy.3": "params['embeddings']['tables']",
    }  # %copy.9 carries none
    assert trace_names.under("jit(f)/transpose(jvp(ff_loss))/softmax/sub", ["ff_loss"])
    assert trace_names.under("jit(f)/a/b;jit(f)/ff_opt/c", ["ff_opt"])
    assert trace_names.under("params['embeddings']['tables']", ["embeddings"])
    assert not trace_names.under("jit(f)/ff_loss_extra/x", ["ff_loss"])


@pytest.mark.parametrize("metric,want", [
    ("train_loss_share_pct", 16.0),   # forward and transpose; the while that holds them is left out
    ("train_opt_share_pct", 14.0),    # the merged operation
    ("embed_share_pct.dlrm", 10.0),   # the compiler's copy of the table, by the parameter's name
])
def test_trace_scope_time(trace, metric, want):
    reducer, args = _metric(metric)
    assert reducer.reduce(args, _rctx(trace)) == pytest.approx(want)
    assert reducer.reduce(dict(args, scopes=["no_such_scope"]), _rctx(trace)) is None
    assert reducer.reduce(args, _rctx(trace, "cpu")) is None


def test_kernel_patterns_find_the_named_call(trace):
    spec = common.load_json(REPO, "benchmark", "metrics", "kernel_roofline.flash_attn.json")
    ops = trace.devices[0]
    got = {k["cost"]: trace_read.op_seconds(ops, k["patterns"]) for k in spec["args"]["kernels"]}
    assert got["flash_fwd"] == (pytest.approx(16e-6), 1) and got["flash_dq"][1] == got["flash_dkv"][1] == 0


@pytest.mark.parametrize("events,want", [
    ([{"ev": "decode_superstep", "active": 24, "capacity": 48}, {"ev": "decode_superstep", "active": 48, "capacity": 48},
      {"ev": "prefill", "active": 1, "capacity": 1}], 75.0),
    ([{"ev": "decode_superstep", "active": 24}], None),  # a program from before ``capacity``
    ([], None),
])
def test_occupancy(events, want):
    reducer, args = _metric("serve_occupancy_pct")
    got = reducer.reduce(args, {"events": events})
    assert got == (pytest.approx(want) if want is not None else None)


def test_manifest_loads_with_the_new_entries():
    bench = manifest.load(REPO)
    new = ["serve_idle_pct.admit", "serve_idle_pct.decode", "serve_idle_pct.bookkeep", "serve_occupancy_pct",
           "kernel_roofline.flash_attn", "kernel_roofline.flash_decode", "kernel_roofline.row_kernels",
           "train_opt_share_pct", "train_loss_share_pct", "embed_share_pct.dlrm"]
    assert [m["name"] for m in bench["per_layer"]][-len(new):] == new  # appended, in this order
    for m in bench["per_layer"][-len(new):]:
        spec = json.load(open(os.path.join(REPO, "benchmark", "metrics", m["name"] + ".json")))
        assert set(spec) == {"reducer", "args"}
        assert hasattr(common.load_module("reducers", spec["reducer"]), "reduce")
