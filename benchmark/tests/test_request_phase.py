"""The three request-phase metrics (``reducers/request_phase.py``) on a
recorded stream, by hand; the reducer's own fold against the program's
(``flexflow_tpu/obs/spans.py``); nothing to read on a stream without the
stamps.  Run by hand with the rest (``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q``) and, through ``tests/test_benchmark_request_phase.py``,
in tier-1.

``data/stream.server_run.jsonl`` is the stream of a tiny model on the
CPU, recorded as ``runners/serve_closed.py`` leaves one: a warm-up run of
``Server.run`` (two requests), then the window (five requests over two
slots, K = 4, and a sixth that no bucket holds), both numbering their
requests from 0.  The window, in ms on the run's clock:

    id  admitted  prefill's end  rounds decoded in (t0 -> t1)         finished
    0   0.068     0.680          1.667->2.136                         2.329
    1   0.874     1.492          1.667->2.136, 3.030->3.432           3.592
    2   2.396     2.858          3.030->3.432, 4.408->5.015           5.154
    3   3.635     4.205          4.408->5.015                         5.190
    4   5.225     5.756          5.998->6.452, 6.601->7.114           7.239
    5   rejected (an error: not among the clean requests)
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402
from benchmark.reducers import request_phase  # noqa: E402

STREAM = os.path.join(HERE, "data", "stream.server_run.jsonl")
METRICS = {"serve_slot_wait_pct": "slot_wait_pct", "serve_token_gap_ms.p95": "token_gap_ms",
           "serve_superstep_gap_ms.p95": "superstep_gap_ms"}


def _window():
    """The events the harness hands a reducer: those from the window's
    start on, which the runner marks just before its ``Server.run``."""
    events = common.read_events(STREAM)
    t_window = [e["ts"] for e in events if e["ev"] == "serve_run"][-1]
    return events, [e for e in events if e["ts"] >= t_window]


def _reduce(name, events, **extra):
    spec = common.load_json(REPO, "benchmark", "metrics", name + ".json")
    assert spec["reducer"] == "request_phase" and spec["args"]["stat"] == METRICS[name]
    return common.load_module("reducers", spec["reducer"]).reduce(
        {**spec["args"], **extra}, {"events": events})


def test_the_three_numbers_by_hand():
    _, win = _window()
    # time in the slot, less the own prefill and the rounds decoded in, in us:
    #   0: 2261 - 612 - 469          = 1180      1: 2718 - 618 - (469 + 402) = 1229
    #   2: 2758 - 462 - (402 + 607)  = 1287      3: 1555 - 570 - 607         =  378
    #   4: 2014 - 531 - (454 + 513)  =  516
    reqs, rounds = request_phase.fold(win)
    assert {i: request_phase.slot_wait_us(r) for i, r in reqs.items()} == {
        0: 1180, 1: 1229, 2: 1287, 3: 378, 4: 516}
    assert _reduce("serve_slot_wait_pct", win) == pytest.approx(100 * 4590 / 11306)
    # longest stretch between the ends of two rounds, the own prefill's end first, in us:
    #   0: 2136 - 680 = 1456   1: max(644, 1296)   2: max(574, 1583)   3: 810   4: max(696, 662)
    # sorted 696 810 1296 1456 1583; p95 = 1456 + 0.8 * 127
    assert _reduce("serve_token_gap_ms.p95", win) == pytest.approx(1.5576)
    assert _reduce("serve_token_gap_ms.p95", win, pct=50) == pytest.approx(1.296)
    assert _reduce("serve_token_gap_ms.p95", win, pct=100) == pytest.approx(1.583)
    # one round's end to the next one's start, in us: 894 976 983 149; p95 = 976 + 0.85 * 7
    assert rounds == [(1667, 2136), (3030, 3432), (4408, 5015), (5998, 6452), (6601, 7114)]
    assert _reduce("serve_superstep_gap_ms.p95", win) == pytest.approx(0.98195)


@pytest.mark.parametrize("run", [0, 1])
def test_the_reducers_fold_is_the_programs(run):
    """On each run of the recorded stream, request by request: the
    reducer's ``slot_wait`` and rounds are ``obs/spans.py``'s
    ``slot_wait`` and ``decode``, and its life is ``e2e`` less ``queued``."""
    from flexflow_tpu.obs import spans

    events, _ = _window()
    marks = [i for i, e in enumerate(events) if e["ev"] == "serve_run"] + [len(events)]
    reqs, _ = request_phase.fold(events[marks[run]:marks[run + 1]])
    tls = spans.build_timelines(events, run)
    assert sorted(reqs) == sorted(i for i, t in tls.items() if t.error is None) and reqs
    for i, r in reqs.items():
        assert tls[i].reconciled
        assert request_phase.slot_wait_us(r) == tls[i].phase_us["slot_wait"], i
        assert r["decode"] == tls[i].phase_us["decode"], i
        assert r["prefill"] - r["start"] == tls[i].phase_us["prefill"], i
        assert r["end"] - r["start"] == tls[i].total_us - tls[i].phase_us["queued"], i


@pytest.mark.parametrize("name", list(METRICS))
def test_nothing_to_read_on_a_stream_without_the_stamps(name):
    """A program from before the stamps (this PR's parent): the metric is
    left out of the line, and nothing fails."""
    _, win = _window()
    bare = [{k: v for k, v in e.items() if k not in ("t_ms", "t0_ms", "arrival_ms", "e2e_ms")}
            for e in win if e["ev"] != "serve_run"]
    assert _reduce(name, bare) is None
    assert _reduce(name, []) is None
    assert _reduce(name, win) is not None


def test_the_metric_files_are_listed_for_the_four_serving_cells():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    serving = [m["workloads"] for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s"][0]
    for name in METRICS:
        entry = [m for m in bench["per_layer"] if m["name"] == name]
        assert len(entry) == 1 and entry[0]["workloads"] == serving, name
        assert entry[0]["moves"] == "serve_tokens_per_s" and entry[0]["better"] == "lower"
        assert entry[0]["source"] == "program_span" and entry[0]["layer"] == "Serving engine"
