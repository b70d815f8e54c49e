"""The four set-up metrics (``reducers/program_builds.py``) on a
recorded stream, by hand; nothing to read on a stream without the event;
a tiny cell on the CPU whose traced line carries them.  Run by hand with
the rest (``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``) and,
through ``tests/test_benchmark_program_builds.py``, in tier-1.

``data/stream.program_builds.jsonl`` is the stream of ``tiny.gpt2.serve``
on the CPU, its second run over a cache of the checkout's own, as
``runners/serve_closed.py`` leaves one, less the lines no reader here
looks at (requests, fences, prefills): the backlog (``init`` traced by
``jax.eval_shape``, ``jit(make)`` the seeded weights), the warm-up run
(three prefill buckets, the install, the superstep, each probed by
``program_cost`` before its first call, and one ``small`` line of two
eager programs), then the window's ``serve_run`` and its rounds.  Two
lines were added by hand (``by_hand``): a ``jit(prefill)`` lowered and
compiled inside the window, which set-up must leave out.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402
from benchmark.reducers import program_builds  # noqa: E402
from benchmark.tests import sandbox  # noqa: E402

STREAM = os.path.join(HERE, "data", "stream.program_builds.jsonl")
METRICS = {"setup_trace_lower_s": "trace_lower_s", "setup_compile_s": "compile_s",
           "setup_cache_misses": "cache_misses", "setup_cost_probe_s": "cost_probe_s"}


def _reduce(name, path):
    spec = common.load_json(REPO, "benchmark", "metrics", name + ".json")
    assert spec["reducer"] == "program_builds" and spec["args"]["stat"] == METRICS[name]
    return common.load_module("reducers", spec["reducer"]).reduce(
        spec["args"], {"result": {"telemetry_path": path}, "events": []})


def _write(tmp_path, events):
    path = str(tmp_path / "stream.jsonl")
    with open(path, "w") as f:
        f.writelines(json.dumps(e) + "\n" for e in events)
    return path


def test_set_up_is_the_backlog_and_what_ended_before_the_windows_run():
    events = common.read_events(STREAM)
    builds, probes = program_builds.setup_events(events)
    assert [(b["phase"], b.get("fun")) for b in builds if b.get("backlog")] == [
        ("trace", "init"), ("lower", "jit(make)"), ("compile", "jit(make)")]
    cut = [e["ts"] for e in events if e["ev"] == "serve_run"][-1]
    assert all(b["t0"] < b["t1"] < events[0]["ts"] for b in builds if b.get("backlog"))
    live = [b for b in builds if not b.get("backlog")]
    assert [b["phase"] for b in live] == ["small"] + ["lower", "compile"] * 5
    assert all(events[0]["ts"] < b["t1"] <= cut for b in live)
    assert not any(b.get("by_hand") for b in builds)
    assert sum(1 for e in events if e.get("by_hand")) == 2
    assert [p["kind"] for p in probes] == ["prefill"] * 3 + ["decode_superstep"]


def test_the_four_numbers_by_hand():
    # lower (trace_s + wall_s): jit(make) 0.469191 + 0.20623, jit(prefill) x3
    # 0.08532 + 0.212463, 0.076113 + 0.225479, 0.12973 + 0.221266, jit(install)
    # 0.006811 + 0.007976, jit(superstep) 0.101685 + 0.100369 = 1.842633;
    # trace: init 0.092985; small: 0.009544
    assert _reduce("setup_trace_lower_s", STREAM) == pytest.approx(1.842633 + 0.092985 + 0.009544)
    # compile: 0.072469 + 0.02565 + 0.004594 + 0.02385 + 0.039927 + 0.031919; small 0.0044
    assert _reduce("setup_compile_s", STREAM) == pytest.approx(0.198409 + 0.0044)
    # every compile of set-up was a hit (the by-hand miss is in the window)
    assert _reduce("setup_cache_misses", STREAM) == 0.0
    # probes: 0.337284 + 0.339943 + 0.388023 + 0.257287
    assert _reduce("setup_cost_probe_s", STREAM) == pytest.approx(1.322537)


def test_a_miss_is_counted_whatever_it_is_called(tmp_path):
    events = common.read_events(STREAM)
    for e in events:
        if e.get("fun") == "jit(install)" and e["phase"] == "compile":
            e["cache"] = "off"
        if e.get("phase") == "small":
            e["misses"] = 2
    assert _reduce("setup_cache_misses", _write(tmp_path, events)) == 3.0


def test_a_stream_that_opens_at_the_window_is_all_backlog(tmp_path):
    """A training cell's: no ``serve_run``, every build before ``run_start``."""
    events = [e for e in common.read_events(STREAM)
              if e["ev"] in ("run_start", "run_end") or e.get("backlog")]
    path = _write(tmp_path, events)
    assert _reduce("setup_trace_lower_s", path) == pytest.approx(0.092985 + 0.469191 + 0.20623)
    assert _reduce("setup_compile_s", path) == pytest.approx(0.072469)
    assert _reduce("setup_cost_probe_s", path) == 0.0  # a number, not nothing
    # a build with neither mark (made live, in the window) is not set-up
    events.insert(-1, {"ts": events[-1]["ts"], "seq": 99, "ev": "program_build", "fun": "jit(f)",
                       "phase": "compile", "wall_s": 5.0, "cache": "miss", "t0": 1.0, "t1": 6.0})
    assert _reduce("setup_compile_s", _write(tmp_path, events)) == pytest.approx(0.072469)


@pytest.mark.parametrize("name", list(METRICS))
def test_nothing_to_read_on_a_stream_without_the_event(name, tmp_path):
    """A program from before the event (this PR's parent): the metric is
    left out of the line, and nothing fails."""
    bare = [e for e in common.read_events(STREAM) if e["ev"] != "program_build"]
    assert _reduce(name, _write(tmp_path, bare)) is None
    assert _reduce(name, None) is None  # a timed training run writes no file
    assert _reduce(name, STREAM) is not None


def test_the_metric_files_are_listed_as_the_issue_says():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    serving = [m["workloads"] for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s"][0]
    cells = [w["name"] for w in bench["workloads"]]
    for name in METRICS:
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["moves"] == "setup_s" and entry["better"] == "lower"
        assert entry["source"] == "program_counter"
        assert entry["workloads"] == (serving if name == "setup_cost_probe_s" else cells), name


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``sandbox.make``'s copy with the tiny cells put on the four metrics'
    lists in the copy's own ``BENCHMARK.json``."""
    root = sandbox.make(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    for m in bench["per_layer"]:
        if m["name"] in METRICS:
            m["workloads"] += ["tiny.gpt2.serve"] + (
                [] if m["name"] == "setup_cost_probe_s" else ["tiny.gpt2.train"])
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root


@pytest.mark.parametrize("cell", ["tiny.gpt2.serve", "tiny.gpt2.train"])
def test_a_tiny_cells_traced_line_carries_the_metrics(copy, cell):
    p = sandbox.run_cell(copy, cell, seed=2**31 + 53, trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    got = sandbox.last_line(p)["metrics"]
    names = [n for n in METRICS if cell.endswith("serve") or n != "setup_cost_probe_s"]
    assert set(names) <= set(got) and "nothing to read" not in p.stdout
    assert got["setup_trace_lower_s"]["value"] > 0 and got["setup_compile_s"]["value"] > 0
    assert got["setup_trace_lower_s"]["unit"] == "s"
    assert got["setup_cache_misses"]["unit"] == "count"
    # the first run under a new path compiles everything: nothing is a hit
    assert got["setup_cache_misses"]["value"] >= 2
    if cell.endswith("serve"):
        # the probe before a program's first call is where it is traced and lowered
        assert 0 < got["setup_cost_probe_s"]["value"]
    setup_s = float(next(line for line in p.stdout.splitlines() if line.startswith("[run] boot_s"))
                    .split("setup_s ")[1].split()[0])
    assert got["setup_trace_lower_s"]["value"] + got["setup_compile_s"]["value"] < setup_s
