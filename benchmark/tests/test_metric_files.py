"""Every per-layer metric has a file, every file an entry, and every name
a file reads from the program is one the program still registers (run by
hand with the rest: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``).

The driver refuses a traced line that lacks a metric ``BENCHMARK.json``
lists for the cell, and ``run.py`` leaves a metric out when its reducer
finds nothing to read.  So a PR that renames a kernel, a scope, a span
or a telemetry event strands the metric that read it, and the refusal
falls on the PR after (PR 25 named the kernels, PR 26 was refused for
``row_kernels_roofline``).  What a file may name is the catalog of
``flexflow_tpu/obs/events.py`` or an op of the graph its cells build;
one case a metric, so the rename fails here, in the PR that makes it.
"""

import functools
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common, manifest  # noqa: E402

METRICS = os.path.join(REPO, "benchmark", "metrics")
KERNEL_HEAD = re.compile(r"\^%([A-Za-z0-9_]+)")  # the instruction's name, as a pattern opens


@functools.lru_cache(maxsize=None)
def _bench():
    return manifest.load(REPO)


def _names():
    """The manifest's per-layer metrics and the files, either without the other too.
    Read raw: this runs as the file is collected, where a manifest ``check`` refuses must not exit."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    return sorted(listed | {f[:-len(".json")] for f in os.listdir(METRICS)})


@functools.lru_cache(maxsize=None)
def _op_names(cell_name):
    """The names of the ops the cell's graph holds (each is a ``named_scope``)."""
    cell, config, traffic, _, family = common.load_cell(_bench(), cell_name)
    if hasattr(family, "build_serve") and "slots" in traffic:
        ff = family.build_serve(config, traffic)[0]
    else:
        ff = family.build_train(config, traffic, cell["chips"])[0]
    return frozenset(op.name for op in ff.layers)


def faults(spec, cells):
    """What of the program ``spec`` names that the program does not have."""
    from flexflow_tpu.obs import events

    try:
        reducer = common.load_module("reducers", spec["reducer"])
    except (ImportError, SystemExit) as e:  # SystemExit: a name ``load_module`` will not import
        return [f"reducer {spec['reducer']!r}: {e!r}"]
    if not callable(getattr(reducer, "reduce", None)):
        return [f"reducer {spec['reducer']!r} has no reduce()"]
    out, args = [], spec.get("args", {})
    if spec["reducer"] == "roofline_share":
        for k in args["kernels"]:
            for p in k["patterns"]:
                head = KERNEL_HEAD.match(p)
                if not head or head.group(1) not in events.KERNEL_CATALOG:
                    out.append(f"kernel pattern {p!r} opens with no name of KERNEL_CATALOG")
    elif spec["reducer"] == "trace_scope_time":
        for s in args["scopes"]:
            if s not in events.SCOPE_CATALOG and not all(s in _op_names(c) for c in cells):
                out.append(f"scope {s!r} is not in SCOPE_CATALOG and not an op of every cell listed")
    elif spec["reducer"] == "span_idle":
        out += [f"span {s!r} is not in SPAN_CATALOG" for s in args["spans"]
                if s not in events.SPAN_CATALOG]
    elif spec["reducer"] in ("telemetry_stat", "telemetry_ratio"):
        if args["event"] not in events.EVENT_CATALOG:
            out.append(f"event {args['event']!r} is not in EVENT_CATALOG")
    return out


@pytest.mark.parametrize("name", _names())
def test_metric_file_reads_what_the_program_has(name):
    entries = [m for m in _bench()["per_layer"] if m["name"] == name]
    assert len(entries) == 1, f"benchmark/metrics/{name}.json has no entry in BENCHMARK.json"
    path = os.path.join(METRICS, name + ".json")
    assert os.path.isfile(path), f"BENCHMARK.json lists {name}, and {path} is not there"
    spec = common.load_json(path)
    assert set(spec) <= {"reducer", "args"} and "reducer" in spec
    cells = entries[0].get("workloads") or [w["name"] for w in _bench()["workloads"]]
    assert faults(spec, tuple(cells)) == []


@pytest.mark.parametrize("spec,cells,why", [
    # the three metrics PR 27 took out, as they stood: the enclosing jit's name, not the kernel's
    ({"reducer": "roofline_share", "args": {"costs": "dlrm", "kernels": [{"cost": "gather_rows", "patterns": [
        "^%sparse_train_step\\S* = f32\\[\\d+,1,\\d+\\]\\S* custom-call\\("]}]}}, ("dlrm.random.b1024",), "kernel"),
    ({"reducer": "roofline_share", "args": {"costs": "gpt2", "kernels": [{"cost": "flash_decode", "patterns": [
        "^%closed_call\\S* = bf16\\[\\d+,\\d+,\\d+\\]\\S* custom-call\\("]}]}}, ("gpt2m.serve.closed48",), "kernel"),
    ({"reducer": "roofline_share", "args": {"costs": "gpt2", "kernels": [{"cost": "flash_fwd", "patterns": [
        "^%jvp_blk\\d+_attn_\\S* = \\(bf16\\[[\\d,]+\\]\\S* f32\\[[\\d,]+\\]\\S*\\) custom-call\\("]}]}},
     ("gpt2m.train.b8s1024",), "kernel"),
    ({"reducer": "trace_scope_time", "args": {"scopes": ["embeddings"]}}, ("gpt2m.train.b8s1024",), "scope"),
    ({"reducer": "trace_scope_time", "args": {"scopes": ["ff_optimizer"]}}, ("dlrm.random.b1024",), "scope"),
    ({"reducer": "span_idle", "args": {"spans": ["ff/serve/admit", "ff/serve/sample"]}},
     ("gpt2m.serve.closed48",), "span"),
    ({"reducer": "telemetry_ratio", "args": {"event": "decode_step", "field": "active", "over": "capacity"}},
     ("gpt2m.serve.closed48",), "event"),
    ({"reducer": "no_such_reducer", "args": {}}, ("dlrm.random.b1024",), "reducer"),
])
def test_the_guard_refuses_a_stranded_name(spec, cells, why):
    found = faults(spec, cells)
    assert len(found) == 1 and found[0].startswith(why), found
