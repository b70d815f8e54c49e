"""Run analytics (obs/): reader, cross-run compare, registry, device-
time attribution, CLI.

What is pinned here:

- **Reader round-trip**: ``RunLog.reconstruct_summary`` replicates
  ``Telemetry.step_summary`` bit for bit from raw events (same
  nearest-rank percentiles, same rounding), and ``summary()`` prefers
  the authoritative ``run_end`` block.
- **Exit classification**: ``clean`` / ``exception:<type>`` /
  ``preempt`` recorded on ``run_end`` by ``Telemetry.__exit__``, plus
  the one only absence can signal — ``truncated``.
- **Drift detection**: the PIPELINE_OVERHEAD.md round-6 incident (a
  ~1.5x silent box-state drift) as a checked property — a synthetic
  1.5x step-p50 pair reads ``drift:step_ms_p50``; an A/A pair reads
  ``ok``.
- **Catalog sync**: fflint FF008's dependency-free event-name copy
  must equal ``obs.events.EVENT_CATALOG`` (same precedent as
  FUSED_STEPS_CAP).
- **Attribution**: a synthetic ``.xplane.pb`` summarizes to exact
  device-ms numbers by kernel, scope and host span; a real ``--trace`` + ``--telemetry`` run folds a
  ``trace_summary`` block and ``program_cost`` events into its log.
"""

import io
import json
import os

import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.obs.compare import (
    DEFAULT_THRESHOLDS,
    compare_paths,
    compare_runs,
    paired_measure,
)
from flexflow_tpu.obs.events import (
    EVENT_CATALOG,
    KERNEL_CATALOG,
    SCOPE_CATALOG,
    SPAN_CATALOG,
)
from flexflow_tpu.obs.reader import RunLog, latest_run, resolve_run, run_files
from flexflow_tpu.obs.registry import (
    box_fingerprint,
    fingerprint_diff,
    format_history,
    history,
    index_path,
)
from flexflow_tpu.obs.trace import summarize_trace_dir
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.runtime.telemetry import Telemetry
from flexflow_tpu.runtime.trainer import Trainer


def _model(batch=8, seed=11):
    ff = FFModel(FFConfig(batch_size=batch, seed=seed))
    x = ff.create_tensor((batch, 16), name="x")
    lbl = ff.create_tensor((batch,), dtype=np.int32, name="label")
    t = ff.dense(x, 32, activation="relu", name="fc0")
    t = ff.dense(t, 4, name="head")
    ff.softmax(t, lbl, name="softmax")
    return ff


def _executor(seed=11):
    return Executor(_model(seed=seed), optimizer=SGDOptimizer(lr=0.1))


def _write_lines(path, lines):
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    return path


def _synth_log(path, run_id="run-a", step_ms_p50=2.0, step_ms_p95=2.4,
               fences_per_step=1.0, fence_ms=0.2, fingerprint=None,
               extra_summary=None):
    """A complete golden run log: run_start + steps + run_end with the
    authoritative summary/calibration blocks compare reads."""
    fp = {"git_sha": "abc1234", "jax": "0.4.37", "jaxlib": "0.4.36",
          "platform": "cpu", "devices": 8, "host": "box"}
    fp.update(fingerprint or {})
    summary = {
        "steps": 8, "fences": 8, "fences_per_step": fences_per_step,
        "step_ms_p50": step_ms_p50, "step_ms_p95": step_ms_p95,
        "step_ms_max": step_ms_p95 * 1.5,
    }
    summary.update(extra_summary or {})
    recs = [{"ts": 1.0, "seq": 1, "ev": "run_start", "run_id": run_id,
             "pid": 1, "fingerprint": fp}]
    for i in range(8):
        recs.append({"ts": 2.0 + i, "seq": 2 + i, "ev": "step", "step": i,
                     "loss": 1.0, "wall_s": step_ms_p50 / 1e3})
    recs.append({"ts": 20.0, "seq": 99, "ev": "run_end", "exit": "clean",
                 "summary": summary,
                 "calibration": {"steps": 8, "step_ms_p50": step_ms_p50,
                                 "fences_per_step": fences_per_step,
                                 "fence_ms": fence_ms,
                                 "fence_samples": 4}})
    return _write_lines(path, [json.dumps(r) for r in recs])


# -- catalog sync (satellite e) --------------------------------------------


def test_ff008_catalog_matches_event_catalog():
    # The lint rule may not import flexflow_tpu.obs: it reads the
    # catalogs out of obs/events.py's text, and a catalog that is gone
    # or no longer a literal set fails loudly.
    from flexflow_tpu.analysis.lint import (
        FF008_EVENT_NAMES, _read_catalogs, lint_source)

    assert FF008_EVENT_NAMES == EVENT_CATALOG
    for not_a_catalog in ("NO_SUCH_CATALOG", "EXIT_CLEAN"):
        with pytest.raises(ValueError, match=not_a_catalog):
            _read_catalogs(not_a_catalog)
    bad = 'tel.emit("not_a_registered_event", x=1)\n'
    vs = lint_source(bad, "flexflow_tpu/runtime/foo.py")
    assert [v.rule for v in vs] == ["FF008"]
    # The telemetry module itself (the emit implementation + run_start
    # emission) is out of scope, as are dynamic names.
    assert not lint_source(bad, "flexflow_tpu/runtime/telemetry.py")
    assert not lint_source('tel.emit(name, x=1)\n',
                           "flexflow_tpu/runtime/foo.py")


@pytest.mark.parametrize("copy,catalog,bad,ok,dynamic", [
    ("FF008_SPAN_NAMES", "SPAN_CATALOG",
     '_telemetry.span("ff/serve/made_up", id=1)\n',
     'with telemetry.span("ff/serve/admit", id=1):\n    pass\n',
     '_telemetry.span(name, id=1)\n'),
    ("FF008_KERNEL_NAMES", "KERNEL_CATALOG",
     'pl.pallas_call(k, name="ff_made_up", grid=(1,))\n',
     'pl.pallas_call(k, name="ff_flash_fwd", grid=(1,))\n',
     'pl.pallas_call(k, name=n, grid=(1,))\n'),
    ("FF008_SCOPE_NAMES", "SCOPE_CATALOG",
     'with jax.named_scope("ff_made_up"):\n    pass\n',
     'with jax.named_scope("ff_opt"), jax.named_scope("blk0_attn"):\n'
     '    pass\n',
     'with jax.named_scope(op.name):\n    pass\n'),
])
def test_ff008_trace_name_catalogs(copy, catalog, bad, ok, dynamic):
    """The same reading and the same rule for the three kinds of name a
    profiler trace is read by; a kernel library or the telemetry
    module is no exemption, a dynamic name is."""
    from flexflow_tpu.analysis import lint
    from flexflow_tpu.obs import events

    assert getattr(lint, copy) == getattr(events, catalog)
    def ff008(src, path):
        return [v for v in lint.lint_source(src, path) if v.rule == "FF008"]

    for path in ("flexflow_tpu/ops/pallas_kernels.py",
                 "flexflow_tpu/runtime/telemetry.py"):
        assert len(ff008(bad, path)) == 1
        assert not ff008(ok, path) and not ff008(dynamic, path)


# -- reader ----------------------------------------------------------------


def test_reader_roundtrip_bit_identical(tmp_path):
    with Telemetry(str(tmp_path), meta={"app": "obs-test"}) as tel:
        stats = Trainer(_executor()).fit(iterations=6, warmup=1,
                                         log_every=2)
    log = RunLog.load(tel.path)
    assert log.complete and log.exit == "clean"
    assert log.run_id == tel.run_id
    assert not log.malformed and not log.torn_tail
    assert not log.unknown_events and log.read_error is None
    # run_end's summary block is what fit folded into its stats.
    assert log.summary() == stats["telemetry"]
    # Reconstruction from raw events replicates every field it CAN
    # recover bit for bit; programs_per_step is run_end-only.
    rec = log.reconstruct_summary()
    authoritative = log.summary()
    assert set(authoritative) - set(rec) <= {"programs_per_step"}
    for k, v in rec.items():
        assert authoritative[k] == v, k
    # Step reconstruction: every index once (warmup offsets the
    # numbering to 1..iterations), losses recorded for each.
    assert sorted(log.steps()) == list(range(1, 7))
    # losses() mirrors steps() (values are None in the unfenced k=1
    # regime — per-step losses are a resilient/chaos-run artifact).
    assert sorted(log.losses()) == sorted(log.steps())
    # The box fingerprint rode along on run_start.
    assert log.fingerprint == box_fingerprint()
    assert log.run_start.get("app") == "obs-test"


def test_reader_tolerates_torn_and_malformed(tmp_path):
    path = str(tmp_path / "run-torn.jsonl")
    good = {"ts": 1.0, "seq": 1, "ev": "step", "step": 0, "loss": 1.0,
            "wall_s": 0.002}
    _write_lines(path, [
        json.dumps({"ts": 0.5, "seq": 0, "ev": "run_start",
                    "run_id": "r"}),
        json.dumps(good),
        "not json at all",                       # mid-file garbage
        json.dumps({"loss": 1.0}),               # no ev: malformed
        json.dumps({"ev": "fence", "wall_s": 0.001}),  # bare ev: kept
        json.dumps({"ts": 2.0, "seq": 3, "ev": "wild_event"}),
        '{"ts": 3.0, "seq": 4, "ev": "ru',       # torn tail
    ])
    log = RunLog.load(path)
    assert log.malformed == 2
    assert log.torn_tail
    assert log.unknown_events == ["wild_event"]
    assert len(log.events) == 4
    # ts/seq default on the bare-ev record (hand-built calibration
    # logs omit them — from_jsonl's pre-reader contract).
    bare = log.select("fence")[0]
    assert bare.ts == 0.0 and bare.seq == 2
    # No run_end arrived: the exit only absence can signal.
    assert not log.complete and log.exit == "truncated"
    # Reconstruction still works on what survived.
    assert log.summary()["steps"] == 1
    # A missing file reports, never raises.
    gone = RunLog.load(str(tmp_path / "nope.jsonl"))
    assert gone.read_error and gone.events == []


def test_exit_classification(tmp_path):
    with Telemetry(str(tmp_path / "clean")) as tel_c:
        pass
    assert RunLog.load(tel_c.path).exit == "clean"

    with pytest.raises(ValueError):
        with Telemetry(str(tmp_path / "exc")) as tel_e:
            raise ValueError("boom")
    log = RunLog.load(tel_e.path)
    assert log.complete and log.exit == "exception:ValueError"

    with Telemetry(str(tmp_path / "pre")) as tel_p:
        tel_p.emit("preempt", step=3, signum=15)
    assert RunLog.load(tel_p.path).exit == "preempt"


def test_run_selection_skips_registry_index(tmp_path):
    a = _synth_log(str(tmp_path / "run-20250101T000000Z-1-0.jsonl"))
    b = _synth_log(str(tmp_path / "run-20250102T000000Z-1-0.jsonl"),
                   run_id="run-b")
    _write_lines(str(tmp_path / "runs.jsonl"), ['{"run_id": "idx"}'])
    os.utime(a, (1, 1))  # make b unambiguously the newest
    assert run_files(str(tmp_path)) == [a, b]
    assert latest_run(str(tmp_path)) == b
    assert latest_run(str(tmp_path), exclude=b) == a
    assert resolve_run(str(tmp_path)) == b
    assert resolve_run(a) == a


# -- cross-run compare (tentpole: the round-6 sentry) ----------------------


def test_compare_aa_reads_ok(tmp_path):
    a = _synth_log(str(tmp_path / "run-a.jsonl"), run_id="A")
    b = _synth_log(str(tmp_path / "run-b.jsonl"), run_id="B")
    res = compare_paths(a, b)
    assert res.ok and res.verdict == "ok"
    assert res.fingerprint_delta == []  # same box state
    assert "verdict: ok" in res.format()


def test_compare_flags_round6_drift(tmp_path):
    # The round-6 incident: same code, same flags, ~1.5x step time
    # from silent box-state drift.  The comparator must read it.
    a = _synth_log(str(tmp_path / "run-a.jsonl"), run_id="A",
                   step_ms_p50=2.0, step_ms_p95=2.4)
    b = _synth_log(str(tmp_path / "run-b.jsonl"), run_id="B",
                   step_ms_p50=3.0, step_ms_p95=3.6,
                   fingerprint={"git_sha": "fff9999"})
    res = compare_paths(a, b)
    assert not res.ok
    assert res.verdict == "drift:step_ms_p50"
    row = {r.metric: r for r in res.rows}["step_ms_p50"]
    assert row.drifted and row.rel == pytest.approx(0.5)
    # The fingerprint delta names WHAT about the box changed.
    assert any("git_sha" in d for d in res.fingerprint_delta)
    out = res.format()
    assert "<-- DRIFT" in out and "verdict: drift:step_ms_p50" in out


def test_compare_counter_metrics_are_accounting(tmp_path):
    # fences/step is accounting, not timing: ANY change is drift.
    a = _synth_log(str(tmp_path / "run-a.jsonl"), fences_per_step=1.0)
    b = _synth_log(str(tmp_path / "run-b.jsonl"), fences_per_step=1.06)
    assert compare_paths(a, b).verdict == "drift:fences_per_step"


def test_compare_metric_in_one_run_never_drifts(tmp_path):
    # Regimes differ legitimately: a pipeline run has programs/step, a
    # full-mesh run does not — report, don't flag.
    a = _synth_log(str(tmp_path / "run-a.jsonl"),
                   extra_summary={"programs_per_step": 4.0})
    b = _synth_log(str(tmp_path / "run-b.jsonl"))
    res = compare_paths(a, b)
    assert res.ok
    row = {r.metric: r for r in res.rows}["programs_per_step"]
    assert row.a == 4.0 and row.b is None and not row.drifted


def test_compare_threshold_override(tmp_path):
    a = _synth_log(str(tmp_path / "run-a.jsonl"), step_ms_p50=2.0)
    b = _synth_log(str(tmp_path / "run-b.jsonl"), step_ms_p50=2.2)
    assert compare_runs(RunLog.load(a), RunLog.load(b)).ok  # 10% < 25%
    res = compare_runs(RunLog.load(a), RunLog.load(b),
                       thresholds={"step_ms_p50": 0.05})
    assert res.verdict == "drift:step_ms_p50"
    assert DEFAULT_THRESHOLDS["step_ms_p50"] == 0.25  # the library copy


# -- paired protocol (the measure-tool dedup) ------------------------------


def test_paired_measure_alternates_and_cancels():
    calls = []

    def leg(name, value):
        def fn(r):
            calls.append((r, name))
            return value
        return fn

    res = paired_measure(leg("a", 100.0), leg("b", 110.0), reps=4,
                         control=leg("c", 50.0))
    # Order alternates between reps: a,b then b,a (controls after).
    assert calls[0][1] == "a" and calls[1][1] == "b"
    assert calls[4][1] == "b" and calls[5][1] == "a"
    assert res.median_a == 100.0 and res.median_b == 110.0
    assert res.median_delta_pct == pytest.approx(10.0)
    assert res.median_ratio == pytest.approx(100.0 / 110.0)
    # A constant control cancels exactly: the A/A floor reads zero.
    assert res.median_aa_pct == 0.0
    assert res.median_aa_ratio == 1.0
    # Without a control the A/A columns take their neutral values.
    bare = paired_measure(leg("a", 1.0), leg("b", 2.0), reps=2)
    assert bare.median_aa_pct == 0.0 and bare.median_aa_ratio == 1.0


# -- registry --------------------------------------------------------------


def test_registry_appends_on_close_and_history(tmp_path):
    d = str(tmp_path)
    with Telemetry(d, meta={"app": "alexnet"}):
        Trainer(_executor()).fit(iterations=2, warmup=1)
    with pytest.raises(RuntimeError):
        with Telemetry(d, meta={"app": "alexnet"}):
            raise RuntimeError("chaos")
    rows = history(d)
    assert len(rows) == 2
    assert rows[0]["exit"] == "clean" and rows[0]["steps"] == 2
    assert rows[1]["exit"] == "exception:RuntimeError"
    assert rows[0]["fingerprint"] == box_fingerprint()
    assert rows[0]["meta"] == {"app": "alexnet"}
    assert rows[0]["path"].startswith("run-")
    # The index is the one non-run-log .jsonl, and the table renders.
    assert os.path.basename(index_path(d)) == "runs.jsonl"
    table = format_history(rows)
    assert "alexnet" in table and "exception:RuntimeError" in table
    assert format_history([]) == "run registry: no runs recorded"


def test_fingerprint_diff():
    a = {"git_sha": "x", "jax": "0.4.37"}
    b = {"git_sha": "y", "jax": "0.4.37"}
    assert fingerprint_diff(a, a) == []
    assert fingerprint_diff(a, b) == ["git_sha: 'x' -> 'y'"]


# -- device-time attribution ----------------------------------------------


#: A TPU trace in small: six device operations over 60 us (a named
#: kernel, the loss's forward and transpose inside a ``while`` that only
#: holds them, a merged optimizer fusion whose scope is given by
#: reference, a copy with no scope), and ``Server.run``'s spans round the
#: gaps [10,14) [30,34) and [50,52).
_XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 14000000 duration_ps: 16000000 }
    events { metadata_id: 2 offset_ps: 14000000 duration_ps: 8000000 }
    events { metadata_id: 3 offset_ps: 22000000 duration_ps: 8000000 }
    events { metadata_id: 4 offset_ps: 34000000 duration_ps: 16000000 }
    events { metadata_id: 5 offset_ps: 52000000 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "%ff_flash_decode.7 = bf16[2,2,8]{2,1,0} custom-call(s32[2]{0} %p.1)"
    stats { metadata_id: 1 str_value: "jit(decode)/blk0_attn/ff_flash_decode/pallas_call:" } } }
  event_metadata { key: 2 value { id: 2 name: "%convert_reduce_fusion = f32[8]{0} fusion(bf16[8,64]{1,0} %p.2), kind=kLoop"
    stats { metadata_id: 1 str_value: "jit(train_step)/jvp(ff_loss)/softmax/reduce_max:" } } }
  event_metadata { key: 3 value { id: 3 name: "%convert_subtract_fusion = f32[8,64]{1,0} fusion(bf16[8,64]{1,0} %p.2), kind=kLoop"
    stats { metadata_id: 1 str_value: "jit(train_step)/transpose(jvp(ff_loss))/softmax/sub:" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.7 = f32[8,16]{1,0} fusion(f32[8,16]{1,0} %p.3), kind=kLoop"
    stats { metadata_id: 1 ref_value: 2 } } }
  event_metadata { key: 5 value { id: 5 name: "%copy.9 = f32[8,16]{0,1} copy(f32[8,16]{1,0} %fusion.7)" } }
  event_metadata { key: 6 value { id: 6 name: "%while.2 = (s32[], f32[8,64]{1,0}) while((s32[], f32[8,64]{1,0}) %t.3), body=%b.1"
    stats { metadata_id: 1 str_value: "jit(train_step)/jvp(ff_loss)/softmax/while:" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "jit(train_step)/ff_opt/mul;jit(train_step)/ff_opt/add:" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 28000000 }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 31500000 duration_ps: 3500000 }
    events { metadata_id: 4 offset_ps: 38000000 duration_ps: 11000000 }
    events { metadata_id: 5 offset_ps: 49000000 duration_ps: 1500000 } }
  event_metadata { key: 1 value { id: 1 name: "ff/serve/admit" } }
  event_metadata { key: 2 value { id: 2 name: "ff/serve/prefill_dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "ff/serve/install" } }
  event_metadata { key: 4 value { id: 4 name: "ff/serve/decode_fence" } }
  event_metadata { key: 5 value { id: 5 name: "$not/a/span" } } }
"""


def _write_xplane(tmp_path, text=_XSPACE):
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "20250101"
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_trace_summary_synthetic_exact(tmp_path):
    path = _write_xplane(tmp_path)
    s = summarize_trace_dir(str(tmp_path))
    assert s["trace_file"] == path
    # busy: [0,10) [14,30) [34,50) [52,60) of a window of 60 us
    assert s["device_ms_total"] == pytest.approx(0.05)
    assert s["window_ms"] == pytest.approx(0.06)
    assert s["kernels"] == {"ff_flash_decode": {"device_ms": 0.01, "count": 1}}
    # The while is left out (its two children are counted); the merged
    # fusion's scope came by reference; the copy has none.
    assert s["scopes"] == {"ff_loss": 0.016, "ff_opt": 0.016}
    # [10,14) under prefill_dispatch (inside admit), [30,34) under
    # install, [50,52): its middle lies past decode_fence's end.
    assert s["idle_ms_by_span"] == {
        "ff/serve/prefill_dispatch": 0.004, "ff/serve/install": 0.004,
        "<none>": 0.002,
    }
    assert set(s["idle_ms_by_span"]) - {"<none>"} <= SPAN_CATALOG
    assert set(s["kernels"]) <= KERNEL_CATALOG
    assert set(s["scopes"]) <= SCOPE_CATALOG


def test_trace_summary_absent_is_none(tmp_path):
    assert summarize_trace_dir(str(tmp_path)) is None
    # ... and one that cannot be read warns and attaches nothing.
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "bad.xplane.pb").write_bytes(b"\x0a\xff\xff")
    assert summarize_trace_dir(str(tmp_path)) is None


def test_trace_and_program_cost_end_to_end(tmp_path):
    # --trace + --telemetry: the run folds device-time attribution into
    # run_end and emits program_cost at first build (cost_analysis of
    # the Lowered — compiling a second time would breach the <2% bar).
    ex = _executor()
    ex.config.trace_dir = str(tmp_path / "xprof")
    with Telemetry(str(tmp_path / "tel")) as tel:
        Trainer(ex).fit(iterations=4, warmup=1)
    log = RunLog.load(tel.path)
    costs = log.select("program_cost")
    assert len(costs) == 1  # dedup: first build only
    c = costs[0]
    assert c["kind"] == "train_step"
    assert c["flops"] > 0 and c["bytes_accessed"] > 0
    ts = log.trace_summary()
    assert ts, "run_end must carry trace_summary for a traced tel run"
    assert ts["trace_file"].endswith(".xplane.pb")
    # A CPU run has no device plane: nothing is written as device time.
    assert ts["device_ms_total"] == 0.0 and ts["kernels"] == {}
    assert ts["scopes"] == {} and ts["idle_ms_by_span"] == {}


def test_superstep_program_cost(tmp_path):
    with Telemetry(str(tmp_path)) as tel:
        Trainer(_executor()).fit(iterations=8, warmup=2, steps_per_call=4)
    costs = RunLog.load(tel.path).select("program_cost")
    assert [c["kind"] for c in costs] == ["superstep"]
    assert costs[0]["k"] == 4 and costs[0]["flops"] > 0


@pytest.mark.parametrize("kind,width", [("decode_superstep", {"k": 8}),
                                        ("spec_verify", {"d": 3})])
def test_a_serving_rounds_steps_from_its_one_event(kind, width):
    """A fused serving round no longer writes its ``k`` ``step`` lines
    (PR 40): the event that carries ``superstep`` stands for them, and
    the summary and the calibration come out as from the lines a stream
    from before wrote beside a round WITHOUT the key, to the bit."""
    from flexflow_tpu.obs.reader import round_steps
    from flexflow_tpu.search.cost_model import Calibration

    k = width.get("k") or width["d"] + 1
    walls = [0.080001, 0.064, 0.096003]
    fences = [{"ev": "fence", "label": kind, "wall_s": w / 2} for w in walls]
    new = [e for i, w in enumerate(walls) for e in (
        fences[i], {"ev": kind, **width, "superstep": i, "wall_s": w})]
    old = [e for i, w in enumerate(walls) for e in (
        [fences[i], {"ev": kind, **width, "wall_s": w}]
        + [{"ev": "step", "step": i * k + j, "wall_s": w / k} for j in range(k)])]
    assert round_steps(new[1]) == [walls[0] / k] * k and round_steps(old[1]) == []
    assert round_steps({"ev": "superstep", "k": 8, "wall_s": 1.0, "superstep": 0}) == []
    a, b = RunLog.from_events(new), RunLog.from_events(old)
    assert a.reconstruct_summary() == b.reconstruct_summary()
    assert a.reconstruct_summary()["steps"] == 3 * k
    assert vars(Calibration.from_events(new)) == vars(Calibration.from_events(old))
    # the write side keeps the same k values in memory, and no line
    with Telemetry() as tel:
        for w in walls:
            tel.fence((), kind)
            tel.record_steps(k, w / k)
        live = tel.step_summary()
    for key in ("steps", "fences", "fences_per_step", "step_ms_p50", "step_ms_p95",
                "step_ms_max"):
        assert live[key] == a.reconstruct_summary()[key], key


def test_telemetry_off_hooks_are_noops():
    from flexflow_tpu.runtime.telemetry import NULL

    assert NULL.program_cost("train_step", lambda x: x, (1,)) is None
    assert NULL.record_steps(8, 0.01) is None
    assert NULL.attach_trace_summary("/nowhere") is None


# -- CLI -------------------------------------------------------------------


def test_cli_report_compare_history(tmp_path, capsys):
    from flexflow_tpu.obs.__main__ import main

    d = str(tmp_path / "tel")
    with Telemetry(d, meta={"app": "obs-test"}) as tel:
        Trainer(_executor()).fit(iterations=4, warmup=1)

    assert main(["report", tel.path]) == 0
    out = capsys.readouterr().out
    assert f"run {tel.run_id}" in out
    assert "exit: clean" in out and "summary:" in out
    assert "fingerprint:" in out

    # A dir argument resolves to its latest run.
    assert main(["report", d]) == 0
    assert tel.run_id in capsys.readouterr().out

    a = _synth_log(str(tmp_path / "run-a.jsonl"), run_id="A")
    b = _synth_log(str(tmp_path / "run-b.jsonl"), run_id="B",
                   step_ms_p50=3.0, step_ms_p95=3.6)
    assert main(["compare", a, a]) == 0
    assert "verdict: ok" in capsys.readouterr().out
    assert main(["compare", a, b]) == 0          # report-only by default
    assert main(["compare", a, b, "--gate"]) == 1  # the CI form
    assert "drift:step_ms_p50" in capsys.readouterr().out

    assert main(["history", d]) == 0
    assert "obs-test" in capsys.readouterr().out

    # Missing inputs exit 2, distinct from the --gate drift exit 1.
    assert main(["report", str(tmp_path / "empty")]) == 2
    assert main(["compare", str(tmp_path / "gone.jsonl"), a]) == 2


def test_cli_report_truncated(tmp_path, capsys):
    from flexflow_tpu.obs.__main__ import main

    path = str(tmp_path / "run-trunc.jsonl")
    _write_lines(path, [
        json.dumps({"ts": 1.0, "seq": 1, "ev": "run_start",
                    "run_id": "t"}),
        json.dumps({"ts": 2.0, "seq": 2, "ev": "step", "step": 0,
                    "loss": 1.0, "wall_s": 0.002}),
    ])
    assert main(["report", path]) == 0
    out = capsys.readouterr().out
    assert "exit: truncated" in out
    assert "(reconstructed from events)" in out
