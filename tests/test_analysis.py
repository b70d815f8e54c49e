"""fflint — the framework-invariant static analyzer (ANALYSIS.md).

Layer 1 (AST rules): every rule has a positive test (a planted
violation in a temp module is caught) and rides the repo-wide negative
(the current repo is clean — which also pins the repo clean forever).
Layer 2 (program audit): planted violations — a VJP-less pallas op on
the training path, a host callback inside a compiled-pipeline step,
an undonated "donated" program — are flagged; the clean audit over
every registered op and executor family is the acceptance run.
"""

import importlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.analysis import lint
from flexflow_tpu.analysis import program_audit as pa
from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel
from flexflow_tpu.ops.base import Op
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.runtime.executor import Executor


def _ids(violations):
    return sorted({v.rule for v in violations})


# ---------------------------------------------------------------------------
# Layer 1: AST rules — planted positives
# ---------------------------------------------------------------------------


class TestLintRules:
    def test_fused_steps_cap_matches_runtime(self):
        from flexflow_tpu.runtime.trainer import MAX_STEPS_PER_CALL

        assert lint.FUSED_STEPS_CAP == MAX_STEPS_PER_CALL

    def test_ff001_block_until_ready(self):
        src = "import jax\njax.block_until_ready(x)\n"
        assert "FF001" in _ids(lint.lint_source(src, "planted.py"))
        # Method form too.
        src = "y = f(x).block_until_ready()\n"
        assert "FF001" in _ids(lint.lint_source(src, "planted.py"))

    def test_ff001_from_import_alias_is_caught(self):
        """Review finding: `from jax import block_until_ready` + a
        bare-name call must not evade the rule."""
        src = (
            "from jax import block_until_ready\n"
            "block_until_ready(x)\n"
        )
        vs = lint.lint_source(src, "planted.py")
        assert "FF001" in _ids(vs)
        assert any(v.line == 2 for v in vs)

    def test_ff001_docstring_reference_is_not_a_violation(self):
        src = '"""block_until_ready is mentioned in prose."""\n'
        assert lint.lint_source(src, "planted.py") == []

    def test_ff001_skips_tests(self):
        src = "import jax\njax.block_until_ready(x)\n"
        assert "FF001" not in _ids(
            lint.lint_source(src, "tests/test_planted.py")
        )

    def test_ff003_host_impurity_in_jit(self):
        src = (
            "import time, jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x * time.time()\n"
        )
        assert "FF003" in _ids(lint.lint_source(src, "planted.py"))
        # Call form: jax.jit(g) marks g as traced.
        src = (
            "import numpy as np, jax\n"
            "def g(x):\n"
            "    return x + np.random.rand()\n"
            "h = jax.jit(g)\n"
        )
        assert "FF003" in _ids(lint.lint_source(src, "planted.py"))
        # jax.random inside jit is the sanctioned RNG.
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(key):\n"
            "    return jax.random.normal(key, (4,))\n"
        )
        assert "FF003" not in _ids(lint.lint_source(src, "planted.py"))
        # Host time OUTSIDE jit is fine (the trainer does it).
        src = "import time\ndef f():\n    return time.time()\n"
        assert "FF003" not in _ids(lint.lint_source(src, "planted.py"))
        # jax.jit(g) names the def in scope, never a method called g.
        src = (
            "import time, jax\n"
            "class E:\n"
            "    def g(self):\n"
            "        return time.time()\n"
            "def build():\n"
            "    def g(x):\n"
            "        return x + 1\n"
            "    return jax.jit(g)\n"
        )
        assert "FF003" not in _ids(lint.lint_source(src, "planted.py"))

    def test_ff005_pallas_confinement(self):
        src = (
            "from jax.experimental import pallas as pl\n"
            "y = pl.pallas_call(k, out_shape=s)(x)\n"
        )
        vs = _ids(lint.lint_source(src, "flexflow_tpu/ops/linear.py"))
        assert "FF005" in vs
        # The kernel library is exempt.
        for exempt in lint.PALLAS_ALLOWLIST:
            assert "FF005" not in _ids(lint.lint_source(src, exempt))
        # Review finding: the repo's OWN wrapper library is the
        # sanctioned import surface — not a confinement violation.
        ok = (
            "from flexflow_tpu.ops.pallas_kernels import flash_decode\n"
            "from flexflow_tpu.ops import pallas_kernels as pk\n"
        )
        assert "FF005" not in _ids(
            lint.lint_source(ok, "flexflow_tpu/ops/attention.py")
        )

    def test_ff006_unclamped_superstep_k(self):
        bad = "fn = ex.build_superstep(k)\n"
        assert "FF006" in _ids(lint.lint_source(bad, "planted.py"))
        bad = "fn = sex.build_decode_superstep(steps)\n"
        assert "FF006" in _ids(lint.lint_source(bad, "planted.py"))
        # Literal at/under the cap is safe by inspection.
        ok = f"fn = ex.build_superstep({lint.FUSED_STEPS_CAP})\n"
        assert "FF006" not in _ids(lint.lint_source(ok, "planted.py"))
        # Literal ABOVE the cap is not.
        bad = f"fn = ex.build_superstep({lint.FUSED_STEPS_CAP + 1})\n"
        assert "FF006" in _ids(lint.lint_source(bad, "planted.py"))
        # A module that clamps through the bound's one owner is clean.
        ok = (
            "from flexflow_tpu.runtime.trainer import clamp_fused_steps\n"
            "k = clamp_fused_steps(k)\n"
            "fn = ex.build_superstep(k)\n"
        )
        assert "FF006" not in _ids(lint.lint_source(ok, "planted.py"))

    def test_retired_rules_stay_retired(self):
        """FF002 (named tpu lookup) and FF007 (timeout= in tools/)
        guarded a forwarding service that is gone: their planted cases
        are clean now, and the ids are not reused."""
        assert "FF002" not in lint.RULES_BY_ID
        assert "FF007" not in lint.RULES_BY_ID
        src = 'import jax\nd = jax.devices("tpu")\n'
        assert lint.lint_source(src, "planted.py") == []
        src = "import subprocess\nsubprocess.run([cmd], timeout=30)\n"
        assert lint.lint_source(src, "tools/planted.py") == []

    def test_ff008_unregistered_event_name(self):
        bad = 'tel.emit("made_up_event", x=1)\n'
        assert "FF008" in _ids(lint.lint_source(bad, "planted.py"))
        bad = '_telemetry.current().emit("nope")\n'
        assert "FF008" in _ids(lint.lint_source(bad, "planted.py"))
        # Registered names, dynamic names, unrelated emit APIs: clean.
        ok = 'tel.emit("fault", mode="raise", step=2)\n'
        assert "FF008" not in _ids(lint.lint_source(ok, "planted.py"))
        ok = "tel.emit(name, x=1)\n"
        assert "FF008" not in _ids(lint.lint_source(ok, "planted.py"))
        ok = 'signal_bus.emit("made_up_event")\n'
        assert "FF008" not in _ids(lint.lint_source(ok, "planted.py"))
        # The emitter module itself is the one sanctioned home.
        assert "FF008" not in _ids(lint.lint_source(
            bad, "flexflow_tpu/runtime/telemetry.py"
        ))
        # The catalog is obs/events.py's own, read as text (tests/test_obs.py).
        assert "run_start" in lint.FF008_EVENT_NAMES

    def test_planted_violation_in_temp_module(self, tmp_path):
        """End-to-end through lint_paths: a temp module on disk."""
        mod = tmp_path / "planted.py"
        mod.write_text("import jax\njax.block_until_ready(x)\n")
        vs = lint.lint_paths([str(mod)], root=str(tmp_path))
        assert _ids(vs) == ["FF001"]
        assert vs[0].path == "planted.py"
        assert vs[0].line == 2


class TestSuppression:
    def test_inline_suppression_round_trip(self):
        bad = "import jax\njax.block_until_ready(x)\n"
        assert "FF001" in _ids(lint.lint_source(bad, "planted.py"))
        ok = (
            "import jax\n"
            "jax.block_until_ready(x)  # fflint: disable=FF001\n"
        )
        assert lint.lint_source(ok, "planted.py") == []
        # The WRONG id does not suppress.
        still_bad = (
            "import jax\n"
            "jax.block_until_ready(x)  # fflint: disable=FF003\n"
        )
        assert "FF001" in _ids(lint.lint_source(still_bad, "planted.py"))

    def test_file_level_suppression(self):
        src = (
            "# fflint: disable-file=FF001\n"
            "import jax\n"
            "jax.block_until_ready(x)\n"
            "jax.block_until_ready(y)\n"
        )
        assert lint.lint_source(src, "planted.py") == []

    def test_multi_id_suppression(self):
        src = (
            "import jax\n"
            "fn = ex.build_superstep(jax.block_until_ready(k))"
            "  # fflint: disable=FF001,FF006\n"
        )
        assert lint.lint_source(src, "planted.py") == []


class TestRepoClean:
    def test_repo_is_lint_clean(self):
        """The negative test for every rule at once — and the gate
        that keeps the repo clean: a new violation anywhere fails
        here with its file:line."""
        vs = lint.lint_paths()
        assert vs == [], "\n" + lint.format_report(vs)

    def test_rule_catalog_is_documented(self):
        """Every rule carries a rationale naming its hazard, and
        ANALYSIS.md documents every rule id."""
        import os

        for rule in lint.RULES:
            assert rule.rationale, rule.id
        doc = open(os.path.join(lint.repo_root(), "ANALYSIS.md")).read()
        for rule in lint.RULES:
            assert rule.id in doc, f"{rule.id} missing from ANALYSIS.md"
        for rid in ("FFP000", "FFP001", "FFP002", "FFP003", "FFP004",
                    "FFH001"):
            assert rid in doc, f"{rid} missing from ANALYSIS.md"


# ---------------------------------------------------------------------------
# Layer 2: program audit — planted violations
# ---------------------------------------------------------------------------


def _tiny_cfg(b=8):
    cfg = FFConfig(batch_size=b)
    cfg.num_devices = 8
    return cfg


class _VjplessPallasOp(Op):
    """A pallas kernel with NO AD rule on the training path — the
    exact violation FFP001 exists to catch (interpret mode, CPU-safe;
    the primitive lands in the jaxpr either way)."""

    def __init__(self, name, x):
        super().__init__(name, [x])
        self._make_output(x.shape, x.dtype, x.dim_axes)

    def forward(self, params, xs, state, training):
        from jax.experimental import pallas as pl  # fflint: disable=FF005

        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        (x,) = xs
        y = pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True,
        )(x)  # fflint: disable=FF005
        return [y], state


class _CallbackOp(Op):
    """A host callback inside the op forward — the FFP002 violation
    (reintroduces the per-dispatch host round-trip)."""

    def __init__(self, name, x):
        super().__init__(name, [x])
        self._make_output(x.shape, x.dtype, x.dim_axes)

    def forward(self, params, xs, state, training):
        (x,) = xs
        jax.debug.print("x sum {}", jnp.sum(x))
        return [x * 1.0], state


def _model_with(op_cls, name="bad"):
    ff = FFModel(_tiny_cfg())
    x = ff.create_tensor((8, 8), name="x")
    lbl = ff.create_tensor((8, 8), name="label")
    t = ff.dense(x, 8, name="fc0")
    op = op_cls(name, t)
    ff.layers.append(op)
    ff.mse_loss(op.outputs[0], lbl, name="mse")
    return ff


class TestProgramAuditPlanted:
    def test_vjpless_pallas_on_training_path_is_flagged(self):
        ff = _model_with(_VjplessPallasOp)
        ex = Executor(ff)
        vs = pa.audit_executor(ex)
        assert any(v.rule == "FFP001" for v in vs), [str(v) for v in vs]
        # Attribution names the offending op.
        assert any(v.op == "bad" for v in vs if v.rule == "FFP001")

    def test_sparse_keys_exempts_the_kernel(self):
        """The sparse-protocol escape hatch: the same jaxpr is clean
        when the owning op declares sparse_keys (ops/base.py)."""
        ff = _model_with(_VjplessPallasOp)
        ex = Executor(ff)
        params, _opt, state = ex._abstract_init()
        batch = ex._abstract_batch()

        def fwd(p, s, b):
            return ex.forward(p, s, b, training=True)[0]

        jaxpr = jax.make_jaxpr(fwd)(params, state, batch)
        flagged = pa.ad_reachability_violations(
            jaxpr, "t", ["bad"], sparse_ok=[]
        )
        assert any(v.rule == "FFP001" for v in flagged)
        exempt = pa.ad_reachability_violations(
            jaxpr, "t", ["bad"], sparse_ok=["bad"]
        )
        assert exempt == []
        # Serving programs are exempt wholesale (forward-only).
        assert pa.ad_reachability_violations(
            jaxpr, "t", ["bad"], serving=True
        ) == []

    def test_custom_vjp_wrapped_pallas_is_sanctioned(self):
        """The flash-attention pattern: pallas under custom_vjp."""
        from jax.experimental import pallas as pl  # fflint: disable=FF005

        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        def raw(x):
            return pl.pallas_call(
                kern, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=True,
            )(x)  # fflint: disable=FF005

        @jax.custom_vjp
        def wrapped(x):
            return raw(x)

        wrapped.defvjp(lambda x: (raw(x), None), lambda _, g: (2.0 * g,))
        jaxpr = jax.make_jaxpr(wrapped)(jnp.ones((8, 8)))
        assert pa.ad_reachability_violations(jaxpr, "t") == []

    def test_host_callback_in_compiled_step_is_flagged(self):
        """A host callback planted inside a COMPILED pipeline step."""
        from flexflow_tpu.runtime.pipeline import PipelineExecutor

        ff = FFModel(_tiny_cfg(16))
        x = ff.create_tensor((16, 8), name="x")
        lbl = ff.create_tensor((16, 8), name="label")
        t = ff.dense(x, 8, name="l0")
        op = _CallbackOp("cb", t)
        ff.layers.append(op)
        t2 = ff.dense(op.outputs[0], 8, name="l1")
        ff.mse_loss(t2, lbl, name="mse")
        store = StrategyStore(8)
        store.set("l0", ParallelConfig(n=4, device_ids=(0, 1, 2, 3)))
        store.set("l1", ParallelConfig(n=4, device_ids=(4, 5, 6, 7)))
        pipe = PipelineExecutor(ff, store, microbatches=2, compiled=True)
        vs = pa.audit_executor(pipe)
        assert any(v.rule == "FFP002" for v in vs), [str(v) for v in vs]

    def test_callback_in_full_mesh_train_step_is_flagged(self):
        ff = _model_with(_CallbackOp, name="cb")
        ex = Executor(ff)
        vs = pa.audit_executor(ex)
        assert any(v.rule == "FFP002" for v in vs), [str(v) for v in vs]

    def test_dropped_donation_is_flagged(self):
        """An undonated jit of the same step fails FFP003; the real
        (donated) train step passes."""
        ff = pa._conv_graph()
        ex = Executor(ff)
        params, opt, state = ex._abstract_init()
        batch = ex._abstract_batch()
        undonated = jax.jit(ex.build_train_step())
        vs = pa.donation_violations(
            undonated, "planted", (params, opt, state),
            params, opt, state, batch,
        )
        assert [v.rule for v in vs] == ["FFP003"]
        ok = pa.donation_violations(
            ex.train_step, "real", (params, opt, state),
            params, opt, state, batch,
        )
        assert ok == []

    def test_coverage_rule_fires_on_missing_op(self):
        partial = [("conv", pa._conv_graph())]
        vs = pa.coverage_violations(partial)
        assert vs and all(v.rule == "FFP000" for v in vs)
        missing = " ".join(v.message for v in vs)
        assert "LSTM" in missing and "MultiHeadAttention" in missing


class TestDispatchAccounting:
    def test_formulas_agree_with_schedule(self):
        """2*S*ceil(m/c) — the cost model, the schedule builder and
        the executor must all derive the same count."""
        assert pa._exec_config_programs_per_step(2, 4, 1, False) == 16
        assert pa._exec_config_programs_per_step(2, 4, 2, False) == 8
        assert pa._exec_config_programs_per_step(4, 8, 3, False) == 24
        assert pa._exec_config_programs_per_step(2, 4, 1, True) == 1.0
        assert pa._exec_config_programs_per_step(
            2, 4, 1, True, 8
        ) == pytest.approx(1 / 8)

    def test_live_pipeline_counters_match(self):
        """One real host-driven step and one compiled step on the
        virtual mesh must land exactly on the formulas (the telemetry
        cross-check of the full audit)."""
        assert pa._accounting_live_violations() == []


class TestAuditRepoClean:
    def test_fast_audit_is_clean(self):
        """The acceptance negative: every registered op and every
        executor family (full-mesh, pipeline host-driven, pipeline
        compiled, serving), trace-only layer."""
        vs = pa.audit_repo(fast=True)
        assert vs == [], "\n" + pa.format_report(vs)

    @pytest.mark.slow
    def test_full_audit_is_clean(self):
        """Compile-level layer: donation, HLO collectives, live
        telemetry accounting."""
        vs = pa.audit_repo(fast=False)
        assert vs == [], "\n" + pa.format_report(vs)

    def test_summary_line(self):
        assert pa.summary_line([]) == "audit: clean"
        v = pa.ProgramViolation("FFP001", "p", "m")
        assert "FFP001" in pa.summary_line([v])


# ---------------------------------------------------------------------------
# Migration: one audit surface
# ---------------------------------------------------------------------------


class TestAuditMigration:
    def test_runtime_audit_retired_with_pointer(self):
        # The deprecation shim served its cycle; a stale import must
        # now fail LOUDLY, naming the relocated surface.
        sys.modules.pop("flexflow_tpu.runtime.audit", None)
        with pytest.raises(ImportError, match="analysis.hlo"):
            importlib.import_module("flexflow_tpu.runtime.audit")
        sys.modules.pop("flexflow_tpu.runtime.audit", None)

    def test_hlo_family_reachable_from_analysis(self):
        from flexflow_tpu.analysis.hlo import collective_stats

        stats = collective_stats(
            "%ag = f32[16,128]{1,0} all-gather(%x), dimensions={0}"
        )
        assert len(stats) == 1 and stats[0].opcode == "all-gather"


# ---------------------------------------------------------------------------
# CLI + dry-run wiring
# ---------------------------------------------------------------------------


class TestCli:
    def test_lint_only_cli_exits_zero(self, capsys):
        from flexflow_tpu.analysis.__main__ import main

        assert main(["--lint-only"]) == 0
        assert "fflint: clean" in capsys.readouterr().out

    def test_lint_only_cli_exits_nonzero_on_violation(self, tmp_path,
                                                      capsys):
        from flexflow_tpu.analysis.__main__ import main

        mod = tmp_path / "planted.py"
        mod.write_text("import jax\njax.block_until_ready(x)\n")
        assert main(["--lint-only", str(mod)]) == 1
        assert "FF001" in capsys.readouterr().out


class TestDryRunAudit:
    def test_training_dry_run_prints_audit_verdict(self, capsys):
        from flexflow_tpu.apps.common import _dry_run

        ff = pa._conv_graph()
        ex = Executor(ff)
        stats = _dry_run(ff, ex, None)
        out = capsys.readouterr().out
        assert "audit: clean" in out
        assert stats["audit_violations"] == 0

    def test_dry_run_audit_event_lands_in_telemetry(self, tmp_path,
                                                    capsys):
        import json

        from flexflow_tpu.apps.common import _dry_run
        from flexflow_tpu.runtime import telemetry as _telemetry

        ff = pa._conv_graph()
        ex = Executor(ff)
        with _telemetry.Telemetry(directory=str(tmp_path)) as tel:
            _dry_run(ff, ex, None)
            path = tel.path
        events = [json.loads(l) for l in open(path)]
        ev = [e for e in events if e["ev"] == "analysis"]
        assert len(ev) == 1 and ev[0]["clean"] is True
