"""fflint — the framework-invariant static analyzer (ANALYSIS.md).

ONE audit surface, two layers:

- **AST lint** (:mod:`~flexflow_tpu.analysis.lint`): repo-wide rules
  FF001–FF008 (FF002 and FF007 retired) encoding the repo's code
  invariants as checkable
  properties, with inline ``# fflint: disable=FF0xx`` suppression.
  Imports no jax — runs anywhere, instantly.
- **Program audit** (:mod:`~flexflow_tpu.analysis.program_audit`):
  traces every registered op and executor family on the 8-dev virtual
  mesh and verifies the properties the AST cannot see —
  AD-reachability (FFP001), purity (FFP002), donation (FFP003),
  dispatch/fence accounting (FFP004), catalog coverage (FFP000) — plus
  the relocated post-SPMD HLO collective audit
  (:mod:`~flexflow_tpu.analysis.hlo`, FFH001).

CLI: ``python -m flexflow_tpu.analysis`` (``tools/fflint``).
``--fast`` = AST + trace-only audit (< 60 s, wired into
``tools/tier1_smoke.sh``); the default additionally compiles for the
donation/HLO checks and cross-checks one live pipeline step against
the telemetry counters.  Exit 0 = clean.

This is the correctness gate the eligibility-widening and shard_map
roadmap items run behind: both touch exactly the invariants audited
here.
"""

from flexflow_tpu.analysis.lint import (  # noqa: F401
    RULES,
    RULES_BY_ID,
    Violation,
    format_report as format_lint_report,
    lint_paths,
    lint_source,
)
from flexflow_tpu.analysis.program_audit import (  # noqa: F401
    ProgramViolation,
    audit_executor,
    audit_repo,
    audit_serving,
    format_report as format_audit_report,
    summary_line,
)
