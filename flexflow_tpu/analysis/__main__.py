"""``python -m flexflow_tpu.analysis`` — run fflint (ANALYSIS.md).

Usage::

    python -m flexflow_tpu.analysis            # lint + full audit
    python -m flexflow_tpu.analysis --fast     # lint + trace-only audit
    python -m flexflow_tpu.analysis --lint-only [paths...]
    python -m flexflow_tpu.analysis --audit-only

Exit status 0 = clean, 1 = violations.  The program audit runs on the
8-device virtual CPU mesh and never touches an accelerator.
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="fflint",
        description="framework-invariant static analyzer "
                    "(AST rules + traced-program audit)",
    )
    ap.add_argument("--fast", action="store_true",
                    help="trace-only program audit (no compiles; the "
                         "tier-1 smoke layer, < 60 s)")
    ap.add_argument("--lint-only", action="store_true")
    ap.add_argument("--audit-only", action="store_true")
    ap.add_argument("paths", nargs="*",
                    help="files to lint (default: the whole repo)")
    args = ap.parse_args(argv)

    # The virtual CPU mesh must be forced BEFORE any jax import can
    # initialize a backend (the flags parse once, and the audit must
    # not take the chip).
    from flexflow_tpu.analysis.program_audit import ensure_cpu_mesh

    if not args.lint_only:
        ensure_cpu_mesh()

    from flexflow_tpu.analysis import lint

    rc = 0
    if not args.audit_only:
        t0 = time.perf_counter()
        vs = lint.lint_paths(args.paths or None)
        print(lint.format_report(vs))
        print(f"lint: {len(lint.iter_python_files()) if not args.paths else len(args.paths)} "
              f"files in {time.perf_counter() - t0:.1f}s", file=sys.stderr)
        if vs:
            rc = 1

    if not args.lint_only:
        from flexflow_tpu.analysis import program_audit

        t0 = time.perf_counter()
        pvs = program_audit.audit_repo(fast=args.fast)
        print(program_audit.format_report(pvs))
        print(f"program audit ({'fast' if args.fast else 'full'}): "
              f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
        if pvs:
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
