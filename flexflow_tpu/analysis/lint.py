"""fflint layer 1: AST rules encoding the repo's code invariants.

Each rule is a checkable code property with a stable id, a one-line
rationale naming the hazard it enforces, and inline suppression::

    dangerous_call()  # fflint: disable=FF001
    # fflint: disable-file=FF005   (anywhere in the file, whole file)

The rules are deliberately AST-based: docstrings and comments cannot
trigger them (the ``block_until_ready`` reference in
``runtime/trainer.py`` prose is not a violation; a call is).  This
module imports no jax so the lint layer runs anywhere, instantly.

Rule catalog (ANALYSIS.md has the full rationale table):

- FF001 ``block_until_ready`` on a runtime path — the repo has ONE
  fence idiom, ``jax.device_get`` of a small output: it waits for the
  program, hands the host the value it needs anyway, and is the call
  telemetry counts (``Telemetry.fence``).  A second idiom splits the
  fence accounting.
- FF003 host time / host RNG (``time.*``, ``np.random``, stdlib
  ``random``) inside a jit-traced function — traced once, frozen
  forever; breaks replay determinism.
- FF005 ``pallas_call`` outside ``ops/pallas_kernels.py`` — kernels
  without AD rules must stay behind the audited reachability choke
  points.
- FF006 ``build_superstep``/``build_decode_superstep`` in a module
  that never references the fused-step bound
  (``clamp_fused_steps``/``MAX_STEPS_PER_CALL``) — the bound has one
  owner; an unclamped k builds a chain the host cannot see into until
  its one fence.
- FF008 telemetry ``emit`` with an unregistered event name — every
  event type must be a row in the OBSERVABILITY.md schema table
  (``obs/events.py::EVENT_CATALOG``); an ad-hoc name is silent
  schema drift the reader cannot validate.  The same rule holds the
  names a profiler trace is read by: a ``span(`` literal
  (``SPAN_CATALOG``), a ``pallas_call(name=`` literal
  (``KERNEL_CATALOG``) and an ``ff_*`` ``named_scope`` literal
  (``SCOPE_CATALOG``).

FF002 (named ``jax.devices("tpu")`` lookup), FF004 (bare stdout writes
in a one-JSON-line benchmark script) and FF007 (``timeout=`` in
``tools/``) are retired with their code: the first and the last guarded
a forwarding service that is gone (on a sealed machine with a budget a
time limit is right, not a hazard), the second a script that went with
PR 47.  The ids are not reused.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

#: The fused-step bound (kept in sync with
#: ``runtime/trainer.py::MAX_STEPS_PER_CALL`` by
#: ``tests/test_analysis.py`` — lint must not import the runtime).
FUSED_STEPS_CAP = 20

_SUPPRESS_RE = re.compile(r"#\s*fflint:\s*disable=([A-Z0-9,\s]+)")
_SUPPRESS_FILE_RE = re.compile(r"#\s*fflint:\s*disable-file=([A-Z0-9,\s]+)")

#: Names whose reference marks a module as bound-aware (FF006).
_CAP_NAMES = frozenset({
    "clamp_fused_steps", "MAX_STEPS_PER_CALL", "MAX_DECODE_STEPS_PER_CALL",
})

#: Sanctioned homes of raw ``pallas_call`` (FF005): the kernel library.
PALLAS_ALLOWLIST = (
    "flexflow_tpu/ops/pallas_kernels.py",
)


@dataclasses.dataclass
class Violation:
    rule: str
    path: str          # repo-relative
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


@dataclasses.dataclass
class Rule:
    id: str
    title: str
    rationale: str     # one line, names the CLAUDE.md/ROADMAP hazard
    applies: Callable[[str], bool]          # repo-relative path -> bool
    check: Callable[[ast.AST, str], List[Tuple[int, str]]]


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression (``a.b.c`` -> "a.b.c")."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_test(path: str) -> bool:
    return path.startswith("tests/") or os.path.basename(path).startswith(
        "test_"
    )


# -- FF001 ------------------------------------------------------------------

def _check_block_until_ready(tree: ast.AST, path: str):
    out = []
    msg = ("block_until_ready on a runtime path: the one fence idiom "
           "is jax.device_get of a small output (Telemetry.fence "
           "counts it)")
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and \
                node.attr == "block_until_ready":
            out.append((node.lineno, msg))
        elif isinstance(node, ast.Name) and \
                node.id == "block_until_ready":
            # `from jax import block_until_ready` + bare-name call.
            out.append((node.lineno, msg))
        elif isinstance(node, ast.ImportFrom) and any(
                a.name == "block_until_ready" for a in node.names):
            out.append((node.lineno, msg))
    return out


# -- FF003 ------------------------------------------------------------------

_HOST_IMPURE_PREFIXES = (
    "time.time", "time.perf_counter", "time.monotonic",
    "np.random.", "numpy.random.", "random.",
)


def _is_jit_expr(node: ast.AST) -> bool:
    """``jax.jit`` / ``jit`` / ``functools.partial(jax.jit, ...)``."""
    name = _dotted(node)
    if name in ("jax.jit", "jit"):
        return True
    if isinstance(node, ast.Call) and _dotted(node.func) in (
            "functools.partial", "partial"):
        return bool(node.args) and _is_jit_expr(node.args[0])
    return False


def _traced_functions(tree: ast.AST) -> List[ast.AST]:
    """Function defs the lint treats as jit-traced: decorated with jit,
    or passed directly to a ``jax.jit(...)`` call as the first argument
    (resolved to a def in the same module; never to a method, which no
    bare name reaches).  A static approximation — the program audit
    (layer 2) checks the real traced programs."""
    methods = {id(f) for c in ast.walk(tree) if isinstance(c, ast.ClassDef)
               for f in c.body}
    defs: Dict[str, ast.AST] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and id(node) not in methods:
            defs.setdefault(node.name, node)
    traced: List[ast.AST] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_jit_expr(d) for d in node.decorator_list):
                traced.append(node)
        elif isinstance(node, ast.Call) and _is_jit_expr(node.func):
            if node.args and isinstance(node.args[0], ast.Name):
                fn = defs.get(node.args[0].id)
                if fn is not None:
                    traced.append(fn)
    return traced


def _check_host_impurity_in_jit(tree: ast.AST, path: str):
    out = []
    seen: Set[int] = set()
    for fn in _traced_functions(tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            if any(
                name == p.rstrip(".") or name.startswith(p)
                for p in _HOST_IMPURE_PREFIXES
            ) and not name.startswith("jax."):
                if node.lineno in seen:
                    continue
                seen.add(node.lineno)
                out.append((node.lineno,
                            f"host-impure call {name!r} inside a "
                            f"jit-traced function: traced once, frozen "
                            f"into the compiled program"))
    return out


# -- FF005 ------------------------------------------------------------------

def _check_pallas_confinement(tree: ast.AST, path: str):
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr == "pallas_call":
            out.append((node.lineno,
                        "raw pallas_call outside ops/pallas_kernels.py: "
                        "kernels without AD rules must stay behind the "
                        "audited choke points (sparse protocol / serving "
                        "decode)"))
        elif isinstance(node, ast.ImportFrom):
            # Raw jax pallas only — the repo's own wrapper library
            # (ops/pallas_kernels) IS the sanctioned import surface.
            if node.module and "pallas" in node.module \
                    and node.module.startswith("jax."):
                out.append((node.lineno,
                            f"import of {node.module!r} outside the "
                            f"kernel library (FF005 confinement)"))
    return out


# -- FF006 ------------------------------------------------------------------

def _module_is_cap_aware(tree: ast.AST) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and node.id in _CAP_NAMES:
            return True
        if isinstance(node, ast.Attribute) and node.attr in _CAP_NAMES:
            return True
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                if alias.name in _CAP_NAMES:
                    return True
    return False


def _check_unclamped_superstep_k(tree: ast.AST, path: str):
    builders = ("build_superstep", "build_decode_superstep")
    calls = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            if name.split(".")[-1] in builders:
                calls.append(node)
    if not calls:
        return []
    cap_aware = _module_is_cap_aware(tree)
    out = []
    for node in calls:
        k = node.args[0] if node.args else None
        if k is None:
            for kw in node.keywords:
                if kw.arg == "k":
                    k = kw.value
        if isinstance(k, ast.Constant) and isinstance(k.value, int) \
                and k.value <= FUSED_STEPS_CAP:
            continue  # literal under the cap: safe by inspection
        if cap_aware:
            continue  # module clamps through the bound's one owner
        out.append((node.lineno,
                    "superstep/decode k flows into a scan build without "
                    "passing the fused-step bound (clamp_fused_steps / "
                    "MAX_STEPS_PER_CALL)"))
    return out


# -- FF008 ------------------------------------------------------------------

def _read_catalogs(*names: str) -> List[frozenset]:
    """The name catalogs of ``flexflow_tpu/obs/events.py``, read out of
    its text: lint imports nothing of the package, and each catalog
    there is ``NAME = frozenset({...literals...})``.  One that is
    missing, or no longer a literal, fails here, at import."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "obs", "events.py")
    with open(path) as fh:
        tree = ast.parse(fh.read(), path)
    found: Dict[str, frozenset] = {}
    for node in tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
            continue
        name, call = _dotted(node.targets[0]), node.value
        if name not in names:
            continue
        if not (isinstance(call, ast.Call) and len(call.args) == 1
                and _dotted(call.func) == "frozenset"):
            raise ValueError(
                f"{path}:{node.lineno}: {name} is not frozenset({{...}}) "
                f"of literals; FF008 reads it as text")
        found[name] = frozenset(ast.literal_eval(call.args[0]))
    missing = [n for n in names if n not in found]
    if missing:
        raise ValueError(f"{path}: no catalog named {missing}")
    return [found[n] for n in names]


#: The registered telemetry event names, and the names a profiler trace
#: is read by: ``obs/events.py``'s own sets.
(FF008_EVENT_NAMES, FF008_SPAN_NAMES, FF008_KERNEL_NAMES,
 FF008_SCOPE_NAMES) = _read_catalogs(
    "EVENT_CATALOG", "SPAN_CATALOG", "KERNEL_CATALOG", "SCOPE_CATALOG")

#: Receiver names that mark an ``.emit(...)`` call as a telemetry
#: emission (vs some unrelated emit API).
_TELEMETRY_RECEIVERS = frozenset({"tel", "telemetry", "_telemetry"})


def _is_telemetry_emit(node: ast.Call) -> bool:
    if not isinstance(node.func, ast.Attribute) or node.func.attr != "emit":
        return False
    recv = node.func.value
    if isinstance(recv, ast.Name):
        return recv.id in _TELEMETRY_RECEIVERS
    if isinstance(recv, ast.Call):
        # `_telemetry.current().emit(...)` / `telemetry.current().emit(...)`
        return _dotted(recv.func).split(".")[-1] == "current"
    return False


def _check_emit_event_names(tree: ast.AST, path: str):
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not _is_telemetry_emit(node):
            continue
        if not node.args:
            continue
        name = node.args[0]
        if not isinstance(name, ast.Constant) or \
                not isinstance(name.value, str):
            continue  # dynamic name: the reader flags it at read time
        if name.value not in FF008_EVENT_NAMES:
            out.append((node.lineno,
                        f"unregistered telemetry event {name.value!r}: "
                        f"every emitted name must be a row in the "
                        f"OBSERVABILITY.md schema table "
                        f"(obs/events.py EVENT_CATALOG)"))
    return out


def _literal(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _check_trace_names(tree: ast.AST, path: str):
    """The other three catalogs: what a ``span(`` opens, what a
    ``pallas_call`` is named, what an ``ff_*`` scope is called."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func).split(".")[-1]
        first = _literal(node.args[0]) if node.args else None
        if callee == "span" and first is not None \
                and first not in FF008_SPAN_NAMES:
            out.append((node.lineno,
                        f"unregistered host span {first!r} "
                        f"(obs/events.py SPAN_CATALOG)"))
        elif callee == "named_scope" and first is not None \
                and first.startswith("ff_") \
                and first not in FF008_SCOPE_NAMES:
            out.append((node.lineno,
                        f"unregistered device scope {first!r} "
                        f"(obs/events.py SCOPE_CATALOG)"))
        elif callee == "pallas_call":
            for kw in node.keywords:
                name = _literal(kw.value) if kw.arg == "name" else None
                if name is not None and name not in FF008_KERNEL_NAMES:
                    out.append((node.lineno,
                                f"unregistered kernel name {name!r} "
                                f"(obs/events.py KERNEL_CATALOG)"))
    return out


def _check_registered_names(tree: ast.AST, path: str):
    out = _check_trace_names(tree, path)
    if path != "flexflow_tpu/runtime/telemetry.py":
        out += _check_emit_event_names(tree, path)
    return out


RULES: List[Rule] = [
    Rule(
        "FF001", "block_until_ready on a runtime path",
        "one fence idiom: jax.device_get of a small output waits for "
        "the program and is what Telemetry.fence counts",
        lambda p: p.endswith(".py") and not _is_test(p),
        _check_block_until_ready,
    ),
    Rule(
        "FF003", "host time/RNG inside a jit-traced function",
        "traced-once host values freeze into the compiled program and "
        "break deterministic replay (RESILIENCE.md)",
        lambda p: p.endswith(".py") and not _is_test(p),
        _check_host_impurity_in_jit,
    ),
    Rule(
        "FF005", "pallas_call outside the kernel library",
        "kernels without AD rules are reachable only via the sparse "
        "protocol or serving programs (CLAUDE.md design invariant)",
        lambda p: p.endswith(".py") and p not in PALLAS_ALLOWLIST
        and not _is_test(p),
        _check_pallas_confinement,
    ),
    Rule(
        "FF006", "unclamped superstep/decode k",
        "the fused-step bound (k <= 20) has one owner: scan builds "
        "must pass clamp_fused_steps",
        lambda p: p.endswith(".py") and not _is_test(p),
        _check_unclamped_superstep_k,
    ),
    Rule(
        "FF008", "unregistered telemetry event name",
        "OBSERVABILITY.md: the name catalogs (obs/events.py) are the "
        "schema; an ad-hoc event, span, kernel or ff_ scope name is "
        "silent drift no reader finds",
        lambda p: p.endswith(".py") and not _is_test(p),
        _check_registered_names,
    ),
]

RULES_BY_ID: Dict[str, Rule] = {r.id: r for r in RULES}


def _suppressions(source: str) -> Tuple[Dict[int, Set[str]], Set[str]]:
    """(line -> suppressed rule ids, file-level suppressed ids)."""
    per_line: Dict[int, Set[str]] = {}
    file_level: Set[str] = set()
    for i, line in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_FILE_RE.search(line)
        if m:
            file_level.update(
                s.strip() for s in m.group(1).split(",") if s.strip()
            )
            continue
        m = _SUPPRESS_RE.search(line)
        if m:
            per_line.setdefault(i, set()).update(
                s.strip() for s in m.group(1).split(",") if s.strip()
            )
    return per_line, file_level


def lint_source(
    source: str,
    path: str,
    rules: Optional[Sequence[Rule]] = None,
) -> List[Violation]:
    """Lint one file's source under its repo-relative ``path``."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Violation("FF000", path, e.lineno or 0,
                          f"syntax error: {e.msg}")]
    per_line, file_level = _suppressions(source)
    out: List[Violation] = []
    for rule in (rules if rules is not None else RULES):
        if not rule.applies(path):
            continue
        for line, msg in rule.check(tree, path):
            if rule.id in file_level or rule.id in per_line.get(line, ()):
                continue
            out.append(Violation(rule.id, path, line, msg))
    return sorted(out, key=lambda v: (v.path, v.line, v.rule))


def repo_root() -> str:
    """The repo root: the directory holding the ``flexflow_tpu``
    package this module lives in."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))


def iter_python_files(root: Optional[str] = None) -> List[str]:
    root = root or repo_root()
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [
            d for d in dirnames
            # ``chiprun_out``, ``_checkout``/``_parent``/``_scratch``:
            # the chip tool's output, the git-archive copies of this
            # tree and its parent, a builder's probes (.gitignore
            # lists all four).
            if d not in ("__pycache__", ".git", ".claude", "ckpts",
                         "chiprun_out", "_checkout", "_parent", "_scratch")
        ]
        for f in filenames:
            if f.endswith(".py"):
                out.append(os.path.join(dirpath, f))
    return sorted(out)


def lint_paths(
    paths: Optional[Sequence[str]] = None,
    root: Optional[str] = None,
) -> List[Violation]:
    """Lint files (absolute or repo-relative paths; default: the whole
    repo).  Rule scopes match on repo-relative paths."""
    root = root or repo_root()
    files = [
        p if os.path.isabs(p) else os.path.join(root, p)
        for p in (paths if paths else iter_python_files(root))
    ]
    out: List[Violation] = []
    for f in files:
        rel = os.path.relpath(f, root)
        try:
            with open(f) as fh:
                src = fh.read()
        except OSError as e:
            out.append(Violation("FF000", rel, 0, f"unreadable: {e}"))
            continue
        out.extend(lint_source(src, rel))
    return out


def format_report(violations: Sequence[Violation]) -> str:
    if not violations:
        return "fflint: clean"
    lines = [str(v) for v in violations]
    lines.append(f"fflint: {len(violations)} violation(s)")
    return "\n".join(lines)
