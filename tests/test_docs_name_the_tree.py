"""A document that sends its reader to a file the tree does not hold is
worse than none.  Every design document at the root, the verify skill
and the package's own comments and docstrings: each path they name under
``tools/``, ``tests/``, ``flexflow_tpu/`` or ``benchmark/`` exists.

``CHANGES.md``, ``ROADMAP.md``, ``ISSUE.md`` and ``PERF.md`` are not
held: what went, and when, is theirs to tell.
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_HISTORY = {"CHANGES.md", "ROADMAP.md", "ISSUE.md", "PERF.md"}
_DOCUMENTS = sorted(
    os.path.basename(p) for p in glob.glob(os.path.join(ROOT, "*.md"))
    if os.path.basename(p) not in _HISTORY
) + [".claude/skills/verify/SKILL.md"]
_PATH = re.compile(
    r"(?<![\w/.-])((?:tools|tests|flexflow_tpu|benchmark)/[\w./-]*\.(?:py|sh|json|md))\b")


def _missing(text):
    return sorted({p for p in _PATH.findall(text)
                   if not os.path.exists(os.path.join(ROOT, p))})


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_a_document_names_only_files_the_tree_holds(document):
    with open(os.path.join(ROOT, document)) as fh:
        missing = _missing(fh.read())
    assert not missing


def test_the_package_names_only_files_the_tree_holds():
    missing = {}
    for path in glob.glob(os.path.join(ROOT, "flexflow_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as fh:
            gone = _missing(fh.read())
        if gone:
            missing[os.path.relpath(path, ROOT)] = gone
    assert not missing
