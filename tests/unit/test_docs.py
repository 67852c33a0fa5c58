"""The documents name only files that exist.

Every backticked word ending in ``.py`` (a ``:line`` or ``::name`` may follow)
whose first path segment is a top-level directory of the repository or a
sub-package of ``deepspeedsyclsupport_tpu/`` must resolve, at the root or under
the package. A document that sends its reader to a deleted file fails here.
"""
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PACKAGE = os.path.join(REPO, "deepspeedsyclsupport_tpu")
TOP_LEVEL = ("tools", "benchmark", "tests", "deepspeedsyclsupport_tpu",
             "examples")
DOCUMENTS = ["README.md", ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

_WORD = re.compile(r"^([\w./-]+\.py)(?::\d+(?:-\d+)?|::[\w:.\[\]-]+)?$")


def python_paths(text):
    """The ``dir/.../file.py`` words inside backticks (inline spans and
    fenced blocks alike), less any ``:line`` / ``::name`` suffix."""
    spans = text.replace("```", "`").split("`")[1::2]
    roots = set(TOP_LEVEL) | {
        d for d in os.listdir(PACKAGE)
        if os.path.isdir(os.path.join(PACKAGE, d))}
    found = []
    for span in spans:
        for word in span.split():
            m = _WORD.match(word.strip("()[]{},;'\""))
            if m and m.group(1).split("/")[0] in roots:
                found.append(m.group(1))
    return found


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_files_that_exist(document):
    with open(os.path.join(REPO, document)) as f:
        paths = python_paths(f.read())
    assert paths, f"{document}: no file reference found (extraction broke?)"
    missing = sorted({p for p in paths
                      if not os.path.exists(os.path.join(REPO, p))
                      and not os.path.exists(os.path.join(PACKAGE, p))})
    assert not missing, f"{document} names files that do not exist: {missing}"
