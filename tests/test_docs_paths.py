"""Every ``*.py`` path a document names in backticks exists in the tree.

A document that names a module outlives it otherwise: the deletion of a
module has to reach the prose. ``ROADMAP.md``, ``CHANGES.md`` and
``ADVICE.md`` are history and are not cases.
"""

from __future__ import annotations

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# What git commits that holds Python: scratch copies of other trees
# (.gitignore) are not the tree.
SOURCE_DIRS = ("dynamo_tpu", "tests", "benchmark", "docs", "deploy", "recipes")
DOCUMENTS = ["README.md", "PERF.md", "benchmark/README.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "design_docs", "*.md"))
)
# `pkg/mod.py`, `mod.py::name`, `mod.py:12`, `python pkg/mod.py --flag`
_PATH = re.compile(r"(?<![\w./-])([\w.-]+(?:/[\w.-]+)*\.py)\b")


def _tree() -> set:
    files = {n for n in os.listdir(ROOT) if n.endswith(".py")}
    for top in SOURCE_DIRS:
        for d, _, names in os.walk(os.path.join(ROOT, top)):
            files.update(
                os.path.relpath(os.path.join(d, n), ROOT)
                for n in names if n.endswith(".py")
            )
    return files


def named_paths(text: str) -> set:
    """The ``*.py`` paths inside the backticked spans of ``text``."""
    spans = re.findall(r"`([^`\n]+)`", text)
    return {m for span in spans for m in _PATH.findall(span)}


@pytest.mark.parametrize("document", DOCUMENTS)
def test_named_python_paths_exist(document):
    tree = _tree()
    with open(os.path.join(ROOT, document)) as f:
        named = named_paths(f.read())
    # A document may shorten a path from the left (`ops/attention.py`,
    # `runner.py`): it exists if some file's path ends with it.
    missing = sorted(
        p for p in named
        if not any(t == p or t.endswith("/" + p) for t in tree)
    )
    assert not missing, f"{document} names files that do not exist: {missing}"


def test_named_paths_reads_suffixes_and_commands():
    text = "see `ops/a.py::f`, `b.py:12` and `python tools/c.py --x`; not d.py"
    assert named_paths(text) == {"ops/a.py", "b.py", "tools/c.py"}
