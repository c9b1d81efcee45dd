"""The documents a reader starts from name only files the tree holds: a
path in backticks (or on a line of a fenced block) that begins with one of
the repo's directories, or names a root-level `*.py`, must resolve."""

import fnmatch
import functools
import os
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCUMENTS = ("README.md", "docs/DESIGN.md", "BASELINE.md")
_DIRS = ("tools/", "tpukit/", "tests/", "benchmark/", "docs/")
_FENCE = re.compile(r"^```.*?^```", re.MULTILINE | re.DOTALL)
_SPAN = re.compile(r"`([^`\n]+)`")
_ROOT_PY = re.compile(r"[\w*\-]+\.py")


def _candidates(text: str):
    """Words that may be paths: the first word of each inline code span and
    every word of the fenced blocks, line and test suffixes cut off."""
    fenced = " ".join(_FENCE.findall(text))
    words = fenced.replace("`", " ").split()
    words += [m.split()[0] for m in _SPAN.findall(_FENCE.sub("", text)) if m.strip()]
    for word in words:
        yield word.split(":")[0].rstrip(".,;)")


def _names_a_path(word: str) -> bool:
    return word.startswith(_DIRS) or _ROOT_PY.fullmatch(word) is not None


@functools.cache
def _tree() -> frozenset[str]:
    """Files and directories of the checkout, without the directories
    `.gitignore` names (a builder's ignored copy of the parent commit still
    holds what went)."""
    ignored = {".git"} | {
        line.strip().rstrip("/")
        for line in (REPO / ".gitignore").read_text().splitlines()
        if line.strip().endswith("/")
    }
    out = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in ignored]
        rel = Path(root).relative_to(REPO)
        out.update((rel / name).as_posix() for name in dirs + files)
    return frozenset(out)


def _resolves(word: str, tree: frozenset[str]) -> bool:
    word = word.rstrip("/")
    if fnmatch.filter(tree, word):
        return True
    # `gpt.py` beside a sentence about tpukit/model/ is a basename, not a path
    return "/" not in word and bool(fnmatch.filter(tree, f"*/{word}"))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_documents_name_only_files_that_exist(document):
    text = (REPO / document).read_text()
    named = sorted({w for w in _candidates(text) if _names_a_path(w)})
    assert named, f"{document} names no path at all: the scan is broken"
    tree = _tree()
    missing = [w for w in named if not _resolves(w, tree)]
    assert not missing, f"{document} names files the tree does not hold: {missing}"
