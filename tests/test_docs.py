"""A document names only files the tree holds.

Every backticked word of ``README.md`` and ``docs/*.md`` that reads as a path
of this repository (it begins with one of the top-level directories, or is a
root-level ``*.py`` or a root-level name in capitals with an extension) must
exist; a pattern must match something. ``docs/changelog.md`` is the history
of earlier rounds and names files that have gone since.
"""

import glob
import os
import re

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
    if os.path.basename(p) != "changelog.md"
)

_DIRS = ("horovod_tpu/", "benchmark/", "tools/", "tests/", "examples/",
         "docs/", "cpp/")
_ROOT_PY = re.compile(r"^[\w*<>{},-]+\.py$")
_ROOT_CAPS = re.compile(r"^[A-Z][A-Z0-9]*([._][\w*<>{},-]+)*\.[a-z]+$")


def _upstream(word):
    """A citation of the reference tree, not of this one."""
    return (word.endswith(".rst") or word.startswith("horovod/")
            or "/root/reference/" in word)


def named_paths(text):
    """The words inside backticks that read as paths of this repository,
    cut at ``:`` (a line, a test's name) and ``#`` (an anchor)."""
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = re.split(r"[:#]", word.strip("()[]\"',;"))[0].rstrip(".,")
            if not word or _upstream(word):
                continue
            if word.startswith(_DIRS) or (
                "/" not in word
                and (_ROOT_PY.match(word) or _ROOT_CAPS.match(word))
            ):
                yield word


def missing(text):
    out = []
    for word in sorted(set(named_paths(text))):
        # <name> and {a,b} stand for any name
        pattern = re.sub(r"<[^>]*>|\{[^}]*\}", "*", word)
        if not glob.glob(os.path.join(REPO, pattern)):
            out.append(word)
    return out


@pytest.mark.parametrize("doc", DOCS)
def test_a_document_names_only_files_the_tree_holds(doc):
    with open(os.path.join(REPO, doc)) as f:
        gone = missing(f.read())
    assert not gone, f"{doc} names files the tree does not hold: {gone}"
