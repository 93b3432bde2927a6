"""The documents name only what the tree holds.

README.md, every ``docs/*.md`` and the verify skill's notes are read
by people who then open the files they name. A path in backticks that
no longer exists, or an instruction to run a measurement script that
is gone, is a false statement: these tests go red the moment a file is
deleted under a document that names it.
"""

import functools
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = sorted(
    ["README.md", ".claude/skills/verify/SKILL.md"]
    + ["docs/" + f for f in os.listdir(os.path.join(ROOT, "docs"))
       if f.endswith(".md")])

#: a backticked token is a repository path when it starts with one of
#: these directories, or is a bare root-level file name of these kinds
_DIRS = ("rafiki_tpu/", "benchmark/", "tests/", "scripts/", "docs/",
         "examples/")
_ROOT_FILE = re.compile(r"^[A-Za-z0-9_.\-]+\.(py|json|md)$")
_SKIP = set("*<{$")


@functools.lru_cache(maxsize=None)
def _read(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        return f.read()


def _named_paths(text):
    """(path, test name or None) of every backticked repository path."""
    for token in re.findall(r"`([^`\n]+)`", text):
        token = token.strip()
        if _SKIP & set(token) or " " in token:
            continue
        path, _, test = token.partition("::")
        path = re.sub(r":[0-9][0-9,\-]*$", "", path)  # a :line suffix
        if path.startswith(_DIRS) or _ROOT_FILE.match(path):
            yield path, test or None


def _holds(path, test):
    full = os.path.join(ROOT, path)
    if not os.path.exists(full):
        return False
    return test is None or re.search(
        rf"^(def )?{re.escape(test)}\b", _read(path), re.M) is not None


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_every_path_a_document_names_exists(doc):
    """Files exist, and ``tests/x.py::name`` is a name that file
    defines at its top level."""
    missing = sorted({f"{p}::{t}" if t else p
                      for p, t in _named_paths(_read(doc))
                      if not _holds(p, t)})
    assert not missing, f"{doc} names what the tree does not hold"


def test_documents_quote_no_cpu_fallback_measurement():
    """The pre-chip measurement system is gone: no document tells a
    reader to set its variables, or cites a number by the provenance
    its records carried."""
    stale = [doc for doc in DOCUMENTS
             if re.search(r"RAFIKI_BENCH_|cpu-fallback", _read(doc))]
    assert not stale
