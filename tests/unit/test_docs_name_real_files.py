"""The README and ``docs/`` send a reader only to files the tree has."""

import functools
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
DOCS = [REPO / "README.md", *sorted((REPO / "docs").glob("*.md"))]
ROOTS = ("automodel_tpu/", "benchmarks/", "examples/", "tools/", "tests/", "docs/")


@functools.cache
def _basenames():
    """What a document may name without a directory: the tree's own sources and
    documents, not what a run writes (``run_ledger.json``, ``trace_report.json``)."""
    names = {p.name for root in ROOTS for p in (REPO / root).rglob("*.*")}
    return names | {p.name for p in REPO.iterdir()}


def _missing(doc):
    text = doc.read_text()
    spans = re.findall(r"`([^`\n]+)`", text) + re.findall(r"\]\(([^)#\s]+)", text)
    words = {w.strip(".,;()[]'\"").split(":")[0] for span in spans for w in span.split()}
    missing = []
    for word in sorted(words):
        if word.startswith(ROOTS):
            # `examples/<family>/*.yaml`: what stands before a placeholder exists
            fixed = re.split(r"[<*{$]", word)[0]
            path = REPO / fixed
            ok = path.exists() if fixed == word or fixed.endswith("/") else path.parent.exists()
        elif re.fullmatch(r"[\w.-]+\.(py|md)|[A-Z][A-Z0-9_]*\.json", word):
            ok = word in _basenames()
        else:
            continue
        if not ok:
            missing.append(word)
    return missing


@pytest.mark.parametrize("doc", DOCS, ids=lambda p: str(p.relative_to(REPO)))
def test_paths_exist(doc):
    missing = _missing(doc)
    assert not missing, f"{doc.relative_to(REPO)} names files the tree does not have: {missing}"
