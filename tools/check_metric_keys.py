#!/usr/bin/env python
"""Static lint: emitted metric keys and docs/observability.md must agree.

The observability pillars emit flat namespaced metric keys (``goodput/*``,
``mem_plan/*``, ``mem/*``, ``moe/*``, ``moe_load/*``, ``dynamics/*``) that ride
the training.jsonl rows; docs/observability.md is the contract downstream
dashboards are built against. The two drift silently: a new key lands in code
without a docs entry, or a doc promises a key that was renamed away. This tool
makes the drift a CI failure in both directions:

- every tracked-family key (or key *pattern*) emitted by ``automodel_tpu/``
  source must match something documented in docs/observability.md, and
- every tracked-family key documented there must match something the code can
  emit.

Key extraction is AST-based, not regex-over-source: string constants and
f-strings are collected (docstrings excluded), with f-string interpolations
normalized to ``*`` wildcards — ``f"dynamics/{bucket}/{metric}"`` becomes the
pattern ``dynamics/*/*``. Two resolution passes keep the patterns tight:

- module-level string constants and parameter string defaults substitute into
  f-strings (``f"dynamics/{NUMERICS_BUCKET}/grad_amax"`` -> literal), and
- emitters parameterized by a ``prefix`` argument (moe/metrics.py serves both
  the ``moe_load/*`` and ``moe/*`` families) expand over the parameter default
  plus every constant ``prefix=`` value found at call sites.

Docs-side keys come from inline code spans and fenced code blocks only (prose
mentions of file paths never match), with ``<placeholder>`` / ``{placeholder}``
segments normalized to the same ``*`` wildcard.

Exit 0 when the two sets cover each other, 1 with a report otherwise.
"""

from __future__ import annotations

import argparse
import ast
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SOURCE_ROOT = REPO / "automodel_tpu"
DOC = REPO / "docs" / "observability.md"

# the namespaced families under contract ("mem" before "moe" is irrelevant —
# matching is anchored) plus the bare "goodput" headline scalar
FAMILIES = ("goodput", "mem_plan", "mem", "moe_load", "moe", "dynamics",
            "trace", "signals", "supervisor", "ledger", "badput")
_FAMILY_RE = re.compile(r"^(?:%s)/[^ ]+$" % "|".join(FAMILIES))
BARE_KEYS = {"goodput", "overlap_frac", "a2a_byte_share"}
# bare-prefix family: the measured trace-attribution keys ride log rows
# without a slash namespace (measured_frac_compute, measured_t_comm_s,
# measured_comm_axis_<ax>_s, measured_bound, ...); "*" appears in normalized
# f-string/doc-placeholder patterns
_BARE_PREFIX_RE = re.compile(r"^measured_[\w*]+$")

# strings that carry a family prefix but are not metric keys (paths, globs)
_NOT_A_KEY = re.compile(r"\.(py|json|jsonl|yaml|md)\b|[ :(),]|\.\*")


def _pattern_ok(p: str) -> bool:
    if p.endswith(("_", "/")):  # a startswith() prefix literal, not a key
        return False
    if p in BARE_KEYS or _BARE_PREFIX_RE.match(p):
        return not _NOT_A_KEY.search(p)
    return bool(_FAMILY_RE.match(p)) and not _NOT_A_KEY.search(p)


# ---------------------------------------------------------------- code side


def _docstring_ids(tree: ast.AST) -> set[int]:
    """ids of Constant nodes that are module/class/function docstrings."""
    out: set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef)):
            body = getattr(node, "body", [])
            if body and isinstance(body[0], ast.Expr):
                v = body[0].value
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    out.add(id(v))
    return out


def _module_consts(tree: ast.Module) -> dict[str, str]:
    """Module-level NAME = "literal" bindings, for f-string substitution."""
    out: dict[str, str] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Constant):
            if isinstance(node.value.value, str):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out[t.id] = node.value.value
    return out


def _param_defaults(fn: ast.AST) -> dict[str, str]:
    """param -> constant-string default for one function definition."""
    out: dict[str, str] = {}
    a = fn.args
    pos = a.posonlyargs + a.args
    for arg, default in zip(pos[len(pos) - len(a.defaults):], a.defaults):
        if isinstance(default, ast.Constant) and isinstance(default.value, str):
            out[arg.arg] = default.value
    for arg, default in zip(a.kwonlyargs, a.kw_defaults):
        if default is not None and isinstance(default, ast.Constant) \
                and isinstance(default.value, str):
            out[arg.arg] = default.value
    return out


def _prefix_call_values(tree: ast.AST) -> set[str]:
    """Constant values passed as a prefix= keyword anywhere in this module."""
    out: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg == "prefix" and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, str):
                    out.add(kw.value.value)
    return out


def _fstring_patterns(
    node: ast.JoinedStr, scope: dict[str, str], prefix_values: set[str]
) -> list[str]:
    """Wildcard patterns for one f-string; >1 when a prefix param fans out."""
    parts: list[list[str]] = [[""]]

    def _append(texts: list[str]) -> None:
        nonlocal parts
        parts = [p + [t] for p in parts for t in texts]

    for v in node.values:
        if isinstance(v, ast.Constant):
            _append([str(v.value)])
        elif isinstance(v, ast.FormattedValue) and isinstance(v.value, ast.Name) \
                and v.value.id in scope:
            if v.value.id == "prefix":
                _append(sorted({scope[v.value.id], *prefix_values}))
            else:
                _append([scope[v.value.id]])
        else:
            _append(["*"])
    return ["".join(p) for p in parts]


def code_patterns(root: Path = SOURCE_ROOT) -> dict[str, list[str]]:
    """pattern -> list of "file:line" emit sites for every tracked key."""
    out: dict[str, list[str]] = {}
    # prefix= fan-out values are collected repo-wide: the emitter
    # (moe/metrics.py) and its callers (observability/moe_stats.py) are
    # different modules
    prefix_values: set[str] = set()
    trees: list[tuple[Path, ast.Module]] = []
    for path in sorted(root.rglob("*.py")):
        try:
            tree = ast.parse(path.read_text(), filename=str(path))
        except SyntaxError as exc:  # pragma: no cover - repo must stay parseable
            print(f"[metric-lint] cannot parse {path}: {exc}", file=sys.stderr)
            continue
        trees.append((path, tree))
        prefix_values |= _prefix_call_values(tree)

    for path, tree in trees:
        skip = _docstring_ids(tree)
        consts = _module_consts(tree)
        rel = path.relative_to(REPO)

        def visit(node: ast.AST, scope: dict[str, str]) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    visit(child, {**scope, **_param_defaults(child)})
                    continue
                if isinstance(child, ast.Constant) and isinstance(child.value, str):
                    if id(child) not in skip and _pattern_ok(child.value):
                        out.setdefault(child.value, []).append(
                            f"{rel}:{child.lineno}")
                    continue
                if isinstance(child, ast.JoinedStr):
                    for pat in _fstring_patterns(child, scope, prefix_values):
                        if _pattern_ok(pat):
                            out.setdefault(pat, []).append(f"{rel}:{child.lineno}")
                    continue  # don't re-collect the f-string's Constant parts
                visit(child, scope)

        visit(tree, consts)
    return out


# ---------------------------------------------------------------- docs side

_CODE_SPAN = re.compile(r"`([^`\n]+)`")
_FENCE = re.compile(r"```[^\n]*\n(.*?)```", re.S)
_DOC_TOKEN = re.compile(r"[\w*{}<>./-]+")


def doc_patterns(doc: Path = DOC) -> dict[str, list[str]]:
    """pattern -> mention count holder for every documented tracked key."""
    text = doc.read_text()
    spans: list[str] = _CODE_SPAN.findall(text) + _FENCE.findall(text)
    # JSON examples quote keys; the token regex below doesn't cross quotes
    out: dict[str, list[str]] = {}
    for span in spans:
        for token in _DOC_TOKEN.findall(span):
            token = token.strip(".,")
            # <layer> / {rank} placeholders are the docs' wildcard spelling
            pat = re.sub(r"<[^/>]*>|\{[^/}]*\}", "*", token)
            if _pattern_ok(pat):
                out.setdefault(pat, []).append(token)
    return out


# ---------------------------------------------------------------- matching


def _seg_regex(seg: str) -> re.Pattern:
    return re.compile(".+".join(re.escape(p) for p in seg.split("*")) or ".+")


def _seg_match(a: str, b: str) -> bool:
    if a == "*" or b == "*":
        return True
    return bool(
        _seg_regex(a).fullmatch(b.replace("*", "x"))
        or _seg_regex(b).fullmatch(a.replace("*", "x"))
    )


def patterns_match(a: str, b: str) -> bool:
    """True when key-patterns a and b can name the same metric key.

    Segment-wise; ``*`` (and doc placeholders, already normalized to ``*``)
    match any non-empty segment text. A trailing bare ``*`` is glob-like and
    absorbs any number of remaining segments, so the docs' family shorthand
    ``mem_plan/*`` covers the whole family.
    """
    sa, sb = a.split("/"), b.split("/")
    if len(sa) != len(sb):
        if sa[-1] == "*" and len(sb) > len(sa):
            sa = sa[:-1] + ["*"] * (len(sb) - len(sa) + 1)
        elif sb[-1] == "*" and len(sa) > len(sb):
            sb = sb[:-1] + ["*"] * (len(sa) - len(sb) + 1)
        else:
            return False
    return all(_seg_match(x, y) for x, y in zip(sa, sb))


def _is_bare_shorthand(pat: str) -> bool:
    """True for a family-wide glob like ``moe_load/*`` (docs prose shorthand)."""
    return pat.split("/", 1)[-1] == "*" and pat.split("/")[0] in FAMILIES


def check(code: dict[str, list[str]], docs: dict[str, list[str]]):
    """(undocumented, unemitted): the two one-directional failure lists."""
    # a prose mention of "the moe_load/* family" is not documentation of any
    # specific key — only non-shorthand doc patterns satisfy the code side
    specific_docs = [d for d in docs if not _is_bare_shorthand(d)]
    undocumented = {
        pat: sites for pat, sites in code.items()
        if not any(patterns_match(pat, d) for d in specific_docs)
    }
    unemitted = {
        pat: toks for pat, toks in docs.items()
        if not any(patterns_match(pat, c) for c in code)
    }
    return undocumented, unemitted


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--list", action="store_true",
                        help="print the extracted key patterns and exit")
    args = parser.parse_args(argv)

    code = code_patterns()
    docs = doc_patterns()
    if args.list:
        for pat in sorted(code):
            print(f"code {pat}  ({code[pat][0]})")
        for pat in sorted(docs):
            print(f"docs {pat}")
        return 0

    undocumented, unemitted = check(code, docs)
    for pat, sites in sorted(undocumented.items()):
        print(f"UNDOCUMENTED {pat}  emitted at {', '.join(sites[:3])}"
              f" — add it to {DOC.relative_to(REPO)}")
    for pat, toks in sorted(unemitted.items()):
        print(f"UNEMITTED    {pat}  documented as {toks[0]!r}"
              f" — no automodel_tpu/ source emits it")
    if undocumented or unemitted:
        print(f"\nmetric-key lint: {len(undocumented)} undocumented, "
              f"{len(unemitted)} unemitted (families: {', '.join(FAMILIES)})")
        return 1
    print(f"metric-key lint: {len(code)} code patterns <-> {len(docs)} doc "
          "patterns, all covered")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
