"""Every module of the package imports, and every import of the package's own
modules resolves, the lazy ones inside functions too: a module or a name that
left the tree fails here by name, not at the first run that reaches the line."""

import ast
import importlib
import importlib.util
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
PACKAGE = REPO / "automodel_tpu"
SUBPACKAGES = sorted(p.name for p in PACKAGE.iterdir() if (p / "__init__.py").exists())


def _unresolved_own_imports(path):
    """``import automodel_tpu.x`` / ``from automodel_tpu.x import y`` anywhere in
    the file (relative forms too) whose module or name does not exist."""
    tree = ast.parse(path.read_text(), filename=str(path))
    package = ".".join(path.relative_to(REPO).with_suffix("").parts[:-1])
    bad = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            targets = [(a.name, None) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            module = ("." * node.level) + (node.module or "")
            if node.level:
                module = importlib.util.resolve_name(module, package)
            targets = [(module, a.name) for a in node.names]
        else:
            continue
        for module, name in targets:
            if module.split(".")[0] != "automodel_tpu":
                continue
            try:
                mod = importlib.import_module(module)
                if name not in (None, "*") and not hasattr(mod, name):
                    importlib.import_module(f"{module}.{name}")
            except ImportError as exc:
                bad.append(f"{path.relative_to(REPO)}:{node.lineno}: {exc}")
    return bad


@pytest.mark.parametrize("subpackage", SUBPACKAGES + ["tools"])
def test_every_module_imports(subpackage):
    # the tools run ``main`` at import's edge: they are parsed below, not imported
    files = sorted((REPO / "tools").glob("*.py") if subpackage == "tools"
                   else (PACKAGE / subpackage).rglob("*.py"))
    if subpackage != "tools":
        for f in files:
            parts = f.relative_to(REPO).with_suffix("").parts
            importlib.import_module(".".join(parts[:-1] if parts[-1] == "__init__" else parts))
    bad = [line for f in files for line in _unresolved_own_imports(f)]
    assert not bad, "\n".join(bad)
