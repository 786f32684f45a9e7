"""Every module under ``src/repro`` imports, and every ``repro`` name it
imports exists.

The import alone misses an import inside a function body (resolved only
when the function runs), so each module's source is also parsed and every
``import``/``from ... import`` of a ``repro`` name in it, relative ones
resolved, must name an existing module or an attribute of one.
"""
import ast
import importlib
import pathlib

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def _module_name(path):
    parts = path.relative_to(SRC).with_suffix("").parts
    return ".".join(parts[:-1] if parts[-1] == "__init__" else parts)


MODULES = sorted(_module_name(p) for p in (SRC / "repro").rglob("*.py"))


def _imported_names(path, module):
    """Fully qualified names of everything ``module`` imports."""
    package = module if path.name == "__init__.py" else module.rpartition(".")[0]
    out = []
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            out.extend(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = package.split(".")
                base = base[:len(base) - node.level + 1]
                base = ".".join(base + ([node.module] if node.module else []))
            else:
                base = node.module
            out.append(base)
            out.extend(f"{base}.{a.name}" for a in node.names)
    return out


def _resolves(name):
    """Whether ``name`` is an importable module or an attribute of one."""
    try:
        importlib.import_module(name)
        return True
    except ModuleNotFoundError:
        parent, _, attr = name.rpartition(".")
        try:
            return hasattr(importlib.import_module(parent), attr)
        except ModuleNotFoundError:
            return False


@pytest.mark.parametrize("module", MODULES)
def test_module_imports_and_names_no_missing_module(module):
    importlib.import_module(module)
    path = SRC.joinpath(*module.split("."))
    path = path / "__init__.py" if path.is_dir() else path.with_suffix(".py")
    names = [n for n in _imported_names(path, module)
             if n == "repro" or n.startswith("repro.")]
    dangling = [n for n in names if not _resolves(n)]
    assert not dangling, dangling
