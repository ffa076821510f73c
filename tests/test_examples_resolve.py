"""Every script under ``examples/`` compiles, and every name it imports
exists.

The scripts are run by hand on the chip and by nothing here, so an example
left importing a module that was deleted would pass every other test.  Each
file's AST is walked without running it, imports inside functions included:
a top-level module must be findable, and a name taken from a module of this
repository must resolve by ``importlib`` and ``getattr``.
"""

import ast
import importlib
import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCRIPTS = sorted((ROOT / "examples").glob("*.py"))


def _in_tree(top: str) -> bool:
    return (ROOT / top).is_dir() or (ROOT / f"{top}.py").is_file()


def _imports(tree: ast.AST):
    """``(module, name or None, lineno)`` for every import in the file."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, None, node.lineno
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, "examples are scripts: no relative imports"
            for alias in node.names:
                yield node.module, alias.name, node.lineno


@pytest.mark.parametrize("script", SCRIPTS, ids=lambda p: p.name)
def test_example_compiles_and_its_imports_resolve(script):
    tree = ast.parse(script.read_text(), filename=str(script))
    compile(tree, str(script), "exec")
    missing = []
    for module, name, lineno in _imports(tree):
        top = module.split(".")[0]
        where = f"{script.name}:{lineno}"
        if not _in_tree(top):
            if importlib.util.find_spec(top) is None:
                missing.append(f"{where}: no module {top!r}")
            continue
        try:
            mod = importlib.import_module(module)
        except ImportError as e:
            missing.append(f"{where}: import {module}: {e}")
            continue
        if name is None or name == "*" or hasattr(mod, name):
            continue
        try:
            importlib.import_module(f"{module}.{name}")
        except ImportError:
            missing.append(f"{where}: {module} has no {name!r}")
    assert not missing, "\n".join(missing)
