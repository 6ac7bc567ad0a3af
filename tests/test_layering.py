"""The import graph points one way, runtime -> models -> kernels, and the
step's scope names live in a leaf module all three import."""
import ast
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


def _imports(path: Path):
    """The absolute names of the modules a file imports, at any depth of
    its AST, relative imports resolved against its package."""
    package = ".".join(path.relative_to(SRC.parent).parent.parts)
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package.split(".")
            base = base[:len(base) - node.level + 1] if node.level else []
            module = ".".join(base + ([node.module] if node.module else []))
            yield module
            yield from (f"{module}.{a.name}" for a in node.names)


def _offenders(paths, prefix):
    return sorted(f"{p.relative_to(SRC)}: {m}" for p in paths
                  for m in _imports(p)
                  if m == prefix or m.startswith(prefix + "."))


def test_models_and_kernels_do_not_import_the_runtime():
    files = [p for d in ("models", "kernels") for p in (SRC / d).rglob("*.py")]
    assert len(files) > 5
    assert _offenders(files, "repro.runtime") == []


def test_scopes_module_imports_nothing_of_the_repo():
    assert _offenders([SRC / "scopes.py"], "repro") == []
