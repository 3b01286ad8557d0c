"""Every module under ``src/repro`` is reached from an entry point.

The entry points are the library (``repro.core``), the ``repro-mine``
CLI (``repro.core.cli``), the mining mesh (``repro.launch.mesh``), the
benchmark (``bench/*.py``) and the examples (``examples/*.py``).  The
import graph is read statically with ``ast``, function-local imports
included, so a module that nothing imports any more fails here by name
instead of lingering as unread code.
"""

import ast
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"


def _module_name(path: Path) -> str:
    base = SRC if SRC in path.parents else ROOT
    parts = list(path.relative_to(base).with_suffix("").parts)
    if parts[-1] == "__init__":
        parts.pop()
    return ".".join(parts)


def _module_files():
    files = list((SRC / "repro").rglob("*.py"))
    files += [p for p in (ROOT / "bench").rglob("*.py")
              if "tests" not in p.relative_to(ROOT).parts]
    files += list((ROOT / "examples").glob("*.py"))
    return {_module_name(p): p for p in files}


def _imports(path: Path, name: str, modules) -> set:
    """Modules of the graph that ``path`` imports, with their parents
    (importing ``a.b.c`` runs ``a/__init__`` and ``a/b/__init__``)."""
    is_pkg = path.name == "__init__.py"
    out = set()
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            targets = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                pkg = name.split(".")
                if not is_pkg:
                    pkg = pkg[:-1]
                pkg = pkg[:len(pkg) - (node.level - 1)]
                base = ".".join(pkg + ([node.module] if node.module else []))
            else:
                base = node.module
            # ``from a import b`` may name module a.b or an attribute of a
            targets = [base] + [f"{base}.{a.name}" for a in node.names]
        else:
            continue
        for t in targets:
            parts = t.split(".")
            for i in range(1, len(parts) + 1):
                mod = ".".join(parts[:i])
                if mod in modules:
                    out.add(mod)
    return out


def test_every_repro_module_is_reached_from_an_entry_point():
    modules = _module_files()
    roots = {"repro.core", "repro.core.cli", "repro.launch.mesh"}
    roots |= {m for m, p in modules.items()
              if p.parent in (ROOT / "bench", ROOT / "examples")}
    assert {"repro.core", "repro.core.cli", "repro.launch.mesh"} <= set(
        modules)
    seen, todo = set(), list(roots)
    while todo:
        mod = todo.pop()
        if mod in seen:
            continue
        seen.add(mod)
        todo.extend(_imports(modules[mod], mod, modules) - seen)
    orphans = sorted(m for m in modules
                     if m.startswith("repro.") and m not in seen)
    assert not orphans, f"modules no entry point imports: {orphans}"
