"""What `benchmarks/` takes from the program still resolves.

The chip benchmark (`benchmarks/`, BENCHMARK.json) imports the program by
name: modules, functions, private helpers of the fused trainer.  A program
PR that renames one of them would otherwise learn it from the chip, after
the tier-1 run, as a driver that cannot start.  This file reads the
benchmark's own sources (it edits nothing there), collects every program
module they import and every name they take from it — `from m import name`
and `alias.name` through an alias of the module — and checks each against
the imported module, one case a program module.
"""

import ast
import importlib
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "rainbow_iqn_apex_tpu"


def _is_module(dotted):
    base = os.path.join(REPO, *dotted.split("."))
    return os.path.isfile(base + ".py") or os.path.isfile(
        os.path.join(base, "__init__.py"))


def _benchmark_sources():
    top = os.path.join(REPO, "benchmarks")
    for dirpath, dirnames, filenames in os.walk(top):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__"
                             and (dirpath, d) != (top, "tests"))
        for name in sorted(filenames):
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


def _seam():
    """{program module: {(name, "file:line"), ...}} over the benchmark's
    files outside its tests."""
    seam = {}
    for path in _benchmark_sources():
        rel = os.path.relpath(path, REPO)
        with open(path, encoding="utf-8") as fh:
            tree = ast.parse(fh.read(), filename=rel)
        aliases = {}  # local name -> program module it is bound to
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.split(".")[0] == PKG:
                        seam.setdefault(a.name, set())
                        if a.asname:
                            aliases[a.asname] = a.name
            elif (isinstance(node, ast.ImportFrom) and node.level == 0
                  and (node.module or "").split(".")[0] == PKG):
                for a in node.names:
                    sub = f"{node.module}.{a.name}"
                    if _is_module(sub):  # `from pkg.replay import device_sequence`
                        seam.setdefault(sub, set())
                        aliases[a.asname or a.name] = sub
                    else:
                        seam.setdefault(node.module, set()).add(
                            (a.name, f"{rel}:{node.lineno}"))
        for node in ast.walk(tree):
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id in aliases):
                seam[aliases[node.value.id]].add(
                    (node.attr, f"{rel}:{node.lineno}"))
    return seam


SEAM = _seam()


@pytest.mark.parametrize("module", sorted(SEAM))
def test_program_module_has_what_the_benchmark_takes(module):
    try:
        mod = importlib.import_module(module)
    except ImportError as e:
        pytest.fail(f"benchmarks/ imports {module}, which no longer "
                    f"imports: {e}")
    missing = [f"{name} ({where})" for name, where in sorted(SEAM[module])
               if not hasattr(mod, name)]
    assert not missing, (
        f"{module} no longer has what benchmarks/ takes from it: "
        + ", ".join(missing))
