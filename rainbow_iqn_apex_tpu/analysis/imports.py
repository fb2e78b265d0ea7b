"""jax-free import checker (id ``jax-free``).

Several subsystems promise jax-free IMPORT in their docstrings and lean on
it operationally: respawned actor/league children must start in ~0.3s
(parallel/elastic.py consumers), router front-end processes own no device
(serving/fleet, serving/net), and the offline tooling (obs_report,
obs/attribution, lint_jsonl) must run on boxes with no jax install at all.
The PEP-562 lazy package ``__init__``s exist exactly to protect this — and
a single eager ``from .apex import ...`` regression silently re-taints
every consumer (the PR-4 lesson).

This analyzer makes the claim structural: for every module in
``JAX_FREE_MODULES`` (and every lazy package ``__init__`` in
``LAZY_PACKAGE_INITS``), the TRANSITIVE closure of its top-level,
eagerly-executed imports — following package-internal edges — must not
reach ``jax`` (or jaxlib/flax/optax/orbax/chex, which all import jax).
``if TYPE_CHECKING:`` bodies and function-local imports are not eager and
do not count; ``try:`` bodies do (they execute).

The finding message carries the full import chain, so a taint introduced
three modules deep names every hop.  Suppression: ``# jax-ok: <reason>``
on the offending import line.

Self-hosting: ``analysis/*`` is itself in the declared set, and
scripts/obs_report.py + scripts/lint_jsonl.py are checked through their
repo-relative paths (the ISSUE-14 satellite).
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Sequence, Tuple

from rainbow_iqn_apex_tpu.analysis.core import (
    Finding,
    SourceModule,
    apply_pragmas,
)

ANALYZER = "jax-free"

PACKAGE = "rainbow_iqn_apex_tpu"

# modules that import jax (directly or by construction) — reaching any of
# these eagerly is the violation
_TAINT_ROOTS = ("jax", "jaxlib", "flax", "optax", "orbax", "chex")

# Modules whose docstrings/CHANGES claim jax-free import.  Directories end
# with "/" and mean every .py directly inside (obs/trace.py is the one
# deliberate exception: it IS the jax-facing half of obs/).
JAX_FREE_MODULES: Tuple[str, ...] = (
    "rainbow_iqn_apex_tpu/analysis/",
    "rainbow_iqn_apex_tpu/league/",
    "rainbow_iqn_apex_tpu/obs/__init__.py",
    "rainbow_iqn_apex_tpu/obs/attribution.py",
    "rainbow_iqn_apex_tpu/obs/export.py",
    "rainbow_iqn_apex_tpu/obs/health.py",
    "rainbow_iqn_apex_tpu/obs/pipeline_trace.py",
    "rainbow_iqn_apex_tpu/obs/registry.py",
    "rainbow_iqn_apex_tpu/netcore/",
    "rainbow_iqn_apex_tpu/obs/net/",
    "rainbow_iqn_apex_tpu/obs/schema.py",
    "rainbow_iqn_apex_tpu/parallel/elastic.py",
    "rainbow_iqn_apex_tpu/parallel/failover.py",
    "rainbow_iqn_apex_tpu/parallel/sharded_replay.py",
    "rainbow_iqn_apex_tpu/replay/net/",
    "rainbow_iqn_apex_tpu/serving/batcher.py",
    "rainbow_iqn_apex_tpu/serving/fleet/",
    "rainbow_iqn_apex_tpu/serving/metrics.py",
    "rainbow_iqn_apex_tpu/serving/net/",
    "rainbow_iqn_apex_tpu/utils/faults.py",
    "rainbow_iqn_apex_tpu/utils/logging.py",
    "rainbow_iqn_apex_tpu/utils/quantize.py",
    "scripts/lint_jsonl.py",
    "scripts/obs_report.py",
    "scripts/obs_top.py",
)

# PEP-562 lazy package __init__s: importing the PACKAGE must stay jax-free
# (their submodule values may be tainted; eagerly importing one is the bug)
LAZY_PACKAGE_INITS: Tuple[str, ...] = (
    "rainbow_iqn_apex_tpu/analysis/__init__.py",
    "rainbow_iqn_apex_tpu/league/__init__.py",
    "rainbow_iqn_apex_tpu/netcore/__init__.py",
    "rainbow_iqn_apex_tpu/parallel/__init__.py",
    "rainbow_iqn_apex_tpu/replay/__init__.py",
    "rainbow_iqn_apex_tpu/replay/net/__init__.py",
    "rainbow_iqn_apex_tpu/serving/__init__.py",
    "rainbow_iqn_apex_tpu/serving/fleet/__init__.py",
    "rainbow_iqn_apex_tpu/serving/net/__init__.py",
    "rainbow_iqn_apex_tpu/utils/__init__.py",
)


def declared_paths(repo_root: str) -> List[str]:
    """Expand JAX_FREE_MODULES + LAZY_PACKAGE_INITS to concrete files."""
    out = []
    for entry in JAX_FREE_MODULES:
        absd = os.path.join(repo_root, entry)
        if entry.endswith("/"):
            for name in sorted(os.listdir(absd)):
                if name.endswith(".py"):
                    out.append(entry + name)
        else:
            out.append(entry)
    for entry in LAZY_PACKAGE_INITS:
        if entry not in out:
            out.append(entry)
    return sorted(set(out))


def _eager_imports(tree: ast.Module, pkg_dir: str) -> List[Tuple[str, int]]:
    """(module, lineno) for every import executed at import time.
    ``pkg_dir`` is the dotted package of the FILE (for relative imports)."""
    out: List[Tuple[str, int]] = []

    def visit(body) -> None:
        for n in body:
            if isinstance(n, ast.Import):
                out.extend((a.name, n.lineno) for a in n.names)
            elif isinstance(n, ast.ImportFrom):
                mod = n.module or ""
                if n.level:
                    base = pkg_dir
                    for _ in range(n.level - 1):
                        base = base.rsplit(".", 1)[0] if "." in base else ""
                    mod = base + ("." + mod if mod else "")
                out.append((mod, n.lineno))
                # ``from pkg import sub`` / ``from . import sub`` execute
                # the SUBMODULE too when the name resolves to one — the
                # eager edge a lazy package __init__ exists to avoid; the
                # composite either resolves to a real module file or is a
                # plain attribute import and drops out in _module_to_path
                for a in n.names:
                    if a.name != "*":
                        out.append(
                            (f"{mod}.{a.name}" if mod else a.name, n.lineno)
                        )
            elif isinstance(n, ast.If):
                if "TYPE_CHECKING" not in ast.dump(n.test):
                    visit(n.body)
                visit(n.orelse)
            elif isinstance(n, ast.Try):
                visit(n.body)
                for h in n.handlers:
                    visit(h.body)
                visit(n.orelse)
                visit(n.finalbody)
            elif isinstance(n, (ast.With, ast.AsyncWith)):
                visit(n.body)
    visit(tree.body)
    return out


# repo-internal import roots the closure follows (scripts import each
# other as ``from scripts.lint_jsonl import ...``)
_INTERNAL_ROOTS = (PACKAGE, "scripts")


def _module_to_path(repo_root: str, mod: str) -> Optional[str]:
    root = mod.split(".", 1)[0]
    if root not in _INTERNAL_ROOTS:
        return None
    rel = mod.replace(".", "/")
    for cand in (rel + ".py", rel + "/__init__.py"):
        if os.path.isfile(os.path.join(repo_root, cand)):
            return cand
    return None


def _taint_chain(
    repo_root: str,
    rel_path: str,
    cache: Dict[str, Optional[Tuple[Tuple[str, int, str], ...]]],
    visiting: Optional[set] = None,
) -> Tuple[Optional[Tuple[Tuple[str, int, str], ...]], bool]:
    """(chain, complete): the (file, lineno, imported-module) chain from
    ``rel_path`` to the first taint root, or None when the eager import
    closure is jax-free.  ``complete=False`` marks a clean verdict computed
    with an import-cycle edge cut — correct for the traversal ROOT (the cut
    loops back into its own stack) but NOT cacheable for inner nodes, whose
    verdict would otherwise ignore an ancestor's still-pending taint."""
    if rel_path in cache:
        return cache[rel_path], True
    visiting = visiting if visiting is not None else set()
    if rel_path in visiting:
        return None, False  # cycle edge cut: verdict depends on an ancestor
    visiting.add(rel_path)
    abspath = os.path.join(repo_root, rel_path)
    try:
        with open(abspath, "r", encoding="utf-8") as fh:
            tree = ast.parse(fh.read(), filename=rel_path)
    except (OSError, SyntaxError):
        visiting.discard(rel_path)
        cache[rel_path] = None
        return None, True
    pkg_dir = os.path.dirname(rel_path).replace("/", ".")
    result: Optional[Tuple[Tuple[str, int, str], ...]] = None
    complete = True
    for mod, lineno in _eager_imports(tree, pkg_dir):
        root = mod.split(".", 1)[0]
        if root in _TAINT_ROOTS:
            result = ((rel_path, lineno, mod),)
            break
        sub = _module_to_path(repo_root, mod)
        if sub is not None:
            deeper, sub_complete = _taint_chain(
                repo_root, sub, cache, visiting
            )
            if deeper is not None:
                result = ((rel_path, lineno, mod),) + deeper
                break
            complete = complete and sub_complete
    visiting.discard(rel_path)
    if result is not None or complete:
        cache[rel_path] = result
    return result, result is not None or complete


def check_repo(
    repo_root: str, paths: Optional[Sequence[str]] = None
) -> List[Finding]:
    """Run the checker over the declared set (or an explicit path list)."""
    rels = list(paths) if paths is not None else declared_paths(repo_root)
    cache: Dict[str, Optional[Tuple[Tuple[str, int, str], ...]]] = {}
    findings: List[Finding] = []
    for rel in rels:
        chain, _complete = _taint_chain(repo_root, rel, cache)
        if chain is None:
            continue
        hops = " -> ".join(
            f"{p}:{ln} imports {m}" for p, ln, m in chain
        )
        top_line = chain[0][1]
        findings.append(
            Finding(
                analyzer=ANALYZER,
                path=rel,
                line=top_line,
                key=f"{ANALYZER}:{rel}:{chain[-1][2].split('.', 1)[0]}",
                message=(
                    f"{rel} claims jax-free import but eagerly reaches "
                    f"{chain[-1][2]}: {hops}"
                ),
            )
        )
    # pragma filtering needs each module's comments
    out: List[Finding] = []
    for f in findings:
        try:
            module = SourceModule(os.path.join(repo_root, f.path), repo_root)
        except (OSError, SyntaxError):
            out.append(f)
            continue
        out.extend(apply_pragmas(module, [f]))
    return out
