"""Config/schema cross-checker (id ``config-drift``) + doc-reference lint
(id ``doc-drift``).

Four drift classes this repo has paid for by hand:

1. **cfg reads**: every ``cfg.X`` / ``config.X`` / ``self.cfg.X`` attribute
   read in the package must resolve to a declared ``Config`` field (or
   method/property).  A typo'd read of a frozen dataclass only explodes on
   the code path that executes it — statically it is free to catch.
2. **row kinds**: every ``logger.log("<kind>", ...)`` literal emitted in
   the package AND in scripts/ must be registered in
   ``obs/schema.py REQUIRED_KEYS`` (the ONE registry — lint_jsonl and the
   golden-schema test read the same dict) and listed in
   docs/OBSERVABILITY.md's row-kind table.
3. **default-off families**: flags documented as off-by-default gates
   (``league_*``, ``serve_net_*``, ``device_sampling``, ...) must actually
   default to their OFF value — the "no flag set => bitwise the previous
   PR" guarantee tier-1 asserts dynamically, checked at the source.
4. **doc refs** (``doc-drift``): a backticked ``cfg.<name>`` in docs/*.md
   must name a real Config field — the PR-8 "pmap-era" stale-doc incident
   class as a test failure.
5. **fields nothing uses**: the converse of 1.  Every ``Config`` field must
   be named by some Python file other than config.py — the package,
   scripts/, benchmarks/, tests/ or a root entry point — as an attribute
   read (``x.field``, whatever ``x`` is called: the config travels as
   ``cfg``, ``c``, ``args``, ``self._cfg``), a ``getattr`` name or a
   keyword argument.  By name only, so it proves a field dead, never
   alive: a field that only a test asserts the default of still passes.

Suppression: ``# drift-ok: <reason>`` (code) / ``<!-- drift-ok: reason -->``
on the same line (docs).
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from rainbow_iqn_apex_tpu.analysis.core import (
    Finding,
    SourceModule,
    apply_pragmas,
    iter_package_files,
)

ANALYZER = "config-drift"
DOC_ANALYZER = "doc-drift"

CONFIG_PATH = "rainbow_iqn_apex_tpu/config.py"
SCHEMA_PATH = "rainbow_iqn_apex_tpu/obs/schema.py"
OBSERVABILITY_DOC = "docs/OBSERVABILITY.md"

# names that look like ``cfg``-rooted reads
_CFG_NAMES = frozenset({"cfg", "config", "_cfg", "_config"})

# gate fields documented default-off and the OFF value each must hold —
# "no flag set => bitwise the previous PR" (tier-1 asserts it dynamically;
# this pins the source default)
DEFAULT_OFF: Dict[str, object] = {
    "fault_spec": "",
    "trace_dir": "",
    "obs_http_port": 0,
    "trace_sample_every": 0,
    "heartbeat_interval_s": 0.0,
    "max_weight_lag": 0,
    "games": "",
    "device_sampling": False,
    "pipelined_actor": False,
    "serve_quantize": "off",
    "publish_compression": "off",
    "league_dir": "",
    "league_population": 0,
    "league_member_id": -1,
    "serve_net_host": "",
    "serve_net_port": 0,
    "serve_net_advertise": "",
    "serve_net_gossip_port": 0,
    "serve_net_gossip_peers": "",
    "replay_net_host": "",
    "replay_net_port": 0,
    "replay_net_advertise": "",
    "replay_net_remote": False,
    "coordinator_address": "",
    "snapshot_replay": False,
    "resume": "",
    "failover_standby": False,
    "failover_warm": False,
    "obs_net": False,
    "obs_net_host": "",
    "obs_net_port": 0,
    "obs_net_advertise": "",
    "obs_net_http_port": 0,
    "net_chaos_spec": "",
    "lease_skew_tolerance_s": 0.0,
}

_DOC_CFG_RE = re.compile(r"`cfg\.([A-Za-z_][A-Za-z0-9_]*)`")
_DOC_PRAGMA_RE = re.compile(r"<!--\s*drift-ok\s*:\s*\S")
_DOC_KIND_CELL_RE = re.compile(r"^\|\s*`([A-Za-z_][A-Za-z0-9_]*)`\s*\|")


def config_surface(repo_root: str) -> Tuple[Set[str], Dict[str, object]]:
    """(valid attribute names, field -> literal default) from config.py's
    AST — fields, methods, and properties, no import needed."""
    with open(os.path.join(repo_root, CONFIG_PATH), encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=CONFIG_PATH)
    names: Set[str] = set()
    defaults: Dict[str, object] = {}
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == "Config":
            for item in node.body:
                if isinstance(item, ast.AnnAssign) and isinstance(
                    item.target, ast.Name
                ):
                    names.add(item.target.id)
                    if isinstance(item.value, ast.Constant):
                        defaults[item.target.id] = item.value.value
                    elif isinstance(item.value, ast.UnaryOp) and isinstance(
                        item.value.operand, ast.Constant
                    ):
                        # e.g. ``league_member_id: int = -1``
                        op = item.value.op
                        v = item.value.operand.value
                        defaults[item.target.id] = (
                            -v if isinstance(op, ast.USub) else v
                        )
                elif isinstance(
                    item, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    names.add(item.name)
    return names, defaults


def registered_kinds(repo_root: str) -> Set[str]:
    """Keys of obs/schema.py REQUIRED_KEYS, read from the AST (one source
    of truth — the same dict lint_jsonl validates against)."""
    with open(os.path.join(repo_root, SCHEMA_PATH), encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=SCHEMA_PATH)
    for node in tree.body:
        targets = []
        if isinstance(node, ast.AnnAssign) and isinstance(
            node.target, ast.Name
        ):
            targets = [node.target.id]
        elif isinstance(node, ast.Assign):
            targets = [t.id for t in node.targets if isinstance(t, ast.Name)]
        if "REQUIRED_KEYS" in targets and isinstance(node.value, ast.Dict):
            return {
                k.value
                for k in node.value.keys
                if isinstance(k, ast.Constant) and isinstance(k.value, str)
            }
    return set()


def documented_kinds(repo_root: str) -> Set[str]:
    """Backticked first-cell tokens of docs/OBSERVABILITY.md tables."""
    out: Set[str] = set()
    path = os.path.join(repo_root, OBSERVABILITY_DOC)
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            m = _DOC_KIND_CELL_RE.match(line.strip())
            if m:
                out.add(m.group(1))
    return out


def _cfg_reads(module: SourceModule) -> List[Tuple[str, int]]:
    reads: List[Tuple[str, int]] = []
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Attribute):
            continue
        base = node.value
        if isinstance(base, ast.Name) and base.id in _CFG_NAMES:
            reads.append((node.attr, node.lineno))
        elif isinstance(base, ast.Attribute) and base.attr in _CFG_NAMES:
            reads.append((node.attr, node.lineno))
    return reads


def _emitted_kinds(module: SourceModule) -> List[Tuple[str, int]]:
    out: List[Tuple[str, int]] = []
    for node in ast.walk(module.tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "log"
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            out.append((node.args[0].value, node.lineno))
    return out


def check_repo(
    repo_root: str,
    modules: Optional[Sequence[SourceModule]] = None,
    config_path: str = CONFIG_PATH,
) -> List[Finding]:
    """cfg-read + row-kind + default-off checks over the package (and
    scripts/, whose soak harnesses emit row kinds of their own)."""
    if modules is None:
        paths = iter_package_files(
            repo_root, subdirs=("rainbow_iqn_apex_tpu", "scripts")
        )
        modules = [SourceModule(p, repo_root) for p in paths]
    valid, defaults = config_surface(repo_root)
    known = registered_kinds(repo_root)
    documented = documented_kinds(repo_root)

    findings: List[Finding] = []
    for module in modules:
        local: List[Finding] = []
        if module.path != config_path:
            for attr, lineno in _cfg_reads(module):
                if attr.startswith("__") or attr in valid:
                    continue
                local.append(
                    Finding(
                        analyzer=ANALYZER,
                        path=module.path,
                        line=lineno,
                        key=f"{ANALYZER}:{module.path}:cfg.{attr}",
                        message=(
                            f"cfg.{attr} does not resolve to a Config "
                            f"field/method ({config_path})"
                        ),
                    )
                )
        for kind, lineno in _emitted_kinds(module):
            if kind not in known:
                local.append(
                    Finding(
                        analyzer=ANALYZER,
                        path=module.path,
                        line=lineno,
                        key=f"{ANALYZER}:{module.path}:kind.{kind}",
                        message=(
                            f"row kind '{kind}' is emitted here but not "
                            f"registered in obs/schema.py REQUIRED_KEYS — "
                            f"lint_jsonl would reject the run dir"
                        ),
                    )
                )
            elif kind not in documented:
                local.append(
                    Finding(
                        analyzer=ANALYZER,
                        path=module.path,
                        line=lineno,
                        key=f"{ANALYZER}:{module.path}:kind-doc.{kind}",
                        message=(
                            f"row kind '{kind}' is emitted here but missing "
                            f"from the {OBSERVABILITY_DOC} row-kind table"
                        ),
                    )
                )
        findings.extend(apply_pragmas(module, local))

    # default-off families (anchored to config.py's Config class)
    cfg_module = SourceModule(os.path.join(repo_root, config_path), repo_root)
    off_findings: List[Finding] = []
    for field, off_value in sorted(DEFAULT_OFF.items()):
        if field not in valid:
            off_findings.append(
                Finding(
                    analyzer=ANALYZER,
                    path=config_path,
                    line=1,
                    key=f"{ANALYZER}:{config_path}:off-missing.{field}",
                    message=(
                        f"default-off gate '{field}' is declared in the "
                        f"analyzer but no longer a Config field"
                    ),
                )
            )
            continue
        got = defaults.get(field, "<non-literal>")
        if got != off_value or type(got) is not type(off_value):
            off_findings.append(
                Finding(
                    analyzer=ANALYZER,
                    path=config_path,
                    line=1,
                    key=f"{ANALYZER}:{config_path}:off.{field}",
                    message=(
                        f"'{field}' is documented default-off but defaults "
                        f"to {got!r} (expected {off_value!r}) — the "
                        f"no-flag path would no longer be the previous "
                        f"PR's bitwise behaviour"
                    ),
                )
            )
    findings.extend(apply_pragmas(cfg_module, off_findings))
    return findings


# where a field's user may live, besides the root's entry points
_USE_SUBDIRS = ("rainbow_iqn_apex_tpu", "scripts", "benchmarks", "tests")


def _names_used(tree: ast.AST) -> Set[str]:
    """Every name a file reads as an attribute, hands to ``getattr`` /
    ``hasattr`` as a literal, or passes as a keyword argument."""
    used: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and isinstance(node.ctx, ast.Load):
            used.add(node.attr)
        elif isinstance(node, ast.Call):
            used.update(k.arg for k in node.keywords if k.arg)
            if (
                isinstance(node.func, ast.Name)
                and node.func.id in ("getattr", "hasattr")
                and len(node.args) >= 2
                and isinstance(node.args[1], ast.Constant)
                and isinstance(node.args[1].value, str)
            ):
                used.add(node.args[1].value)
    return used


def check_field_use(
    repo_root: str,
    modules: Sequence[SourceModule] = (),
    config_path: str = CONFIG_PATH,
) -> List[Finding]:
    """A ``Config`` field no Python file besides config.py names (class 5
    of the module docstring): it is a CLI flag that does nothing.
    ``modules`` are files the caller has parsed already."""
    cfg_module = SourceModule(os.path.join(repo_root, config_path), repo_root)
    fields = {
        item.target.id: item.lineno
        for node in cfg_module.tree.body
        if isinstance(node, ast.ClassDef) and node.name == "Config"
        for item in node.body
        if isinstance(item, ast.AnnAssign) and isinstance(item.target, ast.Name)
    }
    parsed = {m.abspath: m.tree for m in modules}
    used: Set[str] = set()
    for path in iter_package_files(
        repo_root,
        subdirs=_USE_SUBDIRS,
        extra=[n for n in os.listdir(repo_root) if n.endswith(".py")],
    ):
        path = os.path.abspath(path)
        if path == cfg_module.abspath:
            continue
        tree = parsed.get(path)
        if tree is None:
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=path)
        used |= _names_used(tree)
    findings = [
        Finding(
            analyzer=ANALYZER,
            path=config_path,
            line=lineno,
            key=f"{ANALYZER}:{config_path}:unused.{field}",
            message=(
                f"Config.{field} is read by nothing: no module, script, "
                f"benchmark file, test or entry point names it"
            ),
        )
        for field, lineno in sorted(fields.items())
        if field not in used
    ]
    return apply_pragmas(cfg_module, findings)


def check_docs(
    repo_root: str,
    doc_paths: Optional[Sequence[str]] = None,
    config_path: str = CONFIG_PATH,
) -> List[Finding]:
    """Backticked ``cfg.<name>`` doc references must resolve (doc-drift)."""
    valid, _ = config_surface(repo_root)
    if doc_paths is None:
        docs_dir = os.path.join(repo_root, "docs")
        doc_paths = sorted(
            os.path.join("docs", n)
            for n in os.listdir(docs_dir)
            if n.endswith(".md")
        )
    findings: List[Finding] = []
    for rel in doc_paths:
        with open(os.path.join(repo_root, rel), encoding="utf-8") as fh:
            for lineno, line in enumerate(fh, 1):
                for m in _DOC_CFG_RE.finditer(line):
                    name = m.group(1)
                    if name in valid:
                        continue
                    if _DOC_PRAGMA_RE.search(line):
                        continue
                    findings.append(
                        Finding(
                            analyzer=DOC_ANALYZER,
                            path=rel.replace(os.sep, "/"),
                            line=lineno,
                            key=f"{DOC_ANALYZER}:{rel}:cfg.{name}",
                            message=(
                                f"doc names `cfg.{name}` but Config has no "
                                f"such field ({config_path}) — stale-doc "
                                f"drift (the PR-8 'pmap-era' class)"
                            ),
                        )
                    )
    return findings
