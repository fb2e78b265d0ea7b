"""Full-package analyzer run + baseline compare (the tier-1 entry point).

``run_all(repo_root)`` executes every analyzer over its declared scope and
returns the findings NOT grandfathered by the checked-in baseline
(analysis/baseline.txt — shipped empty, so everything fails tier-1).
``scripts/static_analysis.py`` is the CLI; tests/test_analysis.py is the
tier-1 meta-test; ``make static-smoke`` runs both.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence

from rainbow_iqn_apex_tpu.analysis import (
    configcheck,
    core,
    hostsync_lint,
    imports,
    locks,
    wirecheck,
)
from rainbow_iqn_apex_tpu.analysis.core import Finding

# repo-relative; "empty at merge" — any new finding fails tier-1 rather
# than joining a debt pile
BASELINE_PATH = "rainbow_iqn_apex_tpu/analysis/baseline.txt"

ANALYZER_IDS = (
    locks.ANALYZER,
    hostsync_lint.ANALYZER,
    imports.ANALYZER,
    configcheck.ANALYZER,
    configcheck.DOC_ANALYZER,
    wirecheck.ANALYZER,
)


def run_all(
    repo_root: str,
    analyzers: Optional[Sequence[str]] = None,
    baseline_path: Optional[str] = None,
) -> List[Finding]:
    """All findings (baseline-filtered, sorted by path/line).

    ``analyzers`` restricts to a subset of ANALYZER_IDS; ``baseline_path``
    overrides the checked-in baseline (None = the checked-in file,
    "" = no baseline at all)."""
    wanted = set(analyzers) if analyzers is not None else set(ANALYZER_IDS)
    unknown = wanted - set(ANALYZER_IDS)
    if unknown:
        raise ValueError(
            f"unknown analyzer id(s) {sorted(unknown)}; "
            f"valid: {list(ANALYZER_IDS)}"
        )
    findings: List[Finding] = []

    per_module = []
    if locks.ANALYZER in wanted:
        per_module.append(locks.check_module)
    if hostsync_lint.ANALYZER in wanted:
        per_module.append(hostsync_lint.check_module)

    # parse each file ONCE: locks/host-sync scan the package, config-drift
    # additionally scans scripts/ (its soak harnesses emit row kinds)
    need_modules = bool(per_module) or configcheck.ANALYZER in wanted
    modules = []
    if need_modules:
        paths = core.iter_package_files(
            repo_root, subdirs=("rainbow_iqn_apex_tpu", "scripts")
        )
        modules = [core.SourceModule(p, repo_root) for p in paths]
    for module in modules:
        if module.path.startswith("rainbow_iqn_apex_tpu/"):
            for check in per_module:
                findings.extend(check(module))

    if imports.ANALYZER in wanted:
        findings.extend(imports.check_repo(repo_root))
    if configcheck.ANALYZER in wanted:
        findings.extend(configcheck.check_repo(repo_root, modules=modules))
        findings.extend(configcheck.check_field_use(repo_root, modules))
    if configcheck.DOC_ANALYZER in wanted:
        findings.extend(configcheck.check_docs(repo_root))
    if wirecheck.ANALYZER in wanted:
        findings.extend(wirecheck.check_repo(repo_root))

    if baseline_path is None:
        baseline_path = os.path.join(repo_root, BASELINE_PATH)
    baseline = (
        core.load_baseline(baseline_path) if baseline_path else frozenset()
    )
    findings = core.filter_baseline(findings, baseline)
    return sorted(findings, key=lambda f: (f.path, f.line, f.analyzer, f.key))
