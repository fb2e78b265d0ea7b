"""House-invariant static analyzers (ISSUE 14; analysis/,
docs/OBSERVABILITY.md "Static invariants").

Coverage map (the ISSUE's test satellite):
1. Golden fixtures per analyzer (tests/fixtures/analysis/): one POSITIVE
   (the planted violation fires), one PRAGMA (a reasoned suppression
   silences it, a reasonless one surfaces as pragma-reason), one CLEAN.
2. Meta-test: the full-package run is finding-free against the checked-in
   baseline — which is asserted EMPTY (no grandfathered debt at merge).
3. Self-hosting: the jax-free checker's declared set covers analysis/*
   itself plus scripts/obs_report.py + obs/attribution.py, and all of it
   verifies clean.
4. Regression pins for the real findings this PR fixed (elastic beat
   counters, gossip counters, RemoteTransport version, router cadence
   stamp, Agent.act hand-off, notice/actor/adopt row kinds).
5. lint_jsonl <-> schema registry dedupe: unknown kinds now fail lint via
   obs/schema.KNOWN_KINDS — no second list anywhere.
"""

import os
import subprocess
import sys

import pytest

from rainbow_iqn_apex_tpu.analysis import configcheck, core, hostsync_lint
from rainbow_iqn_apex_tpu.analysis import imports as jaxfree
from rainbow_iqn_apex_tpu.analysis import locks, runner

pytestmark = pytest.mark.static

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join("tests", "fixtures", "analysis")


def fixture_module(name):
    return core.SourceModule(os.path.join(REPO, FIXTURES, name), REPO)


def keys(findings):
    return sorted(f.key for f in findings)


# ------------------------------------------------------------ lock fixtures
def test_lock_positive_fires():
    fs = locks.check_module(fixture_module("lock_positive.py"))
    assert len(fs) == 3, keys(fs)
    msgs = " | ".join(f.message for f in fs)
    assert "Racy.count" in msgs
    assert any("_release_locked" in f.message for f in fs)
    # both the thread-side and the public-side unlocked writes are named
    lines = {f.line for f in fs}
    assert len(lines) == 3


def test_lock_pragma_suppresses_with_reason_only():
    fs = locks.check_module(fixture_module("lock_pragma.py"))
    # reasoned pragmas silence count/other writes EXCEPT the reasonless
    # one, which surfaces as a pragma-reason finding
    assert len(fs) == 1, keys(fs)
    assert fs[0].key.endswith(":pragma-reason")
    assert "needs a reason" in fs[0].message


def test_lock_clean_is_clean():
    assert locks.check_module(fixture_module("lock_clean.py")) == []


def test_lockish_names_are_not_locks():
    # review-round regression: an unanchored lock regex exempted 'clock'
    # (contains 'lock') and 'seconds' (contains 'cond') from tracking and
    # accepted `with self.clock:` as a held lock
    fs = locks.check_module(fixture_module("lock_lockish_names.py"))
    flagged = {f.key.split(":")[-2].split(".")[-1] for f in fs}
    assert {"clock", "seconds", "blocked"} <= flagged, keys(fs)


# -------------------------------------------------------- hostsync fixtures
HOT_FIXTURE = {
    f"{FIXTURES}/hostsync_positive.py": ("*",),
    f"{FIXTURES}/hostsync_pragma.py": ("*",),
    f"{FIXTURES}/hostsync_clean.py": ("*",),
}


def test_hostsync_positive_fires():
    fs = hostsync_lint.check_module(
        fixture_module("hostsync_positive.py"), hot_path=HOT_FIXTURE
    )
    whats = sorted(f.key.rsplit(":", 1)[-1] for f in fs)
    assert whats == [".item()", "float()", "np.asarray()"], keys(fs)


def test_hostsync_pragma_suppresses_with_reason_only():
    fs = hostsync_lint.check_module(
        fixture_module("hostsync_pragma.py"), hot_path=HOT_FIXTURE
    )
    assert len(fs) == 1, keys(fs)
    assert fs[0].key.endswith(":pragma-reason")


def test_hostsync_clean_is_clean():
    fs = hostsync_lint.check_module(
        fixture_module("hostsync_clean.py"), hot_path=HOT_FIXTURE
    )
    assert fs == []


def test_hostsync_undeclared_module_not_scanned():
    # the forbidden set is DECLARED: a module outside it never flags
    fs = hostsync_lint.check_module(fixture_module("hostsync_positive.py"))
    assert fs == []


# --------------------------------------------------------- jax-free fixtures
def test_jaxfree_positive_fires_with_chain():
    fs = jaxfree.check_repo(
        REPO, paths=[f"{FIXTURES}/jaxfree_positive.py"]
    )
    assert len(fs) == 1, keys(fs)
    # the chain names every hop: fixture -> ops/__init__ -> ops/learn.py ->
    # the first taint root (chex, which imports jax)
    assert "rainbow_iqn_apex_tpu/ops/learn.py" in fs[0].message
    assert " -> " in fs[0].message
    assert "eagerly reaches" in fs[0].message


def test_jaxfree_submodule_import_form_fires():
    # review-round regression: ``from pkg import sub`` executes the
    # submodule even under a lazy PEP-562 package __init__ — the composite
    # module path must be resolved, not just the (clean) package
    fs = jaxfree.check_repo(
        REPO, paths=[f"{FIXTURES}/jaxfree_positive_submodule.py"]
    )
    assert len(fs) == 1, keys(fs)
    assert "rainbow_iqn_apex_tpu/parallel/apex.py" in fs[0].message


def test_jaxfree_pragma_suppresses():
    fs = jaxfree.check_repo(REPO, paths=[f"{FIXTURES}/jaxfree_pragma.py"])
    assert fs == []


def test_jaxfree_clean_is_clean():
    fs = jaxfree.check_repo(REPO, paths=[f"{FIXTURES}/jaxfree_clean.py"])
    assert fs == []


def test_jaxfree_self_hosting_declared_set():
    declared = jaxfree.declared_paths(REPO)
    # the ISSUE-14 satellite: the checker's OWN module list pins the
    # analysis package and the offline tooling
    for must in (
        "rainbow_iqn_apex_tpu/analysis/core.py",
        "rainbow_iqn_apex_tpu/analysis/locks.py",
        "rainbow_iqn_apex_tpu/analysis/imports.py",
        "rainbow_iqn_apex_tpu/analysis/configcheck.py",
        "rainbow_iqn_apex_tpu/analysis/runner.py",
        "scripts/obs_report.py",
        "rainbow_iqn_apex_tpu/obs/attribution.py",
        "scripts/lint_jsonl.py",
    ):
        assert must in declared, must
    assert jaxfree.check_repo(REPO) == []


def test_jaxfree_import_cycle_taints_both_members(tmp_path):
    # review-round regression: a cycle-cut traversal was permanently
    # cached as 'clean', certifying a tainted cycle member jax-free
    scripts = tmp_path / "scripts"
    scripts.mkdir()
    (scripts / "a.py").write_text(
        "from scripts import b\nimport jax\n"
    )
    (scripts / "b.py").write_text("from scripts import a\n")
    fs = jaxfree.check_repo(
        str(tmp_path), paths=["scripts/a.py", "scripts/b.py"]
    )
    assert sorted(f.path for f in fs) == ["scripts/a.py", "scripts/b.py"], (
        keys(fs)
    )


def test_jaxfree_scripts_to_scripts_edge_traversed(tmp_path):
    # review-round regression: only package-prefixed imports were
    # followed, so a scripts/ helper tainting a declared script was missed
    scripts = tmp_path / "scripts"
    scripts.mkdir()
    (scripts / "helper.py").write_text("import jax\n")
    (scripts / "tool.py").write_text("from scripts.helper import thing\n")
    fs = jaxfree.check_repo(str(tmp_path), paths=["scripts/tool.py"])
    assert len(fs) == 1, keys(fs)
    assert "scripts/helper.py" in fs[0].message


def test_pragma_requires_colon(tmp_path):
    # review-round regression: '# unlocked-ok racy on purpose' (colon
    # forgotten) must NOT suppress — the finding stays live
    src = tmp_path / "racy.py"
    src.write_text(
        "import threading\n"
        "class C:\n"
        "    def _run(self):\n"
        "        self.n += 1  # unlocked-ok racy on purpose\n"
        "    def start(self):\n"
        "        threading.Thread(target=self._run).start()\n"
        "    def reset(self):\n"
        "        self.n = 0  # unlocked-ok racy on purpose\n"
    )
    fs = locks.check_module(core.SourceModule(str(src), str(tmp_path)))
    assert len(fs) == 2, keys(fs)


def test_pragma_in_string_literal_does_not_suppress(tmp_path):
    # review-round regression: a docstring QUOTING a pragma directly above
    # the violating line must not count — only real comments index
    src = tmp_path / "hot.py"
    src.write_text(
        "def hot_learn(info):\n"
        '    """docs quote the escape hatch:\n'
        "    # host-sync-ok: like this\n"
        '    """\n'
        '    return float(info["loss"])\n'
    )
    # the string sits on the line above the call in source order; move the
    # violation adjacent to the quoted pragma line to prove immunity
    src.write_text(
        "def hot_learn(info):\n"
        "    x = (\n"
        '        "# host-sync-ok: quoted, not a comment"\n'
        '    ); y = float(info["loss"])\n'
        "    return x, y\n"
    )
    fs = hostsync_lint.check_module(
        core.SourceModule(str(src), str(tmp_path)),
        hot_path={"hot.py": ("*",)},
    )
    assert len(fs) == 1, keys(fs)


# ----------------------------------------------------------- config fixtures
def test_config_positive_fires():
    fs = configcheck.check_repo(
        REPO, modules=[fixture_module("config_positive.py")]
    )
    assert any("cfg.not_a_real_field" in f.message for f in fs), keys(fs)
    assert any("bogus_kind_xyz" in f.message for f in fs), keys(fs)


def test_config_pragma_suppresses():
    fs = configcheck.check_repo(
        REPO, modules=[fixture_module("config_pragma.py")]
    )
    assert fs == [], keys(fs)


def test_config_clean_is_clean():
    fs = configcheck.check_repo(
        REPO, modules=[fixture_module("config_clean.py")]
    )
    assert fs == [], keys(fs)


def test_default_off_families_hold():
    # the declared gates are real Config fields and hold their OFF values
    fs = configcheck.check_repo(REPO, modules=[])
    assert fs == [], keys(fs)
    valid, defaults = configcheck.config_surface(REPO)
    for field in ("league_dir", "serve_net_host", "device_sampling"):
        assert field in valid
        assert defaults[field] == configcheck.DEFAULT_OFF[field]


def test_every_config_field_has_a_reader():
    # a field nothing names is a CLI flag that does nothing
    fs = configcheck.check_field_use(REPO)
    assert fs == [], keys(fs)


def test_doc_fixtures():
    pos = configcheck.check_docs(
        REPO, doc_paths=[f"{FIXTURES}/doc_positive.md"]
    )
    assert len(pos) == 1 and "totally_fake_knob" in pos[0].message
    assert configcheck.check_docs(
        REPO, doc_paths=[f"{FIXTURES}/doc_pragma.md"]
    ) == []
    assert configcheck.check_docs(
        REPO, doc_paths=[f"{FIXTURES}/doc_clean.md"]
    ) == []


# ------------------------------------------------------------- the meta-test
def test_full_package_run_is_finding_free():
    findings = runner.run_all(REPO)
    assert findings == [], "\n" + core.render_report(findings)


def test_baseline_ships_empty():
    baseline = core.load_baseline(os.path.join(REPO, runner.BASELINE_PATH))
    assert baseline == frozenset(), (
        "the baseline must ship empty — fix or pragma instead of "
        f"grandfathering: {sorted(baseline)}"
    )


def test_cli_runner_green():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "static_analysis.py")],
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_cli_rejects_unknown_analyzer():
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "scripts", "static_analysis.py"),
            "--analyzer",
            "nope",
        ],
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 2


# ------------------------------------------- regression pins for real fixes
def _module_findings(rel):
    module = core.SourceModule(os.path.join(REPO, rel), REPO)
    return locks.check_module(module) + hostsync_lint.check_module(module)


def test_fix_heartbeat_beat_counters_locked():
    # PR-14 fix: HeartbeatWriter.beats/.suppressed raced beat() inline vs
    # the beat thread (the PR-7 heartbeat-payload race's counter sibling)
    fs = _module_findings("rainbow_iqn_apex_tpu/parallel/elastic.py")
    assert not [f for f in fs if "HeartbeatWriter" in f.message], keys(fs)


def test_fix_gossip_counters_locked():
    fs = _module_findings("rainbow_iqn_apex_tpu/serving/net/gossip.py")
    assert not [f for f in fs if "RouterGossip" in f.message], keys(fs)


def test_fix_remote_transport_version_locked():
    fs = _module_findings("rainbow_iqn_apex_tpu/serving/net/client.py")
    assert not [f for f in fs if "_version" in f.message], keys(fs)


def test_fix_router_emit_stamp_locked():
    fs = _module_findings("rainbow_iqn_apex_tpu/serving/fleet/router.py")
    assert not [f for f in fs if "_t_last_emit" in f.message], keys(fs)


def test_fix_agent_act_sanctioned():
    fs = _module_findings("rainbow_iqn_apex_tpu/agents/agent.py")
    assert not [f for f in fs if "Agent.act" in f.message], keys(fs)


def test_gossip_counters_still_count():
    # behavioural half of the gossip fix: locked counters still advance
    from rainbow_iqn_apex_tpu.serving.net.gossip import RouterGossip

    g = RouterGossip(router_id=1, snapshot_fn=lambda: {"engines": {}},
                     peers=[])
    try:
        g.broadcast()
        g.broadcast()
        assert g.sent == 2 and g._seq == 2
    finally:
        g.stop()


def test_heartbeat_beat_still_counts(tmp_path):
    from rainbow_iqn_apex_tpu.parallel.elastic import HeartbeatWriter

    w = HeartbeatWriter(str(tmp_path), process_id=0, interval_s=60.0)
    w.beat()
    w.beat()
    assert w.beats == 2
    w.stop()


# ------------------------------------- schema registry / lint_jsonl dedupe
def test_notice_actor_adopt_kinds_registered():
    from rainbow_iqn_apex_tpu.obs.schema import KNOWN_KINDS, REQUIRED_KEYS

    assert {"notice", "actor", "adopt"} <= KNOWN_KINDS
    assert REQUIRED_KEYS["notice"] == frozenset({"event"})


def test_lint_jsonl_uses_schema_registry():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        from lint_jsonl import lint_line
    finally:
        sys.path.pop(0)
    envelope = '"ts": 1.0, "host": 0, "run": "r", "schema": 1'
    ok = lint_line('{"kind": "notice", "event": "x", %s}' % envelope)
    assert ok is None, ok
    err = lint_line('{"kind": "never_registered", %s}' % envelope)
    assert err is not None and "unknown row kind" in err
    # required keys still enforced through the same registry
    err = lint_line('{"kind": "adopt", "tick": 1, %s}' % envelope)
    assert err is not None and "version" in err


def test_validate_row_known_kind_flag():
    from rainbow_iqn_apex_tpu.obs.schema import validate_row

    row = {"kind": "custom", "schema": 1, "ts": 0.0, "host": 0, "run": "r"}
    assert validate_row(row) == []  # permissive by default (in-process uses)
    errs = validate_row(row, require_known_kind=True)
    assert errs and "unknown row kind" in errs[0]


# --------------------------------------------------- framework odds and ends
def test_finding_keys_are_line_free():
    fs = locks.check_module(fixture_module("lock_positive.py"))
    for f in fs:
        assert str(f.line) not in f.key.split(":")[-1] or f.line > 999


def test_analysis_package_imports_jax_free():
    # runtime twin of the static self-hosting check: importing the
    # analysis package (and running an analyzer) must not pull in jax
    code = (
        "import sys; "
        "from rainbow_iqn_apex_tpu.analysis import runner, core; "
        "from rainbow_iqn_apex_tpu.analysis import locks; "
        "m = core.SourceModule("
        f"'{FIXTURES}/lock_clean.py', '.'); "
        "locks.check_module(m); "
        "assert 'jax' not in sys.modules, 'analysis import pulled in jax'"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
