"""bench.py process discipline: one process, and a failure is an exit code."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RAISING_MEASURE = """
import bench

def boom():
    raise RuntimeError("device-replay phase blew up")

bench.measure = boom
raise SystemExit(bench.main())
"""


def test_bench_exits_nonzero_when_measure_raises():
    """A run whose measurement raises must fail the process — no CPU row, no
    0.0 row and no exit 0 in its place."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, "-c", RAISING_MEASURE],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert "device-replay phase blew up" in p.stderr
    assert p.stdout.strip() == ""


def test_bench_refuses_a_cpu_run_the_caller_did_not_ask_for(monkeypatch, capsys):
    """No accelerator and no JAX_PLATFORMS=cpu from the caller: measure()
    raises before it prints a row under a device metric's name."""
    import pytest

    import bench

    monkeypatch.delenv("JAX_PLATFORMS")  # jax itself is already up, on cpu
    with pytest.raises(RuntimeError, match="no accelerator"):
        bench.measure()
    assert capsys.readouterr().out == ""


def test_run_row_budgeted_emits_timeout_row_instead_of_dying():
    """ISSUE 6 satellite (the r05 regression): a row that exhausts its
    budget slice — or raises — must yield a labelled status row so the rows
    queued behind it still run and downstream sees WHY a value is 0.0."""
    import time

    import bench

    def overrunning(left):
        while left() > 0:
            time.sleep(0.005)
        return []

    rows = bench._run_row_budgeted(
        "sample_path", "m", overrunning, lambda: 1.0, share=0.05)
    assert rows[0]["status"] == "timeout"
    assert rows[0]["path"] == "sample_path" and rows[0]["value"] == 0.0

    rows = bench._run_row_budgeted(
        "apex_loop", "m", lambda left: 1 / 0, lambda: 100.0, share=0.5)
    assert rows[0]["status"] == "error"

    healthy = [{"metric": "m", "value": 1.0}]
    rows = bench._run_row_budgeted(
        "x", "m", lambda left: list(healthy), lambda: 100.0, share=0.5)
    assert rows == healthy
