"""One process for each chip: the sweep orchestrators' process discipline.

A sweep parent outlives every training child.  If it initialised an
accelerator backend it would hold the chip and every child would fail or
hang, so the parent pins itself to the CPU backend (through jax's config,
which children do not inherit) before any backend comes up, and children run
under the caller's own environment.  A child that exits non-zero is a failed
game in the aggregate, not an empty row.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Runs a one-game sweep in a fresh interpreter whose environment asks for the
# chip the way the TPU machine's does.  The stub standing in for the training
# child records which backend the parent holds by the time it spawns.
PARENT = """
import json, subprocess, sys
import jax

seen = []

def fake_run(cmd, **kw):
    seen.append({"parent_backend": jax.default_backend(),
                 "env_passed": kw.get("env") is not None})
    return subprocess.CompletedProcess(
        cmd, 0, stdout=json.dumps({"eval_score_mean": 1.0, "frames": 64}),
        stderr="")

subprocess.run = fake_run
from rainbow_iqn_apex_tpu.jaxsuite import run_sweep

agg = run_sweep([], games=["catch"], results_dir=sys.argv[1],
                baseline_episodes=2)
print(json.dumps({"seen": seen, "backend": jax.default_backend(),
                  "games": agg["games"]}))
"""


def test_sweep_parent_stays_off_the_chip_and_children_keep_callers_env(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="tpu,cpu")  # as on the chip machine
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, "-c", PARENT, str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    # when the child spawned the parent was already pinned to the CPU backend
    # (with the chip requested and none present, anything else would have
    # raised), and the child inherits the caller's environment untouched
    # (JAX_PLATFORMS=tpu,cpu: it gets the chip)
    assert out["seen"] == [{"parent_backend": "cpu", "env_passed": False}]
    # the parent's own baseline rollouts then ran on the CPU backend too
    assert out["backend"] == "cpu"
    assert out["games"] == 1


def _failing_child(monkeypatch):
    def fake_run(cmd, **kw):
        return subprocess.CompletedProcess(
            cmd, 3, stdout="", stderr="Traceback ...\nRuntimeError: TPU is busy")

    monkeypatch.setattr(subprocess, "run", fake_run)


def test_failed_child_raises_with_its_stderr_tail(monkeypatch, capsys):
    from rainbow_iqn_apex_tpu.atari57 import SweepChildFailed, train_one_game

    _failing_child(monkeypatch)
    with pytest.raises(SweepChildFailed, match="exited 3.*TPU is busy"):
        train_one_game("jaxgame:catch", "r", [])
    assert "RuntimeError: TPU is busy" in capsys.readouterr().err


def test_failed_child_is_a_failed_game_in_both_sweeps(tmp_path, monkeypatch):
    from rainbow_iqn_apex_tpu import atari57, jaxsuite

    _failing_child(monkeypatch)
    agg = jaxsuite.run_sweep([], games=["catch"], results_dir=str(tmp_path / "j"))
    assert agg["games_failed"] == 1 and agg["failed_games"] == ["catch"]
    assert agg["games"] == 0
    row = (tmp_path / "j" / "per_game.csv").read_text()
    assert "training CLI exited 3" in row and "salvaged" not in row

    agg = atari57.run_sweep([], games=["Pong"], results_dir=str(tmp_path / "a"))
    assert agg["games_failed"] == 1
    assert "exited 3" in agg["failed_games"]["Pong"]
