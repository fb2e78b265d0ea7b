"""The CLI (`train_agent_apex.py --architecture r2d2 --core-config <file>
--role anakin --env-id jaxgame:freeway`) with every non-LSTM core, at tiny
widths: the fused trainer (the host-fed roles' cases are
tests/test_core_cli.py's, whose table of cores this file reads)."""

import json

import pytest

from test_core_cli import CORES


def run_fused_cli(tmp_path, core):
    """A fused run of ten 8-tick dispatches of 4 lanes; its `learn` rows."""
    import train_agent_apex

    rc = train_agent_apex.main([
        "--role", "anakin", "--architecture", "r2d2",
        "--env-id", "jaxgame:freeway", "--core-config", CORES[core],
        "--compute-dtype", "float32", "--history-length", "2",
        "--hidden-size", "32", "--r2d2-burn-in", "4", "--r2d2-seq-len", "8",
        "--r2d2-overlap", "4", "--batch-size", "4", "--multi-step", "2",
        "--memory-capacity", "480", "--learn-start", "96",
        "--frames-per-learn", "2", "--num-envs-per-actor", "4",
        "--anakin-segment-ticks", "8", "--learner-devices", "1",
        "--eval-episodes", "1", "--eval-interval", "0",
        "--checkpoint-interval", "0", "--metrics-interval", "1",
        "--t-max", "320", "--run-id", "cli",
        "--results-dir", str(tmp_path / "results"),
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ])
    assert rc == 0
    rows = [json.loads(line) for line in open(
        tmp_path / "results" / "cli" / "metrics.jsonl")]
    return [r for r in rows if r["kind"] == "learn"]


@pytest.mark.parametrize("core", sorted(CORES))
def test_cli_runs_the_fused_trainer_with_core_config(tmp_path, core):
    learn = run_fused_cli(tmp_path, core)
    assert learn
    if core == "ouro":  # no expert layer: no such counter in its rows
        assert all("moe_tokens_dropped" not in r and r["loop_passes"] == 3.0
                   and "moe_act_touched_expert_share" not in r
                   for r in learn)
    else:
        assert all(r["moe_tokens_dropped"] == 0.0 for r in learn)
        # the ticks' own counter, the mean over a dispatch's ticks
        assert all(0.0 <= r["moe_act_touched_expert_share"] <= 1.0
                   for r in learn)
    assert all("core_state_bytes_per_lane" in r for r in learn)
    # every core has attention windows, rings of 12 slots in the tiny files:
    # a tick writes one slot of each
    assert all(r["attn_act_window_written_share"] == pytest.approx(1 / 12)
               for r in learn)
