"""The CLI (`train_agent_apex.py --architecture r2d2 --core-config <file>
--role anakin --env-id jaxgame:freeway`) with every non-LSTM core, at tiny
widths: the fused trainer (the run is tests/core_families.py's; the host-fed
roles' cases are tests/test_core_cli_{anakin,single,apex}.py's)."""

import pytest

import core_families as cf


@pytest.mark.parametrize("core", sorted(cf.CORES))
def test_cli_runs_the_fused_trainer_with_core_config(tmp_path, core):
    learn = cf.run_fused_cli(tmp_path, core)
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
    # every core has attention windows, rings of 12 slots in the tiny files
    # (one of 16 and two of 8 in the one whose layers have spans of their
    # own): a tick writes one slot of each
    written = (1 / 16 + 2 / 8) / 3 if core == "laguna" else 1 / 12
    assert all(r["attn_act_window_written_share"] == pytest.approx(written)
               for r in learn)
