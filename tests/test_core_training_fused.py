"""The fused trainer's segment with every core family's tiny core
(`train_anakin_r2d2` on freeway, tests/core_families.py's `tiny_config`): the
run trains, and its `learn` rows carry what the family's core lists.  A case's
body is written once; what a family's rows look like stands in a function a
family, which returns the name of its live-key counter, if it has one.  (The
core, the cut and the act step: tests/test_core_training.py.)"""

import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models.cores import make_core, state_bytes_per_lane

import core_families as cf


def kimi_linear(learn):
    assert all(r["moe_tokens_dropped"] == 0.0 for r in learn)
    assert all(0.0 <= r["moe_held_assign_share"] <= 1.0 for r in learn)
    assert all(r["moe_expert_load_max_over_mean"] >= 1.0 for r in learn)
    # on the CPU every KDA layer's preparation took the plain path
    assert all(r["kda_fused_tile_share"] == 0.0 for r in learn)
    assert "kda_scalar_gate_share" not in learn[0]  # its gate is dk wide


def deepseek_v3(learn):
    assert all(r["moe_tokens_dropped"] == 0.0 for r in learn)
    assert all(0.0 <= r["moe_held_assign_share"] <= 1.0 for r in learn)
    assert "kda_fused_tile_share" not in learn[0]
    return "mla_live_key_share"


def qwen3_next(learn):
    assert all(r["moe_tokens_dropped"] == 0.0 for r in learn)
    assert all(0.0 <= r["moe_held_assign_share"] <= 1.0 for r in learn)
    assert all(r["kda_fused_tile_share"] == 0.0 for r in learn)
    assert all(r["kda_scalar_gate_share"] == 1.0 for r in learn)
    assert "mla_live_key_share" not in learn[0]
    return "gattn_live_key_share"


def ouro(learn):
    # a core with no expert layer: the rows and the segment's outputs
    # carry no `moe_*` counter, and say how often the weights were used
    assert not [n for n in learn[0] if n.startswith("moe_")]
    assert all(r["loop_passes"] == 3.0 for r in learn)
    return "attn_live_key_share"  # in all six uses


def lfm2_moe(learn):
    # the rows carry what the core lists and nothing else
    assert all(r["moe_tokens_dropped"] == 0.0 for r in learn)
    assert all(0.0 <= r["moe_row_fill_share"] <= 1.0 for r in learn)
    assert all(0.0 <= r["moe_held_assign_share"] <= 1.0 for r in learn)
    assert "loop_passes" not in learn[0]
    return "attn_live_key_share"


def laguna(learn):
    assert all(r["moe_tokens_dropped"] == 0.0 for r in learn)
    assert all(0.0 <= r["moe_row_fill_share"] <= 1.0 for r in learn)
    assert all(0.0 <= r["moe_held_assign_share"] <= 1.0 for r in learn)
    # the sliding layers' 8 queries see the last 8 of their 5 to 12 keys
    # (5 + 6 + 7 + 5 x 8 of 8 x 12), and one block computes all 12 slots
    assert all(r["attn_live_key_share_sliding"] == pytest.approx(
        58 / 96) for r in learn)
    assert all(r["attn_band_key_share"] == 1.0 for r in learn)
    # a tick writes one slot of a ring of 16 and of two of 8
    assert all(r["attn_act_window_written_share"] == pytest.approx(
        (1 / 16 + 2 / 8) / 3) for r in learn)
    return "attn_live_key_share_full"


ROWS = {f.__name__: f for f in (
    kimi_linear, deepseek_v3, qwen3_next, ouro, lfm2_moe, laguna)}


@pytest.mark.parametrize("family", sorted(cf.FAMILIES))
def test_fused_segment_trains_with_the_core(tmp_path, family):
    from rainbow_iqn_apex_tpu.train_anakin_r2d2 import train_anakin_r2d2

    cfg = cf.tiny_config(tmp_path, family)
    summary = train_anakin_r2d2(cfg, max_frames=4 * 8 * 12)
    assert summary["learn_steps"] > 4
    learn = [r for r in cf.metric_rows(cfg.results_dir, cfg.run_id)
             if r["kind"] == "learn"]
    assert all(np.isfinite(r["loss"]) for r in learn)
    live_keys = ROWS[family](learn)
    if live_keys:
        # freeway has no terminals: the trained slice's 8 queries see the 4
        # burn-in keys and their own causal half, of 4 + 8 slots
        assert all(r[live_keys] == pytest.approx((8 * 4 + 36) / (8 * 12))
                   for r in learn)
    assert learn[0]["core_state_bytes_per_lane"] == state_bytes_per_lane(
        make_core(cfg))
