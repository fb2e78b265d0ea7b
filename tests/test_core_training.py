"""The R2D2 agent with every core family's tiny core (`Config.core_config`,
tests/core_families.py's table) through the normal paths: the core picked by
the file's `model_type`, the parameter count of the published cut and the act
step, at tiny widths (the trunk's 2,304 features at 80x80
frames go through the input projection to the core's hidden size, which the
heads read; they ARE the Kimi-Linear core's hidden size, as in its published
configuration).  A case's body is written once; what a family's core, cut
and state look like stands in a function a family.  The fused segment:
tests/test_core_training_fused.py (a file of its own: a fused run a family is
the longest case here, and no file may hold more than 400 s of test time);
the learn step against the reference: tests/test_core_training_learn.py; the
CLI: tests/test_core_cli_*.py."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models.cores import (
    LSTMCore,
    make_core,
    state_bytes_per_lane,
    zero_lanes,
)
from rainbow_iqn_apex_tpu.ops.r2d2 import build_r2d2_act_step, init_r2d2_state

import core_families as cf
from ring_windows import aged

families = pytest.mark.parametrize("family", sorted(cf.FAMILIES))


def count(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def leaf_names(tree):
    return {jax.tree_util.keystr(p).rsplit("'", 2)[-2]
            for p, _ in jax.tree_util.tree_leaves_with_path(tree)}


# A class a family, as a namespace of plain functions (never instantiated).
# -------------------------------------------------------------- Kimi-Linear
class KimiLinear:
    def core(cfg, core, published):
        assert core.kc.hidden == 2304
        assert make_core(cfg.replace(core_config="")) == LSTMCore(
            cfg.lstm_size)
        # 4 KDA layers of S [2, 8, 8] + tails [3, 48], one MLA window
        # [12, 20+1] and the ring's head
        assert state_bytes_per_lane(core) == (
            4 * (4 * (128 + 144)) + 4 * (12 * 21 + 1))
        kda = 32 * 128 * 128 + 3 * 3 * 4096
        assert state_bytes_per_lane(published) == 4 * (
            4 * kda + 120 * 577 + 1)

    def state(state):
        pass


# -------------------------------------------------------------- DeepSeek-V3
class DeepSeekV3:
    def core(cfg, core, published):
        assert core.kc.hidden == 32 and core.kc.in_proj
        assert core.kc.rope_theta == 1000.0
        assert [m.layer_name for m in core.kc.mixers] == ["mla"] * 5
        # five windows of 12 latents (16 + 8), their validity and the ring's
        # head, float32
        assert state_bytes_per_lane(core) == 5 * 4 * (12 * (24 + 1) + 1)
        kc = published.kc
        assert (kc.hidden, kc.layers, kc.mla_heads, kc.nope, kc.rope,
                kc.v_dim, kc.kv_rank) == (2048, 5, 32, 128, 64, 128, 512)
        assert (kc.experts, kc.experts_here, kc.top_k, kc.expert_width,
                kc.shared_width, kc.dense_width) == (
                    128, 16, 6, 768, 1536, 6144)
        assert (kc.rope_theta, kc.route_scale, kc.window) == (1e6, 2.448, 120)
        # 1.38 MB a lane
        assert state_bytes_per_lane(published) == 5 * 4 * (120 * 577 + 1)

    def cut(core, params):
        """benchmarks/configs/kanana-2-r2d2-1chip.json: 519 million."""
        mla = 2048 * 6144 + 2048 * 576 + 512 + 512 * 8192 + 4096 * 2048
        assert count(core["layer_1"]["mla"]) == mla == 26_345_984
        assert count(core["layer_1"]["ffn"]) == 3 * 2048 * 6144
        moe = core["layer_2"]["moe"]
        assert count(moe["experts"]) == 16 * 3 * 2048 * 768
        assert count(moe["shared"]) == 3 * 2048 * 1536
        assert count(moe["router"]) == 2048 * 128 + 128
        assert count(core) == 515_007_488
        assert count(params) == 519_285_928  # x 20 B = 10.39 GB

    def state(state):
        # the rope keys are kept un-rotated: a step's latent does not depend
        # on when it was written
        lat = np.asarray(aged(state)["layer_1"]["lat"])
        np.testing.assert_allclose(
            lat[:, -1], lat[:, -2], rtol=1e-6, atol=1e-7)


# --------------------------------------------------------------- Qwen3-Next
class Qwen3Next:
    def core(cfg, core, published):
        assert core.kc.hidden == 32 and core.kc.in_proj
        assert [m.layer_name for m in core.kc.mixers] == (
            ["gdn"] * 3 + ["gattn"])
        # three states S [4, 8, 8] with tails [3, 2x16 + 32], one window of
        # 12 keys and values [2, 8], its validity and the ring's head, float32
        assert state_bytes_per_lane(core) == 4 * (
            3 * (4 * 8 * 8 + 3 * 64) + 12 * (2 * 2 * 8 + 1) + 1)
        assert state_bytes_per_lane(published) == 7_078_372  # 7.08 MB a lane

    def cut(core, params):
        """benchmarks/configs/qwen3-next-r2d2-1chip.json: 557 million."""
        gdn = (2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128
               + 4096 * 2048)
        assert count(core["layer_1"]["gdn"]) == gdn == 33_718_464
        gattn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
        assert count(core["layer_4"]["gattn"]) == gattn == 27_263_488
        for i in (1, 2, 3, 4):  # every feed-forward is an expert layer
            moe = core[f"layer_{i}"]["moe"]
            assert count(moe["experts"]) == 32 * 3 * 2048 * 512
            assert count(moe["shared"]) == 3 * 2048 * 512
            assert count(moe["shared_gate"]) == 2048
            assert count(moe["router"]) == 2048 * 512 + 512
        assert count(core) == 552_596_544
        assert count(params) == 556_874_984  # x 20 B = 11.14 GB
        # every leaf bears a name benchmarks/weights_core.py fills
        assert leaf_names(core) == {
            "kernel", "gate", "up", "down", "scale", "taps", "A_log",
            "dt_bias", "select_bias"}

    def state(state):
        # the keys are kept un-rotated; a key is a function of the residual
        # stream, which three recurrent layers have moved between the two
        # ticks
        keys = np.asarray(aged(state)["layer_4"]["k"])
        assert np.abs(keys[:, -1]).max() > 0 and np.abs(keys[:, -2]).max() > 0
        assert not np.any(keys[:, :-2])
        assert np.abs(np.asarray(state["layer_1"]["S"])).max() > 0


# --------------------------------------------------------------------- Ouro
class Ouro:
    def core(cfg, core, published):
        assert core.kc.hidden == 32 and core.kc.in_proj
        assert [m.layer_name for m in core.kc.mixers] == ["mha"] * 2
        assert core.kc.passes == 3
        # 3 x 2 windows of 12 keys and values [4, 8], their validity and the
        # ring's head, float32
        assert state_bytes_per_lane(core) == (
            4 * 6 * (12 * (2 * 4 * 8 + 1) + 1))
        # 31.5 MB a lane
        assert state_bytes_per_lane(published) == 31_465_024

    def cut(core, params):
        """benchmarks/configs/ouro-r2d2-1chip.json: 215 million.  Four
        layers' leaves, once, though the stack is run four times."""
        assert sorted(core) == ["final_norm", "in_proj", "layer_1", "layer_2",
                                "layer_3", "layer_4"]
        for i in (1, 2, 3, 4):
            layer = core[f"layer_{i}"]
            assert count(layer["mha"]) == 4 * 2048 * 2048
            assert count(layer["ffn"]) == 3 * 2048 * 5632
            assert count(layer) == 51_388_416  # with its four norms
        assert count(core) == 210_274_304
        assert count(params) == 214_552_744  # x 20 B = 4.29 GB
        # every leaf bears a name benchmarks/weights_core.py fills
        assert leaf_names(core) == {"kernel", "scale"}

    def state(state):
        # every (pass, layer) window holds the two steps' keys, un-rotated,
        # in its two newest slots, and a pass's keys are not another pass's
        for name, window in aged(state).items():
            keys = np.asarray(window["k"])
            assert np.abs(keys[:, -1]).max() > 0
            assert np.abs(keys[:, -2]).max() > 0
            assert not np.any(keys[:, :-2]), name
        assert np.abs(np.asarray(state["pass_1_layer_1"]["k"])
                      - np.asarray(state["pass_2_layer_1"]["k"])).max() > 0


# --------------------------------------------------------------------- LFM2
class Lfm2:
    def core(cfg, core, published):
        assert core.kc.hidden == 32 and core.kc.in_proj
        assert [m.layer_name for m in core.kc.mixers] == [
            "sconv", "mha", "sconv", "sconv", "sconv"]
        # four 2-step tails of 32, one window of 12 keys and values [2, 8]
        # and its validity and the ring's head, float32
        assert state_bytes_per_lane(core) == 4 * (
            4 * 2 * 32 + 12 * (2 * 2 * 8 + 1) + 1)
        assert state_bytes_per_lane(published) == 557_540  # 0.56 MB a lane

    def cut(core, params):
        """benchmarks/configs/lfm2-r2d2-1chip.json: 483 million."""
        assert sorted(core) == ["final_norm", "in_proj"] + [
            f"layer_{i}" for i in range(1, 6)]
        assert count(core["layer_1"]["sconv"]) == 4 * 2048 * 2048 + 3 * 2048
        assert count(core["layer_1"]["ffn"]) == 3 * 2048 * 7168
        assert count(core["layer_1"]) == 60_827_648
        assert count(core["layer_2"]["mha"]) == (
            2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64)
        assert count(core["layer_2"]) == 98_635_936
        # router 2048 x 32 + 32, 8 experts of 3 x 2048 x 1792
        for i in (2, 3, 4, 5):
            moe = core[f"layer_{i}"]["moe"]
            assert sorted(moe) == ["experts", "router"]  # no shared expert
            assert count(moe["router"]) == 65_568
            assert count(moe["experts"]) == 8 * 11_010_048
        for i in (3, 4, 5):
            assert count(core[f"layer_{i}"]) == 104_933_408
        assert count(core) == 478_984_448
        assert count(params) == 483_262_888  # x 20 B = 9.67 GB
        experts = sum(count(core[f"layer_{i}"]["moe"]["experts"])
                      for i in (2, 3, 4, 5))
        assert round(100 * experts / count(params)) == 73
        # every leaf bears a name benchmarks/weights_core.py fills
        assert leaf_names(core) == {"kernel", "scale", "taps", "gate", "up",
                                    "down", "select_bias"}

    def state(state):
        # the window holds the two steps' keys in its two newest slots, and
        # every convolution's tail the two steps' gated inputs
        keys = np.asarray(aged(state)["layer_2"]["k"])
        assert np.abs(keys[:, -1]).max() > 0 and np.abs(keys[:, -2]).max() > 0
        assert not np.any(keys[:, :-2])
        for i in (1, 3, 4, 5):
            tail = np.asarray(state[f"layer_{i}"]["conv"])
            assert tail.shape == (2, 2, 32)
            assert np.abs(tail[:, 0]).max() > 0
            assert np.abs(tail[:, 1]).max() > 0


# ------------------------------------------------------------------- Laguna
class Laguna:
    def core(cfg, core, published):
        assert core.kc.hidden == 32 and core.kc.in_proj
        assert [m.layer_name for m in core.kc.mixers] == ["gqa"] * 3
        geoms = [m.geom for m in core.kc.mixers]
        assert [(g.kind, g.heads, g.span) for g in geoms] == [
            ("full", 6, 0), ("sliding", 8, 8), ("sliding", 8, 8)]
        # two readings of one file build the same classes
        assert core.kc.mixers[1] is core.kc.mixers[2]
        # a ring of 16 slots (the memory) and two of 8 (the sliding span) of
        # keys and values [2, 16], their validity and the rings' heads
        assert state_bytes_per_lane(core) == 4 * (
            16 * (2 * 2 * 16 + 1) + 1 + 2 * (8 * (2 * 2 * 16 + 1) + 1))
        kc = published.kc
        assert [(m.geom.kind, m.geom.heads, m.span(kc)) for m in kc.mixers] == [
            ("full", 48, 1024), ("sliding", 64, 512), ("sliding", 64, 512),
            ("sliding", 64, 512), ("full", 48, 1024)]
        full, sliding = kc.mixers[0].geom.rotation, kc.mixers[1].geom.rotation
        assert (len(full.freq), full.factor) == (32, 1.4158883083359672)
        assert (len(sliding.freq), sliding.factor) == (64, 1.0)
        assert (kc.hidden, kc.attn_kv_heads, kc.attn_head_dim, kc.window) == (
            2048, 8, 128, 1024)
        assert (kc.experts, kc.experts_here, kc.top_k, kc.expert_width,
                kc.shared_width, kc.dense_width, kc.first_dense) == (
                    256, 16, 8, 512, 512, 8192, 1)
        assert (kc.route, kc.route_scale, kc.shared_gate) == (
            "sigmoid", 2.5, False)
        # three rings of 512 and two of 1,024 slots: 29.4 MB a lane
        assert state_bytes_per_lane(published) == 4 * (
            3 * (512 * 2049 + 1) + 2 * (1024 * 2049 + 1)) == 29_374_484

    def cut(core, params):
        """benchmarks/configs/laguna-xs2-r2d2-1chip.json: 448 million."""
        assert sorted(core) == ["final_norm", "in_proj"] + [
            f"layer_{i}" for i in range(1, 6)]
        full = 2 * 2048 * 48 * 128 + 2 * 2048 * 1024 + 2048 * 48
        sliding = 2 * 2048 * 64 * 128 + 2 * 2048 * 1024 + 2048 * 64
        assert count(core["layer_1"]["gqa"]) == full == 29_458_432
        assert count(core["layer_5"]["gqa"]) == full
        assert count(core["layer_1"]["ffn"]) == 3 * 2048 * 8192
        assert count(core["layer_1"]) == 79_794_176
        for i in (2, 3, 4, 5):
            moe = core[f"layer_{i}"]["moe"]
            assert sorted(moe) == ["experts", "router", "shared"]
            assert count(moe["router"]) == 2048 * 256 + 256
            assert count(moe["experts"]) == 16 * 3 * 2048 * 512
            assert count(moe["shared"]) == 3 * 2048 * 512
        for i in (2, 3, 4):
            assert count(core[f"layer_{i}"]["gqa"]) == sliding == 37_879_808
            assert count(core[f"layer_{i}"]) == 91_885_824
        assert count(core["layer_5"]) == 83_464_448
        assert count(core) == 443_636_736
        assert count(params) == 447_915_176  # x 20 B = 8.96 GB
        experts = sum(count(core[f"layer_{i}"]["moe"]["experts"])
                      for i in (2, 3, 4, 5))
        assert round(100 * experts / count(params)) == 45
        # every leaf bears a name benchmarks/weights_core.py fills
        assert leaf_names(core) == {"kernel", "scale", "gate", "up", "down",
                                    "select_bias"}

    def state(state):
        # every ring, of either length, holds the two steps' keys in its two
        # newest slots
        for i, slots in ((1, 16), (2, 8), (3, 8)):
            keys = np.asarray(aged(state)[f"layer_{i}"]["k"])
            assert keys.shape == (2, slots, 2, 16)
            assert np.abs(keys[:, -1]).max() > 0
            assert np.abs(keys[:, -2]).max() > 0
            assert not np.any(keys[:, :-2])


# what is a family's own: `core` (the tiny core and the published one),
# `cut` (the published cut's parameter tree) and `state` (a lane's state after
# two ticks); a fused run's rows: tests/test_core_training_fused.py
EXPECTED = {"kimi_linear": KimiLinear, "deepseek_v3": DeepSeekV3,
            "qwen3_next": Qwen3Next, "ouro": Ouro, "lfm2_moe": Lfm2,
            "laguna": Laguna}


@families
def test_the_core_comes_from_the_files_model_type(tmp_path, family):
    fam = cf.FAMILIES[family]
    cfg = cf.tiny_config(tmp_path, family)
    core = make_core(cfg)
    published = make_core(cfg.replace(
        core_config="configs/cores/" + fam.published))
    _, core_class = fam.classes()
    assert isinstance(core, core_class) and isinstance(published, core_class)
    assert core.stored_width == 0
    EXPECTED[family].core(cfg, core, published)
    bad = tmp_path / "other.json"
    bad.write_text(json.dumps({"model_type": "llama"}))
    with pytest.raises(ValueError, match="no core for model_type 'llama'"):
        make_core(cfg.replace(core_config=str(bad)))


@pytest.mark.parametrize(
    "family", ["deepseek_v3", "laguna", "lfm2_moe", "ouro", "qwen3_next"])
def test_the_published_cut_is_so_many_million_parameters(tmp_path, family):
    """The byte count of the family's benchmarks/configs/*-r2d2-1chip.json,
    from `jax.eval_shape` of the program's own init: nothing is allocated."""
    cfg = cf.tiny_config(
        tmp_path, family,
        core_config="configs/cores/" + cf.FAMILIES[family].published,
        history_length=4, hidden_size=512, compute_dtype="bfloat16")
    params = jax.eval_shape(
        lambda k: init_r2d2_state(cfg, 3, k, (80, 80)).params,
        jax.random.PRNGKey(0))
    core = params["core"]
    assert count(core["in_proj"]) == 2304 * 2048
    # the heads read the core's hidden size
    assert params["value_hidden"]["w_mu"].shape == (2048, 512)
    EXPECTED[family].cut(core, params)


@families
@pytest.mark.parametrize("how", ["zero_lanes", "reset_lanes"])
def test_act_step_carries_the_state_and_a_cut_empties_it(
        tmp_path, how, family):
    cfg = cf.tiny_config(tmp_path, family)
    core = make_core(cfg)
    # the multiply of every leaf, and the core's own reset (a window by its
    # validity, what it held left in its slots): the same lane afterwards
    cut = zero_lanes if how == "zero_lanes" else core.reset_lanes
    ts = init_r2d2_state(cfg, 3, jax.random.PRNGKey(1), (80, 80))
    act = jax.jit(build_r2d2_act_step(cfg, 3, use_noise=False))
    obs = jax.random.bits(jax.random.PRNGKey(2), (2, 80, 80, 2), jnp.uint8)
    state = core.initial_state(2)
    _, q0, state = act(ts.params, obs, state, jax.random.PRNGKey(3))
    _, q1, state = act(ts.params, obs, state, jax.random.PRNGKey(3))
    assert np.abs(np.asarray(q1 - q0)).max() > 0  # the state matters
    EXPECTED[family].state(state)
    state = cut(state, jnp.asarray([0, 1], jnp.uint8))
    _, q2, _ = act(ts.params, obs, state, jax.random.PRNGKey(3))
    np.testing.assert_allclose(np.asarray(q2[0]), np.asarray(q0[0]),
                               rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(q2[1] - q0[1])).max() > 0
