"""The LFM2 core (models/lfm2.py over models/mla_moe.py and models/ouro.py's
attention) against its plain float32 reference (tests/reference_lfm2_core.py),
at tiny widths (the cut's five layers in its order, 4 query heads over 2
key/value heads of 8, 8 experts of which 2 are held and 2 a token), float32
compute, seeded weights: what is this family's own (the cases every family
shares are tests/test_core_reference.py's); an expert layer without a shared
expert, whose shares add up to the uncut layer; and the accepted cores, whose
trees the two new switches (`shared_width` 0, `attn_qk_norm`) leave as they
were."""

import dataclasses
import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models import lfm2, mla_moe, ouro
from rainbow_iqn_apex_tpu.models.cores import (
    CORE_STATS,
    reduce_stats,
    state_bytes_per_lane,
)
from rainbow_iqn_apex_tpu.obs import device_scopes as ds

import core_families as cf
import reference_lfm2_core as ref
from core_families import close, grads_close
from ring_windows import aged

FAMILY = "lfm2_moe"
FEATURES = cf.FAMILIES[FAMILY].features
tiny_cc = functools.partial(cf.tiny_cc, FAMILY)
make = functools.partial(cf.make, FAMILY)
jitted = functools.partial(cf.jitted, FAMILY)


def test_the_stack_is_the_cuts_five_layers_in_their_order():
    core, _, params, _, _, state = make(tiny_cc())
    kc = core.kc
    assert [m.layer_name for m in kc.mixers] == [
        "sconv", "mha", "sconv", "sconv", "sconv"]
    assert (kc.layers, kc.passes, kc.out_norms, kc.first_dense) == (
        5, 1, False, 1)
    assert (kc.experts, kc.top_k, kc.experts_here, kc.first_expert,
            kc.shared_width, kc.route, kc.route_scale) == (
                8, 2, 2, 0, 0, "sigmoid", 1)
    assert kc.attn_qk_norm and (kc.attn_heads, kc.attn_kv_heads,
                                kc.attn_head_dim, kc.conv_kernel) == (4, 2, 8, 3)
    assert sorted(params) == ["final_norm", "in_proj"] + [
        f"layer_{i}" for i in range(1, 6)]
    assert sorted(params["layer_1"]) == ["ffn", "ffn_norm", "mix_norm", "sconv"]
    assert sorted(params["layer_2"]) == ["ffn_norm", "mha", "mix_norm", "moe"]
    assert sorted(params["layer_3"]) == ["ffn_norm", "mix_norm", "moe", "sconv"]
    assert jax.tree.map(jnp.shape, params["layer_3"]["sconv"]) == {
        "in_proj": {"kernel": (32, 96)}, "conv": {"taps": (3, 32)},
        "out_proj": {"kernel": (32, 32)}}
    assert sorted(params["layer_2"]["mha"]) == [
        "k_norm", "k_proj", "o_proj", "q_norm", "q_proj", "v_proj"]
    assert params["layer_2"]["mha"]["q_norm"]["scale"].shape == (8,)
    # an expert layer holds its router and its held experts and nothing else
    assert jax.tree.map(jnp.shape, params["layer_2"]["moe"]) == {
        "router": {"kernel": (32, 8), "select_bias": (8,)},
        "experts": {"gate": (2, 32, 16), "up": (2, 32, 16),
                    "down": (2, 16, 32)}}
    # the state: one K/V window and four 2-step tails, every leaf led by lanes
    assert sorted(state) == [f"layer_{i}" for i in range(1, 6)]
    assert sorted(state["layer_2"]) == ["head", "k", "v", "valid"]
    assert state["layer_2"]["k"].shape == (3, 32, 2, 8)
    for i in (1, 3, 4, 5):
        assert jax.tree.map(jnp.shape, state[f"layer_{i}"]) == {
            "conv": (3, 2, 32)}
    assert core.stat_names == (
        "moe_expert_load_max_over_mean", "moe_held_assign_share",
        "moe_tokens_dropped", "attn_live_key_share", "moe_row_fill_share")


def test_the_convolution_reads_no_gated_input_from_before_a_cut():
    """The mixer alone: a cut before step t, and steps t and t + 1 (the two
    the 3 taps could reach across it) do not move when everything before t
    does; step t - 1 and an uncut lane do.  And the handed-on tail is the
    last two steps' `B * u`, void where they lie before the cut."""
    kc = lfm2.Lfm2Config.from_dict(tiny_cc())
    mixer = lfm2._ShortConv(kc, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, kc.hidden))
    state = mixer.zero_state(kc, 2)
    seg = jnp.zeros((2, 9), jnp.int32).at[0, 5:].set(1)  # lane 0 cut before 5
    p = mixer.init(jax.random.PRNGKey(1), x, state, seg)["params"]
    y, _ = mixer.apply({"params": p}, x, state, seg)
    moved = x.at[:, :5].add(1.0)
    y2, _ = mixer.apply({"params": p}, moved, state, seg)
    np.testing.assert_array_equal(np.asarray(y[0, 5:]), np.asarray(y2[0, 5:]))
    assert float(jnp.abs(y[0, 4] - y2[0, 4]).max()) > 1e-3
    assert float(jnp.abs(y[1, 5] - y2[1, 5]).max()) > 1e-3  # no cut there
    assert float(jnp.abs(y[1, 6] - y2[1, 6]).max()) > 1e-3
    np.testing.assert_array_equal(np.asarray(y[1, 7:]), np.asarray(y2[1, 7:]))
    # a cut at the last step: the tail's older slot lies before it
    late = jnp.zeros((2, 9), jnp.int32).at[0, 8:].set(1)
    _, out = mixer.apply({"params": p}, x, state, late)
    b_gate, _, u = jnp.split(
        ref.plain_dot(x, p["in_proj"]["kernel"]), 3, axis=-1)
    z = np.asarray(b_gate * u)
    close(out["conv"][1], z[1, 7:])
    close(out["conv"][0, 1], z[0, 8])
    assert not np.any(np.asarray(out["conv"][0, 0]))


def test_the_taps_stand_in_lag_order_the_published_kernel_reversed():
    """`taps[j]` meets z_{t-j}.  The published depthwise `Conv1d` (kernel
    [channel, 1, K], padding K-1, cross-correlation, cut to the first T
    outputs) puts its LAST tap on z_t: the same numbers read backwards."""
    kk, ch, t = 3, 5, 11
    z = jax.random.normal(jax.random.PRNGKey(0), (2, t, ch))
    taps = jax.random.normal(jax.random.PRNGKey(1), (kk, ch))
    ours, _ = mla_moe._causal_conv(
        z, taps, jnp.zeros((2, kk - 1, ch)), jnp.zeros((2, t), jnp.int32))
    weight = jnp.transpose(taps[::-1])[:, None, :]  # [channel, 1, K]
    published = jax.lax.conv_general_dilated(
        jnp.transpose(z, (0, 2, 1)), weight, (1,), [(kk - 1, kk - 1)],
        feature_group_count=ch, precision=jax.lax.Precision.HIGHEST)[..., :t]
    close(ours, jnp.transpose(published, (0, 2, 1)), 1e-6)


def test_the_four_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """8 experts, 2 a token, NO shared expert: 4 shares of 2 (experts 0-1,
    2-3, ...), each computed by the program's layer told which 2 it holds;
    with nothing that every chip computes alike to count once, their plain
    sum is the uncut reference layer."""
    cc = tiny_cc()
    x = jax.random.normal(jax.random.PRNGKey(0), (60, cc["hidden_size"]))
    cfg = lfm2.Lfm2Config.from_dict({**cc, "experts_here": 8})
    assert (cfg.experts, cfg.top_k, cfg.shared_width) == (8, 2, 0)
    p, _ = cf.expert_layer(cfg, x)
    assert sorted(p) == ["experts", "router"]
    p["router"]["select_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(2), (8,))
    whole = ref.moe_ffn(p, cc, x, (0, 8), ref.plain_dot)
    cf.shares_add_up(cfg, cc, ref, p, x, 2, whole, 0.0)


def test_no_shared_expert_is_no_leaf_and_no_op():
    """`shared_width` 0: no `shared` leaf in any expert layer and no op under
    `moe_shared` in the lowered module; the mixer's work wears `sconv_mix`."""
    kc = lfm2.Lfm2Config.from_dict(tiny_cc())
    shapes, _, _ = cf.stack_shapes(kc, FEATURES)
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(shapes)]
    assert not [p for p in paths if "shared" in p]
    text = cf.lowered_text(kc, FEATURES)
    # (this test's own name is in the text's locations: hence the "/")
    assert f"{ds.MOE_SHARED}/" not in text
    for scope in (ds.MOE_ROUTE, ds.MOE_EXPERTS, ds.SCONV_MIX, ds.MHA_PROJ,
                  ds.MHA_ATTN, ds.MHA_ROPE, ds.DENSE_FFN, ds.CORE_EMBED):
        assert f"{scope}/" in text, scope
    # inside `core_layer`, all of the mixer: its two products, the gates
    # and the taps' sums
    inside = set(re.findall(
        rf"{ds.CORE_LAYER}/[^\"]*/sconv/{ds.SCONV_MIX}/(\w+)", text))
    assert {"in_proj", "out_proj", "mul", "add"} <= inside
    assert ds.SCONV_MIX in ds.ALL_SCOPES


@pytest.mark.parametrize("family,gate", [
    ("kimi_linear", False), ("deepseek_v3", False), ("qwen3_next", True)])
def test_the_accepted_expert_cores_keep_their_shared_expert(family, gate):
    """The three accepted readers give `shared_width` a width: every expert
    layer keeps its `shared` leaves (and Qwen3-Next its gate) and the module
    its `moe_shared` ops; none of them lists the new counter (a listed
    counter is an output of the compiled segment)."""
    _, core, width = cf.tiny_core(family)
    assert core.kc.shared_width > 0 and not core.kc.attn_qk_norm
    shapes, _, _ = cf.stack_shapes(core.kc, width)
    moes = [v["moe"] for v in shapes.values() if "moe" in v]
    assert moes and all(sorted(m) == sorted(
        ["experts", "router", "shared"] + ["shared_gate"] * gate)
        for m in moes)
    assert "moe_row_fill_share" not in core.stat_names
    if family == "deepseek_v3":  # one lowering is enough: `_MoE` is one class
        assert f"{ds.MOE_SHARED}/" in cf.lowered_text(core.kc, width)


def test_attention_without_the_norms_is_ouros_tree_leaf_for_leaf():
    """`attn_qk_norm` is `CoreConfig`'s default False in Ouro's reader: its
    published tree has the four projections and nothing else, and `_MHA` told
    to norm differs from it by `q_norm` and `k_norm` alone."""
    with open(cf.FAMILIES["ouro"].published_path) as f:
        kc = ouro.OuroConfig.from_dict(json.load(f))
    assert not kc.attn_qk_norm
    x = jax.ShapeDtypeStruct((1, 2, kc.hidden), jnp.float32)
    seg = jax.ShapeDtypeStruct((1, 2), jnp.int32)

    def leaves(kc):
        state = jax.eval_shape(lambda: ouro._MHA.zero_state(kc, 1))
        shapes = jax.eval_shape(
            lambda k, x, s, g: ouro._MHA(kc, jnp.bfloat16).init(
                k, x, s, g)["params"], jax.random.PRNGKey(0), x, state, seg)
        return {jax.tree_util.keystr(p): v.shape
                for p, v in jax.tree_util.tree_leaves_with_path(shapes)}

    plain = leaves(kc)
    assert plain == {
        "['q_proj']['kernel']": (2048, 2048), "['k_proj']['kernel']": (2048, 2048),
        "['v_proj']['kernel']": (2048, 2048), "['o_proj']['kernel']": (2048, 2048)}
    normed = leaves(dataclasses.replace(kc, attn_qk_norm=True))
    assert {k: v for k, v in normed.items() if k not in plain} == {
        "['q_norm']['scale']": (128,), "['k_norm']['scale']": (128,)}
    assert {k: normed[k] for k in plain} == plain


def test_eight_key_value_heads_under_thirty_two_against_the_reference():
    """The grouped path at the published head counts (32 query heads over 8
    key/value heads; heads of 4 here): the mixer alone against the
    reference's attention, values and gradients, with a cut."""
    cc = tiny_cc(num_attention_heads=32, num_key_value_heads=8,
                 hidden_size=128)
    kc = lfm2.Lfm2Config.from_dict(cc)
    assert (kc.attn_heads, kc.attn_kv_heads, kc.attn_head_dim) == (32, 8, 4)
    mixer = ouro._MHA(kc, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 128))
    resets = jnp.zeros((2, 10), bool).at[1, 4].set(True)
    seg = jnp.cumsum(resets.astype(jnp.int32), axis=1)
    state = mixer.zero_state(kc, 2)
    p = mixer.init(jax.random.PRNGKey(1), x, state, seg)["params"]
    p["q_norm"]["scale"] = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (4,))
    p["k_norm"]["scale"] = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(3), (4,))
    w = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    prog = jax.jit(jax.value_and_grad(lambda p: jnp.sum(
        mixer.apply({"params": p}, x, state, seg)[0] * w)))
    want = jax.jit(jax.value_and_grad(lambda p: jnp.sum(
        ref.attention(p, cc, x, resets, 0, ref.plain_dot) * w)))
    (a, ga), (b, gb) = prog(p), want(p)
    assert float(a) == pytest.approx(float(b), rel=1e-4)
    grads_close(ga, gb)
    # the window keeps the keys after their norm, un-rotated
    _, new = mixer.apply({"params": p}, x, state, seg)
    k = ref.rms_norm(ref.plain_dot(x, p["k_proj"]["kernel"]).reshape(
        2, 10, 8, 4), p["k_norm"]["scale"], cc["norm_eps"])
    close(aged({"mha": new})["mha"]["k"][:, -10:], k)


@pytest.mark.parametrize("experts,chosen_held", [
    (16, 0),  # some held assignments, under the token count: that rung
    (8, 1),  # one held expert a token: the same rung, every row live
])
def test_row_fill_share_by_the_buffer_the_switch_took(experts, chosen_held):
    """`moe_row_fill_share` = held assignments over the rows of the buffer
    taken.  600 tokens, 2 a token, 2 held: buffers of 0, 600 and 1200 rows.
    An untrained router over 16 experts sends the 2 held ones some 150
    assignments, a quarter of the 600-row buffer; a selection bias on one
    held and one absent expert sends them 600, which fill it, as the cell's
    7,680 fill its 7,680 rows."""
    cc = tiny_cc(num_experts=experts)
    cfg = lfm2.Lfm2Config.from_dict(cc)
    n, k = 600, cfg.top_k
    rows = n  # the rung both cases take
    x = jax.random.normal(jax.random.PRNGKey(0), (n, cfg.hidden))
    p, run = cf.expert_layer(cfg, x)
    if chosen_held:
        p["router"]["select_bias"] = jnp.zeros((experts,)).at[
            jnp.asarray([1, 5])].set(2.0)
    y, stats = run(p, x)
    n_held = float(stats["moe_held_assign_share"]) * n * k
    assert 0 < n_held <= rows
    assert float(stats["moe_row_fill_share"]) == pytest.approx(n_held / rows)
    assert float(stats["moe_tokens_dropped"]) == 0.0
    if chosen_held:
        assert n_held == 600 and float(stats["moe_row_fill_share"]) == 1.0
        assert float(stats["moe_expert_load_max_over_mean"]) == experts / k
    else:
        assert n_held < 300
    close(y, ref.moe_ffn(p, cc, x, (0, 2), ref.plain_dot))


@pytest.mark.parametrize("steps,filled,lane,share", [
    (40, 0, False, 820 / (40 * 40)),  # the burn-in from a sequence's start
    (80, 40, False, (80 * 40 + 3240) / (80 * 120)),  # the trained slice after
    (1, 120, True, 1.0),  # a warmed actor's tick: written first, its ring whole
])
def test_live_key_share_and_the_expert_counters_of_the_learn_steps_passes(
        steps, filled, lane, share):
    """`attn_live_key_share` of the one attention layer at the published
    window and sequence lengths (tiny widths), and what the four expert
    layers sow beside it."""
    cc = tiny_cc(window=120)
    core, stack, params, _, _, state = make(cc, batch=1, steps=2, reset_at=())
    x = jax.random.normal(jax.random.PRNGKey(1), (1, filled + steps, FEATURES))
    none = jnp.zeros((1, filled + steps), bool)
    if not lane:  # the learner's passes: from a sequence's zero-slot start
        state = core.from_stored(jnp.zeros((1, 0)), jnp.zeros((1, 0)))
        assert state["layer_2"]["k"].shape == (1, 0, 2, 8)
        assert state["layer_1"]["conv"].shape == (1, 2, 32)
    if filled:
        _, state = jitted(cc)[0](
            params, x[:, :filled], state, none[:, :filled])
    _, sown = cf.jitted_sown(FAMILY, cc)(
        params, x[:, filled:], state, none[:, filled:])
    assert sorted(n for n in sown[CORE_STATS] if "moe" in sown[CORE_STATS][n]
                  ) == ["layer_2", "layer_3", "layer_4", "layer_5"]
    stats = reduce_stats(sown)
    assert float(stats["attn_live_key_share"]) == pytest.approx(share, rel=1e-6)
    assert set(core.stat_names) <= set(stats)
    assert float(stats["moe_tokens_dropped"]) == 0.0
    assert 0.0 <= float(stats["moe_row_fill_share"]) <= 1.0
    assert "loop_passes" not in stats


def test_the_published_file_reads_the_published_sizes():
    with open(cf.FAMILIES[FAMILY].published_path) as f:
        cc = json.load(f)
    assert len(cc["layer_types"]) == cc["num_hidden_layers"] == 24
    assert [i for i, k in enumerate(cc["layer_types"])
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    kc = lfm2.Lfm2Config.from_dict(cc)
    assert [m.layer_name for m in kc.mixers] == [
        "sconv", "mha", "sconv", "sconv", "sconv"]
    assert (kc.hidden, kc.passes, kc.out_norms, kc.first_dense, kc.dense_width,
            kc.eps, kc.in_proj, kc.conv_kernel) == (
                2048, 1, False, 1, 7168, 1e-5, True, 3)
    assert (kc.attn_heads, kc.attn_kv_heads, kc.attn_head_dim, kc.window,
            kc.rope_theta, kc.attn_qk_norm) == (32, 8, 64, 120, 1e6, True)
    assert (kc.experts, kc.top_k, kc.expert_width, kc.shared_width,
            kc.experts_here, kc.first_expert, kc.route, kc.route_scale) == (
                32, 4, 1792, 0, 8, 0, "sigmoid", 1)
    # four 2-step tails of 2,048 and one window of 120 keys and values
    # [8, 64] with its validity and the ring's head, float32: 0.56 MB a lane
    assert state_bytes_per_lane(lfm2.Lfm2Core(kc)) == 4 * (
        4 * 2 * 2048 + 2 * 120 * 8 * 64 + 120 + 1) == 557_540
    # the whole published model from its first layer: two dense layers lead
    whole = lfm2.Lfm2Config.from_dict(
        {**cc, "first_layer_here": 0, "layers_here": 24})
    assert (whole.layers, whole.first_dense) == (24, 2)
    assert [i for i, m in enumerate(whole.mixers) if m is ouro._MHA] == [
        2, 6, 10, 14, 18, 21]
    for key, bad in (("conv_bias", True),
                     ("rope_scaling", {"type": "yarn"}),
                     ("norm_topk_prob", False),
                     ("layer_types", ["conv", "sliding_attention"] * 12),
                     ("layers_here", 30)):
        with pytest.raises(ValueError, match="not written"):
            lfm2.Lfm2Config.from_dict({**cc, key: bad})
