"""The Ouro core (models/ouro.py over models/mla_moe.py) against its plain
float32 reference (tests/reference_ouro_core.py), at tiny widths (2 layers run
3 times, 4 heads of 8), float32 compute, seeded weights: what is this family's
own (the cases every family shares are tests/test_core_reference.py's): that
the weights are shared across the passes and the state is not; and the three
accepted cores, which run the same stack once with two norms a block."""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models import mla_moe
from rainbow_iqn_apex_tpu.models import ouro
from rainbow_iqn_apex_tpu.models.cores import (
    CORE_STATS,
    reduce_stats,
    state_bytes_per_lane,
)

import core_families as cf
import reference_ouro_core as ref
from core_families import close, grads_close

FAMILY = "ouro"
FEATURES = cf.FAMILIES[FAMILY].features
tiny_cc = functools.partial(cf.tiny_cc, FAMILY)
make = functools.partial(cf.make, FAMILY)
jitted = functools.partial(cf.jitted, FAMILY)


def test_the_stack_is_two_dense_attention_layers_run_three_times():
    core, _, params, _, _, state = make(tiny_cc())
    kc = core.kc
    assert [m.layer_name for m in kc.mixers] == ["mha", "mha"]
    assert (kc.layers, kc.passes, kc.out_norms, kc.first_dense) == (2, 3, True, 2)
    assert (kc.experts, kc.top_k, kc.experts_here) == (0, 0, 0)
    # each layer's leaves stand once, whatever the number of passes
    assert sorted(params) == ["final_norm", "in_proj", "layer_1", "layer_2"]
    assert sorted(params["layer_1"]) == [
        "ffn", "ffn_norm", "ffn_out_norm", "mha", "mix_norm", "mix_out_norm"]
    assert sorted(params["layer_2"]["mha"]) == [
        "k_proj", "o_proj", "q_proj", "v_proj"]
    # the state has one entry a (pass, layer), every leaf led by the lanes
    assert sorted(state) == [f"pass_{r}_layer_{i}"
                             for r in (1, 2, 3) for i in (1, 2)]
    assert sorted(state["pass_2_layer_1"]) == ["head", "k", "v", "valid"]
    assert state["pass_3_layer_2"]["k"].shape == (3, 32, 4, 8)
    assert all(leaf.shape[0] == 3 for leaf in jax.tree.leaves(state))
    assert core.stat_names == ("attn_live_key_share", "loop_passes")


@pytest.mark.parametrize("passes", [1, 3, 4])
def test_the_parameter_tree_does_not_go_by_the_number_of_passes(passes):
    cc = tiny_cc(total_ut_steps=passes)
    core, _, params = cf.built(FAMILY, cc)
    _, _, three = cf.built(FAMILY, tiny_cc())
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(jnp.shape, three)
    assert len(core.initial_state(1)) == passes * 2


def test_a_shared_leafs_gradient_is_the_sum_over_its_untied_copies():
    """The reference with a copy of every layer's leaves a pass, the copies
    set equal: the program's gradient of a (shared) leaf is the sum of the
    gradients of its three copies, and no copy's gradient is another's."""
    cc = tiny_cc()
    core, stack, params, x, resets, state = make(cc)
    w = jax.random.normal(jax.random.PRNGKey(6), (*x.shape[:2], core.kc.hidden))
    layers = {n: v for n, v in params.items() if n.startswith("layer_")}
    untied = {r: jax.tree.map(jnp.copy, layers) for r in (1, 2, 3)}

    def loss(untied):
        y = ref.core_forward(
            params, cc, x, resets,
            layer_of=lambda r, l: untied[r][f"layer_{l}"])
        return jnp.sum(y * w)

    copies = jax.jit(jax.grad(loss))(untied)
    shared = jax.jit(jax.grad(lambda p: jnp.sum(
        jitted(cc)[0](p, x, state, resets)[0] * w)))(params)
    summed = jax.tree.map(lambda a, b, c: a + b + c, *copies.values())
    grads_close({n: shared[n] for n in layers}, summed)
    first, last = (np.asarray(copies[r]["layer_1"]["mha"]["q_proj"]["kernel"])
                   for r in (1, 3))
    assert np.abs(first - last).max() > 1e-3 * np.abs(first).max()


@pytest.mark.parametrize("shift", [0, 7, 100000])
def test_whole_head_rotation_against_the_published_form(shift):
    """The program's `rotate_halves` over a whole head against the
    reference's `u cos + rotate_half(u) sin`; and a score depends on the
    difference of the two positions alone (a common shift changes nothing,
    which is what lets the windows carry un-rotated keys)."""
    theta, d = 1e6, 128
    kq, kk = jax.random.split(jax.random.PRNGKey(shift))
    q = jax.random.normal(kq, (2, 9, 3, d))
    k = jax.random.normal(kk, (2, 9, 3, d))
    pos = jnp.arange(9)
    # float32 angles at position 1e5 are good to about 0.01 rad: the reason
    # the program keeps every position under W + T
    far = shift >= 1000
    prog = lambda u, s: mla_moe.rotate_halves(u, pos + s, theta)  # noqa: E731
    close(prog(q, shift), ref.rope(q, pos + shift, theta),
          1e-2 if far else 1e-6)
    score = lambda a, b: jnp.einsum(  # noqa: E731
        "bthd,bshd->bhts", a, b, precision=jax.lax.Precision.HIGHEST)
    close(score(prog(q, shift), prog(k, shift)),
          score(ref.rope(q, pos, theta), ref.rope(k, pos, theta)),
          3e-2 if far else 1e-5)


@pytest.mark.parametrize("steps,filled,lane,share", [
    (40, 0, False, 820 / (40 * 40)),  # the burn-in from a sequence's start
    (80, 40, False, (80 * 40 + 3240) / (80 * 120)),  # the trained slice after
    (1, 120, True, 1.0),  # a warmed actor's tick: written first, its ring whole
])
def test_live_key_share_and_loop_passes_of_the_learn_steps_two_passes(
        steps, filled, lane, share):
    """`attn_live_key_share`: the share of score columns the mask leaves, the
    mean over the six (pass, layer) uses, at the published window and
    sequence lengths (tiny widths); `loop_passes`: how often the weights
    were used."""
    cc = tiny_cc(window=120)
    core, stack, params, _, _, state = make(cc, batch=1, steps=2, reset_at=())
    x = jax.random.normal(jax.random.PRNGKey(1), (1, filled + steps, FEATURES))
    none = jnp.zeros((1, filled + steps), bool)
    if not lane:  # the learner's passes: from a sequence's zero-slot start
        state = core.from_stored(jnp.zeros((1, 0)), jnp.zeros((1, 0)))
    if filled:
        _, state = jitted(cc)[0](
            params, x[:, :filled], state, none[:, :filled])
    _, sown = cf.jitted_sown(FAMILY, cc)(
        params, x[:, filled:], state, none[:, filled:])
    assert len(sown[CORE_STATS]["layer_2"]["mha"]["attn_live_key_share"]) == 3
    stats = reduce_stats(sown)
    assert float(stats["attn_live_key_share"]) == pytest.approx(share, rel=1e-6)
    assert float(stats["loop_passes"]) == 3.0
    assert not [n for n in stats if n.startswith("moe_")]


def test_the_published_file_reads_the_published_sizes():
    with open(cf.FAMILIES[FAMILY].published_path) as f:
        cc = json.load(f)
    kc = ouro.OuroConfig.from_dict(cc)
    assert [m.layer_name for m in kc.mixers] == ["mha"] * 4
    assert (kc.hidden, kc.passes, kc.out_norms, kc.first_dense, kc.dense_width,
            kc.eps, kc.in_proj) == (2048, 4, True, 4, 5632, 1e-6, True)
    assert (kc.attn_heads, kc.attn_kv_heads, kc.attn_head_dim, kc.window,
            kc.rope_theta) == (16, 16, 128, 120, 1e6)
    # 16 (pass, layer) windows of 120 keys and values [16, 128], their
    # validity and the ring's head, float32: 31.5 MB a lane
    assert state_bytes_per_lane(ouro.OuroCore(kc)) == 16 * (
        2 * 120 * 16 * 128 + 120 + 1) * 4 == 31_465_024
    for key, bad in (("use_sliding_window", True),
                     ("rope_scaling", {"type": "yarn"}),
                     ("early_exit_threshold", 0.5),
                     ("layer_types", ["sliding_attention"] * 48)):
        with pytest.raises(ValueError, match="not written"):
            ouro.OuroConfig.from_dict({**cc, key: bad})


@pytest.mark.parametrize(
    "family", ["kimi_linear", "deepseek_v3", "qwen3_next"])
def test_the_accepted_cores_run_the_stack_once_with_two_norms_a_block(family):
    """`passes` 1 and no output norms are `CoreConfig`'s defaults, which the
    three accepted readers leave alone: their state is keyed by the layer,
    their blocks hold the two pre-norms, and they list no `loop_passes` (the
    pinned trees of tests/test_qwen3_next_core.py hold them leaf for leaf)."""
    _, core, width = cf.tiny_core(family)
    assert (core.kc.passes, core.kc.out_norms) == (1, False)
    shapes, state, _ = cf.stack_shapes(core.kc, width)
    assert sorted(state) == [f"layer_{i}" for i in range(1, core.kc.layers + 1)]
    norms = {n for i in range(1, core.kc.layers + 1)
             for n in shapes[f"layer_{i}"] if n.endswith("norm")}
    assert norms == {"mix_norm", "ffn_norm"}
    assert "loop_passes" not in core.stat_names
    assert set(core.moe_stat_names) <= set(core.stat_names)


@pytest.mark.parametrize("family,passes", [
    ("kimi_linear", 1), ("deepseek_v3", 1), ("qwen3_next", 1), ("ouro", 3)])
def test_only_a_stack_run_several_times_wears_loop_pass(family, passes):
    """`loop_pass` stands round a pass only where there is more than one: a
    stack run once keeps the scope paths it had (`.../learn_step/core_layer`,
    which the accepted readers match whole: `core_unnamed_device_ms` sums
    the paths that hold `core_layer` and no scope it does not know)."""
    from rainbow_iqn_apex_tpu.obs import device_scopes as ds

    _, core, width = cf.tiny_core(family)
    assert core.kc.passes == passes
    text = cf.lowered_text(core.kc, width)
    assert ds.CORE_LAYER in text and ds.CORE_NORM in text
    # (this test's own name is in the text's locations: hence the "/")
    assert (f"{ds.LOOP_PASS}/{ds.CORE_LAYER}" in text) == (passes > 1)
    assert (f"{ds.LOOP_PASS}/" in text) == (passes > 1)


def test_one_pass_and_no_output_norms_is_the_plain_pre_norm_stack():
    """The Ouro mixer in the accepted cores' block: `passes` 1 and
    `out_norms` False give the pre-norm residual stack with the state keyed
    by the layer, written out here from the reference's pieces."""
    cc = tiny_cc(total_ut_steps=1)
    core, _, params, x, resets, _ = make(cc)
    kc = dataclasses.replace(core.kc, out_norms=False)
    plain_core = ouro.OuroCore(kc, jnp.float32)
    stack = mla_moe._Stack(kc, jnp.float32)
    state = plain_core.initial_state(x.shape[0])
    assert sorted(state) == ["layer_1", "layer_2"]
    pre = jax.tree.map(lambda a: a, params)
    for i in (1, 2):
        for name in ("mix_out_norm", "ffn_out_norm"):
            del pre[f"layer_{i}"][name]
    y = jax.jit(stack.apply)({"params": pre}, x, state, resets)[0]
    eps = cc["rms_norm_eps"]
    h = ref.plain_dot(x, pre["in_proj"]["kernel"])
    for i in (1, 2):
        lp = pre[f"layer_{i}"]
        h = h + ref.attention(lp["mha"], cc, ref.norm(h, lp["mix_norm"], eps),
                              resets, 0, ref.plain_dot)
        h = h + ref.swiglu(lp["ffn"], ref.norm(h, lp["ffn_norm"], eps),
                           ref.plain_dot)
    close(y, ref.norm(h, pre["final_norm"], eps))
