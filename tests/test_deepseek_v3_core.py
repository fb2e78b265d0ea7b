"""The DeepSeek-V3-family core (models/deepseek_v3.py over models/mla_moe.py)
against its plain float32 reference (tests/reference_deepseek_v3_core.py), at
tiny widths, float32 compute, seeded weights; and the Kimi-Linear core, which
runs the same blocks, held to what it computed before they moved."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models import deepseek_v3 as ds3
from rainbow_iqn_apex_tpu.models import kimi_linear as kl
from rainbow_iqn_apex_tpu.models import mla_moe
from rainbow_iqn_apex_tpu.models.cores import CORE_STATS, reduce_stats

import reference_deepseek_v3_core as ref
from ring_windows import aged

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "fixtures", "deepseek_v3_core_tiny.json")
FEATURES = 24  # what the trunk would feed; the input projection takes any


def tiny_cc(window=32, **over):
    """The reference attends over the whole sequence, so the window is as
    long as the sequences compared with it unless a test says otherwise."""
    with open(TINY) as f:
        cc = json.load(f)
    cc["assumed"]["mla_window"] = window
    cc.update(over)
    return cc


def make(cc, batch=3, steps=20, seed=0, reset_at=((0, 5), (1, 9), (1, 10))):
    """(core, stack, params, x, resets, zero state) with every leaf random,
    the norms' scales and the router's selection bias included."""
    core = ds3.DeepSeekV3Core(ds3.DeepSeekV3Config.from_dict(cc), jnp.float32)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k1, (batch, steps, FEATURES))
    resets = np.zeros((batch, steps), bool)
    for b, t in reset_at:
        if b < batch and t < steps:
            resets[b, t] = True
    resets = jnp.asarray(resets)
    state = core.initial_state(batch)
    stack = mla_moe._Stack(core.kc, jnp.float32)
    params = stack.init(k2, x, state, resets)["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(k3, len(leaves))
    leaves = [p + 0.1 * jax.random.normal(k, p.shape) if p.ndim == 1 else p
              for p, k in zip(leaves, keys)]
    return core, stack, jax.tree.unflatten(tree, leaves), x, resets, state


def close(a, b, tol=2e-4):
    a, b = np.asarray(a), np.asarray(b)
    scale = max(float(np.abs(b).max()), 1e-6)
    assert float(np.abs(a - b).max()) <= tol * scale, (
        float(np.abs(a - b).max()), scale)


def grads_close(g1, g2, tol=2e-3):
    for (path, a), c in zip(jax.tree_util.tree_leaves_with_path(g1),
                            jax.tree.leaves(g2)):
        if "select_bias" in jax.tree_util.keystr(path):
            # the bias enters the choice alone: no gradient on either side
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(c))
            continue
        close(a, c, tol)


def test_sequence_pass_matches_the_reference_values_and_gradients():
    cc = tiny_cc()
    core, stack, params, x, resets, state = make(cc)
    w = jax.random.normal(jax.random.PRNGKey(4), (*x.shape[:2], core.kc.hidden))

    def prog(p):
        return stack.apply({"params": p}, x, state, resets)[0]

    def plain(p):
        return ref.core_forward(p, cc, x, resets)

    assert prog(params).shape == (*x.shape[:2], cc["hidden_size"])
    close(prog(params), plain(params))
    grads_close(jax.grad(lambda p: jnp.sum(prog(p) * w))(params),
                jax.grad(lambda p: jnp.sum(plain(p) * w))(params))


def test_burn_in_then_trained_slice_match_one_full_pass():
    """The learn step's two passes (burn-in, its final state stop-gradiented,
    then the trained slice from it) against the reference's one pass with its
    stop-gradient boundary: values, and the gradient of the trained slice.
    The slice's keys sit in the window at slots that are not their absolute
    positions; the scores are the same."""
    cc = tiny_cc()
    burn, steps = 6, 14
    core, stack, params, x, resets, state = make(
        cc, steps=steps, reset_at=((0, 2), (1, 9)))
    w = jax.random.normal(
        jax.random.PRNGKey(5), (x.shape[0], steps - burn, core.kc.hidden))

    def prog(p):
        _, st = stack.apply({"params": p}, x[:, :burn], state, resets[:, :burn])
        st = jax.lax.stop_gradient(st)
        return stack.apply({"params": p}, x[:, burn:], st, resets[:, burn:])[0]

    def plain(p):
        return ref.core_forward(p, cc, x, resets, burn=burn)[:, burn:]

    close(prog(params), plain(params))
    grads_close(jax.grad(lambda p: jnp.sum(prog(p) * w))(params),
                jax.grad(lambda p: jnp.sum(plain(p) * w))(params))


def test_act_ticks_over_a_window_that_rolls_twice_match_absolute_positions():
    """T = 2.5 W ticks of one step each from the empty state: every key is
    rotated by the slot it sits in when it is used, a slot that changes with
    every tick, and the scores are those of the published absolute positions
    0..T-1 (the reference, told the window's length), and of the program's
    own pass over the sequence."""
    window = 12
    steps = int(2.5 * window)
    cc = tiny_cc(window=window)
    core, stack, params, x, resets, state = make(
        cc, batch=2, steps=steps, reset_at=((0, 7), (1, 19), (1, 20)))
    step = jax.jit(lambda st, xt, rt: stack.apply({"params": params}, xt, st, rt))
    st, ys = state, []
    for t in range(steps):
        y, st = step(st, x[:, t:t + 1], resets[:, t:t + 1])
        ys.append(y)
    ticks = jnp.concatenate(ys, axis=1)
    close(ticks, ref.core_forward(params, cc, x, resets, window=window))
    seq, seq_state = stack.apply({"params": params}, x, state, resets)
    close(ticks, seq)
    for a, c in zip(jax.tree.leaves(aged(st)),
                    jax.tree.leaves(aged(seq_state))):
        close(a, c)
    # the window matters here: the unwindowed pass differs
    assert float(jnp.abs(
        ticks - ref.core_forward(params, cc, x, resets)).max()) > 1e-3


@pytest.mark.parametrize("cut", [1, 6, 13, 19])
def test_a_cut_inside_a_sequence_starts_the_memory_anew(cut):
    """After a reset before step `cut` the outputs are those of a sequence
    that starts there: nothing of the steps before it is attended, and the
    positions that matter are relative."""
    cc = tiny_cc()
    core, stack, params, x, _, state = make(cc, batch=2, reset_at=())
    resets = jnp.zeros(x.shape[:2], bool).at[:, cut].set(True)
    whole = stack.apply({"params": params}, x, state, resets)[0]
    close(whole, ref.core_forward(params, cc, x, resets))
    fresh = stack.apply({"params": params}, x[:, cut:], state,
                        jnp.zeros_like(resets[:, cut:]))[0]
    close(whole[:, cut:], fresh)


@pytest.mark.parametrize("shift", [0, 7, 100000])
def test_rotation_of_adjacent_pairs_against_permute_and_rotate_half(shift):
    """The program turns adjacent pairs in place; the published code permutes
    to halves and applies `rotate_half`.  Same scores; and a score depends on
    the difference of the two positions alone (a common shift changes
    nothing, which is what lets the window carry un-rotated keys)."""
    theta, d = 1e6, 64
    kq, kk = jax.random.split(jax.random.PRNGKey(shift))
    q = jax.random.normal(kq, (2, 9, 3, d))
    k = jax.random.normal(kk, (2, 9, d))
    pos = jnp.arange(9)
    # float32 angles at position 1e5 are good to about 0.01 rad (the two
    # sides round the frequencies differently): the reason the program keeps
    # every position under W + T
    far = shift >= 1000
    close(mla_moe.rotate_pairs(q, pos + shift, theta),
          ref.rope_pairs(q, pos + shift, theta), 1e-2 if far else 1e-6)
    close(mla_moe.rotate_pairs(k, pos + shift, theta),
          ref.rope_pairs(k, pos + shift, theta), 1e-2 if far else 1e-6)
    score = lambda rope, s: jnp.einsum(  # noqa: E731
        "bthd,bsd->bhts", rope(q, pos + s, theta), rope(k, pos + s, theta),
        precision=jax.lax.Precision.HIGHEST)
    pairs = score(mla_moe.rotate_pairs, shift)
    close(pairs, score(ref.rope_halves, shift), 1e-2 if far else 1e-5)
    close(pairs, score(ref.rope_pairs, 0), 3e-2 if far else 1e-5)


def test_the_eight_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """128 experts, 6 a token, 2 shared: 8 shares of 16 (experts 0-15, 16-31,
    ...), each computed by the program's layer told which 16 it holds; the
    shared experts, which every chip computes alike, counted once: their sum
    is the uncut reference layer."""
    cc = tiny_cc(n_routed_experts=128, num_experts_per_tok=6)
    x = jax.random.normal(jax.random.PRNGKey(0), (60, cc["hidden_size"]))
    cfg = ds3.DeepSeekV3Config.from_dict({**cc, "experts_here": 128})
    assert (cfg.experts, cfg.top_k) == (128, 6)
    assert cfg.shared_width == 2 * cc["moe_intermediate_size"]
    p = mla_moe._MoE(cfg, jnp.float32).init(jax.random.PRNGKey(1), x)["params"]
    p["router"]["select_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(2), (128,))
    whole = ref.moe_ffn(p, cc, x, (0, 128), ref.plain_dot)
    shared = ref.swiglu(p["shared"], x, ref.plain_dot)
    total, held = shared, 0.0
    for first in range(0, 128, 16):
        share_cfg = dataclasses.replace(cfg, experts_here=16, first_expert=first)
        share_p = {**p, "experts": {n: w[first:first + 16]
                                    for n, w in p["experts"].items()}}
        y, sown = mla_moe._MoE(share_cfg, jnp.float32).apply(
            {"params": share_p}, x, mutable=[CORE_STATS])
        close(y, ref.moe_ffn(share_p, cc, x, (first, 16), ref.plain_dot))
        total = total + (y - shared)
        stats = reduce_stats(sown)
        assert float(stats["moe_tokens_dropped"]) == 0.0
        held += float(stats["moe_held_assign_share"])
    close(total, whole)
    assert abs(held - 1.0) < 1e-6  # every assignment fell on one share


def test_no_token_is_dropped_when_every_token_picks_the_held_experts():
    """The worst case of the row buffer at this geometry: 16 experts in all,
    all held, 6 a token, and enough tokens that the buffer is picked among
    three sizes (the largest, n x 6, is the one taken)."""
    cc = tiny_cc(n_routed_experts=16, num_experts_per_tok=6)
    cfg = ds3.DeepSeekV3Config.from_dict({**cc, "experts_here": 16})
    x = jax.random.normal(jax.random.PRNGKey(0), (600, cc["hidden_size"]))
    moe = mla_moe._MoE(cfg, jnp.float32)
    p = moe.init(jax.random.PRNGKey(1), x)["params"]
    y, sown = moe.apply({"params": p}, x, mutable=[CORE_STATS])
    stats = reduce_stats(sown)
    assert float(stats["moe_tokens_dropped"]) == 0.0
    assert float(stats["moe_held_assign_share"]) == 1.0
    close(y, ref.moe_ffn(p, cc, x, (0, 16), ref.plain_dot))


@pytest.mark.parametrize("steps,filled,lane,share", [
    (40, 0, False, 820 / (40 * 40)),  # the burn-in from a sequence's start
    (80, 40, False, (80 * 40 + 3240) / (80 * 120)),  # the trained slice after
    (1, 120, True, 1.0),  # a warmed actor's tick: written first, its ring whole
])
def test_live_key_share_of_the_learn_steps_two_passes(
        steps, filled, lane, share):
    """`mla_live_key_share`: the share of score columns the mask leaves, at
    the published window and sequence lengths (tiny widths)."""
    cc = tiny_cc(window=120, layers_here=2)
    core, stack, params, _, _, state = make(cc, batch=1, steps=2, reset_at=())
    x = jax.random.normal(jax.random.PRNGKey(1), (1, filled + steps, FEATURES))
    none = jnp.zeros((1, filled + steps), bool)
    if not lane:  # the learner's passes: from a sequence's zero-slot start
        state = core.from_stored(jnp.zeros((1, 0)), jnp.zeros((1, 0)))
    if filled:
        _, state = stack.apply(
            {"params": params}, x[:, :filled], state, none[:, :filled])
    _, sown = stack.apply({"params": params}, x[:, filled:], state,
                          none[:, filled:], mutable=[CORE_STATS])
    got = float(reduce_stats(sown)["mla_live_key_share"])
    assert got == pytest.approx(share, rel=1e-6)


def test_the_kimi_cores_parameter_paths_and_outputs_are_unchanged():
    """The blocks moved to models/mla_moe.py and `_MLA` learned to rotate:
    the Kimi-Linear core's parameter tree (which benchmarks/weights_core.py
    walks by name) and state are leaf for leaf what they were at the
    published sizes, and its outputs, final state, gradient and counters on
    a fixed seed are what the tree before the move computed."""
    import test_kimi_linear_core as t

    with open(os.path.join(HERE, "fixtures", "kimi_core_pinned.json")) as f:
        pinned = json.load(f)
    with open(os.path.join(os.path.dirname(HERE), "configs", "cores",
                           "kimi_linear_48b_a3b.json")) as f:
        published = json.load(f)
    core = kl.KimiLinearCore(
        kl.KimiLinearConfig.from_dict(published), jnp.bfloat16)
    assert core.kc.rope_theta == 0.0 and not core.kc.in_proj
    state = jax.eval_shape(lambda: core.initial_state(2))
    shapes = jax.eval_shape(
        lambda k, x, s, r: kl._Stack(core.kc, jnp.bfloat16).init(
            k, x, s, r)["params"],
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((2, 3, 2304), jnp.float32),
        state, jax.ShapeDtypeStruct((2, 3), jnp.bool_))
    by_path = lambda tree: {  # noqa: E731
        jax.tree_util.keystr(p): list(v.shape)
        for p, v in jax.tree_util.tree_leaves_with_path(tree)}
    assert by_path(shapes) == pinned["published_param_shapes"]
    assert by_path(state) == pinned["published_state_shapes"]
    assert core.stat_names == (
        "moe_expert_load_max_over_mean", "moe_held_assign_share",
        "moe_tokens_dropped", "kda_fused_tile_share")

    _, stack, params, x, resets, state = t.make(t.tiny_cc())
    (y, new_state), sown = stack.apply(
        {"params": params}, x, state, resets, mutable=[CORE_STATS])
    np.testing.assert_allclose(
        np.asarray(y)[:, ::4, ::8], np.asarray(pinned["tiny_output"]),
        rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(y).sum()) == pytest.approx(
        pinned["tiny_output_abs_sum"], rel=1e-5)
    sums = lambda tree: {  # noqa: E731
        jax.tree_util.keystr(p): float(jnp.abs(v).sum())
        for p, v in jax.tree_util.tree_leaves_with_path(tree)}
    assert sums(new_state) == pytest.approx(
        pinned["tiny_state_abs_sums"], rel=1e-5)
    w = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    grads = jax.grad(lambda p: jnp.sum(
        stack.apply({"params": p}, x, state, resets)[0] * w))(params)
    assert sums(grads) == pytest.approx(
        pinned["tiny_grad_abs_sums"], rel=1e-4, abs=1e-6)
    stats = {k: float(v) for k, v in reduce_stats(sown).items()}
    assert stats.pop("mla_live_key_share") > 0  # sown by `_MLA`, not listed
    assert 0 < stats.pop("moe_row_fill_share") <= 1  # by `_MoE`, not listed
    # by `_MoE` where it walks the held experts (a few tokens), not listed
    assert 0 < stats.pop("moe_act_touched_expert_share") <= 1
    # by `_MLA` on a ring (PR 45): 20 steps from `initial_state` fill 20 of 32
    assert float(stats.pop("attn_act_window_written_share")) == 20 / 32
    assert stats == pytest.approx(pinned["tiny_stats"])


def test_the_two_copies_of_the_reference_are_the_same_text():
    root = os.path.dirname(HERE)
    with open(os.path.join(HERE, "reference_deepseek_v3_core.py")) as a, open(
            os.path.join(root, "benchmarks", "references",
                         "deepseek_v3_core.py")) as b:
        assert a.read() == b.read()
