"""The DeepSeek-V3-family core (models/deepseek_v3.py over models/mla_moe.py)
against its plain float32 reference (tests/reference_deepseek_v3_core.py), at
tiny widths, float32 compute, seeded weights: what is this family's own (the
cases every family shares are tests/test_core_reference.py's); and the
Kimi-Linear core, which runs the same blocks, held to what it computed before
they moved."""

import functools

import jax
import jax.numpy as jnp
import pytest

from rainbow_iqn_apex_tpu.models import deepseek_v3 as ds3
from rainbow_iqn_apex_tpu.models import mla_moe
from rainbow_iqn_apex_tpu.models.cores import reduce_stats

import core_families as cf
import reference_deepseek_v3_core as ref
from core_families import close
from ring_windows import aged

FAMILY = "deepseek_v3"
FEATURES = cf.FAMILIES[FAMILY].features
tiny_cc = functools.partial(cf.tiny_cc, FAMILY)
make = functools.partial(cf.make, FAMILY)
jitted = functools.partial(cf.jitted, FAMILY)


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
    run, plain = jitted(cc)
    ticks, st = cf.ticks_from(run, params, x, resets, state)
    close(ticks, plain(params, x, resets, window=window))
    seq, seq_state = run(params, x, state, resets)
    close(ticks, seq)
    cf.states_close(st, seq_state, aged)
    # the window matters here: the unwindowed pass differs
    assert float(jnp.abs(ticks - plain(params, x, resets)).max()) > 1e-3


@pytest.mark.parametrize("cut", [1, 6, 13, 19])
def test_a_cut_inside_a_sequence_starts_the_memory_anew(cut):
    """After a reset before step `cut` the outputs are those of a sequence
    that starts there: nothing of the steps before it is attended, and the
    positions that matter are relative."""
    cc = tiny_cc()
    core, stack, params, x, _, state = make(cc, batch=2, reset_at=())
    resets = jnp.zeros(x.shape[:2], bool).at[:, cut].set(True)
    run, plain = jitted(cc)
    whole = run(params, x, state, resets)[0]
    close(whole, plain(params, x, resets))
    fresh = run(params, x[:, cut:], state, jnp.zeros_like(resets[:, cut:]))[0]
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
    p, _ = cf.expert_layer(cfg, x)
    p["router"]["select_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(2), (128,))
    whole = ref.moe_ffn(p, cc, x, (0, 128), ref.plain_dot)
    shared = ref.swiglu(p["shared"], x, ref.plain_dot)
    cf.shares_add_up(cfg, cc, ref, p, x, 16, whole, shared)


def test_no_token_is_dropped_when_every_token_picks_the_held_experts():
    """The worst case of the row buffer at this geometry: 16 experts in all,
    all held, 6 a token, and enough tokens that the buffer is picked among
    three sizes (the largest, n x 6, is the one taken)."""
    cc = tiny_cc(n_routed_experts=16, num_experts_per_tok=6)
    cfg = ds3.DeepSeekV3Config.from_dict({**cc, "experts_here": 16})
    x = jax.random.normal(jax.random.PRNGKey(0), (600, cc["hidden_size"]))
    p, run = cf.expert_layer(cfg, x)
    y, stats = run(p, x)
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
        _, state = jitted(cc)[0](
            params, x[:, :filled], state, none[:, :filled])
    _, sown = cf.jitted_sown(FAMILY, cc)(
        params, x[:, filled:], state, none[:, filled:])
    got = float(reduce_stats(sown)["mla_live_key_share"])
    assert got == pytest.approx(share, rel=1e-6)
