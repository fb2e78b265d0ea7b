"""The Kimi-Linear core (models/kimi_linear.py) against its plain float32
reference (tests/reference_kimi_linear_core.py), at tiny widths, float32
compute, seeded weights: what is this family's own (the cases every family
shares are tests/test_core_reference.py's)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models import kimi_linear as kl
from rainbow_iqn_apex_tpu.models.cores import reduce_stats

import core_families as cf
import reference_kimi_linear_core as ref
from core_families import close
from ring_windows import aged

FAMILY = "kimi_linear"
tiny_cc = functools.partial(cf.tiny_cc, FAMILY)
make = functools.partial(cf.make, FAMILY)
jitted = functools.partial(cf.jitted, FAMILY)


def step_recurrence(q, k, v, g, beta, resets, s0):
    def one(s, xs):
        o, s = kl.kda_step(*xs, s)
        return s, o

    mv = lambda z: jnp.moveaxis(z, 1, 0)  # noqa: E731
    s, o = jax.lax.scan(
        one, s0, (mv(q), mv(k), mv(v), mv(g), mv(beta), mv(resets)))
    return jnp.moveaxis(o, 0, 1), s


@pytest.mark.parametrize("steps,chunk,block,strong", [
    (20, 8, 4, False), (40, 40, 8, True), (7, 8, 4, False), (24, 8, 8, True)])
def test_chunked_kda_matches_the_step_recurrence(steps, chunk, block, strong):
    """Values, final state and gradients, with resets inside a chunk, from a
    non-zero state; `strong` decays would overflow a chunk-wide exponent."""
    b, h, d = 2, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(steps), 6)
    norm = lambda z: z / jnp.linalg.norm(z, axis=-1, keepdims=True)  # noqa: E731
    q = norm(jax.random.normal(ks[0], (b, steps, h, d)))
    k = norm(jax.random.normal(ks[1], (b, steps, h, d)))
    v = jax.random.normal(ks[2], (b, steps, h, d))
    g = -jax.random.uniform(ks[3], (b, steps, h, d)) * (12.0 if strong else 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, steps, h)))
    s0 = jax.random.normal(ks[5], (b, h, d, d))
    resets = np.zeros((b, steps), bool)
    resets[0, 3], resets[1, 5], resets[1, min(6, steps - 1)] = True, True, True
    resets = jnp.asarray(resets)
    seg = jnp.cumsum(resets.astype(jnp.int32), axis=1)

    def chunked(q, k, v, g, beta, s0):
        return kl.kda_chunked(q, k, v, g, beta, seg, s0, chunk, block,
                              jnp.float32)

    def plain(q, k, v, g, beta, s0):
        return step_recurrence(q, k, v, g, beta, resets, s0)

    args = (q, k, v, g, beta, s0)
    (o1, s1), (o2, s2) = jax.jit(chunked)(*args), jax.jit(plain)(*args)
    close(o1, o2)
    close(s1, s2)
    w = jax.random.normal(jax.random.PRNGKey(9), o1.shape)
    loss = lambda f: lambda *a: (  # noqa: E731
        jnp.sum(f(*a)[0] * w) + jnp.sum(f(*a)[1] ** 2))
    g1 = jax.jit(jax.grad(loss(chunked), argnums=range(6)))(*args)
    g2 = jax.jit(jax.grad(loss(plain), argnums=range(6)))(*args)
    for a, c in zip(g1, g2):
        close(a, c, 1e-3)


def test_burn_in_then_steps_match_one_full_pass():
    """Burn-in as one sequence, then step by step through the states (the KDA
    state, the conv tails, the MLA window), against the reference's one pass
    with its stop-gradient boundary: values, and the gradient of the steps
    after the burn-in."""
    cc = tiny_cc()
    burn, steps = 6, 14
    core, stack, params, x, resets, state = make(
        cc, steps=steps, reset_at=((0, 2), (1, 9)))
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape)[:, burn:]
    run, plain = jitted(cc)  # the step: compiled once, called eight times

    def prog(p):
        _, st = run(p, x[:, :burn], state, resets[:, :burn])
        st = jax.lax.stop_gradient(st)
        ys = []
        for t in range(burn, steps):
            y, st = run(p, x[:, t:t + 1], st, resets[:, t:t + 1])
            ys.append(y)
        y = jnp.concatenate(ys, axis=1)
        return jnp.sum(y * w), y

    def want(p):
        y = plain(p, x, resets, burn=burn)[:, burn:]
        return jnp.sum(y * w), y

    # (not one program of eight inlined steps: the step's derivative compiles
    # once too, and is called eight times)
    (_, y), g1 = jax.value_and_grad(prog, has_aux=True)(params)
    (_, y_ref), g2 = jax.jit(jax.value_and_grad(want, has_aux=True))(params)
    close(y, y_ref)
    for a, c in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        close(a, c, 2e-3)


def test_act_ticks_one_by_one_match_the_sequence_pass():
    """120 ticks of one step each from the zero state against one pass over
    the 120 steps; the window (12 here) limits both alike."""
    cc = tiny_cc(window=12)
    core, stack, params, x, resets, state = make(
        cc, batch=2, steps=120, reset_at=((0, 30), (1, 77), (1, 78)))
    run, _ = jitted(cc)
    seq, seq_state = run(params, x, state, resets)
    ticks, st = cf.ticks_from(run, params, x, resets, state)
    close(ticks, seq)
    cf.states_close(st, seq_state, aged)


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """4 shares of 16 experts, each computed by the program's layer told which
    4 it holds; the shared expert, which every chip computes alike, counted
    once: their sum is the uncut reference layer."""
    cc = tiny_cc()
    _, _, params, x, _, _ = make(cc)
    x = x.reshape(-1, x.shape[-1])
    cfg = kl.KimiLinearConfig.from_dict({**cc, "experts_here": 16})
    p, _ = cf.expert_layer(cfg, x)
    p["router"]["select_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(2), (16,))
    whole = ref.moe_ffn(p, cc, x, (0, 16), ref.plain_dot)
    shared = ref.swiglu(p["shared"], x, ref.plain_dot)
    cf.shares_add_up(cfg, cc, ref, p, x, 4, whole, shared)


def test_no_token_is_dropped_when_every_token_picks_the_held_experts():
    """The worst case of the row buffer: 4 experts in all, all held, 4 a
    token; and the small buffers are the ones taken when few are held."""
    cc = tiny_cc(num_experts=4, num_experts_per_token=4)
    cfg = kl.KimiLinearConfig.from_dict({**cc, "experts_here": 4})
    x = jax.random.normal(jax.random.PRNGKey(0), (600, 32))
    p, run = cf.expert_layer(cfg, x)
    y, stats = run(p, x)
    assert float(stats["moe_tokens_dropped"]) == 0.0
    assert float(stats["moe_held_assign_share"]) == 1.0
    close(y, ref.moe_ffn(p, cc, x, (0, 4), ref.plain_dot))


def test_the_kimi_cores_parameter_paths_and_outputs_are_unchanged():
    """The blocks moved to models/mla_moe.py and `_MLA` learned to rotate:
    the Kimi-Linear core's parameter tree (which benchmarks/weights_core.py
    walks by name) and state are leaf for leaf what they were at the
    published sizes, and its outputs, final state, gradient and counters on
    a fixed seed are what the tree before the move computed."""
    with open(os.path.join(cf.HERE, "fixtures", "kimi_core_pinned.json")) as f:
        pinned = json.load(f)
    with open(cf.FAMILIES[FAMILY].published_path) as f:
        published = json.load(f)
    core = kl.KimiLinearCore(
        kl.KimiLinearConfig.from_dict(published), jnp.bfloat16)
    assert core.kc.rope_theta == 0.0 and not core.kc.in_proj
    shapes, state, _ = cf.stack_shapes(
        core.kc, cf.TRUNK_FEATURES, jnp.bfloat16)
    assert cf.shapes_by_path(shapes) == pinned["published_param_shapes"]
    assert cf.shapes_by_path(state) == pinned["published_state_shapes"]
    assert core.stat_names == (
        "moe_expert_load_max_over_mean", "moe_held_assign_share",
        "moe_tokens_dropped", "kda_fused_tile_share")

    cc = tiny_cc()
    _, stack, params, x, resets, state = make(cc)
    (y, new_state), sown = cf.jitted_sown(FAMILY, cc)(params, x, state, resets)
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
    run, _ = jitted(cc)
    grads = jax.jit(jax.grad(lambda p: jnp.sum(
        run(p, x, state, resets)[0] * w)))(params)
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
