"""The Kimi-Linear core (models/kimi_linear.py) against its plain float32
reference (tests/reference_kimi_linear_core.py), at tiny widths, float32
compute, seeded weights."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models import kimi_linear as kl
from rainbow_iqn_apex_tpu.models.cores import CORE_STATS, reduce_stats

import reference_kimi_linear_core as ref
from ring_windows import aged

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "fixtures", "kimi_core_tiny.json")


def tiny_cc(window=32, **over):
    """The reference attends over the whole sequence, so the window is as
    long as the sequences compared with it unless a test says otherwise."""
    with open(TINY) as f:
        cc = json.load(f)
    cc["hidden_size"] = 32  # no trunk in front of the core here
    cc["assumed"]["mla_window"] = window
    cc.update(over)
    return cc


def make(cc, batch=3, steps=20, seed=0, reset_at=((0, 5), (1, 9), (1, 10))):
    """(core, params, x, resets, zero state) with every leaf random, the
    router's selection bias and the decay's parameters included."""
    core = kl.KimiLinearCore(kl.KimiLinearConfig.from_dict(cc), jnp.float32)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k1, (batch, steps, cc["hidden_size"]))
    resets = np.zeros((batch, steps), bool)
    for b, t in reset_at:
        if b < batch and t < steps:
            resets[b, t] = True
    resets = jnp.asarray(resets)
    state = core.initial_state(batch)
    stack = kl._Stack(core.kc, jnp.float32)
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
    (o1, s1), (o2, s2) = chunked(*args), plain(*args)
    close(o1, o2)
    close(s1, s2)
    w = jax.random.normal(jax.random.PRNGKey(9), o1.shape)
    loss = lambda f: lambda *a: (  # noqa: E731
        jnp.sum(f(*a)[0] * w) + jnp.sum(f(*a)[1] ** 2))
    g1 = jax.grad(loss(chunked), argnums=range(6))(*args)
    g2 = jax.grad(loss(plain), argnums=range(6))(*args)
    for a, c in zip(g1, g2):
        close(a, c, 1e-3)


def test_sequence_pass_matches_the_reference_values_and_gradients():
    cc = tiny_cc()
    core, stack, params, x, resets, state = make(cc)
    w = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def prog(p):
        return stack.apply({"params": p}, x, state, resets)[0]

    def plain(p):
        return ref.core_forward(p, cc, x, resets)

    close(prog(params), plain(params))
    g1 = jax.grad(lambda p: jnp.sum(prog(p) * w))(params)
    g2 = jax.grad(lambda p: jnp.sum(plain(p) * w))(params)
    for (path, a), c in zip(jax.tree_util.tree_leaves_with_path(g1),
                            jax.tree.leaves(g2)):
        if "select_bias" in jax.tree_util.keystr(path):
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(c))
            continue
        close(a, c, 2e-3)


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

    def prog(p):
        _, st = stack.apply({"params": p}, x[:, :burn], state, resets[:, :burn])
        st = jax.lax.stop_gradient(st)
        ys = []
        for t in range(burn, steps):
            y, st = stack.apply({"params": p}, x[:, t:t + 1], st,
                                resets[:, t:t + 1])
            ys.append(y)
        return jnp.concatenate(ys, axis=1)

    def plain(p):
        return ref.core_forward(p, cc, x, resets, burn=burn)[:, burn:]

    close(prog(params), plain(params))
    g1 = jax.grad(lambda p: jnp.sum(prog(p) * w))(params)
    g2 = jax.grad(lambda p: jnp.sum(plain(p) * w))(params)
    for a, c in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        close(a, c, 2e-3)


def test_act_ticks_one_by_one_match_the_sequence_pass():
    """120 ticks of one step each from the zero state against one pass over
    the 120 steps; the window (12 here) limits both alike."""
    cc = tiny_cc(window=12)
    core, stack, params, x, resets, state = make(
        cc, batch=2, steps=120, reset_at=((0, 30), (1, 77), (1, 78)))
    seq, seq_state = stack.apply({"params": params}, x, state, resets)
    step = jax.jit(lambda st, xt, rt: stack.apply({"params": params}, xt, st, rt))
    st, ys = state, []
    for t in range(120):
        y, st = step(st, x[:, t:t + 1], resets[:, t:t + 1])
        ys.append(y)
    close(jnp.concatenate(ys, axis=1), seq)
    for a, c in zip(jax.tree.leaves(aged(st)),
                    jax.tree.leaves(aged(seq_state))):
        close(a, c)


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """4 shares of 16 experts, each computed by the program's layer told which
    4 it holds; the shared expert, which every chip computes alike, counted
    once: their sum is the uncut reference layer."""
    cc = tiny_cc()
    _, _, params, x, _, _ = make(cc)
    x = x.reshape(-1, x.shape[-1])
    cfg = kl.KimiLinearConfig.from_dict({**cc, "experts_here": 16})
    p = kl._MoE(cfg, jnp.float32).init(jax.random.PRNGKey(1), x)["params"]
    p["router"]["select_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(2), (16,))
    whole = ref.moe_ffn(p, cc, x, (0, 16), ref.plain_dot)
    shared = ref.swiglu(p["shared"], x, ref.plain_dot)
    total, held = shared, 0.0
    for first in (0, 4, 8, 12):
        share_cfg = dataclasses.replace(cfg, experts_here=4, first_expert=first)
        share_p = {**p, "experts": {n: w[first:first + 4]
                                    for n, w in p["experts"].items()}}
        y, sown = kl._MoE(share_cfg, jnp.float32).apply(
            {"params": share_p}, x, mutable=[CORE_STATS])
        close(y, ref.moe_ffn(share_p, cc, x, (first, 4), ref.plain_dot))
        total = total + (y - shared)
        stats = reduce_stats(sown)
        assert float(stats["moe_tokens_dropped"]) == 0.0
        held += float(stats["moe_held_assign_share"])
    close(total, whole)
    assert abs(held - 1.0) < 1e-6  # every assignment fell on one share


def test_no_token_is_dropped_when_every_token_picks_the_held_experts():
    """The worst case of the row buffer: 4 experts in all, all held, 4 a
    token; and the small buffers are the ones taken when few are held."""
    cc = tiny_cc(num_experts=4, num_experts_per_token=4)
    cfg = kl.KimiLinearConfig.from_dict({**cc, "experts_here": 4})
    x = jax.random.normal(jax.random.PRNGKey(0), (600, 32))
    moe = kl._MoE(cfg, jnp.float32)
    p = moe.init(jax.random.PRNGKey(1), x)["params"]
    y, sown = moe.apply({"params": p}, x, mutable=[CORE_STATS])
    stats = reduce_stats(sown)
    assert float(stats["moe_tokens_dropped"]) == 0.0
    assert float(stats["moe_held_assign_share"]) == 1.0
    close(y, ref.moe_ffn(p, cc, x, (0, 4), ref.plain_dot))


def test_the_two_copies_of_the_reference_are_the_same_text():
    root = os.path.dirname(HERE)
    with open(os.path.join(HERE, "reference_kimi_linear_core.py")) as a, open(
            os.path.join(root, "benchmarks", "references",
                         "kimi_linear_core.py")) as b:
        assert a.read() == b.read()
