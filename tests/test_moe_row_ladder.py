"""The ladder of `_MoE`'s row buffer (models/mla_moe.py: `EXPERT_ROWS`, the
`switch` over the rungs): by the held count, every rung against the plain
reference, values and gradients; which branches the `switch` has, what each
runs and what it hands the backward pass; and a stack in which one expert
layer holds every assignment and the others none."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models import lfm2, mla_moe
from rainbow_iqn_apex_tpu.models.cores import CORE_STATS, reduce_stats

import core_families as cf
from core_families import close, equations, grads_close

# 400 tokens, 3 choices a token, experts 0 to 3 held: rungs of 0 and 400 rows
# under the 1,200 that hold every assignment, which is over `FEW_ROWS`
N, K, HELD, EXPERTS = 400, 3, 4, 8
RUNGS = (0, N, K * N)


@functools.lru_cache(maxsize=None)
def _layer():
    """(cc, kc, the layer's seeded parameters with a router whose choice the
    first three features of a token decide; the layer with what it sowed and
    its gradients, and the reference with its gradients, compiled once)."""
    cc = cf.tiny_cc("lfm2_moe", num_experts=EXPERTS, experts_here=HELD,
                    num_experts_per_tok=K)
    kc = lfm2.Lfm2Config.from_dict(cc)
    assert K * N > mla_moe.FEW_ROWS and tuple(sorted(
        {min(K * N, int(N * r)) for r in mla_moe.EXPERT_ROWS} | {K * N})
    ) == RUNGS
    moe = mla_moe._MoE(kc, jnp.float32)
    ref = cf.FAMILIES["lfm2_moe"].ref
    x = jnp.zeros((N, kc.hidden))
    p = jax.jit(moe.init)(jax.random.PRNGKey(1), x)["params"]
    # feature j of a token, at +4 or -4, alone decides whether it chooses
    # held expert j (j < 3): the scores of the three absent experts 4, 5, 6
    # stand near 0.5 under a bias of 0.5, a held expert's near 1 or 0 under
    # 0.6; held expert 3 and absent expert 7 are never chosen
    kernel = 0.02 * p["router"]["kernel"]
    kernel = kernel.at[:K].set(0.0).at[jnp.arange(K), jnp.arange(K)].set(1.0)
    p["router"]["kernel"] = kernel
    p["router"]["select_bias"] = jnp.asarray(
        [0.6, 0.6, 0.6, -10.0, 0.5, 0.5, 0.5, -10.0])
    wgt = jax.random.normal(jax.random.PRNGKey(5), (N, kc.hidden))

    def run(p, x):
        y, sown = moe.apply({"params": p}, x, mutable=[CORE_STATS])
        return jnp.sum(y * wgt), (y, reduce_stats(sown))

    def plain(p, x):
        y = ref.moe_ffn(p, cc, x, (0, HELD), ref.plain_dot)
        return jnp.sum(y * wgt), y

    grad = lambda f: jax.jit(jax.value_and_grad(  # noqa: E731
        f, argnums=(0, 1), has_aux=True))
    return cc, kc, moe, p, grad(run), grad(plain)


def _tokens(n_held):
    """x [N, hidden], random but for the three deciding features: `n_held`
    of the 3 N (token, feature) pairs, token by token within a feature, are
    on."""
    kc = _layer()[1]
    x = jax.random.normal(jax.random.PRNGKey(7), (N, kc.hidden))
    on = (np.arange(K * N) < n_held).reshape(K, N).T  # [N, K]
    return x.at[:, :K].set(jnp.where(jnp.asarray(on), 4.0, -4.0))


@pytest.mark.parametrize("n_held", [
    0, 1, N // 2, N, N + 1, 2 * N, 2 * N + 1, K * N],
    ids=["none", "one", "half_the_tokens", "the_tokens", "the_tokens_and_one",
         "twice_the_tokens", "twice_and_one", "every_assignment"])
def test_every_rung_matches_the_reference_values_and_gradients(n_held):
    """The sequence pass at a held count on, between and beside the rungs:
    the smallest rung that holds the count is taken, nothing is dropped, the
    fill is finite (1.0 on the empty rung), and the output and the gradients
    of the held kernels, the router and the input are the plain
    reference's."""
    _, _, _, p, run, plain = _layer()
    x = _tokens(n_held)
    (_, (y, stats)), g = run(p, x)
    (_, want), g_ref = plain(p, x)
    assert float(stats["moe_held_assign_share"]) * N * K == pytest.approx(
        n_held)
    rows = min(r for r in RUNGS if r >= n_held)
    fill = float(stats["moe_row_fill_share"])
    assert np.isfinite(fill)
    assert fill == pytest.approx(n_held / rows if rows else 1.0)
    assert float(stats["moe_tokens_dropped"]) == 0.0
    if n_held:
        close(y, want)
    else:  # no shared expert: the layer's output is the held part, zeros
        assert not np.any(np.asarray(y)) and not np.any(np.asarray(want))
        assert not any(np.any(np.asarray(leaf))
                       for leaf in jax.tree.leaves(g))
    grads_close(g, g_ref)
    # an expert no token chose takes no gradient
    idle = [e for e in range(HELD) if n_held <= e * N]
    for leaf in g[0]["experts"].values():
        assert not np.any(np.asarray(leaf)[idle])
        assert n_held == 0 or np.any(np.asarray(leaf)[0])


def test_the_switch_has_an_empty_branch_and_two_with_rows():
    """The `switch` over the rungs, forwards: three branches, of which the
    first makes zeros and nothing else (no gather, no product, no scatter)
    and the two others run three grouped products each, on the token count's
    rows and on the rows that hold every assignment.  A rung with rows costs
    program bytes and, under a gradient, fills of what it saves on every
    learn step whichever rung runs (the module's docstring), so one more is a
    decision and not a tuning."""
    _, kc, moe, p, _, _ = _layer()
    traced = jax.make_jaxpr(lambda p, x: moe.apply({"params": p}, x))(
        p, _tokens(N))
    switch, = [eq for eq in equations(traced.jaxpr)
               if eq.primitive.name == "cond"]
    branches = switch.params["branches"]
    assert len(branches) == len(RUNGS)
    names = [[eq.primitive.name for eq in equations(b.jaxpr)]
             for b in branches]
    assert names[0] == ["broadcast_in_dim"]
    assert [n.count("ragged_dot_general") for n in names] == [0, 3, 3]
    assert [n.count("remat2") for n in names] == [0, 0, 1]  # the top rung
    rows = [sorted({eq.outvars[0].aval.shape[0] for eq in equations(b.jaxpr)
                    if eq.primitive.name == "ragged_dot_general"})
            for b in branches[1:]]
    assert rows == [[N], [K * N]]


def _forward_switch(moe, p, x):
    """The forward `switch` over the rungs in the layer's gradient: the first
    `cond` equation with a branch a rung."""
    traced = jax.make_jaxpr(jax.grad(
        lambda p, x: jnp.sum(moe.apply({"params": p}, x)),
        argnums=(0, 1)))(p, x)
    return next(eq for eq in equations(traced.jaxpr)
                if eq.primitive.name == "cond"
                and len(eq.params["branches"]) == len(RUNGS))


def _led_by(eq, rows):
    """The two-dimensional outputs of a `switch` led by `rows`: what a rung
    of so many rows saved (its gathered rows, its products, its mask)."""
    return [v.aval.shape for v in eq.outvars
            if len(v.aval.shape) == 2 and v.aval.shape[0] == rows]


def test_the_switch_hands_the_backward_pass_nothing_of_the_top_rung(
        monkeypatch):
    """Under `jax.grad` the forward `switch` hands on what the rung at the
    token count saved and NOTHING the top rung's rows shape: that rung makes
    its rows again on the way back, from the `switch`'s own operands.  A top
    rung that saved them would hand on its buffers, `top_k` times the other
    rung's, made and zero-filled on every learn step whichever rung ran; the
    plain form, traced here beside it, does."""
    _, kc, moe, p, _, _ = _layer()
    x = _tokens(N)
    eq = _forward_switch(moe, p, x)
    assert not _led_by(eq, K * N)
    assert (N, kc.expert_width) in _led_by(eq, N)  # the lower rung's products
    monkeypatch.setattr(mla_moe.jax, "checkpoint", lambda f, **kw: f)
    assert (K * N, kc.expert_width) in _led_by(_forward_switch(moe, p, x),
                                               K * N)


def test_a_stack_of_one_full_and_three_empty_expert_layers_trains():
    """Four expert layers of which one holds every assignment of every token
    and three hold none (a selection bias a layer): the gradient through the
    stack, each layer under its `nn.remat`, is the plain reference's, and
    the three empty layers' held kernels take none."""
    family = "kimi_linear"
    cc = cf.tiny_cc(family, window=96)
    core, stack, params, x, resets, _ = cf.make(
        family, cc, batch=3, steps=88, reset_at=((1, 40),))
    kc = core.kc
    n, held = 3 * 88, kc.experts_here
    assert n * min(kc.top_k, held) > mla_moe.FEW_ROWS
    assert (kc.first_dense, kc.layers, kc.top_k, held) == (1, 5, 4, 4)
    full = 3
    for i in range(2, 6):
        chosen = range(held) if i == full else range(held, held + kc.top_k)
        params[f"layer_{i}"]["moe"]["router"]["select_bias"] = jnp.zeros(
            (kc.experts,)).at[jnp.asarray(chosen)].set(10.0)
    start = cf.sequence_start(core, 3)
    wgt = jax.random.normal(jax.random.PRNGKey(9), (3, 88, kc.hidden))
    ref = cf.FAMILIES[family].ref

    def run(p):
        (y, _), sown = stack.apply({"params": p}, x, start, resets,
                                   mutable=[CORE_STATS])
        return jnp.sum(y * wgt), sown

    (a, sown), g = jax.jit(jax.value_and_grad(run, has_aux=True))(params)
    b, g_ref = jax.jit(jax.value_and_grad(lambda p: jnp.sum(
        ref.core_forward(p, cc, x, resets) * wgt)))(params)
    assert float(a) == pytest.approx(float(b), rel=1e-4)
    grads_close(g, g_ref)
    for i in range(2, 6):
        stats = sown[CORE_STATS][f"layer_{i}"]["moe"]
        share = float(stats["moe_held_assign_share"][0])
        assert share == (1.0 if i == full else 0.0)
        assert float(stats["moe_row_fill_share"][0]) == 1.0
        assert float(stats["moe_tokens_dropped"][0]) == 0.0
        leaves = jax.tree.leaves(g[f"layer_{i}"]["moe"]["experts"])
        assert all(bool(np.any(np.asarray(leaf))) == (i == full)
                   for leaf in leaves)
