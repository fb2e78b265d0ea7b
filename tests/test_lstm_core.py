"""models/cores.LSTMCore: the gates' input side is one product over all T
steps before the loop (PERF.md, PR 39).

Two references.  `benchmarks/references/r2d2.py::lstm_unroll` is the in-loop
definition in plain float32 JAX (no flax): outputs, final state and every
gradient are held to it.  `InLoopLSTMCore` is the form the program had, an
`nn.scan` over `nn.OptimizedLSTMCell` with the pre-step reset: the parameter
tree and, for a seed, its values are held to that.  The structure is pinned
too, so the product cannot slip back into the loop unnoticed.
"""

import dataclasses
import json
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.extend import core as jex_core

from benchmarks.references.r2d2 import lstm_unroll
from rainbow_iqn_apex_tpu.models.cores import LSTMCore
from rainbow_iqn_apex_tpu.models.r2d2 import R2D2Net
from rainbow_iqn_apex_tpu.obs import device_scopes as ds


# ------------------------------------------------ the form the program had
class _InLoopStep(nn.Module):
    features: int

    @nn.compact
    def __call__(self, carry, xs):
        x_t, reset_t = xs  # [B, F], [B] bool
        c, h = carry
        keep = (1.0 - reset_t.astype(jnp.float32))[:, None]
        return nn.OptimizedLSTMCell(features=self.features, name="cell")(
            (c * keep, h * keep), x_t)


@dataclasses.dataclass(frozen=True)
class InLoopLSTMCore(LSTMCore):
    """`LSTMCore.__call__` until PR 39: `x_t @ W_x` inside the loop."""

    def __call__(self, x, state, resets):
        xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(resets, 1, 0))
        scan = nn.scan(_InLoopStep, variable_broadcast="params",
                       split_rngs={"params": False}, in_axes=0, out_axes=0)
        state, outs = scan(features=self.features, name="lstm")(state, xs)
        return jnp.moveaxis(outs, 0, 1), state


class _Host(nn.Module):
    """A parent scope for a core, as `R2D2Net` is."""

    core: Any

    @nn.compact
    def __call__(self, x, state, resets):
        return self.core(x, state, resets)


F, M = 24, 16  # feature width, LSTM size
CELL_LEAVES = sorted(
    [f"lstm/cell/i{g}/kernel" for g in "ifgo"]
    + [f"lstm/cell/h{g}/{leaf}" for g in "ifgo" for leaf in ("kernel", "bias")])


def _leaves(tree):
    return {"/".join(k.key for k in path): v
            for path, v in jax.tree_util.tree_leaves_with_path(tree)}


def _close(got, want, what, rel=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype, what
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) <= rel * scale, (
        what, float(np.abs(got - want).max()), scale)


def _case(T, B, seed=0):
    """Inputs with resets in the middle of a sequence, a non-zero start
    state, and parameters whose biases are off their zero start."""
    ks = jax.random.split(jax.random.PRNGKey(seed + T * 10 + B), 6)
    x = jax.random.normal(ks[0], (B, T, F))
    state = (jax.random.normal(ks[1], (B, M)), jax.random.normal(ks[2], (B, M)))
    resets = jax.random.bernoulli(ks[3], 0.1, (B, T))
    if T > 1:
        resets = resets.at[0, T // 2].set(True).at[B - 1, T - 1].set(True)
    params = _Host(LSTMCore(M)).init(ks[4], x, state, resets)["params"]
    leaves, tree = jax.tree.flatten(params)
    params = jax.tree.unflatten(tree, [
        p + 0.1 * jax.random.normal(k, p.shape)
        for p, k in zip(leaves, jax.random.split(ks[5], len(leaves)))])
    return x, state, resets, params


# ------------------------------------------- against the plain definition
@pytest.mark.parametrize("T", [1, 7, 40])
@pytest.mark.parametrize("B", [2, 5])
def test_core_matches_the_in_loop_definition(T, B):
    """Outputs, final (c, h) and the gradients with respect to `x`, every
    kernel and bias and the start state against `lstm_unroll`, float32."""
    x, state, resets, params = _case(T, B)
    ks = jax.random.split(jax.random.PRNGKey(99), 3)
    w_out, w_c, w_h = (jax.random.normal(k, s) for k, s in
                       zip(ks, [(B, T, M), (B, M), (B, M)]))

    def run(unroll):
        def loss(params, x, state):
            outs, (c, h) = unroll(params, x, state)
            return ((outs * w_out).sum() + (c * w_c).sum()
                    + (h * w_h).sum()), (outs, c, h)
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(params, x, state)

    core = _Host(LSTMCore(M))
    (_, got), g_got = run(
        lambda p, x, s: core.apply({"params": p}, x, s, resets))
    (_, want), g_want = run(
        lambda p, x, s: lstm_unroll(p["lstm"]["cell"], x, resets, s, None))
    for name, a, b in zip(("outs", "c", "h"), got, want):
        _close(a, b, name)
    got_p, want_p = _leaves(g_got[0]), _leaves(g_want[0])
    assert sorted(got_p) == sorted(want_p) == CELL_LEAVES
    for name in CELL_LEAVES:
        assert np.abs(np.asarray(want_p[name])).max() > 0, name
        _close(got_p[name], want_p[name], "d " + name)
    _close(g_got[1], g_want[1], "dx")
    for name, a, b in zip(("dc0", "dh0"), g_got[2], g_want[2]):
        _close(a, b, name)


def test_one_unroll_equals_single_steps_threading_the_state():
    """T = 1 is the same formula: N calls of one step give the unroll's
    outputs and final state (the actor's tick against the learn step)."""
    x, state, resets, params = _case(9, 3)
    core = jax.jit(lambda *a: _Host(LSTMCore(M)).apply({"params": params}, *a))
    outs, final = core(x, state, resets)
    steps = []
    for t in range(x.shape[1]):
        out, state = core(x[:, t:t + 1], state, resets[:, t:t + 1])
        steps.append(out[:, 0])
    _close(jnp.stack(steps, 1), outs, "outs", rel=1e-6)
    for a, b in zip(state, final):
        _close(a, b, "final state", rel=1e-6)


# ------------------------------------------- the tree and the seeded values
def _net(core=None):
    return R2D2Net(num_actions=3, lstm_size=32, hidden_size=32,
                   compute_dtype=jnp.float32, core=core)


def _init(net, key=0, frame=(44, 44), B=2, T=3):
    obs = jnp.zeros((B, T, *frame, 1), jnp.uint8)
    return net.init(
        {"params": jax.random.PRNGKey(key), "noise": jax.random.PRNGKey(1)},
        obs, net.initial_state(B))["params"]


def test_r2d2net_keeps_the_parameter_tree_leaf_for_leaf():
    """Path, shape and float32 of every leaf at the published widths and
    84x84 frames: the list `tests/test_trunk_stem.py` pins too, which the
    reference, `benchmarks/weights.py` and the checkpoints read by name."""
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "r2d2_parent_tree.json")) as f:
        parent_tree = [(path, tuple(shape))
                       for path, shape in json.load(f)["leaves"]]

    net = R2D2Net(num_actions=6)
    obs = jax.ShapeDtypeStruct((1, 2, 84, 84, 4), jnp.uint8)
    shapes = jax.eval_shape(
        lambda o: net.init({"params": jax.random.PRNGKey(0),
                            "noise": jax.random.PRNGKey(1)},
                           o, net.initial_state(1)), obs)["params"]
    got = _leaves(shapes)
    assert sorted((n, v.shape) for n, v in got.items()) == parent_tree
    assert all(v.dtype == jnp.float32 for v in got.values())
    assert sorted(n for n in got if n.startswith("lstm/")) == CELL_LEAVES


@pytest.mark.parametrize("seed", [0, 2_147_483_659])
def test_a_seed_gives_the_weights_the_flax_cell_under_nn_scan_gave(seed):
    """Same initialisers on the same paths: every leaf of the net, bit for
    bit, and the old module's forward pass on them."""
    old, new = _init(_net(InLoopLSTMCore(32)), seed), _init(_net(), seed)
    old_l, new_l = _leaves(old), _leaves(new)
    assert sorted(old_l) == sorted(new_l)
    for name, want in old_l.items():
        assert new_l[name].dtype == want.dtype, name
        np.testing.assert_array_equal(new_l[name], want, err_msg=name)
    cell = new["lstm"]["cell"]
    for g in "ifgo":  # lecun-normal, orthogonal, zero: eight draws, not one
        r = np.asarray(cell["h" + g]["kernel"])
        np.testing.assert_allclose(r.T @ r, np.eye(32), atol=1e-5)
        assert not np.asarray(cell["h" + g]["bias"]).any()
    kernels = np.stack([np.asarray(cell["i" + g]["kernel"]) for g in "ifgo"])
    assert abs(kernels.var() * kernels.shape[1] - 1.0) < 0.05
    assert len({k.tobytes() for k in kernels}) == 4

    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    obs = jax.random.randint(ks[0], (2, 6, 44, 44, 1), 0, 255).astype(jnp.uint8)
    state = (jax.random.normal(ks[1], (2, 32)), jax.random.normal(ks[2], (2, 32)))
    resets = jnp.zeros((2, 6), bool).at[1, 3].set(True)
    (q_old, s_old), (q_new, s_new) = (
        net.apply({"params": old}, obs, state, resets, rngs={"noise": ks[3]})
        for net in (_net(InLoopLSTMCore(32)), _net()))
    _close(q_new, q_old, "q")
    for a, b in zip(s_new, s_old):
        _close(a, b, "final state")


# ------------------------------------------------------------ the structure
FEATURES = 256  # the trunk's features at 44x44 frames; no other width is


def _dots_touching(jaxpr, width, in_loop=False, out=None):
    """[(inside a scan/while body, the scopes of its name stack, shapes)] of
    every `dot_general` with an axis `width` long among its operands or its
    result, through every sub-jaxpr."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
            if any(width in s for s in shapes):
                out.append((in_loop,
                            ds.scope_path(str(eqn.source_info.name_stack)),
                            shapes))
        loop = in_loop or eqn.primitive.name in ("scan", "while")
        for sub in jax.tree.leaves(
                eqn.params, is_leaf=lambda p: isinstance(
                    p, (jex_core.Jaxpr, jex_core.ClosedJaxpr))):
            if isinstance(sub, jex_core.ClosedJaxpr):
                sub = sub.jaxpr
            if isinstance(sub, jex_core.Jaxpr):
                _dots_touching(sub, width, loop, out)
    return out


@pytest.fixture(scope="module")
def net_at_t8():
    net = _net()
    params = _init(net)
    assert params["lstm"]["cell"]["ii"]["kernel"].shape == (FEATURES, 32)
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    obs = jax.random.randint(ks[0], (2, 8, 44, 44, 1), 0, 255).astype(jnp.uint8)
    resets = jnp.zeros((2, 8), bool).at[0, 4].set(True)

    def q_of(params, state):
        return net.apply({"params": params}, obs, state, resets,
                         rngs={"noise": ks[1]})[0]
    return q_of, params, net.initial_state(2)


def test_forward_has_one_input_product_and_none_in_the_loop(net_at_t8):
    q_of, params, state = net_at_t8
    dots = _dots_touching(jax.make_jaxpr(q_of)(params, state).jaxpr, FEATURES)
    # x stays [T, B, F]: the batch axis is not folded into the product's rows
    assert dots == [(False, (ds.LSTM_SCAN, ds.LSTM_INPUT),
                     [(8, 2, FEATURES), (FEATURES, 128), (8, 2, 128)])], dots


def test_gradient_has_one_product_each_for_the_kernels_and_dx(net_at_t8):
    q_of, params, state = net_at_t8
    grad = jax.grad(lambda p, s: (q_of(p, s) ** 2).sum())
    dots = _dots_touching(jax.make_jaxpr(grad)(params, state).jaxpr, FEATURES)
    assert not any(in_loop for in_loop, _, _ in dots), dots
    assert all(path == (ds.LSTM_SCAN, ds.LSTM_INPUT) for _, path, _ in dots)
    outs = sorted(shapes[2] for _, _, shapes in dots)
    assert outs in (
        # z_x = x W_x; dx = dz W_x^T; the input kernels' gradient x^T dz
        sorted([(8, 2, 128), (8, 2, FEATURES), (FEATURES, 128)]),
        sorted([(8, 2, 128), (8, 2, FEATURES), (128, FEATURES)])), dots


def test_the_in_loop_form_fails_the_structure_test():
    """The detector sees the form the program had: its input product is in
    the loop."""
    net = _net(InLoopLSTMCore(32))
    params = _init(net)
    obs = jnp.zeros((2, 8, 44, 44, 1), jnp.uint8)
    dots = _dots_touching(jax.make_jaxpr(
        lambda p: net.apply({"params": p}, obs, net.initial_state(2),
                            rngs={"noise": jax.random.PRNGKey(0)})[0])(
                                params).jaxpr, FEATURES)
    assert dots and all(in_loop for in_loop, _, _ in dots)
