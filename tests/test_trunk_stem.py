"""The trunk's first conv read from stored single frames (`layers.StemConv`)
against the definition: `stack_seq_frames` + the plain strided conv on the
same parameters.  One kernel, two readings: outputs, gradients, the learn
step's semantics at the sequence start and at the burn-in boundary, the act
path beside the sequence pass, and the parameter tree itself."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.models.layers import ConvTrunk, stack_history
from rainbow_iqn_apex_tpu.ops import r2d2 as ops
from rainbow_iqn_apex_tpu.parallel.multihost import shift_stack

A = 3


def _frames(key, *shape):
    return jax.random.randint(key, shape, 0, 256).astype(jnp.uint8)


class _PlainTrunk(nn.Module):
    """The trunk as it was before the stem: three `nn.Conv`."""

    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.compute_dtype)
        for features, kernel, stride in ((32, 8, 4), (64, 4, 2), (64, 3, 1)):
            x = nn.relu(nn.Conv(
                features, (kernel, kernel), strides=(stride, stride),
                padding="VALID", dtype=self.compute_dtype,
                param_dtype=jnp.float32)(x))
        return x.reshape(x.shape[0], -1)


# history, frame size, whether the stem reads the frames (82 is a size the
# stride does not divide: the frames are stacked and the plain conv runs)
CASES = [(4, 80, True), (4, 84, True), (4, 82, False), (3, 80, True),
         (1, 80, True)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("history,size,reads", CASES)
def test_trunk_from_frames_equals_stack_and_plain_conv(
        history, size, reads, dtype):
    """Features and the gradient of a scalar loss with respect to every
    leaf, `Conv_0/kernel` and `bias` among them: float32 to 1e-5; bfloat16
    within the two paths' rounding (the same bf16 products summed in float32
    in another order, rounded to bf16 once a layer).  A bias gradient is a
    sum of thousands of bf16 cotangents, which XLA:CPU accumulates in bf16:
    either path reads up to 0.5 of its scale off the float32 gradient there,
    by the order of the sum alone, so in bfloat16 each path's biases are
    held to the float32 gradient, at 0.7, and the kernels to each other."""
    dt = jnp.dtype(dtype)
    b, t = 2, 5
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(history * size), 4)
    frames = _frames(k1, b, t, size, size, 1)
    before = _frames(k2, b, history - 1, size, size, 1)
    assert ConvTrunk.stem_reads_frames(size, size) == reads
    trunk = ConvTrunk(compute_dtype=dt)
    stacked = (stack_history(frames, before).astype(dt) * (1.0 / 255.0))
    stacked = stacked.reshape(b * t, size, size, history)
    params = trunk.init(k3, stacked)
    assert params["params"]["Conv_0"]["kernel"].shape == (8, 8, history, 32)
    # the old trunk's parameters, value for value, whichever input made them
    old = _PlainTrunk(dt).init(k3, stacked)
    made_from_frames = trunk.init(k3, frames, before)
    for tree in (old, made_from_frames):
        assert jax.tree.structure(tree) == jax.tree.structure(params)
        for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(params)):
            np.testing.assert_array_equal(x, y)
    # a bias away from zero, so that its reading is tested too
    params = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(k4, x.shape), params)
    weight = jax.random.normal(k4, (b * t, 1))

    def loss(p, *inputs):
        phi = trunk.apply(p, *inputs).astype(jnp.float32)
        return (phi * weight).mean(), phi

    (_, phi_plain), g_plain = jax.value_and_grad(loss, has_aux=True)(
        params, stacked)
    (_, phi_frames), g_frames = jax.value_and_grad(loss, has_aux=True)(
        params, frames, before)
    np.testing.assert_array_equal(
        phi_plain, _PlainTrunk(dt).apply(params, stacked))
    tol = 1e-5 if dtype == "float32" else 0.05
    scale = float(jnp.abs(phi_plain).max())
    assert float(jnp.abs(phi_frames - phi_plain).max()) <= tol * scale
    exact = jax.grad(lambda p: ConvTrunk(jnp.float32).apply(
        p, frames, before).__mul__(weight).mean())(params)
    for (path, gp), gf, ge in zip(
            jax.tree_util.tree_leaves_with_path(g_plain),
            jax.tree.leaves(g_frames), jax.tree.leaves(exact)):
        if dtype == "bfloat16" and path[-1].key == "bias":
            for g in (gp, gf):
                assert float(jnp.abs(g - ge).max()) <= 0.7 * float(
                    jnp.abs(ge).max()), path
        else:
            assert float(jnp.abs(gf - gp).max()) <= tol * float(
                jnp.abs(gp).max()), path
    if not reads:  # the plain path is the same computation
        np.testing.assert_array_equal(phi_frames, phi_plain)


def _cfg(history, dtype="float32"):
    return Config(
        compute_dtype=dtype, history_length=history, hidden_size=16,
        lstm_size=16, r2d2_burn_in=4, r2d2_seq_len=6, r2d2_overlap=2,
        multi_step=2, gamma=0.9, batch_size=2, learning_rate=1e-3,
        target_update_period=10)


def _batch(key, cfg, size):
    b, length = cfg.batch_size, cfg.r2d2_burn_in + cfg.r2d2_seq_len
    ks = jax.random.split(key, 4)
    return ops.SequenceBatch(
        obs=_frames(ks[0], b, length, size, size, 1),
        action=jax.random.randint(ks[1], (b, length), 0, A),
        reward=jax.random.normal(ks[2], (b, length)),
        done=jnp.zeros((b, length), bool).at[0, 6].set(True),
        valid=jnp.ones((b, length), bool),
        init_c=0.1 * jax.random.normal(ks[3], (b, cfg.lstm_size)),
        init_h=jnp.zeros((b, cfg.lstm_size)),
        weight=jnp.ones((b,)))


@pytest.mark.parametrize("history,size,dtype", [
    (4, 44, "float32"), (3, 44, "float32"), (4, 46, "float32"),
    (4, 44, "bfloat16")])
def test_learn_step_on_single_frames_keeps_stack_seq_frames_semantics(
        history, size, dtype):
    """The learn step handed `[B, L, H, W, 1]` against the same step handed
    `stack_seq_frames` of it (channels == history: the plain path).  The
    whole-sequence stack is the definition: zeros before the sequence's
    first step, and the trained slice's first history-1 steps read the
    burn-in's last frames.  Loss, priorities and every gradient leaf."""
    cfg = _cfg(history, dtype)
    batch = _batch(jax.random.PRNGKey(history + size), cfg, size)
    stacked = batch.replace(obs=ops.stack_seq_frames(batch.obs, history))
    assert stacked.obs.shape[-1] == history
    # the definition's two edges, in the frames themselves
    np.testing.assert_array_equal(stacked.obs[:, 0, ..., :-1], 0)
    burn = cfg.r2d2_burn_in
    np.testing.assert_array_equal(
        stacked.obs[:, burn, ..., 0], batch.obs[:, burn - (history - 1), ..., 0])
    state = ops.init_r2d2_state(cfg, A, jax.random.PRNGKey(0), (size, size))
    net = ops.make_r2d2_network(cfg, A)
    key = jax.random.PRNGKey(9)

    def q_sum(params, b, hist):
        q, _ = ops._unroll(net, params, b, burn, key, hist)
        return (q * jnp.arange(1, 1 + q.shape[1])[None, :, None]).mean(), q

    (_, q_def), g_def = jax.value_and_grad(q_sum, has_aux=True)(
        state.params, stacked, history)
    (_, q_new), g_new = jax.value_and_grad(q_sum, has_aux=True)(
        state.params, batch, history)
    tol = 1e-5 if dtype == "float32" else 0.05
    assert float(jnp.abs(q_new - q_def).max()) <= tol * float(
        jnp.abs(q_def).max())
    for gd, gn in zip(jax.tree.leaves(g_def), jax.tree.leaves(g_new)):
        assert float(jnp.abs(gn - gd).max()) <= tol * max(
            float(jnp.abs(gd).max()), 1e-6)
    step = jax.jit(ops.build_r2d2_learn_step(cfg, A))
    _, info_def = step(state, stacked, key)
    _, info_new = step(state, batch, key)
    for name in ("loss", "priorities", "q_mean", "grad_norm"):
        np.testing.assert_allclose(
            info_new[name], info_def[name], rtol=200 * tol, atol=tol)
    # a frame before the burn-in boundary reaches the trained slice's first
    # steps through the history and through nothing else once the state is
    # cut: perturb it and the first trained step's q moves
    poked = batch.replace(obs=batch.obs.at[:, burn - 1].add(64))
    cut = lambda b: b.replace(  # noqa: E731
        done=b.done.at[:, burn - 1].set(True))
    _, q_poked = q_sum(state.params, cut(poked), history)
    _, q_cut = q_sum(state.params, cut(batch), history)
    assert float(jnp.abs(q_poked[:, 0] - q_cut[:, 0]).max()) > 0


def test_act_path_and_sequence_pass_read_one_kernel():
    """Eight ticks through `build_r2d2_act_step` on `shift_stack`'s stacks
    (the plain conv on `[lanes, 1, H, W, 4]`) give the Q-values of one
    sequence pass over the same eight single frames (the reading from
    frames), on the same parameters."""
    cfg, size, lanes, ticks = _cfg(4), 44, 3, 8
    state = ops.init_r2d2_state(cfg, A, jax.random.PRNGKey(1), (size, size))
    frames = _frames(jax.random.PRNGKey(2), lanes, ticks, size, size, 1)
    act = jax.jit(ops.build_r2d2_act_step(cfg, A, use_noise=False))
    net = ops.make_r2d2_network(cfg, A, use_noise=False)
    stack = jnp.zeros((lanes, size, size, 4), jnp.uint8)
    core_state, qs = net.initial_state(lanes), []
    for t in range(ticks):
        stack = shift_stack(stack, frames[:, t, ..., 0], jnp.ones((lanes,)))
        _, q, core_state = act(state.params, stack, core_state,
                               jax.random.PRNGKey(t))
        qs.append(q)
    q_seq, final = net.apply(
        {"params": state.params}, frames, net.initial_state(lanes),
        frames_before=jnp.zeros_like(frames[:, :3]))
    np.testing.assert_allclose(jnp.stack(qs, 1), q_seq, rtol=1e-4, atol=1e-5)
    for x, y in zip(jax.tree.leaves(core_state), jax.tree.leaves(final)):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5)


# `init_r2d2_state(Config(), 6, key, (84, 84))` at the parent of the PR that
# brought the stem: every leaf, by path and shape (tests/test_lstm_core.py
# holds `R2D2Net` to the same file)
with open(os.path.join(os.path.dirname(__file__), "fixtures",
                       "r2d2_parent_tree.json")) as _f:
    PARENT_TREE = [(path, tuple(shape))
                   for path, shape in json.load(_f)["leaves"]]


def test_parameter_tree_is_the_parents_leaf_for_leaf():
    shapes = jax.eval_shape(
        lambda k: ops.init_r2d2_state(Config(), 6, k, (84, 84)).params,
        jax.random.PRNGKey(0))
    leaves = sorted(
        ("/".join(k.key for k in path), leaf.shape, leaf.dtype)
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes))
    assert [(p, s) for p, s, _ in leaves] == PARENT_TREE
    assert {d for _, _, d in leaves} == {jnp.dtype("float32")}


def test_a_batch_split_over_a_mesh_takes_the_plain_conv():
    """The reading from frames folds the batch into the conv's innermost
    axis behind time; a batch split over chips cannot follow it there, so a
    step traced under a mesh (`traced_under`, as both mesh builders trace
    the learn step) stacks the frames and runs the plain conv."""
    from jax.sharding import Mesh

    from rainbow_iqn_apex_tpu.parallel.mesh import traced_under

    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    assert ConvTrunk.stem_reads_frames(80, 80)
    assert not traced_under(mesh, ConvTrunk.stem_reads_frames)(80, 80)
    frames = _frames(jax.random.PRNGKey(0), 2, 3, 80, 80, 1)
    before = _frames(jax.random.PRNGKey(1), 2, 3, 80, 80, 1)
    trunk = ConvTrunk(compute_dtype=jnp.float32)
    params = trunk.init(jax.random.PRNGKey(2), frames, before)
    stacked = stack_history(frames, before).astype(jnp.float32) * (1.0 / 255.0)
    under = traced_under(mesh, trunk.apply)(params, frames, before)
    np.testing.assert_array_equal(
        under, trunk.apply(params, stacked.reshape(6, 80, 80, 4)))
    lowered = jax.jit(traced_under(mesh, trunk.apply)).lower(
        params, frames, before).as_text()
    assert "<20x20x64x" not in lowered  # no space-to-depth history
    assert "<20x20x64x" in jax.jit(trunk.apply).lower(
        params, frames, before).as_text()


def test_learn_rows_counter_says_which_reading_was_compiled():
    assert ops.stem_from_frames_share(_cfg(4), (80, 80)) == 1.0
    assert ops.stem_from_frames_share(_cfg(4), (84, 84)) == 1.0
    assert ops.stem_from_frames_share(_cfg(4), (82, 80)) == 0.0
    assert ops.stem_from_frames_share(_cfg(1), (80, 80)) == 0.0
    assert ops.stem_from_frames_share(_cfg(4), (80, 80), learner_chips=4) == 0.0
