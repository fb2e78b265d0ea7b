"""R2D2 learn step under dp mesh sharding: the recurrent path is mesh-ready
(compiles + matches single-device numerics) even before the apex role wires
it — the same GSPMD recipe as the IQN learner."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.ops.r2d2 import (
    SequenceBatch,
    build_r2d2_learn_step,
    init_r2d2_state,
)
from rainbow_iqn_apex_tpu.parallel.mesh import learner_mesh

CFG = Config(
    compute_dtype="float32",
    history_length=1,
    hidden_size=32,
    lstm_size=32,
    r2d2_burn_in=2,
    r2d2_seq_len=6,
    multi_step=2,
    gamma=0.9,
    target_update_period=10,
)
A, FRAME, L = 3, (44, 44), 8


def _batch(b=8):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    return SequenceBatch(
        obs=jax.random.randint(ks[0], (b, L, *FRAME, 1), 0, 255).astype(jnp.uint8),
        action=jax.random.randint(ks[1], (b, L), 0, A).astype(jnp.int32),
        reward=jax.random.normal(ks[2], (b, L)),
        done=jnp.zeros((b, L), bool),
        valid=jnp.ones((b, L), bool),
        init_c=jnp.zeros((b, 32)),
        init_h=jnp.zeros((b, 32)),
        weight=jnp.ones((b,)),
    )


def test_r2d2_learn_dp_sharded_matches_single_device():
    mesh = learner_mesh(jax.devices()[:4])
    rep = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("dp"))

    state0 = init_r2d2_state(CFG, A, jax.random.PRNGKey(0), FRAME)
    batch = _batch(8)
    key = jax.random.PRNGKey(2)

    ref_step = jax.jit(build_r2d2_learn_step(CFG, A))
    ref_state, ref_info = ref_step(state0, batch, key)

    sh_step = jax.jit(
        build_r2d2_learn_step(CFG, A), in_shardings=(rep, shard, rep)
    )
    sh_state0 = jax.device_put(init_r2d2_state(CFG, A, jax.random.PRNGKey(0), FRAME), rep)
    sh_state, sh_info = sh_step(sh_state0, batch, key)

    np.testing.assert_allclose(float(ref_info["loss"]), float(sh_info["loss"]), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ref_info["priorities"]), np.asarray(sh_info["priorities"]), rtol=1e-4
    )
    for a, b in zip(jax.tree.leaves(ref_state.params), jax.tree.leaves(sh_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    # params replicated over the 4 learner devices
    assert len(jax.tree.leaves(sh_state.params)[0].sharding.device_set) == 4


def test_lstm_input_product_keeps_a_dp_split_batch_split():
    """The LSTM's input product contracts the feature axis with the features
    kept `[T, B, F]` (PR 39), so a batch split over a 2-device `dp` mesh
    passes through it: every `lstm_input` product of the partitioned program
    has half the batch's rows, `x` is gathered nowhere (the only collectives
    are the gradients' all-reduces), and the unroll's gradients are the single
    device's leaf for leaf (the learn step's: the test above)."""
    from rainbow_iqn_apex_tpu.obs import device_scopes as ds
    from rainbow_iqn_apex_tpu.ops.r2d2 import _unroll, make_r2d2_network

    mesh = learner_mesh(jax.devices()[:2])
    rep, shard = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    state0 = init_r2d2_state(CFG, A, jax.random.PRNGKey(0), FRAME)
    batch, key = _batch(8), jax.random.PRNGKey(2)
    batch = batch.replace(
        done=batch.done.at[1, 3].set(True).at[6, 0].set(True),
        init_c=jax.random.normal(jax.random.PRNGKey(3), (8, 32)),
        init_h=jax.random.normal(jax.random.PRNGKey(4), (8, 32)))
    net = make_r2d2_network(CFG, A)

    def grads(params, batch):
        return jax.grad(lambda p: (_unroll(
            net, p, batch, CFG.r2d2_burn_in, key)[0] ** 2).mean())(params)

    want = jax.jit(grads)(state0.params, batch)
    sharded = jax.jit(grads, in_shardings=(rep, shard)).lower(
        state0.params, batch).compile()
    got = sharded(jax.device_put(state0.params, rep),
                  jax.device_put(batch, shard))
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got)):
        scale = float(np.abs(np.asarray(w)).max())
        assert scale > 0, path
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=1e-5 * scale, rtol=0,
            err_msg=jax.tree_util.keystr(path))

    text = sharded.as_text()
    assert "all-reduce" in text
    for gathers in ("all-gather", "all-to-all", "collective-permute"):
        assert gathers not in text, gathers
    # burn-in 2 and slice 6 steps of 8 / 2 lanes a device, 256 features,
    # 4m = 128: z_x twice, and from the slice dx and the kernels' gradient
    rows, _caller = ds._parse(text)
    products = sorted(
        shape.split("{")[0].replace("[256,128]", "[128,256]")
        for _i, _c, shape, opcode, op_name, _a in rows
        if opcode == "dot" and op_name
        and ds.LSTM_INPUT in ds.scope_path(op_name))
    assert products == [
        "f32[128,256]", "f32[24,128]", "f32[24,256]", "f32[8,128]"], products

