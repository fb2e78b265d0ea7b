"""The R2D2 agent's learn step (ops/r2d2.py) with every core family's tiny
core (`Config.core_config`, tests/core_families.py's table) against the plain
reference's loss (benchmarks/references/r2d2_<family>.py): the loss, the
counters the core lists, and the gradient, read from Adam's first moment.
A file of its own: these are the slowest of the training cases
(tests/test_core_training.py holds the others), and the suite runs a file a
worker."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.ops.r2d2 import (
    SequenceBatch,
    build_r2d2_learn_step,
    init_r2d2_state,
)

import core_families as cf


def kimi_linear(info):
    pass


def deepseek_v3(info):
    assert 0.0 < float(info["mla_live_key_share"]) < 1.0


def qwen3_next(info):
    assert 0.0 < float(info["gattn_live_key_share"]) < 1.0
    # a one-wide gate: the scalar form, on every platform, and no tile kernel
    assert float(info["kda_scalar_gate_share"]) == 1.0
    assert float(info["kda_fused_tile_share"]) == 0.0


def ouro(info):
    assert 0.0 < float(info["attn_live_key_share"]) < 1.0
    assert float(info["loop_passes"]) == 3.0
    assert not [n for n in info if n.startswith("moe_")]


def lfm2_moe(info):
    assert 0.0 < float(info["attn_live_key_share"]) < 1.0
    assert 0.0 <= float(info["moe_row_fill_share"]) <= 1.0
    assert "loop_passes" not in info


def laguna(info):
    assert 0.0 < float(info["attn_live_key_share_full"]) < 1.0
    assert 0.0 < float(info["attn_live_key_share_sliding"]) < float(
        info["attn_live_key_share_full"])
    assert float(info["attn_band_key_share"]) == 1.0  # 12 steps: one block
    assert 0.0 <= float(info["moe_row_fill_share"]) <= 1.0


# what a family's step reports beside the loss
COUNTERS = {f.__name__: f for f in (
    kimi_linear, deepseek_v3, qwen3_next, ouro, lfm2_moe, laguna)}


@pytest.mark.parametrize("family", sorted(cf.FAMILIES))
def test_learn_step_loss_and_gradient_match_the_reference(tmp_path, family):
    fam = cf.FAMILIES[family]
    cfg = cf.tiny_config(tmp_path, family, history_length=4, batch_size=2)
    with open(fam.tiny) as f:
        cc = json.load(f)
    hp = {k: getattr(cfg, k) for k in (
        "r2d2_burn_in", "multi_step", "gamma", "r2d2_eta",
        "value_rescale_eps", "history_length")}
    b, length, actions = 2, cfg.r2d2_burn_in + cfg.r2d2_seq_len, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    frames = jax.random.bits(ks[0], (b, length, 80, 80), jnp.uint8)
    done = np.zeros((b, length), bool)
    done[0, 2], done[1, 7] = True, True  # cuts in the burn-in and after it
    batch = {
        "frames": frames,
        "action": jax.random.randint(ks[1], (b, length), 0, actions),
        "reward": jax.random.normal(ks[2], (b, length)),
        "done": jnp.asarray(done),
        "valid": jnp.ones((b, length), bool),
        "weight": jnp.asarray([1.0, 0.5]),
    }
    ts = init_r2d2_state(cfg, actions, ks[3], (80, 80))
    ts = ts.replace(target_params=init_r2d2_state(
        cfg, actions, ks[4], (80, 80)).params)
    zero = jnp.zeros((b, 0), jnp.float32)
    seq = SequenceBatch(
        obs=frames[..., None], action=batch["action"],
        reward=batch["reward"], done=batch["done"], valid=batch["valid"],
        init_c=zero, init_h=zero, weight=batch["weight"])
    new, info = jax.jit(build_r2d2_learn_step(cfg, actions))(ts, seq, ks[5])
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p, t, b, k: fam.loss_fn(p, t, b, k, hp, cc),
        has_aux=True))(ts.params, ts.target_params, batch, ks[5])
    # float32 on both sides, sums in another order: 1e-4 of the loss
    assert float(info["loss"]) == pytest.approx(float(loss), rel=1e-4)
    if family != "ouro":  # a core with expert layers
        assert float(info["moe_tokens_dropped"]) == 0.0
    COUNTERS[family](info)
    mu = [s for s in jax.tree.leaves(
        new.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(s, "mu")][0].mu
    # Adam's first moment is 0.1 x the first gradient; a gradient sums over
    # every step, token and pass: 2e-3 of the leaf's largest
    for (path, m), g in zip(jax.tree_util.tree_leaves_with_path(mu),
                            jax.tree.leaves(grads)):
        got, want = np.asarray(m) / 0.1, np.asarray(g)
        assert np.abs(got - want).max() <= 2e-3 * max(
            np.abs(want).max(), 1e-6), jax.tree_util.keystr(path)
