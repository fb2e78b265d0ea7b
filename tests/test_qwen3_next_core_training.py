"""The R2D2 agent with the Qwen3-Next core (`Config.core_config`) through the
normal paths: the core picked by the file's `model_type`, the learn step
against the plain reference, the fused segment and the act step (the CLI
cases are tests/test_core_cli.py's, run with every core), at tiny widths (the
trunk's 2,304 features at 80x80 frames go through the input projection to
the core's hidden size, which the heads read)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.models.cores import make_core, state_bytes_per_lane
from rainbow_iqn_apex_tpu.models.qwen3_next import Qwen3NextCore
from rainbow_iqn_apex_tpu.ops.r2d2 import (
    SequenceBatch,
    build_r2d2_act_step,
    build_r2d2_learn_step,
    init_r2d2_state,
)

from ring_windows import aged

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "fixtures", "qwen3_next_core_tiny.json")
PUBLISHED = "configs/cores/qwen3_next_80b_a3b.json"


def _cfg(tmp_path, **kw):
    base = dict(
        env_id="jaxgame:freeway", architecture="r2d2", role="anakin",
        core_config=TINY, compute_dtype="float32", history_length=2,
        hidden_size=32, r2d2_burn_in=4, r2d2_seq_len=8, r2d2_overlap=4,
        batch_size=4, learning_rate=1e-3, multi_step=2, gamma=0.9,
        memory_capacity=12 * 40, learn_start=12 * 8, frames_per_learn=2,
        target_update_period=100, num_envs_per_actor=4,
        anakin_segment_ticks=8, learner_devices=1, metrics_interval=1,
        eval_interval=0, checkpoint_interval=0, eval_episodes=2,
        max_grad_norm=1e6,
        results_dir=str(tmp_path / "results"),
        checkpoint_dir=str(tmp_path / "ckpt"), seed=3,
    )
    base.update(kw)
    return Config(**base)


def _rows(cfg):
    path = os.path.join(cfg.results_dir, cfg.run_id, "metrics.jsonl")
    return [json.loads(line) for line in open(path)]


def test_the_core_comes_from_the_files_model_type(tmp_path):
    cfg = _cfg(tmp_path)
    core = make_core(cfg)
    assert isinstance(core, Qwen3NextCore)
    assert core.stored_width == 0 and core.kc.hidden == 32 and core.kc.in_proj
    assert [m.layer_name for m in core.kc.mixers] == ["gdn"] * 3 + ["gattn"]
    # three states S [4, 8, 8] with tails [3, 2x16 + 32], one window of 12
    # keys and values [2, 8], its validity and the ring's head, float32
    assert state_bytes_per_lane(core) == 4 * (
        3 * (4 * 8 * 8 + 3 * 64) + 12 * (2 * 2 * 8 + 1) + 1)
    published = make_core(cfg.replace(core_config=PUBLISHED))
    assert isinstance(published, Qwen3NextCore)
    assert state_bytes_per_lane(published) == 7_078_372  # 7.08 MB a lane
    bad = tmp_path / "other.json"
    bad.write_text(json.dumps({"model_type": "llama"}))
    with pytest.raises(ValueError, match="no core for model_type 'llama'"):
        make_core(cfg.replace(core_config=str(bad)))


def test_the_published_cut_is_557_million_parameters(tmp_path):
    """The byte count of benchmarks/configs/qwen3-next-r2d2-1chip.json, from
    `jax.eval_shape` of the program's own init: nothing is allocated."""
    cfg = _cfg(tmp_path, core_config=PUBLISHED, history_length=4,
               hidden_size=512, compute_dtype="bfloat16")
    params = jax.eval_shape(
        lambda k: init_r2d2_state(cfg, 3, k, (80, 80)).params,
        jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))  # noqa: E731
    core = params["core"]
    gdn = (2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * 2048)
    assert count(core["layer_1"]["gdn"]) == gdn == 33_718_464
    gattn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    assert count(core["layer_4"]["gattn"]) == gattn == 27_263_488
    for i in (1, 2, 3, 4):  # every feed-forward is an expert layer
        moe = core[f"layer_{i}"]["moe"]
        assert count(moe["experts"]) == 32 * 3 * 2048 * 512
        assert count(moe["shared"]) == 3 * 2048 * 512
        assert count(moe["shared_gate"]) == 2048
        assert count(moe["router"]) == 2048 * 512 + 512
    assert count(core["in_proj"]) == 2304 * 2048
    assert count(core) == 552_596_544
    assert count(params) == 556_874_984  # x 20 B = 11.14 GB
    # the heads read the core's hidden size
    assert params["value_hidden"]["w_mu"].shape == (2048, 512)
    # every leaf bears a name benchmarks/weights_core.py fills
    names = {jax.tree_util.keystr(p).rsplit("'", 2)[-2]
             for p, _ in jax.tree_util.tree_leaves_with_path(core)}
    assert names == {"kernel", "gate", "up", "down", "scale", "taps", "A_log",
                     "dt_bias", "select_bias"}


def test_learn_step_loss_and_gradient_match_the_reference(tmp_path):
    from benchmarks.references import r2d2_qwen3_next

    cfg = _cfg(tmp_path, history_length=4, batch_size=2)
    with open(TINY) as f:
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
        lambda p, t, b, k: r2d2_qwen3_next.loss_fn(p, t, b, k, hp, cc),
        has_aux=True))(ts.params, ts.target_params, batch, ks[5])
    assert float(info["loss"]) == pytest.approx(float(loss), rel=1e-4)
    assert float(info["moe_tokens_dropped"]) == 0.0
    assert 0.0 < float(info["gattn_live_key_share"]) < 1.0
    # a one-wide gate: the scalar form, on every platform, and no tile kernel
    assert float(info["kda_scalar_gate_share"]) == 1.0
    assert float(info["kda_fused_tile_share"]) == 0.0
    mu = [s for s in jax.tree.leaves(
        new.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(s, "mu")][0].mu
    for (path, m), g in zip(jax.tree_util.tree_leaves_with_path(mu),
                            jax.tree.leaves(grads)):
        got, want = np.asarray(m) / 0.1, np.asarray(g)
        assert np.abs(got - want).max() <= 2e-3 * max(
            np.abs(want).max(), 1e-6), jax.tree_util.keystr(path)


def test_fused_segment_trains_with_the_core(tmp_path):
    from rainbow_iqn_apex_tpu.train_anakin_r2d2 import train_anakin_r2d2

    cfg = _cfg(tmp_path)
    summary = train_anakin_r2d2(cfg, max_frames=4 * 8 * 12)
    assert summary["learn_steps"] > 4
    learn = [r for r in _rows(cfg) if r["kind"] == "learn"]
    assert all(np.isfinite(r["loss"]) for r in learn)
    assert all(r["moe_tokens_dropped"] == 0.0 for r in learn)
    assert all(0.0 <= r["moe_held_assign_share"] <= 1.0 for r in learn)
    assert all(r["kda_fused_tile_share"] == 0.0 for r in learn)
    assert all(r["kda_scalar_gate_share"] == 1.0 for r in learn)
    assert "mla_live_key_share" not in learn[0]
    # freeway has no terminals: the trained slice's 8 queries see the 4
    # burn-in keys and their own causal half, of 4 + 8 slots
    assert all(r["gattn_live_key_share"] == pytest.approx(
        (8 * 4 + 36) / (8 * 12)) for r in learn)
    assert learn[0]["core_state_bytes_per_lane"] == state_bytes_per_lane(
        make_core(cfg))


@pytest.mark.parametrize("how", ["zero_lanes", "reset_lanes"])
def test_act_step_carries_the_state_and_a_cut_empties_it(tmp_path, how):
    from rainbow_iqn_apex_tpu.models.cores import zero_lanes

    cfg = _cfg(tmp_path)
    core = make_core(cfg)
    # the multiply of every leaf, and the core's own reset (a window by its
    # validity, what it held left in its slots): the same lane afterwards
    cut = zero_lanes if how == "zero_lanes" else core.reset_lanes
    ts = init_r2d2_state(cfg, 3, jax.random.PRNGKey(1), (80, 80))
    act = jax.jit(build_r2d2_act_step(cfg, 3, use_noise=False))
    obs = jax.random.bits(jax.random.PRNGKey(2), (2, 80, 80, 2), jnp.uint8)
    state = core.initial_state(2)
    _, q0, state = act(ts.params, obs, state, jax.random.PRNGKey(3))
    _, q1, state = act(ts.params, obs, state, jax.random.PRNGKey(3))
    assert np.abs(np.asarray(q1 - q0)).max() > 0  # the window matters
    # the keys are kept un-rotated; a key is a function of the residual
    # stream, which three recurrent layers have moved between the two ticks
    keys = np.asarray(aged(state)["layer_4"]["k"])
    assert np.abs(keys[:, -1]).max() > 0 and np.abs(keys[:, -2]).max() > 0
    assert not np.any(keys[:, :-2])
    assert np.abs(np.asarray(state["layer_1"]["S"])).max() > 0
    state = cut(state, jnp.asarray([0, 1], jnp.uint8))
    _, q2, _ = act(ts.params, obs, state, jax.random.PRNGKey(3))
    np.testing.assert_allclose(np.asarray(q2[0]), np.asarray(q0[0]),
                               rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(q2[1] - q0[1])).max() > 0
