"""Pure-JAX games (envs/device_games.py): contract, dynamics, and jit/vmap
legality.  These games must satisfy the same observation/termination contract
as every other env (uint8 frames, two-channel terminal/truncation) AND be
fully traceable — vmap over lanes, scan over time — since the fused Anakin
trainer compiles them into the learn graph.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.envs import make_env
from rainbow_iqn_apex_tpu.envs.device_games import (
    GAMES,
    BreakoutGame,
    CatchGame,
    FreewayGame,
    JaxGameEnv,
    batched_init,
    batched_reset_step,
    make_device_game,
)

ALL = sorted(GAMES)


# ---------------------------------------------------------------- contract


@pytest.mark.parametrize("name", ALL)
def test_render_contract(name):
    game = make_device_game(name)
    s = game.init(jax.random.PRNGKey(0))
    frame = game.render(s)
    assert frame.shape == game.frame_shape
    assert frame.dtype == jnp.uint8
    assert frame.shape[0] >= 44  # conv-trunk minimum (three VALID convs)
    assert int(jnp.asarray(frame).max()) > 0  # something visible


@pytest.mark.parametrize("name", ALL)
def test_step_is_jittable_and_deterministic(name):
    game = make_device_game(name)
    step = jax.jit(game.step)
    s = game.init(jax.random.PRNGKey(1))
    k = jax.random.PRNGKey(2)
    s1, r1, t1, u1 = step(s, jnp.int32(0), k)
    s2, r2, t2, u2 = step(s, jnp.int32(0), k)
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(r1) == float(r2)
    assert r1.dtype == jnp.float32
    assert bool(t1) == bool(t2)


@pytest.mark.parametrize("name", ALL)
def test_random_rollout_stays_legal(name):
    """500 random steps: state indices stay on-grid, rewards bounded, and
    terminal lanes always produce a fresh episode (auto-reset wrapper)."""
    game = make_device_game(name)
    lanes = 4
    states = batched_init(game, jax.random.PRNGKey(3), lanes)
    ep = jnp.zeros(lanes)
    step = jax.jit(batched_reset_step(game))
    key = jax.random.PRNGKey(4)
    total_cuts = 0
    for i in range(500):
        key, ka, ks = jax.random.split(key, 3)
        actions = jax.random.randint(ka, (lanes,), 0, game.num_actions)
        states, ep, frames, reward, term, trunc, out_ret = step(
            states, ep, actions, ks
        )
        assert frames.shape == (lanes, *game.frame_shape)
        assert frames.dtype == jnp.uint8
        r = np.asarray(reward)
        assert np.all(np.abs(r) <= 1.0)
        cuts = np.asarray(term) | np.asarray(trunc)
        total_cuts += int(cuts.sum())
        # ep_return reported exactly on cut lanes
        assert np.array_equal(~np.isnan(np.asarray(out_ret)), cuts)
        # terminal and truncated never both set
        assert not np.any(np.asarray(term) & np.asarray(trunc))
    if name in ("catch", "breakout", "asterix", "invaders", "freeway"):
        assert total_cuts > 0, "random play should end episodes within 500 ticks"


def test_scan_over_time_compiles():
    """The Anakin shape: lax.scan of vmapped steps in one jit — must trace."""
    game = make_device_game("breakout")
    lanes = 8
    step = batched_reset_step(game)

    @jax.jit
    def rollout(states, ep, key):
        def tick(carry, k):
            states, ep = carry
            ka, ks = jax.random.split(k)
            actions = jax.random.randint(ka, (lanes,), 0, game.num_actions)
            states, ep, frames, reward, term, trunc, _ = step(states, ep, actions, ks)
            return (states, ep), (frames.sum(), reward.sum())

        return jax.lax.scan(tick, (states, ep), jax.random.split(key, 32))

    states = batched_init(game, jax.random.PRNGKey(5), lanes)
    (_, out) = rollout(states, jnp.zeros(lanes), jax.random.PRNGKey(6))
    assert np.isfinite(np.asarray(out[1])).all()


# ---------------------------------------------------------------- dynamics


def test_catch_scripted_policy_wins():
    """Tracking the ball column must catch it: +1 at the bottom row."""
    game = CatchGame()
    s = game.init(jax.random.PRNGKey(7))
    step = jax.jit(game.step)
    done, total = False, 0.0
    for _ in range(game.frame_shape[0]):
        diff = int(s.ball_c) - int(s.paddle)
        a = 0 if diff == 0 else (2 if diff > 0 else 1)
        s, r, term, _ = step(s, jnp.int32(a), jax.random.PRNGKey(0))
        total += float(r)
        if bool(term):
            done = True
            break
    assert done and total == 1.0


def test_catch_miss_loses():
    game = CatchGame()
    s = game.init(jax.random.PRNGKey(8))
    step = jax.jit(game.step)
    total = 0.0
    for _ in range(20):
        # run away from the ball
        a = 1 if int(s.ball_c) >= int(s.paddle) else 2
        s, r, term, _ = step(s, jnp.int32(a), jax.random.PRNGKey(0))
        total += float(r)
        if bool(term):
            break
    assert total == -1.0


def test_breakout_brick_hit_scores_and_clears():
    game = BreakoutGame()
    s = game.init(jax.random.PRNGKey(9))
    # place the ball just under the wall, flying up into a brick
    s = s._replace(ball_r=jnp.int32(4), ball_c=jnp.int32(5), dr=jnp.int32(-1),
                   dc=jnp.int32(1))
    assert bool(s.bricks[3, 6])
    ns, r, term, _ = jax.jit(game.step)(s, jnp.int32(0), jax.random.PRNGKey(0))
    assert float(r) == 1.0 and not bool(term)
    assert not bool(ns.bricks[3, 6])  # the brick it flew into is gone
    assert int(ns.dr) == 1  # bounced back down


def test_breakout_miss_terminates():
    game = BreakoutGame()
    s = game.init(jax.random.PRNGKey(10))
    s = s._replace(ball_r=jnp.int32(8), ball_c=jnp.int32(2), dr=jnp.int32(1),
                   dc=jnp.int32(1), paddle=jnp.int32(7))
    _, r, term, _ = jax.jit(game.step)(s, jnp.int32(0), jax.random.PRNGKey(0))
    assert bool(term) and float(r) == 0.0


def test_breakout_paddle_bounce():
    game = BreakoutGame()
    s = game.init(jax.random.PRNGKey(11))
    s = s._replace(ball_r=jnp.int32(8), ball_c=jnp.int32(4), dr=jnp.int32(1),
                   dc=jnp.int32(1), paddle=jnp.int32(5))
    ns, _, term, _ = jax.jit(game.step)(s, jnp.int32(0), jax.random.PRNGKey(0))
    assert not bool(term)
    assert int(ns.dr) == -1 and int(ns.ball_r) == 8


def test_freeway_truncates_not_terminates():
    game = FreewayGame(cap=50)
    s = game.init(jax.random.PRNGKey(12))
    step = jax.jit(game.step)
    for i in range(50):
        s, r, term, trunc = step(s, jnp.int32(0), jax.random.PRNGKey(i))
        assert not bool(term)
    assert bool(trunc)


@pytest.mark.parametrize("cap,ticks", [(0, 500), (7, 7), (3414, 3414)])
def test_the_tick_cap_sets_the_time_limit_of_a_game_that_has_one(cap, ticks):
    """`Config.device_game_tick_cap` through `make_device_game`: 0 leaves the
    game's own limit, and a game that ends no episode by time refuses one."""
    game = make_device_game("freeway", cap)
    assert game.cap == ticks
    s = game.init(jax.random.PRNGKey(0))._replace(t=jnp.int32(ticks - 2))
    s, _, _, trunc = game.step(s, jnp.int32(0), jax.random.PRNGKey(1))
    assert not bool(trunc)
    assert bool(game.step(s, jnp.int32(0), jax.random.PRNGKey(2))[3])
    with pytest.raises(ValueError, match="no time limit of its own"):
        make_device_game("breakout", cap or 9)


def test_the_fused_trainer_hands_the_games_tick_cap_on(tmp_path):
    """A fused R2D2 run of 96 ticks on freeway: under the game's own limit of
    500 no episode ends; with `device_game_tick_cap` 11 every lane's do, so
    the run has returns to report."""
    import core_families as cf
    from rainbow_iqn_apex_tpu.train_anakin_r2d2 import train_anakin_r2d2

    cfg = cf.tiny_config(tmp_path, "ouro", core_config="", lstm_size=16)
    assert cfg.device_game_tick_cap == 0
    own = train_anakin_r2d2(cfg, max_frames=4 * 8 * 12)
    assert np.isnan(own["train_return_mean"])
    capped = train_anakin_r2d2(
        cfg.replace(device_game_tick_cap=11, run_id="capped"),
        max_frames=4 * 8 * 12)
    assert np.isfinite(capped["train_return_mean"])


def test_freeway_scripted_crossing_scores():
    """Going up forever must eventually score (+1) despite collisions."""
    game = FreewayGame(cap=10_000)
    s = game.init(jax.random.PRNGKey(13))
    step = jax.jit(game.step)
    total = 0.0
    for i in range(400):
        s, r, _, _ = step(s, jnp.int32(1), jax.random.PRNGKey(i))
        total += float(r)
        if total > 0:
            break
    assert total >= 1.0


# ---------------------------------------------------------------- adapter


def test_host_adapter_runs_in_vector_env():
    env = make_env("jaxgame:breakout", seed=0)
    assert isinstance(env, JaxGameEnv)
    obs = env.reset()
    assert obs.shape == env.frame_shape and obs.dtype == np.uint8
    rng = np.random.default_rng(0)
    done = False
    for _ in range(300):
        ts = env.step(int(rng.integers(0, env.num_actions)))
        assert ts.obs.dtype == np.uint8
        if ts.terminal or ts.truncated:
            assert ts.info and "episode_return" in ts.info
            done = True
            break
    assert done, "random breakout should terminate within 300 steps"
