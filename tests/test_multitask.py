"""Multi-game Ape-X tests (multitask/; docs/MULTITASK.md).

Covers the ISSUE-10 contract: per-game shard isolation (one game's
drop_shard never starves another's sampling — chaos-marked, with live
append/sample/write-back traffic around the drop/readmit), interleave-
schedule determinism under a fixed seed, task-conditioned forward parity
vs the single-game network at N=1, multi-game eval aggregation against
hand-computed human-normalized medians, the games/eval_mt obs surface,
and a seeded 2-game end-to-end apex run.
"""

import json
import os

import jax
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.multitask.eval import aggregate_human_normalized
from rainbow_iqn_apex_tpu.multitask.lanes import (
    GameLaneEnv,
    build_game_lanes,
    lane_games,
)
from rainbow_iqn_apex_tpu.multitask.replay import (
    InterleaveSchedule,
    MultiGameReplay,
    apportion,
)
from rainbow_iqn_apex_tpu.multitask.spec import MultiGameSpec, parse_games

TOY2 = MultiGameSpec(
    games=("toy:catch", "toy:chain"),
    num_actions=(3, 2),
    frame_shape=(80, 80),
)

CFG = Config(
    compute_dtype="float32",
    history_length=2,
    hidden_size=64,
    num_cosines=16,
    num_tau_samples=8,
    num_tau_prime_samples=8,
    num_quantile_samples=4,
    batch_size=16,
    multi_step=3,
    gamma=0.9,
)


def _fill(mem: MultiGameReplay, ticks: int = 48, lanes: int = 8,
          seed: int = 0) -> None:
    rng = np.random.default_rng(seed)
    h, w = mem.spec.frame_shape
    for _ in range(ticks):
        mem.append_batch(
            rng.integers(0, 255, (lanes, h, w), np.uint8),
            rng.integers(0, 2, lanes).astype(np.int32),
            rng.normal(size=lanes).astype(np.float32),
            rng.random(lanes) < 0.05,
            np.abs(rng.normal(size=lanes)) + 0.1,
        )


def _build(schedule="uniform", shards_per_game=1, seed=11) -> MultiGameReplay:
    return MultiGameReplay.build_games(
        TOY2, shards_per_game, 2048, 8, schedule=schedule,
        history=2, n_step=3, gamma=0.9, seed=seed,
    )


# ------------------------------------------------------------------ spec/lanes
def test_parse_games_rejects_duplicates():
    assert parse_games("a, b ,c") == ("a", "b", "c")
    assert parse_games("") == ()
    with pytest.raises(ValueError):
        parse_games("a,b,a")


def test_spec_probe_and_lane_blocks():
    spec = MultiGameSpec.probe(("toy:catch", "toy:chain"))
    assert spec.num_actions == (3, 2)
    assert spec.max_actions == 3
    assert spec.frame_shape == (80, 80)  # catch 80x80, chain padded from 40
    env = build_game_lanes(spec, 3, seed=0)
    assert len(env) == 6 and env.num_actions == 3
    assert env.frame_shape == (80, 80)
    np.testing.assert_array_equal(
        lane_games(spec, 3), [0, 0, 0, 1, 1, 1])
    # padded chain frames keep their pixels top-left, pad black
    obs = env.reset()
    assert obs.shape == (6, 80, 80)
    assert obs[3:, 40:, :].max() == 0 and obs[3:, :40, :40].max() > 0


def test_game_lane_env_maps_out_of_range_actions():
    from rainbow_iqn_apex_tpu.envs import make_env

    env = GameLaneEnv(make_env("toy:chain", seed=0), TOY2, 1)
    env.reset()
    ts = env.step(2)  # chain has 2 actions; 2 % 2 == 0 must not crash
    assert ts.obs.shape == (80, 80)


# ----------------------------------------------------------------- scheduling
def test_apportion_deterministic_and_exact():
    counts = apportion(16, np.asarray([0.5, 0.5]))
    np.testing.assert_array_equal(counts, [8, 8])
    counts = apportion(10, np.asarray([0.34, 0.33, 0.33]))
    assert counts.sum() == 10 and counts[0] == 4
    # ties break toward the lower index, reproducibly
    np.testing.assert_array_equal(
        apportion(5, np.asarray([1.0, 1.0])), [3, 2])


def test_interleave_schedule_modes():
    sched = InterleaveSchedule("uniform", 2)
    np.testing.assert_allclose(
        sched.shares(np.asarray([10.0, 1000.0])), [0.5, 0.5])
    # a mass-less game drops out; survivors renormalise
    np.testing.assert_allclose(
        sched.shares(np.asarray([0.0, 7.0])), [0.0, 1.0])
    mass = InterleaveSchedule("mass", 2)
    np.testing.assert_allclose(
        mass.shares(np.asarray([1.0, 3.0])), [0.25, 0.75])
    loss = InterleaveSchedule("loss", 2)
    for _ in range(60):
        loss.note_td(np.asarray([0, 0, 1, 1]),
                     np.asarray([4.0, 4.0, 1.0, 1.0]))
    shares = loss.shares(np.asarray([1.0, 1.0]))
    assert shares[0] > 0.7  # the struggling game earns more replay
    with pytest.raises(ValueError):
        InterleaveSchedule("nope", 2)


@pytest.mark.multitask
def test_interleave_determinism_under_fixed_seed():
    """Same seed + same appends -> identical sample streams, per schedule."""
    for schedule in ("uniform", "loss", "mass"):
        a, b = _build(schedule), _build(schedule)
        _fill(a, seed=5), _fill(b, seed=5)
        for draw in range(6):
            sa, sb = a.sample(16, 0.6), b.sample(16, 0.6)
            np.testing.assert_array_equal(sa.idx, sb.idx)
            np.testing.assert_array_equal(sa.game, sb.game)
            np.testing.assert_allclose(sa.weight, sb.weight)
            td = np.abs(np.sin(np.arange(16) + draw)) + 0.1
            a.update_priorities(sa.idx, td)
            b.update_priorities(sb.idx, td)
        if schedule == "uniform":
            np.testing.assert_array_equal(
                np.bincount(sa.game, minlength=2), [8, 8])


# ------------------------------------------------------------ shard isolation
@pytest.mark.multitask
@pytest.mark.chaos
def test_per_game_shard_drop_never_starves_siblings():
    """The acceptance chaos case: drop one game's shards MID-TRAFFIC —
    appends, samples, and priority write-backs keep flowing for the
    surviving game with zero interruption; readmission restores the
    dropped game's share."""
    mem = _build(shards_per_game=2)
    _fill(mem, ticks=48)
    rng = np.random.default_rng(0)

    def traffic_tick(t):
        # a mini learn loop around the drop: append + sample + write-back
        h, w = mem.spec.frame_shape
        mem.append_batch(
            rng.integers(0, 255, (8, h, w), np.uint8),
            rng.integers(0, 2, 8).astype(np.int32),
            rng.normal(size=8).astype(np.float32),
            rng.random(8) < 0.05,
            np.abs(rng.normal(size=8)) + 0.1,
        )
        batch = mem.sample(16, 0.6)
        mem.update_priorities(
            batch.idx, np.abs(rng.normal(size=len(batch.idx))) + 0.1)
        return batch

    for t in range(4):
        traffic_tick(t)
    # kill BOTH of game 0's shards (its whole host went away)
    for k in mem.game_shards(0):
        mem.drop_shard(k)
    assert mem.dead_games() == [0]
    assert mem.sampleable  # survivors keep the learner fed
    for t in range(6):
        batch = traffic_tick(t)
        assert (batch.game == 1).all()  # only the survivor is drawn
        assert len(batch.idx) == 16  # full batches, no starvation
    # heal: readmit under bumped epochs; both games sampled again
    for k in mem.game_shards(0):
        mem.readmit_shard(k)
    assert mem.dead_games() == []
    for t in range(6):
        batch = traffic_tick(t)
    counts = np.bincount(batch.game, minlength=2)
    assert counts[0] > 0 and counts[1] > 0
    np.testing.assert_array_equal(counts, [8, 8])  # uniform restored


def test_all_games_dead_raises():
    mem = _build()
    with pytest.raises(RuntimeError):
        # the last-survivor guard protects the final shard
        for k in range(2):
            mem.drop_shard(k)


# ------------------------------------------------------- forward parity (N=1)
@pytest.mark.multitask
def test_task_conditioned_forward_parity_at_n1():
    """MultiGameIQN with the zero-initialized game embedding must reproduce
    the single-game RainbowIQN forward pass EXACTLY when handed the same
    trunk/head params (the N=1 bitwise-parity claim)."""
    from rainbow_iqn_apex_tpu.models.iqn import RainbowIQN
    from rainbow_iqn_apex_tpu.multitask.ops import (
        init_mt_train_state,
        make_mt_network,
    )
    from rainbow_iqn_apex_tpu.ops.learn import init_train_state

    spec1 = MultiGameSpec(
        games=("toy:catch",), num_actions=(3,), frame_shape=(44, 44))
    cfg = CFG.replace(frame_height=44, frame_width=44)
    key = jax.random.PRNGKey(0)
    single = init_train_state(cfg, 3, key, state_shape=(44, 44, 2))
    mt = init_mt_train_state(cfg, spec1, key)
    # graft: same trunk/head leaves, keep the zero game embedding
    emb = mt.params["game_embed"]
    assert float(np.abs(np.asarray(emb["embedding"])).max()) == 0.0
    mt_params = dict(single.params)
    mt_params["game_embed"] = emb

    net1 = RainbowIQN(
        num_actions=3, hidden_size=cfg.hidden_size,
        num_cosines=cfg.num_cosines, dueling=cfg.dueling,
        compute_dtype=np.float32)
    netG = make_mt_network(cfg, spec1)
    obs = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (4, 44, 44, 2), 0, 255),
        np.uint8)
    rngs = {"taus": jax.random.PRNGKey(2), "noise": jax.random.PRNGKey(3)}
    q1, taus1 = net1.apply({"params": single.params}, obs, 8, rngs=rngs)
    qG, tausG = netG.apply(
        {"params": mt_params}, obs, np.zeros(4, np.int32), 8, rngs=rngs)
    np.testing.assert_array_equal(np.asarray(taus1), np.asarray(tausG))
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(qG))


def test_masked_greedy_respects_per_game_action_sets():
    from rainbow_iqn_apex_tpu.multitask.model import (
        MASK_FILL,
        masked_greedy_action,
        masked_q_values,
    )
    from rainbow_iqn_apex_tpu.multitask.ops import action_mask_table

    table = action_mask_table(TOY2)
    np.testing.assert_array_equal(
        table, [[True, True, True], [True, True, False]])
    # quantiles that would pick the padded slot without the mask
    quantiles = np.zeros((2, 4, 3), np.float32)
    quantiles[:, :, 2] = 10.0
    quantiles[:, :, 1] = 1.0
    game = np.asarray([0, 1], np.int32)
    a = np.asarray(masked_greedy_action(quantiles, game, table))
    np.testing.assert_array_equal(a, [2, 1])
    q = np.asarray(masked_q_values(quantiles, game, table))
    assert q[1, 2] == MASK_FILL and q[0, 2] == 10.0


# --------------------------------------------------------------- aggregation
@pytest.mark.multitask
def test_multigame_eval_aggregation_hand_computed():
    """Human-normalized aggregates against hand math: toy:catch random/human
    = -0.8/1.0, toy:chain = 0.15/1.0 (eval.HUMAN_BASELINES); a game without
    a baseline is reported raw but excluded from the normalized aggregate."""
    from rainbow_iqn_apex_tpu.eval import human_normalized

    hn_catch = human_normalized("toy:catch", 0.5)
    hn_chain = human_normalized("toy:chain", 0.55)
    assert hn_catch == pytest.approx((0.5 + 0.8) / 1.8)
    assert hn_chain == pytest.approx((0.55 - 0.15) / 0.85)
    agg = aggregate_human_normalized({
        "toy:catch": hn_catch,
        "toy:chain": hn_chain,
        "atari:NoSuchGame": None,  # unknown baseline: excluded
    })
    assert agg["hn_games"] == 2
    assert agg["hn_median"] == pytest.approx(
        float(np.median([hn_catch, hn_chain])))
    assert agg["hn_mean"] == pytest.approx((hn_catch + hn_chain) / 2)
    empty = aggregate_human_normalized({"x": None})
    assert empty["hn_median"] is None and empty["hn_games"] == 0


def test_games_obs_row_shapes():
    from rainbow_iqn_apex_tpu.multitask.obs import GamesObs
    from rainbow_iqn_apex_tpu.obs.schema import validate_row

    gobs = GamesObs(TOY2)
    gobs.note_eval({"games": {"toy:catch": {
        "score_mean": -1.0, "human_normalized": -0.111}}})
    payload = gobs.row(
        learn_shares=np.asarray([0.25, 0.75]),
        learn_rows=np.asarray([25, 75]),
        game_sizes=np.asarray([100, 300]),
        game_occupancy=np.asarray([0.1, 0.3]),
        dead_games=[],
    )
    assert payload["games"]["toy:catch"]["learn_share"] == 0.25
    assert payload["games"]["toy:chain"]["replay_size"] == 300
    assert payload["hn_games"] == 1  # only catch has an eval so far
    row = {"kind": "games", "schema": 1, "ts": 0.0, "host": 0,
           "run": "r", "step": 5, **payload}
    assert validate_row(row) == []
    mt_row = {"kind": "eval_mt", "schema": 1, "ts": 0.0, "host": 0,
              "run": "r", "step": 5, "hn_median": 0.1, "hn_mean": 0.1}
    assert validate_row(mt_row) == []


def test_obs_report_games_section():
    from scripts.obs_report import aggregate

    rows = [
        {"kind": "games", "schema": 1, "ts": 1.0, "host": 0, "run": "r",
         "step": 10, "schedule": "uniform",
         "games": {"toy:catch": {"learn_share": 0.5,
                                 "replay_occupancy": 0.2}},
         "hn_median": 0.3, "hn_mean": 0.3},
        {"kind": "eval", "schema": 1, "ts": 2.0, "host": 0, "run": "r",
         "step": 10, "game": "toy:catch", "score_mean": -1.0,
         "human_normalized": -0.111},
        {"kind": "eval_mt", "schema": 1, "ts": 2.0, "host": 0, "run": "r",
         "step": 10, "hn_median": 0.4, "hn_mean": 0.5},
    ]
    report = aggregate(rows)
    sec = report["games"]
    assert sec["n"] == 1 and sec["schedule"] == "uniform"
    assert sec["hn_median"] == 0.4  # the newest eval_mt wins
    assert sec["games"]["toy:catch"]["score_mean"] == -1.0
    # single-game runs show no games section
    assert aggregate([{"kind": "learn", "schema": 1, "ts": 0.0, "host": 0,
                       "run": "r", "step": 1, "frames": 1,
                       "loss": 0.0}])["games"] == {}


def test_health_attribution_per_game_tallies(tmp_path):
    from rainbow_iqn_apex_tpu.obs.attribution import health_attribution

    path = tmp_path / "metrics.jsonl"
    rows = [
        {"kind": "health", "status": "ok", "step": 1},
        {"kind": "games", "step": 1, "games": {}},
        {"kind": "eval", "step": 1, "game": "toy:catch",
         "score_mean": 2.0, "human_normalized": 1.5},
        {"kind": "eval", "step": 2, "game": "toy:chain", "score_mean": 0.1},
        {"kind": "eval_mt", "step": 2, "hn_median": 0.7, "hn_mean": 0.7},
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    att = health_attribution(str(path))
    assert att["games"]["games"] == 1 and att["games"]["eval_mt"] == 1
    assert att["games"]["by_game"]["toy:catch"]["human_normalized"] == 1.5
    assert att["games"]["aggregate"]["hn_median"] == 0.7
    # an untagged run carries no games attribution key
    path.write_text(json.dumps({"kind": "health", "status": "ok"}) + "\n")
    assert "games" not in health_attribution(str(path))


# ------------------------------------------------------------------ end to end
@pytest.mark.multitask
def test_two_game_apex_run_end_to_end(tmp_path):
    """The acceptance run: a seeded 2-game toy apex run completes with
    per-game eval rows for BOTH games, `games` rows with human-normalized
    aggregates, an eval_mt aggregate, and every row lint-clean."""
    from rainbow_iqn_apex_tpu.obs.schema import validate_row
    from rainbow_iqn_apex_tpu.parallel.apex import train_apex
    from scripts.lint_jsonl import lint_line

    cfg = CFG.replace(
        games="toy:catch,toy:chain",
        batch_size=16,
        learning_rate=1e-3,
        memory_capacity=4096,
        learn_start=256,
        frames_per_learn=4,
        target_update_period=200,
        num_envs_per_actor=8,
        metrics_interval=50,
        eval_interval=0,  # the final eval still emits per-game rows
        checkpoint_interval=0,
        eval_episodes=2,
        run_id="mt_e2e",
        results_dir=str(tmp_path / "results"),
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    summary = train_apex(cfg, max_frames=768)
    assert summary["frames"] == 768 and summary["learn_steps"] > 0
    assert summary["eval_hn_games"] == 2
    assert np.isfinite(summary["eval_hn_median"])

    metrics_path = os.path.join(str(tmp_path), "results", "mt_e2e",
                                "metrics.jsonl")
    rows = []
    for line in open(metrics_path):
        assert lint_line(line) is None, line
        row = json.loads(line)
        assert validate_row(row) == [], row
        rows.append(row)
    eval_games = {r["game"] for r in rows
                  if r["kind"] == "eval" and r.get("game")}
    assert eval_games == {"toy:catch", "toy:chain"}
    games_rows = [r for r in rows if r["kind"] == "games"]
    assert games_rows and set(games_rows[-1]["games"]) == eval_games
    shares = [g["learn_share"] for g in games_rows[-1]["games"].values()]
    assert all(s == pytest.approx(0.5, abs=0.05) for s in shares)
    mt_rows = [r for r in rows if r["kind"] == "eval_mt"]
    assert mt_rows and mt_rows[-1]["hn_median"] is not None


@pytest.mark.multitask
def test_multigame_rejects_multihost_and_bad_lanes():
    from rainbow_iqn_apex_tpu.parallel.apex import train_apex

    cfg = CFG.replace(games="toy:catch,toy:chain", num_envs_per_actor=3)
    with pytest.raises(ValueError, match="divide across"):
        train_apex(cfg, max_frames=64)


def test_device_batch_threads_game_ids():
    from rainbow_iqn_apex_tpu.agents.agent import to_device_batch

    mem = _build()
    _fill(mem)
    sample = mem.sample(16, 0.5)
    batch = to_device_batch(sample)
    np.testing.assert_array_equal(np.asarray(batch.game), sample.game)
    np.testing.assert_array_equal(
        sample.game, mem.games_of(sample.idx))
